"""A kernel family's share of its roofline, in %: the least time the
chip could take for the calls that ran (the larger of their operations
over the peak FLOP/s and their bytes over the peak bytes/s, both from
shapes by benchmarks/flops.py) over the time they took in the trace.

`kernels` maps a kind flops.py knows (`fwd`, `dkv`, `dq`) to a regex
that picks that kernel's events by the instruction's own name; `operand`
is a regex whose three groups read batch x heads, sequence length and
head size from the event's first operand. Where the family's value head
size is another than its query-key one, `value_operand` is a regex whose
one group reads it from the value's operand; without it both are
`operand`'s. An event a kernel's regex picks and a given regex cannot
read is an error that names the event: the kernel's call shape changed,
or the regex picks what it should not. The band comes from the
configuration's `window`."""
import re

from .. import flops
from ..trace_reduce import whole_events


def read(regex: str, what: str, pattern: str, kind: str, event) -> tuple:
    found = re.search(regex, event[0])
    if found is None:
        raise ValueError(
            f"trace_roofline: {pattern!r} picks this event as a {kind!r} "
            f"kernel, but {regex!r} finds no {what} in it: {event[0][:300]}")
    return tuple(map(int, found.groups()))


def share(facts, counted):
    """`counted` yields (event, operations, bytes) of each call."""
    least = took = 0.0
    for event, operations, nbytes in counted:
        least += flops.roofline_seconds(operations, nbytes, facts.peak)[0]
        took += event[2] / 1e9
    return 100.0 * least / took if took > 0 else None


def reduce(facts, kernels: dict, operand: str, value_operand: str = None):
    trace = facts.trace
    if trace is None:
        return None

    def counted():
        window = facts.sizes.get("window", 0)
        for kind, pattern in kernels.items():
            for event in whole_events(trace.ops, pattern, trace.lo, trace.hi):
                bh, t, d = read(operand, "batch x heads, length and head "
                                "size", pattern, kind, event)
                shape = dict(batch=1, seq_len=t, n_head=bh, head_dim=d)
                if value_operand is not None:
                    shape["v_head_dim"], = read(
                        value_operand, "value head size", pattern, kind, event)
                yield (event,
                       flops.flash_call_flops(kind, window=window, **shape),
                       flops.flash_call_bytes(kind, **shape))

    return share(facts, counted())
