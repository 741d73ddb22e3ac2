"""A kernel family's share of its roofline, in %: the least time the
chip could take for the calls that ran (the larger of their operations
over the peak FLOP/s and their bytes over the peak bytes/s, both from
shapes by benchmarks/flops.py) over the time they took in the trace.

`kernels` maps a kind flops.py knows (`fwd`, `dkv`, `dq`) to a regex
that picks that kernel's events; `operand` is a regex whose three groups
read batch x heads, sequence length and head size from the event's
first operand. The band comes from the configuration's `window`."""
import re

from .. import flops
from ..trace_reduce import clipped


def reduce(facts, kernels: dict, operand: str):
    trace = facts.trace
    if trace is None:
        return None
    least = took = 0.0
    for kind, pattern in kernels.items():
        for event in trace.ops:
            if not re.search(pattern, event[0]):
                continue
            inside = clipped([event], trace.lo, trace.hi)
            if not inside or inside[0][1] - inside[0][0] < event[2]:
                continue            # cut by the window's edge
            bh, t, d = map(int, re.search(operand, event[0]).groups())
            shape = dict(batch=1, seq_len=t, n_head=bh, head_dim=d)
            seconds, _ = flops.roofline_seconds(
                flops.flash_call_flops(kind, window=facts.sizes.get(
                    "window", 0), **shape),
                flops.flash_call_bytes(kind, **shape), facts.peak)
            least += seconds
            took += event[2] / 1e9
    return 100.0 * least / took if took > 0 else None
