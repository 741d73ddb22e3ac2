"""The state-space convolution's backward kernel's share of its
roofline, in %: as `trace_roofline`, with the operations and bytes of
`flops.ssm_conv_bwd_flops` and `ssm_conv_bwd_bytes`.

`kernel` is a regex that picks the kernel's events by the instruction's
own name; `result` one whose three groups read batch, channels and
positions from what the call returns first, the input's gradient (its
first operand is the whole projection the channels lie in, and says
nothing of how many the call reads); `taps` one whose group reads the
taps a channel from the first operand of two dimensions, `[channels,
taps]`. An event the kernel's regex picks and either cannot read is an
error that names the event."""
from .. import flops
from ..trace_reduce import whole_events
from .trace_roofline import read, share


def reduce(facts, kernel: str, result: str, taps: str):
    trace = facts.trace
    if trace is None:
        return None

    def counted():
        for event in whole_events(trace.ops, kernel, trace.lo, trace.hi):
            b, c, t = read(result, "batch, channels and positions", kernel,
                           "ssm_conv_bwd", event)
            k, = read(taps, "taps a channel", kernel, "ssm_conv_bwd", event)
            yield (event, flops.ssm_conv_bwd_flops(b, c, t, k),
                   flops.ssm_conv_bwd_bytes(b, c, t, k))

    return share(facts, counted())
