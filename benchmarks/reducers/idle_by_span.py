"""Device idle time of the traced window, in ms per traced step, that
lies inside a span of the thread that dispatches the step: a span whose
name matches `within` and none of `outside`. The spans are the
program's `span()`s, which enter a `jax.profiler.TraceAnnotation` and so
land on the calling thread's line of the capture, on the device events'
clock. With `within` null: the idle time inside no span that matches
any of `outside` (give it every prefix the program uses, and it is the
idle time the program has no name for).

A program without such spans has no idle time inside one: 0.0 for a
`within`, all of the idle time without. None only where the capture
holds no host thread that dispatches the step."""
import re

from .. import xplane
from ..trace_reduce import clipped, idle_gaps, merged


def reduce(facts, thread: str, within=None, outside=()):
    trace = facts.trace
    if trace is None:
        return None
    events = xplane.thread_events(xplane.newest_capture(), thread)
    if not events:
        return None

    def named(name):
        return any(re.search(p, name) for p in outside)

    if within is None:
        spans = [e for e in events if named(e[0])]
    else:
        spans = [e for e in events
                 if re.search(within, e[0]) and not named(e[0])]
    inside = idle = 0.0
    for a, b in idle_gaps(trace.ops, trace.lo, trace.hi):
        idle += b - a
        inside += sum(q - p for p, q in merged(clipped(spans, a, b)))
    return (idle - inside if within is None else inside) / 1e6 / trace.steps
