"""Time of the device operations whose name matches `pattern`, as a
union on one device's operations line, in ms per traced step; with
`exposed`, only the part during which no other operation runs there."""
from ..trace_reduce import exposed_ns


def reduce(facts, pattern: str, exposed: bool = False):
    trace = facts.trace
    if trace is None:
        return None
    total, alone = exposed_ns(trace.ops, pattern, trace.lo, trace.hi)
    if total <= 0:
        return None
    return (alone if exposed else total) / 1e6 / trace.steps
