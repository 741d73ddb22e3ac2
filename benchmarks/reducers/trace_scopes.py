"""Self time of the device operations whose scope lies `within` one
pattern and `outside` all of some others, in ms per traced step. The
scope of an operation is the `op_name` of its HLO instruction: jax's
name stack (`jvp(...)`, `transpose(jvp(...))`, the remat marker
`rematted_computation`, flax's module path, `jax.named_scope`s such as
`optimizer` and `head_loss`), read from the newest capture's metadata
(benchmarks/xplane.py). Self time is `trace_reduce.self_times`: a loop
or call is charged only what its children leave, so the classes of a
partition add up to the device's busy time.

An operation the compiler made itself (a copy, an async slice) has no
scope and is inside no pattern. A fusion carries one instruction's
scope, so a fusion that spans two scopes is charged to one.

A trace whose operations carry no scope (a CPU rehearsal: that backend
keeps none in the capture) has no time inside one: 0.0 for a `within`,
the whole busy time without, as for any operation no pattern names."""
import re

from .. import xplane
from ..trace_reduce import self_times


def reduce(facts, within=None, outside=()):
    trace = facts.trace
    if trace is None:
        return None
    scopes = xplane.op_scopes(xplane.newest_capture(), trace.device)
    total = 0.0
    for name, t in self_times(trace.ops, trace.lo, trace.hi).items():
        scope = scopes.get(name, "")
        if (within is None or re.search(within, scope)) and not any(
                re.search(p, scope) for p in outside):
            total += t
    return total / 1e6 / trace.steps
