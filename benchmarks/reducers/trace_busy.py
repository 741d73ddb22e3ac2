"""Device busy time of the traced steps: per step in ms, or the idle
share of the traced window in %."""


def reduce(facts, what: str):
    trace = facts.trace
    if trace is None:
        return None
    if what == "ms_per_step":
        return trace.busy_s * 1e3 / trace.steps
    if what == "idle_pct":
        return 100.0 * (1.0 - trace.busy_s / trace.window_s)
    raise ValueError(f"trace_busy: unknown reading {what!r}")
