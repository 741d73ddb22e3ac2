"""From jax's monitoring events up to the window's close: `seconds` sums
the backend_compile_duration events (which cover cache reads and
executable loading too), `misses` counts persistent-cache misses, the
real compiles."""


def reduce(facts, what: str):
    events = facts.compile_events
    if what == "seconds":
        return sum(e.get("dur_ms", 0.0) for e in events
                   if e["event"].endswith("backend_compile_duration")) / 1e3
    if what == "misses":
        return sum(e["event"].endswith("cache_misses") for e in events)
    raise ValueError(f"compile_events: unknown reading {what!r}")
