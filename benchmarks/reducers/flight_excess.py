"""Seconds the iterations before the window took beyond the window's
median iteration, the process's very first iteration (compile or cache
load, counted by compile_s) left out: the one-time host work PR 22 found
in iterations 2-4."""
import statistics


def reduce(facts, field: str = "wall_ms"):
    window = [r[field] for r in facts.window_records if field in r]
    early = [r[field] for r in facts.setup_records if field in r][1:]
    if not window or not early:
        return None
    typical = statistics.median(window)
    return sum(max(x - typical, 0.0) for x in early) / 1e3
