"""The largest value of one flight-record field over the window's
iterations, those left out that carry `without`: an iteration in which
the profiler's capture starts or stops carries `profile_ms` (writing
the trace takes seconds), and a traced run always has one, so the worst
`wall_ms` with them in reads the profiler and not the run. Records of a
program that marks no such iteration are all counted."""


def reduce(facts, field: str, without: str):
    values = [r[field] for r in facts.window_records
              if field in r and without not in r]
    return max(values) if values else None
