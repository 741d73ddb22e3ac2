"""How many compilations ran inside the window: the entries of the
window's flight records' `compile_events` whose event ends in `event`
(`backend_compile_duration` fires for a real compile and for a cache
read alike). 0 in a sound run; where it is not, the entries' `fun_name`
says whose. None where the window left no record."""


def reduce(facts, event: str = "backend_compile_duration"):
    if not facts.window_records:
        return None
    return sum(e["event"].endswith(event) for r in facts.window_records
               for e in r.get("compile_events", ()))
