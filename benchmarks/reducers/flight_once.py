"""A key of the one record the program writes once a run: `field` is a
dict on one of the flight records before the window (`setup`, on the
trainer's first: seconds inside each of its set-up's spans), `key` a
name in it. None where no record has the field, or the field no such
key: a program without those spans."""


def reduce(facts, field: str, key: str):
    found = [r[field] for r in facts.setup_records
             if isinstance(r.get(field), dict)]
    return found[0].get(key) if len(found) == 1 else None
