"""One file per reducer, found by the name a `layer_metrics/<metric>.json`
gives under `reducer`. A reducer is `reduce(facts, **args) -> number or
None`; None means there was nothing to read, and the harness then leaves
the metric out (and fails the run if the cell declares it). `facts` is
`benchmarks.run.Facts`. A new kind of reduction is a new file here."""
import importlib


def get(name: str):
    return importlib.import_module(f"{__name__}.{name}").reduce
