"""A quantile of one flight-record field over the window's iterations."""
from ..stats import quantile


def reduce(facts, field: str, q: float):
    values = [r[field] for r in facts.window_records if field in r]
    return quantile(values, q) if values else None
