"""value = scale * sum(numerator deltas) / sum(denominator deltas) over the
window's counter deltas. Nothing to read (a zero denominator) returns None."""

from ..harness import counters


def read(metric: dict, ctx) -> float | None:
    den = counters.total(ctx.counters, metric["denominator"])
    if den <= 0:
        return None
    num = counters.total(ctx.counters, metric["numerator"])
    return metric.get("scale", 1.0) * num / den
