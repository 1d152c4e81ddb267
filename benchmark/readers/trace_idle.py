"""Device idle share of the traced window: 1 - (union of the intervals in
which an operation ran on the device) / window, from the device trace."""


def read(metric: dict, ctx) -> float | None:
    tr = ctx.trace
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
