"""The few statistics the benchmark reports."""

from __future__ import annotations

import math


class Marks(list):
    """(what was done, seconds it took) of a driver's set-up, in order."""

    def add(self, what: str, since: float) -> float:
        import time

        now = time.monotonic()
        self.append((what, now - since))
        return now


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile (q in 0..100) over all of ``values``."""
    if not values:
        return None
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return float(s[rank - 1])
