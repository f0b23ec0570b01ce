"""The arithmetic from logs to numbers: quantiles, token-weighted means,
rates over whole steps, and the spread the bounds are set from.  No JAX.
"""

from __future__ import annotations

import math
import statistics


def quantile(values, q: float) -> float:
    """Nearest-rank quantile (copied from ``tools/summarize_run._quantile``)."""
    if not values:
        return math.nan
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def weighted_mean(totals, weights) -> float | None:
    """Sum of ``totals`` over sum of ``weights``: a mean over every unit of
    weight (every token gap), not a mean of per-request means."""
    w = sum(weights)
    return sum(totals) / w if w > 0 else None


def steps_in(steps: list[dict], t0: float, t1: float) -> list[dict]:
    """The steps that ENDED inside the window, whole."""
    return [s for s in steps if t0 < s["t_end"] <= t1]


def whole_step_rate(steps: list[dict], t0: float, t1: float,
                    key: str = "tokens") -> float | None:
    """Units of ``key`` done by every step that ended in ``[t0, t1]``, over
    the time from the start of the first of them to the end of the last.
    No step is cut at an edge, and a stall between two steps is inside the
    span, so it lowers the rate."""
    inside = steps_in(steps, t0, t1)
    if not inside:
        return None
    span = inside[-1]["t_end"] - inside[0]["t_start"]
    if span <= 0:
        return None
    return sum(s[key] for s in inside) / span


def spread(values) -> float:
    """Distance between the first and third quartile over the median, the
    way the bounds are set (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
