"""Metric arithmetic kept with the yardstick: percentiles, proration of a
request's tokens over the window, and the driver's spread estimator."""

from __future__ import annotations

import statistics


def percentile(values, q: float):
    """Linear-interpolated percentile (q in 0..100); None when empty."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def prorated_tokens(req: dict, w0: float, w1: float) -> float:
    """A request's tokens that fall inside the window [w0, w1): each output
    token counts where it arrived; the prompt's tokens are spread evenly
    over [start, first token] (the prefill), so a request that straddles an
    edge adds its share and the count never jumps by a whole request."""
    total = sum(1.0 for t in req["token_times"] if w0 <= t < w1)
    start = req["start"]
    first = req["token_times"][0] if req["token_times"] else req.get("end")
    if first is None:
        first = w1  # still prefilling at the window's end
    span = max(first - start, 1e-9)
    total += req["prompt_tokens"] * overlap(start, first, w0, w1) / span
    return total


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with Python's statistics.quantiles (the driver's)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def driver_spread(values) -> dict:
    """Both of the driver's readings of one set of runs: `wide` over all
    runs (looseness test), `trimmed` with the run farthest from the median
    left out where that narrows it (tightness test)."""
    wide = iqr_share(values)
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    rest = [v for i, v in enumerate(values) if i != far]
    trimmed = min(wide, iqr_share(rest)) if len(rest) >= 2 else wide
    return {"wide": wide, "trimmed": trimmed, "median": med, "n": len(values)}


# ------------------------------------------------ the window's samples
def window_gaps_ms(records, seconds: float) -> list[float]:
    """Gaps between successive output tokens of a request; a gap belongs to
    the window by the time it ended."""
    out = []
    for r in records:
        ts = r["token_times"]
        out += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if 0.0 <= b < seconds]
    return out


def window_ttfts_ms(records, seconds: float) -> list[float]:
    """Time from a request being due to its first token (session open
    included); a request belongs to the window by the time it was due."""
    return [(r["token_times"][0] - r["due"]) * 1e3 for r in records
            if 0.0 <= r["due"] < seconds and r["token_times"]]


def window_tokens(records, seconds: float) -> float:
    return sum(prorated_tokens(r, 0.0, seconds) for r in records
               if r["start"] is not None)


def delta(ctx: dict, *path):
    """info1 - info0 along a key path of the server's rpc_info."""
    a, b = ctx["info0"], ctx["info1"]
    for key in path:
        a, b = (a or {}).get(key), (b or {}).get(key)
    if a is None or b is None:
        return None
    return b - a
