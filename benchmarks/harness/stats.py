"""Percentile and median arithmetic, with the sample counts that say how
far a tail can be trusted."""
import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), on a plain list."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50)


def samples_beyond(n, q):
    """How many of ``n`` samples lie beyond the ``q``-th percentile: a tail
    with fewer than ten beyond it is closer to a maximum than a percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def summary(values, q=95):
    """Median, the ``q``-th percentile, and the counts that qualify them."""
    n = len(values)
    return {"n": n, "median": median(values), f"p{q}": percentile(values, q),
            "beyond": samples_beyond(n, q), "max": max(values)}
