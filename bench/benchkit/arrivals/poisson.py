"""Open loop: requests are due on the wall clock whatever the server
does, with exponential gaps of mean ``1 / rate_per_s`` (``{"kind":
"poisson", "rate_per_s": r}``).  The gaps sit at the distribution's
midpoint quantiles, scaled so that their mean is exactly the stated
one, and are ordered in blocks that each hold one gap from every
stratum (:func:`benchkit.traffic.blocked`)."""
import math

import numpy as np

from benchkit import traffic


def count(spec: dict, seconds: float, block: int) -> int:
    return block * max(1, math.ceil(spec["rate_per_s"] * seconds / block))


def due(spec: dict, n: int, block: int, rng):
    mean = 1.0 / float(spec["rate_per_s"])
    gaps = traffic.stratified({"dist": "exponential", "mean": mean}, n)
    gaps *= mean / gaps.mean()
    return np.cumsum(traffic.blocked(gaps, block, rng)), np.full(n, -1)
