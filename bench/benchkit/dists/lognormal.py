"""``{"dist": "lognormal", "median": m, "sigma": s}``."""
from statistics import NormalDist

import numpy as np


def quantile(spec: dict, u):
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    return spec["median"] * np.exp(spec["sigma"] * z)
