"""``{"dist": "exponential", "mean": m}``."""
import numpy as np


def quantile(spec: dict, u):
    return -np.log1p(-u) * spec["mean"]
