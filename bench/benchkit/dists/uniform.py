"""``{"dist": "uniform", "min": a, "max": b}``."""


def quantile(spec: dict, u):
    return spec["min"] + u * (spec["max"] - spec["min"])
