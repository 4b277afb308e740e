"""Closed loop: ``clients`` clients each send their first request when
the load starts and each next one as soon as their previous one
finished (``{"kind": "closed", "clients": c}``)."""
import numpy as np

# requests drawn per client: more than any client completes in a run, so
# a client never runs dry
DEPTH = 256


def count(spec: dict, seconds: float, block: int) -> int:
    n = int(spec["clients"]) * DEPTH
    return n + (-n) % block


def due(spec: dict, n: int, block: int, rng):
    i = np.arange(n)
    c = int(spec["clients"])
    return np.zeros(n), np.where(i < c, -1, i - c)   # i follows i - c
