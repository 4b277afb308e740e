"""Production meshes.

Defined as functions (not module constants) so importing never touches jax
device state.  The production target is a TPU v5e pod: 16 x 16 = 256 chips
("data" x "model"), and two pods (2 x 16 x 16 = 512) for the multi-pod
dry-run, with the "pod" axis crossing the inter-pod (DCN/optical) boundary.

Every mesh is built with ``Auto`` axis types: the model code places
arrays through ``with_sharding_constraint`` and shard_map, which is the
automatic-sharding mode (``jax.make_mesh`` defaults to ``Explicit`` axes,
under which those scatters and constraints are rejected).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4, pod: int = 1,
                   devices=None):
    """Small (pod x) data x model mesh over ``devices`` (default: the first
    pod*data*model of ``jax.devices()``).  Raises when too few exist."""
    n = pod * data * model
    devices = list(jax.devices() if devices is None else devices)
    if n > len(devices):
        raise ValueError(f"a {pod}x{data}x{model} mesh needs {n} devices; "
                         f"{len(devices)} {devices[0].platform} device(s) "
                         "are visible")
    devices = devices[:n]
    if pod > 1:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"),
                          devices)
    return _auto_mesh((data, model), ("data", "model"), devices)


# Hardware constants for the roofline (TPU v5e-class chip).
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~usable)
