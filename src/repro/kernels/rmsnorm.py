"""Pallas TPU fused RMSNorm: one HBM round-trip for norm + scale.

Grid (nRows,): a (block_rows, D) tile is read once; the mean-square
reduction, rsqrt and scale all happen in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rms_kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, scale, *, eps=1e-6, block_rows=256, interpret: bool):
    """x (..., D); scale (D,)."""
    orig_shape = x.shape
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    bn = min(block_rows, N)
    while N % bn:
        bn -= 1
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xf, scale)
    return out.reshape(orig_shape)
