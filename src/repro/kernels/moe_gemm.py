"""Pallas TPU grouped GEMM for MoE expert FFNs.

x (E, C, D) @ w (E, D, F) -> (E, C, F): grid (E, nC, nF, nD) with the
contraction dim innermost and an fp32 accumulator tile in VMEM.  Tiles
default to (128, 512, 512) — MXU-aligned, ~1.3 MB working set.  The
expert dim rides the grid so no capacity-sized HBM copies are made
per expert.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nd):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _fin():
        o_ref[0, ...] = acc_ref[...].astype(o_ref.dtype)


def moe_gemm(x, w, *, block_c=128, block_f=512, block_d=512,
             interpret: bool):
    """x (E,C,D) @ w (E,D,F) -> (E,C,F) with fp32 accumulation."""
    E, C, D = x.shape
    F = w.shape[-1]
    bc = min(block_c, C)
    while C % bc:
        bc -= 1
    bf = min(block_f, F)
    while F % bf:
        bf -= 1
    bd = min(block_d, D)
    while D % bd:
        bd -= 1
    nc, nf, nd = C // bc, F // bf, D // bd

    kernel = functools.partial(_mm_kernel, nd=nd)
    return pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, c, f, d: (e, c, d)),
            pl.BlockSpec((1, bd, bf), lambda e, c, f, d: (e, d, f)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, c, f, d: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
