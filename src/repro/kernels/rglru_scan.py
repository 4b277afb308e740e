"""Pallas TPU RG-LRU linear-recurrence scan.

h_t = a_t * h_{t-1} + b_t over the sequence, vectorized across a width
tile.  Grid (B, nW, nS): the width dim is "parallel" (independent lanes),
the sequence dim "arbitrary" (sequential) with the running state h in
VMEM scratch.  Inside a (block_s, block_w) tile the recurrence steps row
by row on the VPU — the width tile (128-lane aligned) keeps the vector
units full.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, o_ref, hT_ref, h_ref, *, block_s, ns):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)   # (bs, bw)
    b = b_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + b[t]
        o_ref[0, t, :] = h
        return h

    h = jax.lax.fori_loop(0, block_s, step, h_ref[...])
    h_ref[...] = h

    @pl.when(si == ns - 1)
    def _fin():
        hT_ref[0, ...] = h_ref[...]


def rglru_scan(a, b, h0, *, block_s=256, block_w=256,
               interpret: bool):
    """a, b (B,S,W) fp32; h0 (B,W). Returns (h (B,S,W), hT (B,W)).

    h0 is folded into b[0] (b'_0 = a_0*h0 + b_0) so the kernel always
    starts from zero state.
    """
    B, S, W = a.shape
    b = b.at[:, 0].add(a[:, 0] * h0)
    bs = min(block_s, S)
    while S % bs:
        bs -= 1
    bw = min(block_w, W)
    while W % bw:
        bw -= 1
    ns, nw = S // bs, W // bw

    kernel = functools.partial(_rglru_kernel, block_s=bs, ns=ns)
    hs, hT = pl.pallas_call(
        kernel,
        grid=(B, nw, ns),
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda bb, w, s: (bb, s, w)),
            pl.BlockSpec((1, bs, bw), lambda bb, w, s: (bb, s, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bw), lambda bb, w, s: (bb, s, w)),
            pl.BlockSpec((1, bw), lambda bb, w, s: (bb, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return hs, hT
