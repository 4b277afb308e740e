"""Pallas TPU flash attention (forward), H-space layout.

Tiling: grid (B, H, nq, nkv), kv innermost ("arbitrary" = sequential) so
the online-softmax state (m, l, acc) lives in VMEM scratch across the kv
sweep.  Block shapes are (block_q, head_dim) / (block_kv, head_dim) —
head_dim is 64..256 for the assigned archs, so a (512, 128) q tile +
(1024, 128) kv tile + fp32 acc uses well under 1 MB of VMEM, and the MXU
contraction dims are multiples of 128 (hardware aligned).

Causal masking is block-exact: fully-masked kv blocks are skipped with
``pl.when`` (no FLOPs on the lower-triangle complement — unlike the
blocked-jnp fallback, which computes the full S^2; the cost model
accounts for both).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale, causal, window, softcap, block_q, block_kv, nkv):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_kv

    # skip kv blocks fully outside the causal / sliding-window band
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(
            run, q_start - (k_start + block_kv - 1) < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bkv, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        iq = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_kv), 0)
        jk = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_kv), 1)
        ok = jnp.ones((block_q, block_kv), bool)
        if causal:
            ok &= jk <= iq
        if window is not None:
            ok &= (iq - jk) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nkv - 1)
    def _finish():
        o_ref[0, 0, ...] = (acc_ref[...]
                            / jnp.maximum(l_ref[...], 1e-37)[:, None]
                            ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    softcap=None, block_q=512, block_kv=1024,
                    interpret: bool):
    """q,k,v (B,S,H,hd), k/v pre-expanded to H heads. Returns (B,S,H,hd)."""
    B, S, H, hd = q.shape
    scale = hd ** -0.5 if scale is None else scale
    bq = min(block_q, S)
    while S % bq:
        bq -= 1
    bkv = min(block_kv, S)
    while S % bkv:
        bkv -= 1
    nq, nkv = S // bq, S // bkv

    qt = jnp.moveaxis(q, 2, 1)   # (B,H,S,hd)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=bq, block_kv=bkv, nkv=nkv)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)
