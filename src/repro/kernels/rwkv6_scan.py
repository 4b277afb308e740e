"""Pallas TPU RWKV-6 wkv kernel — chunked linear attention with
data-dependent per-channel decay.

Grid (B, H, n_chunks), chunks sequential with the (K, V) state in VMEM
scratch.  Per chunk (length L): cumulative log-decays, the inter-chunk
term q~ @ S, an (L, L) masked intra-chunk product (exact log-space — all
exponent differences <= 0), the current-token bonus, and the state
update.  L = 32/64 keeps every tile square-MXU friendly and the whole
working set (~6 (L,K) tiles + (K,V) state) far under VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                 o_ref, sT_ref, s_ref, *, L, nc):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, :, 0].astype(jnp.float32)     # (L, K)
    k = k_ref[0, :, 0].astype(jnp.float32)
    v = v_ref[0, :, 0].astype(jnp.float32)
    lw = lw_ref[0, :, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)           # (K,)

    la = jnp.cumsum(lw, axis=0)                # (L, K) inclusive
    la_prev = la - lw
    S0 = s_ref[...]                            # (K, V)

    q_int = r * jnp.exp(la_prev)
    o = jax.lax.dot_general(q_int, S0, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, V)

    # intra-chunk: A[t,s] = sum_K r_t k_s exp(la_prev_t - la_s), s < t
    diff = la_prev[:, None, :] - la[None, :, :]          # (L, L, K)
    mask = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # mask the exponent (exp overflows for s > t; inf*0 => NaN in VJPs)
    p = jnp.exp(jnp.where(mask[..., None], diff, -jnp.inf))
    A = jnp.sum(r[:, None, :] * k[None, :, :] * p, axis=-1)  # (L, L)
    o = o + jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # current-token bonus
    du = jnp.sum(r * (u[None, :] * k), axis=-1)          # (L,)
    o = o + du[:, None] * v
    o_ref[0, :, 0] = o.astype(o_ref.dtype)

    # state update
    la_L = la[-1]                                        # (K,)
    k_dec = k * jnp.exp(la_L[None, :] - la)
    s_ref[...] = jnp.exp(la_L)[:, None] * S0 + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ci == nc - 1)
    def _fin():
        sT_ref[0, 0] = s_ref[...]


def rwkv6_scan(r, k, v, lw, u, S0, *, chunk=32, interpret: bool):
    """r,k,v,lw (B,S,H,K); u (H,K); S0 (B,H,K,V) fp32.

    Returns (o (B,S,H,V) fp32, S_T (B,H,K,V) fp32).
    """
    B, S, H, K = r.shape
    V = v.shape[-1]
    L = min(chunk, S)
    while S % L:
        L -= 1
    nc = S // L

    kernel = functools.partial(_rwkv_kernel, L=L, nc=nc)
    o, sT = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, L, 1, K), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, L, 1, K), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, L, 1, V), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, L, 1, K), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, K), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, 1, V), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, V), jnp.float32),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, lw, u, S0)
    return o, sT
