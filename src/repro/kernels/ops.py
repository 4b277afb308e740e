"""jit'd public wrappers for the Pallas kernels.

On TPU the kernels lower natively (interpret=False); on the CPU backend
(the test suite) they run under interpret mode, which executes the kernel
body with jnp semantics — the same tiling logic, validated against
ref.py.  Any other backend is an error, not a silent interpreter run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import (decode_attention as _dec, flash_attention as _fa,
                           moe_gemm as _mg, rglru_scan as _rg,
                           rmsnorm as _rn, rwkv6_scan as _rw)


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"the Pallas kernels target the TPU (interpret "
                           f"mode on the CPU); backend {backend!r} has "
                           "neither")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "softcap", "block_q",
                                             "block_kv"))
def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    softcap=None, block_q=512, block_kv=1024):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, softcap=softcap,
                               block_q=block_q, block_kv=block_kv,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "block_t"))
def decode_attention(q, k, v, pos, *, scale=None, softcap=None,
                     block_t=512):
    return _dec.decode_attention(q, k, v, pos, scale=scale, softcap=softcap,
                                 block_t=block_t, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "block_t",
                                             "partials"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, pos, *,
                           scale=None, softcap=None, block_t=None,
                           page_mask=None, partials=False):
    return _dec.paged_decode_attention(q, k_pages, v_pages, block_tables,
                                       pos, scale=scale, softcap=softcap,
                                       block_t=block_t, page_mask=page_mask,
                                       partials=partials,
                                       interpret=_interpret())


@jax.jit
def rglru_scan(a, b, h0):
    return _rg.rglru_scan(a, b, h0, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, lw, u, S0, *, chunk=32):
    return _rw.rwkv6_scan(r, k, v, lw, u, S0, chunk=chunk,
                          interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "block_d"))
def moe_gemm(x, w, *, block_c=128, block_f=512, block_d=512):
    return _mg.moe_gemm(x, w, block_c=block_c, block_f=block_f,
                        block_d=block_d, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, scale, *, eps=1e-6, block_rows=256):
    return _rn.rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                       interpret=_interpret())
