"""Shared model primitives: norms, rotary embeddings, activations, FFN."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.sharding import logical_constraint


def dt(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16, "int8": jnp.int8}[name]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype, scale: float = 1.0):
    std = scale * (d_in ** -0.5)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------
def matmul(x, w, out_dtype=None, row_parallel: bool = False):
    """bf16 inputs, fp32 accumulation (MXU semantics), cast back.

    row_parallel=True marks matmuls whose output is a partial sum over the
    TP axis: the sequence-sharded constraint is applied to the fp32 dot
    result *before* the cast so GSPMD lowers it as a reduce-scatter rather
    than all-reduce + slice (halves the wire bytes).
    """
    out_dtype = out_dtype or x.dtype
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if row_parallel and y.ndim == 3 and y.shape[1] > 1:
        y = logical_constraint(y, "batch", "seq_sp", None)
    return y.astype(out_dtype)


def rmsnorm(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    # gemma-style (1 + scale) keeps zero-init sane; we store scale-1 at init=1
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rmsnorm_init(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# ---------------------------------------------------------------------------
# rotary position embeddings (standard + qwen2-vl M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float, scaling=None) -> jnp.ndarray:
    """Inverse frequencies (head_dim/2,): ``theta^(-2i/head_dim)``, or
    with ``scaling`` (a ``configs.base.RopeScaling``) YaRN's blend of
    them with the interpolated ``freq / factor``, as DeepSeek-V3's
    ``DeepseekV3YarnRotaryEmbedding`` computes it:

        d(r)  = head_dim * ln(L / (2 pi r)) / (2 ln theta)   (L the
                original_max_position_embeddings)
        low   = max(floor(d(beta_fast)), 0)
        high  = min(ceil(d(beta_slow)), head_dim - 1)
        ramp_i = clip((i - low) / (high - low), 0, 1)
        inv_i = freq_i / factor * ramp_i + freq_i * (1 - ramp_i)

    so the fast-rotating dimensions (below ``low``) keep their frequency
    and the slow ones (above ``high``) are interpolated by ``factor``."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if scaling is None:
        return inv
    low, high = yarn_correction_range(scaling, head_dim, theta)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    return inv / scaling.factor * ramp + inv * (1.0 - ramp)


def yarn_correction_range(scaling, head_dim: int, theta: float):
    """(low, high) dimensions of YaRN's ramp (see :func:`rope_freqs`)."""
    def dim(rotations):
        return head_dim * math.log(
            scaling.original_max_position_embeddings
            / (rotations * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(dim(scaling.beta_fast)), 0),
            min(math.ceil(dim(scaling.beta_slow)), head_dim - 1))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude ``0.1 * mscale * ln(factor) + 1`` (1 when
    ``factor <= 1``), DeepSeek-V3's ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_angles(positions, head_dim: int, theta: float,
                sections: Optional[Tuple[int, int, int]] = None,
                scaling=None):
    """Angles (…, S, head_dim/2).

    positions: (B, S) int32 for standard RoPE, or (3, B, S) for M-RoPE where
    the three planes are (temporal, height, width) ids and ``sections``
    gives how many of the head_dim/2 frequencies each plane owns.
    ``scaling``: YaRN's frequencies (:func:`rope_freqs`).
    """
    inv = rope_freqs(head_dim, theta, scaling)           # (half,)
    if sections is None:
        ang = positions[..., None].astype(jnp.float32) * inv  # (B,S,half)
        return ang
    t, h, w = sections
    assert t + h + w == head_dim // 2, (sections, head_dim)
    ang3 = positions[..., None].astype(jnp.float32) * inv     # (3,B,S,half)
    sel = jnp.concatenate([jnp.zeros((t,), jnp.int32),
                           jnp.ones((h,), jnp.int32),
                           jnp.full((w,), 2, jnp.int32)])     # (half,)
    # pick, per frequency, the angle from its assigned plane
    return jnp.take_along_axis(
        jnp.moveaxis(ang3, 0, -1),                            # (B,S,half,3)
        sel[None, None, :, None], axis=-1)[..., 0]            # (B,S,half)


def apply_rope(x, angles):
    """x: (B, S, H, head_dim); angles: (B, S, head_dim/2). NeoX half-split."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = jnp.cos(angles)[..., None, :].astype(jnp.float32)   # (B,S,1,half)
    sin = jnp.sin(angles)[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin,
                           x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense FFN (SwiGLU / GeGLU / plain MLP)
# ---------------------------------------------------------------------------
def ffn_init(key, cfg, dtype):
    ks = jax.random.split(key, 3)
    p = {}
    if cfg.gated_ffn:
        p["w_gate"] = dense_init(ks[0], cfg.d_model, cfg.d_ff, dtype)
    p["w_up"] = dense_init(ks[1], cfg.d_model, cfg.d_ff, dtype)
    p["w_down"] = dense_init(ks[2], cfg.d_ff, cfg.d_model, dtype,
                             scale=1.0 / max(1, cfg.n_layers) ** 0.5)
    return p


def ffn_apply(p, cfg, x):
    from repro.parallel.collectives import column_parallel
    act = activation(cfg.act)
    if cfg.gated_ffn:
        gate, up = column_parallel(x, [p["w_gate"], p["w_up"]])
        h = act(gate) * up
    else:
        (up,) = column_parallel(x, [p["w_up"]])
        h = act(up)
    h = logical_constraint(h, "batch", None, "ffn")
    from repro.parallel.collectives import row_parallel
    out = row_parallel(h, p["w_down"])
    return out
