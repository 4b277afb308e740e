"""Per-layer block assembly: norm -> mixer -> residual -> norm -> FFN/MoE.

Layer kinds: attn / local (GQA attention), mla (DeepSeek latent attention),
rglru (Griffin recurrent block), rwkv6 (complete RWKV layer incl. its own
channel-mix).  gemma2-style post-norms supported via cfg.post_norm.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LOCAL, MLA, RGLRU, RWKV6
from repro.models import attention, mla as mla_mod, modules as nn, moe as moe_mod
from repro.models import rglru as rglru_mod, rwkv6 as rwkv6_mod


def init(key, cfg, kind: str, is_moe: bool, dtype):
    ks = jax.random.split(key, 6)
    p: dict = {"ln1": nn.rmsnorm_init(cfg.d_model)}
    if kind in (ATTN, LOCAL):
        p["attn"] = attention.init(ks[0], cfg, dtype)
    elif kind == MLA:
        p["mla"] = mla_mod.init(ks[0], cfg, dtype)
    elif kind == RGLRU:
        p["rglru"] = rglru_mod.init(ks[0], cfg, dtype)
    elif kind == RWKV6:
        p["rwkv"] = rwkv6_mod.init(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)

    p["ln2"] = nn.rmsnorm_init(cfg.d_model)
    if kind != RWKV6:
        if is_moe:
            p["moe"] = moe_mod.init(ks[1], cfg, dtype)
            if cfg.moe.n_shared:
                shared_cfg = cfg.replace(
                    d_ff=cfg.moe.d_ff_expert * cfg.moe.n_shared)
                p["shared"] = nn.ffn_init(ks[2], shared_cfg, dtype)
        else:
            p["ffn"] = nn.ffn_init(ks[1], cfg, dtype)
    if cfg.post_norm:
        p["ln1_post"] = nn.rmsnorm_init(cfg.d_model)
        p["ln2_post"] = nn.rmsnorm_init(cfg.d_model)
    return p


from repro.parallel.sharding import logical_constraint
from repro.parallel.collectives import gather_seq


def _seq_sp(y):
    """Force row-parallel partial sums to land directly in the sequence-
    sharded residual layout (reduce-scatter, not all-reduce + slice)."""
    if y.ndim == 3 and y.shape[1] > 1:
        return logical_constraint(y, "batch", "seq_sp", None)
    return y


def _post(p, cfg, name, y):
    if cfg.post_norm:
        y = nn.rmsnorm(y, p[name]["scale"], cfg.norm_eps)
    return _seq_sp(y)


def _ffn_part(p, cfg, x):
    """FFN or MoE (+ shared experts), the training path (capacity
    routing). Returns (out, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        out, aux = moe_mod.apply(p["moe"], cfg, x)
        if "shared" in p:
            shared_cfg = cfg.replace(d_ff=cfg.moe.d_ff_expert * cfg.moe.n_shared)
            out = out + nn.ffn_apply(p["shared"], shared_cfg, x)
    else:
        out = nn.ffn_apply(p["ffn"], cfg, x)
    return out, aux


def _ffn_infer(p, cfg, h):
    """FFN of the normed ``h``, or the dropless expert layer over the
    held experts (``moe.apply_dropless``, scopes ``router`` and
    ``experts``) plus the shared experts (scope ``shared``): the
    inference path, where a token's output does not depend on its
    batch.  Returns (out, pairs per token on held experts, zeros for a
    dense FFN)."""
    if "moe" not in p:
        return nn.ffn_apply(p["ffn"], cfg, h), \
            jnp.zeros(h.shape[:-1], jnp.int32)
    out, held = moe_mod.apply_dropless(p["moe"], cfg, h)
    if "shared" in p:
        with jax.named_scope("shared"):
            shared_cfg = cfg.replace(
                d_ff=cfg.moe.d_ff_expert * cfg.moe.n_shared)
            out = out + nn.ffn_apply(p["shared"], shared_cfg, h)
    return out, held


def apply(p, cfg, kind: str, x, *, angles, mode: str, impl=None):
    """Full-sequence path (train / prefill).

    Returns (x, cache_out, aux).  cache_out is None in train mode; in
    prefill mode it is the layer's decode-ready cache contribution
    *before* max-len padding (the LM pads/stacks).
    """
    aux = jnp.zeros((), jnp.float32)
    # norm in the sequence-sharded domain (Megatron-SP): the AG to full
    # sequence happens *after* the norm, so its backward is a cheap
    # reduce-scatter instead of an fp32 (B,S,D) all-reduce
    h = _seq_sp(nn.rmsnorm(x, p["ln1"]["scale"], cfg.norm_eps))
    if kind in (MLA, RGLRU, RWKV6):
        # these mixers need the full sequence locally (latent projections,
        # conv/token-shift halos); attention/FFN gather inside their own
        # fused column_parallel shard_maps instead
        h = gather_seq(h)
    cache_out: Any = None

    if kind in (ATTN, LOCAL):
        out, kv = attention.apply(p["attn"], cfg, h, kind=kind,
                                  angles=angles, impl=impl)
        if mode == "prefill":
            cache_out = kv
        x = x + _post(p, cfg, "ln1_post", out)
    elif kind == MLA:
        out, lat = mla_mod.apply(p["mla"], cfg, h, angles=angles, impl=impl)
        if mode == "prefill":
            cache_out = lat
        x = x + _post(p, cfg, "ln1_post", out)
    elif kind == RGLRU:
        out, rcache = rglru_mod.apply(p["rglru"], cfg, h, impl=impl)
        if mode == "prefill":
            cache_out = rcache
        x = x + _post(p, cfg, "ln1_post", out)
    elif kind == RWKV6:
        cache0 = rwkv6_mod.cache_init(cfg, x.shape[0], x.dtype)
        out, c1 = rwkv6_mod.time_mix(p["rwkv"], cfg, h, cache0, impl=impl)
        x = x + _seq_sp(out)
        h2 = nn.rmsnorm(x, p["ln2"]["scale"], cfg.norm_eps)
        out2, c2 = rwkv6_mod.channel_mix(p["rwkv"], cfg, h2, c1)
        x = x + _seq_sp(out2)
        if mode == "prefill":
            cache_out = c2
        return x, cache_out, aux
    else:
        raise ValueError(kind)

    h2 = _seq_sp(nn.rmsnorm(x, p["ln2"]["scale"], cfg.norm_eps))
    if mode == "train":
        out2, aux = _ffn_part(p, cfg, h2)
    else:
        out2, _ = _ffn_infer(p, cfg, h2)
    x = x + _post(p, cfg, "ln2_post", out2)
    return x, cache_out, aux


def apply_decode(p, cfg, kind: str, x, cache, pos, *, angles):
    """Single-token decode path. Returns (x, new_cache)."""
    h = nn.rmsnorm(x, p["ln1"]["scale"], cfg.norm_eps)
    if kind in (ATTN, LOCAL):
        out, cache = attention.apply_decode(p["attn"], cfg, h, cache, pos,
                                            kind=kind, angles=angles)
        x = x + _post(p, cfg, "ln1_post", out)
    elif kind == MLA:
        out, cache = mla_mod.apply_decode(p["mla"], cfg, h, cache, pos,
                                          angles=angles)
        x = x + _post(p, cfg, "ln1_post", out)
    elif kind == RGLRU:
        out, cache = rglru_mod.apply_decode(p["rglru"], cfg, h, cache)
        x = x + _post(p, cfg, "ln1_post", out)
    elif kind == RWKV6:
        out, c1 = rwkv6_mod.time_mix(p["rwkv"], cfg, h, cache)
        x = x + out
        h2 = nn.rmsnorm(x, p["ln2"]["scale"], cfg.norm_eps)
        out2, cache = rwkv6_mod.channel_mix(p["rwkv"], cfg, h2, c1)
        x = x + out2
        return x, cache
    else:
        raise ValueError(kind)

    h2 = nn.rmsnorm(x, p["ln2"]["scale"], cfg.norm_eps)
    out2, _ = _ffn_infer(p, cfg, h2)
    x = x + _post(p, cfg, "ln2_post", out2)
    return x, cache


# the mixers whose state pages: global attention (k/v) and latent
# attention (one latent row per token); a sliding-window ring cache and
# the recurrent states are constant-size per sequence, nothing to page
PAGED = {ATTN: attention, MLA: mla_mod}


def _paged_mixer(kind: str):
    if kind not in PAGED:
        raise NotImplementedError(
            f"paged KV pools exist for global and latent attention "
            f"layers only, got {kind!r}")
    return PAGED[kind], "attn" if kind == ATTN else "mla"


def _paged_mlp(p, cfg, x):
    with jax.named_scope("mlp"):
        h2 = nn.rmsnorm(x, p["ln2"]["scale"], cfg.norm_eps)
        out2, held = _ffn_infer(p, cfg, h2)
        return x + _post(p, cfg, "ln2_post", out2), held


def apply_decode_paged(p, cfg, kind: str, x, pool, block_tables, pos, *,
                       angles, layer=None):
    """Single-token decode against a paged pool (GQA k/v or latent
    rows).  ``layer`` names the row when ``pool`` is a scanned segment's
    stack.  Returns (x, pool, pairs per token on held experts)."""
    mod, name = _paged_mixer(kind)
    with jax.named_scope("qkv"):
        h = nn.rmsnorm(x, p["ln1"]["scale"], cfg.norm_eps)
    out, pool = mod.apply_decode_paged(p[name], cfg, h, pool, block_tables,
                                       pos, angles=angles, layer=layer)
    with jax.named_scope("out_proj"):
        x = x + _post(p, cfg, "ln1_post", out)
    x, held = _paged_mlp(p, cfg, x)
    return x, pool, held


def apply_prefill_paged(p, cfg, kind: str, x, pool, block_row, start,
                        n_valid, *, angles, layer=None):
    """Prefill slice against a paged pool (chunk, prefix-cache suffix,
    verify): x (1,W,D) tokens at positions start..start+W-1 attend the
    sequence's earlier state through the block row; ``layer`` as in
    :func:`apply_decode_paged`.  Returns (x, pool, held pairs)."""
    mod, name = _paged_mixer(kind)
    with jax.named_scope("qkv"):
        h = nn.rmsnorm(x, p["ln1"]["scale"], cfg.norm_eps)
    out, pool = mod.apply_prefill_paged(p[name], cfg, h, pool, block_row,
                                        start, n_valid, angles=angles,
                                        layer=layer)
    with jax.named_scope("out_proj"):
        x = x + _post(p, cfg, "ln1_post", out)
    x, held = _paged_mlp(p, cfg, x)
    return x, pool, held


def paged_row_width(cfg, kind: str) -> int:
    """Elements one token occupies in a ``kind`` layer's pool."""
    return _paged_mixer(kind)[0].paged_row_width(cfg)


def paged_cache_init(cfg, kind: str, n_pages: int, page_size: int, dtype):
    return _paged_mixer(kind)[0].paged_cache_init(cfg, n_pages, page_size,
                                                  dtype)


def paged_cache_from_prefill(cfg, kind: str, pool, raw, block_row):
    """Scatter one sequence's prefill state (k/v, or c_kv and k_rope)
    into its pages."""
    return _paged_mixer(kind)[0].paged_cache_from_prefill(pool, *raw,
                                                          block_row)


def cache_init(cfg, kind: str, batch: int, max_len: int, dtype):
    if kind == ATTN:
        return attention.cache_init(cfg, batch, max_len, None, dtype)
    if kind == LOCAL:
        return attention.cache_init(cfg, batch, max_len, cfg.sliding_window,
                                    dtype)
    if kind == MLA:
        return mla_mod.cache_init(cfg, batch, max_len, dtype)
    if kind == RGLRU:
        return rglru_mod.cache_init(cfg, batch, dtype)
    if kind == RWKV6:
        return rwkv6_mod.cache_init(cfg, batch, dtype)
    raise ValueError(kind)


def cache_from_prefill(cfg, kind: str, raw, max_len: int):
    """Convert the prefill cache contribution into decode-ready form."""
    if kind == ATTN:
        k, v = raw
        return attention.cache_from_prefill(k, v, None, max_len)
    if kind == LOCAL:
        k, v = raw
        return attention.cache_from_prefill(k, v, cfg.sliding_window, max_len)
    if kind == MLA:
        ckv, k_rope = raw
        return mla_mod.cache_from_prefill(ckv, k_rope, max_len)
    return raw  # rglru / rwkv caches are already decode-ready
