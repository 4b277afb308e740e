"""Multi-head Latent Attention (DeepSeek-V2/V3) with weight-absorbed decode.

Prefill/train: latents are up-projected to per-head k/v and attention runs
as usual (blocked-flash).  Decode: the cache stores only the compressed
latent c_kv (kv_lora) + the shared rope key (qk_rope_head_dim); queries are
absorbed through kv_b so scores/outputs are computed directly in latent
space — cache is O(kv_lora + rope) per token instead of O(H * head_dim).

Paged serving keeps one latent row per token, ``[c_kv, k_rope]`` (kv_lora
+ rope wide), in a pool of pages (:class:`PagedLatentCache`, the page
axis third from last as the GQA pools have it).  Decode attends in latent
space over the rows the shared block-row gather
(``attention.gather_pages``) reads: multi-query attention over one
(kv_lora + rope)-wide key whose first kv_lora lanes are the value.  A
prefill slice of W tokens takes whichever of the two latent paths counts
fewer operations at W (:func:`absorbed_cheaper`): the absorbed one, or
up-projecting each key block through ``kv_b``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import modules as nn
from repro.parallel.sharding import logical_constraint

NEG_INF = attn.NEG_INF


class MLACache(NamedTuple):
    ckv: jnp.ndarray     # (B, T, kv_lora)
    k_rope: jnp.ndarray  # (B, T, rope_dim)


def init(key, cfg, dtype):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "q_a": nn.dense_init(ks[0], d, m.q_lora_rank, dtype),
        "q_a_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
        "q_b": nn.dense_init(ks[1], m.q_lora_rank, H * qd, dtype),
        "kv_a": nn.dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim,
                              dtype),
        "kv_a_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "kv_b": nn.dense_init(ks[3], m.kv_lora_rank,
                              H * (m.qk_nope_head_dim + m.v_head_dim), dtype),
        "wo": nn.dense_init(ks[4], H * m.v_head_dim, d, dtype,
                            scale=1.0 / max(1, cfg.n_layers) ** 0.5),
    }
    return p


def _project_q(p, cfg, x, angles):
    m = cfg.mla
    H = cfg.n_heads
    cq = nn.rmsnorm(nn.matmul(x, p["q_a"]), p["q_a_norm"], cfg.norm_eps)
    q = nn.matmul(cq, p["q_b"]).reshape(
        *x.shape[:-1], H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    if angles is not None:
        q_rope = _magnified(cfg, nn.apply_rope(q_rope, angles))
    return q_nope, q_rope


def _project_kv_latent(p, cfg, x, angles):
    m = cfg.mla
    lat = nn.matmul(x, p["kv_a"])
    ckv, k_rope = lat[..., :m.kv_lora_rank], lat[..., m.kv_lora_rank:]
    ckv = nn.rmsnorm(ckv, p["kv_a_norm"], cfg.norm_eps)
    if angles is not None:
        k_rope = _magnified(cfg, nn.apply_rope(k_rope[..., None, :],
                                               angles)[..., 0, :])
    return ckv, k_rope


def _magnified(cfg, x):
    """``x`` rotated, times YaRN's factor on cos and sin,
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` (no
    factor without YaRN, nor for DeepSeek-V3, whose two are equal)."""
    r = cfg.rope_scaling
    if r is None:
        return x
    mag = nn.yarn_mscale(r.factor, r.mscale) \
        / nn.yarn_mscale(r.factor, r.mscale_all_dim)
    return x if mag == 1.0 else (x * mag).astype(x.dtype)


def _scale(cfg) -> float:
    """Softmax scale: ``(qk_nope + qk_rope)^-1/2``, with YaRN times
    ``mscale(factor, mscale_all_dim)^2`` (DeepSeek-V3's attention:
    ``softmax_scale * mscale * mscale``)."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    r = cfg.rope_scaling
    if r is not None and r.mscale_all_dim:
        scale *= nn.yarn_mscale(r.factor, r.mscale_all_dim) ** 2
    return scale


def apply(p, cfg, x, *, angles, impl=None):
    """Train/prefill. Returns (out, (ckv, k_rope)) for cache building."""
    m = cfg.mla
    H = cfg.n_heads
    B, S, _ = x.shape
    impl = impl or cfg.impl

    q_nope, q_rope = _project_q(p, cfg, x, angles)
    ckv, k_rope = _project_kv_latent(p, cfg, x, angles)
    kv = nn.matmul(ckv, p["kv_b"]).reshape(
        B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = kv[..., :m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]

    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    # H-space core: all operands sharded on heads (H=128 divides TP)
    q = logical_constraint(q, "batch", None, "heads", None)
    k = logical_constraint(k, "batch", None, "heads", None)
    v = logical_constraint(v, "batch", None, "heads", None)

    kw = dict(causal=cfg.causal, window=None, scale=_scale(cfg),
              softcap=cfg.attn_softcap)
    if impl == "ref":
        o = attn.attend_ref(q, k, v_pad(v, q.shape[-1]), **kw)
    elif impl in ("blocked", "pallas"):
        o = attn.attend_blocked(q, k, v_pad(v, q.shape[-1]),
                                block_q=cfg.attn_block_q,
                                block_kv=cfg.attn_block_kv, **kw)
    else:
        raise ValueError(impl)
    o = o[..., :m.v_head_dim]  # un-pad v
    from repro.parallel.collectives import row_parallel
    out = row_parallel(o.reshape(B, S, H * m.v_head_dim), p["wo"])
    return out, (ckv, k_rope)


def v_pad(v, d):
    """Pad v head-dim so the generic attention helpers can be reused."""
    if v.shape[-1] == d:
        return v
    return jnp.pad(v, ((0, 0),) * (v.ndim - 1) + ((0, d - v.shape[-1]),))


# ---------------------------------------------------------------------------
# decode with absorbed weights + latent cache
# ---------------------------------------------------------------------------
def _kv_b_split(p, cfg):
    m = cfg.mla
    H = cfg.n_heads
    kv_b = p["kv_b"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim)
    w_uk = kv_b[..., :m.qk_nope_head_dim]   # (lora, H, nope)
    w_uv = kv_b[..., m.qk_nope_head_dim:]   # (lora, H, v)
    return w_uk, w_uv


def cache_init(cfg, batch: int, max_len: int, dtype):
    m = cfg.mla
    return MLACache(
        ckv=jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        k_rope=jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype))


def cache_from_prefill(ckv, k_rope, max_len):
    B, S = ckv.shape[:2]
    pad = max_len - S
    if pad > 0:
        ckv = jnp.pad(ckv, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    return MLACache(ckv, k_rope)


def _decode_scores_local(q_lat, q_rope, ckv, k_rope, valid, cfg):
    """Partial absorbed-attention over a latent-cache slice.
    Returns (m, l, acc_lat (B,H,lora))."""
    s = jnp.einsum("bhl,btl->bht", q_lat.astype(ckv.dtype), ckv,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhd,btd->bht", q_rope, k_rope,
                       preferred_element_type=jnp.float32)
    s = s * _scale(cfg)
    s = nn.softcap(s, cfg.attn_softcap)
    s = jnp.where(valid[None, None], s, NEG_INF)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    acc = jnp.einsum("bht,btl->bhl", p.astype(ckv.dtype), ckv,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def _absorb(p, cfg, q_nope, q_rope, dtype):
    """Latent-space queries (..., H, kv_lora + rope): ``q_nope`` through
    ``w_uk`` (``q_lat[h] = q_nope[h] @ w_uk[:, h, :]^T``) beside the
    rotated ``q_rope``, so that one product with a latent row
    ``[c_kv, k_rope]`` gives the whole score."""
    w_uk, _ = _kv_b_split(p, cfg)
    q_lat = jnp.einsum("...hd,lhd->...hl", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat.astype(dtype), q_rope.astype(dtype)], -1)


def _latent_attend(q, rows, valid, cfg):
    """Absorbed attention of latent queries q (B,H,C) over latent rows
    (B,T,C) where ``valid`` (B,T): multi-query attention whose values
    are the rows' first kv_lora lanes.  Returns (B,H,kv_lora) float32."""
    lora = cfg.mla.kv_lora_rank
    s = jnp.einsum("bhc,btc->bht", q, rows,
                   preferred_element_type=jnp.float32) * _scale(cfg)
    s = nn.softcap(s, cfg.attn_softcap)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    # the product over whole rows, then the value lanes: slicing the rows
    # first would copy every gathered row
    return jnp.einsum("bht,btc->bhc", w.astype(rows.dtype), rows,
                      preferred_element_type=jnp.float32)[..., :lora]


def _latent_out(p, cfg, o_lat, dtype):
    """Latent attention outputs (..., H, kv_lora) through ``w_uv`` and
    ``wo``."""
    m = cfg.mla
    _, w_uv = _kv_b_split(p, cfg)
    o = jnp.einsum("...hl,lhv->...hv", o_lat.astype(dtype), w_uv,
                   preferred_element_type=jnp.float32).astype(dtype)
    return nn.matmul(o.reshape(*o.shape[:-2], cfg.n_heads * m.v_head_dim),
                     p["wo"])


def apply_decode(p, cfg, x, cache: MLACache, pos, *, angles):
    """x (B,1,D). Absorbed-weight decode in latent space.

    With a mesh, the latent cache is time-sharded over "model" (split-T)
    and the partial softmax stats merge with (B,H)-sized psums.
    """
    q_nope, q_rope = _project_q(p, cfg, x, angles)       # (B,1,H,nope/rope)
    ckv_new, k_rope_new = _project_kv_latent(p, cfg, x, angles)
    ckv = jax.lax.dynamic_update_slice_in_dim(cache.ckv, ckv_new, pos, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(
        cache.k_rope, k_rope_new, pos, axis=1)

    from repro.models.attention import _split_t_applicable
    from repro.parallel.sharding import current_env
    env = current_env()
    T = ckv.shape[1]
    if _split_t_applicable(env, T):
        from repro.models.moe import _shard_map
        w_uk, _ = _kv_b_split(p, cfg)
        # absorb: q_lat[h] = q_nope[h] @ w_uk[:,h,:]^T  -> latent-space query
        q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0], w_uk,
                           preferred_element_type=jnp.float32)  # (B,H,lora)
        axes = env.resolve("seq_sp")
        axes = (axes,) if isinstance(axes, str) else tuple(axes)

        def body(q_lat_l, q_rope_l, ckv_l, kr_l):
            idx = jax.lax.axis_index(axes[0])
            Tl = ckv_l.shape[1]
            valid = idx * Tl + jnp.arange(Tl) <= pos
            mm, ll, acc = _decode_scores_local(q_lat_l, q_rope_l[:, 0],
                                               ckv_l, kr_l, valid, cfg)
            m_g = jax.lax.pmax(mm, axes)
            corr = jnp.exp(mm - m_g)
            l_g = jax.lax.psum(ll * corr, axes)
            acc_g = jax.lax.psum(acc * corr[..., None], axes)
            return acc_g / jnp.maximum(l_g[..., None], 1e-37)

        o_lat = _shard_map(
            body, mesh=env.mesh,
            in_specs=(env.spec("batch", None, None),
                      env.spec("batch", None, None, None),
                      env.spec("batch", "seq_sp", None),
                      env.spec("batch", "seq_sp", None)),
            out_specs=env.spec("batch", None, None),
            check_vma=False)(q_lat, q_rope, ckv, k_rope)
    else:
        # the paged decode's arithmetic (apply_decode_paged), on the slab
        q = _absorb(p, cfg, q_nope[:, 0], q_rope[:, 0], ckv.dtype)
        rows = jnp.concatenate([ckv, k_rope], -1)
        o_lat = _latent_attend(q, rows, attn.causal_valid(
            T, jnp.broadcast_to(pos, (x.shape[0],))), cfg)
    return _latent_out(p, cfg, o_lat[:, None], x.dtype), MLACache(ckv, k_rope)


# ---------------------------------------------------------------------------
# paged latent pool: one [c_kv, k_rope] row per token, in pages
# ---------------------------------------------------------------------------
# keys per block of a prefill slice's latent attention: its scores are
# (H, W, KEY_BLOCK) float32, 268 MB at DeepSeek-V3's 128 heads and W=512
KEY_BLOCK = 1024


class PagedLatentCache(NamedTuple):
    # (n_pages, page_size, kv_lora + rope), or (n_layers, n_pages,
    # page_size, kv_lora + rope) for a scanned segment's stack: the page
    # axis third from last, as PagedAttnCache's
    lat: jnp.ndarray


def latent_width(cfg) -> int:
    return cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim


def paged_row_width(cfg) -> int:
    """Elements one token occupies in a layer's pool: one latent row."""
    return latent_width(cfg)


def paged_cache_init(cfg, n_pages: int, page_size: int, dtype):
    return PagedLatentCache(
        lat=jnp.zeros((n_pages, page_size, latent_width(cfg)), dtype))


def _latent_rows(p, cfg, x, angles):
    """Queries (q_nope, q_rope) and the new latent rows (..., S, C) of
    x's tokens."""
    q_nope, q_rope = _project_q(p, cfg, x, angles)
    ckv, k_rope = _project_kv_latent(p, cfg, x, angles)
    return q_nope, q_rope, jnp.concatenate([ckv, k_rope], -1)


def paged_cache_from_prefill(pool: PagedLatentCache, ckv, k_rope,
                             block_row):
    """Scatter one sequence's prefill latents (1,S,kv_lora) and
    (1,S,rope) (a stack's: (L,1,S,...)) into its pages, every layer of a
    stack in one scatter at ``(layer, page, slot)``, in place."""
    rows = jnp.concatenate([ckv, k_rope], -1)
    S = rows.shape[-2]
    ps = pool.lat.shape[-2]
    t = jnp.arange(S)
    at = (block_row[t // ps], t % ps)
    if pool.lat.ndim == 4:
        L = pool.lat.shape[0]
        at = (jnp.repeat(jnp.arange(L), S),) + tuple(jnp.tile(i, L)
                                                     for i in at)
    return PagedLatentCache(
        lat=pool.lat.at[at].set(rows.reshape(-1, rows.shape[-1])))


def apply_decode_paged(p, cfg, x, pool: PagedLatentCache, block_tables,
                       pos, *, angles, layer=None):
    """Paged absorbed decode: x (B,1,D), pos (B,); ``layer`` names the
    row of a stacked pool.  The new latent row is written at ``pos``;
    the sequence's rows come through ``attention.gather_pages`` (the
    block-row gather GQA decode uses) and are attended in latent space.
    Scopes ``qkv``, ``kv_append``, ``kv_gather``, ``attend``,
    ``out_proj``.  Returns (out, new pool)."""
    B = x.shape[0]
    with jax.named_scope("qkv"):
        q_nope, q_rope, row = _latent_rows(p, cfg, x, angles)
        q = _absorb(p, cfg, q_nope[:, 0], q_rope[:, 0], pool.lat.dtype)
    with jax.named_scope("kv_append"):
        ps = pool.lat.shape[-2]
        page = jnp.take_along_axis(block_tables, (pos // ps)[:, None],
                                   axis=1)[:, 0]
        pool = PagedLatentCache(lat=pool.lat.at[
            attn._at(layer, page, pos % ps)].set(row.reshape(B, -1)))
    with jax.named_scope("kv_gather"):
        rows = attn.gather_pages(pool.lat, block_tables, layer)
    with jax.named_scope("attend"):
        o_lat = _latent_attend(q, rows, attn.causal_valid(rows.shape[1],
                                                          pos), cfg)
    with jax.named_scope("out_proj"):
        return _latent_out(p, cfg, o_lat[:, None], x.dtype), pool


def absorbed_cheaper(cfg, W: int) -> bool:
    """Whether a slice of ``W`` queries counts fewer operations attending
    in latent space than through keys and values up-projected by
    ``kv_b``.  Per key and layer, with C = kv_lora + rope:

        absorbed:      W * 2H(C + kv_lora)
        up-projected:  W * 2H(nope + rope + v) + 2 kv_lora H (nope + v)

    (DeepSeek-V3: 278.5 k and 81.9 k per query, 33.5 M per key), both
    linear in the keys, so the choice rests on W alone (absorbed up to
    W = 170 there)."""
    m, H = cfg.mla, cfg.n_heads
    C = m.kv_lora_rank + m.qk_rope_head_dim
    absorbed = W * 2 * H * (C + m.kv_lora_rank)
    up = W * 2 * H * (m.qk_nope_head_dim + m.qk_rope_head_dim
                      + m.v_head_dim) \
        + 2 * m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
    return absorbed <= up


def _attend_blocks(p, cfg, q, pool, block_row, start, n_valid, *,
                   absorbed: bool, layer):
    """Causal attention of one sequence's W queries at positions
    ``start ..`` over its latent rows, KEY_BLOCK keys at a time, only
    the blocks up to ``start + n_valid`` (a loop of traced length), with
    an online softmax.  ``absorbed``: q (W,H,C) latent queries against
    the rows; else q (W,H,nope+rope) against keys and values each block
    up-projects through ``kv_b``.  Returns (W,H,kv_lora or v) float32."""
    m, H = cfg.mla, cfg.n_heads
    lora, nope = m.kv_lora_rank, m.qk_nope_head_dim
    W = q.shape[0]
    ps = pool.lat.shape[-2]
    per = max(1, KEY_BLOCK // ps)                      # pages per block
    blk = per * ps
    row = jnp.pad(block_row, (0, -block_row.shape[0] % per))
    qpos = start + jnp.arange(W)
    dv = pool.lat.shape[-1] if absorbed else m.v_head_dim
    dt = pool.lat.dtype
    kv_b = p["kv_b"].reshape(lora, H, nope + m.v_head_dim)

    def block(i, carry):
        mx, l, acc = carry
        with jax.named_scope("kv_gather"):
            rows = attn.gather_pages(
                pool.lat, jax.lax.dynamic_slice(row, (i * per,), (per,)),
                layer)                                  # (blk, C)
        with jax.named_scope("attend"):
            if absorbed:
                s = jnp.einsum("whc,tc->hwt", q, rows,
                               preferred_element_type=jnp.float32)
                v = rows                     # the value lanes cut at the end
            else:
                kv = jnp.einsum("tl,lhd->thd", rows[:, :lora], kv_b,
                                preferred_element_type=jnp.float32
                                ).astype(dt)
                k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                    rows[:, None, lora:], (blk, H, rows.shape[-1] - lora))],
                    -1)
                v = kv[..., nope:]
                s = jnp.einsum("whd,thd->hwt", q, k,
                               preferred_element_type=jnp.float32)
            s = nn.softcap(s * _scale(cfg), cfg.attn_softcap)
            s = jnp.where(attn.causal_valid(blk, qpos - i * blk)[None], s,
                          NEG_INF)
            mx_new = jnp.maximum(mx, s.max(-1))
            e = jnp.exp(s - mx_new[..., None])
            corr = jnp.exp(mx - mx_new)
            pv = jnp.einsum("hwt,tl->hwl" if absorbed else "hwt,thl->hwl",
                            e.astype(dt), v,
                            preferred_element_type=jnp.float32)
            return mx_new, l * corr + e.sum(-1), acc * corr[..., None] + pv

    n_blocks = (start + n_valid + blk - 1) // blk
    init = (jnp.full((H, W), NEG_INF, jnp.float32),
            jnp.zeros((H, W), jnp.float32),
            jnp.zeros((H, W, dv), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, init)
    with jax.named_scope("attend"):
        o = jnp.moveaxis(acc / l[..., None], 0, 1)
        return o[..., :lora] if absorbed else o


def apply_prefill_paged(p, cfg, x, pool: PagedLatentCache, block_row, start,
                        n_valid, *, angles, layer=None):
    """Paged prefill slice (chunk, suffix after a prefix hit, speculative
    verify): x (1,W,D) at positions ``start ..``; its latent rows are
    scattered into the sequence's pages (padding past ``n_valid`` to the
    null page), then it attends causally over the sequence's rows by
    the latent path with fewer counted operations at W
    (:func:`absorbed_cheaper`).  Returns (out (1,W,D), new pool)."""
    W = x.shape[1]
    absorbed = absorbed_cheaper(cfg, W)
    with jax.named_scope("qkv"):
        q_nope, q_rope, rows = _latent_rows(p, cfg, x, angles)
        dt = pool.lat.dtype
        q = _absorb(p, cfg, q_nope[0], q_rope[0], dt) if absorbed else \
            jnp.concatenate([q_nope[0], q_rope[0]], -1).astype(dt)
    with jax.named_scope("kv_append"):
        ps = pool.lat.shape[-2]
        t = start + jnp.arange(W)
        idx = jnp.clip(t // ps, 0, block_row.shape[0] - 1)
        # physical page 0 is the null page: padding lands there, masked
        page = jnp.where(jnp.arange(W) < n_valid, block_row[idx], 0)
        pool = PagedLatentCache(lat=pool.lat.at[
            attn._at(layer, page, t % ps)].set(rows[0]))
    o = _attend_blocks(p, cfg, q, pool, block_row, start, n_valid,
                       absorbed=absorbed, layer=layer)
    with jax.named_scope("out_proj"):
        if absorbed:
            return _latent_out(p, cfg, o[None], x.dtype), pool
        o = o.astype(x.dtype).reshape(1, W, cfg.n_heads * cfg.mla.v_head_dim)
        return nn.matmul(o, p["wo"]), pool
