"""GQA attention: reference, blocked-flash (lax.scan), and decode paths.

Sharding strategy (chosen from dry-run HLO attribution, see EXPERIMENTS.md
§Perf): the attention core runs in *H-space* — q sharded on query heads
over the "model" axis, k/v all-gathered (replicated) and expanded to H
before the core.  All block-scan einsums are then shard-local: zero
collectives inside the flash loops (one AG for k/v + the Megatron-SP
AG/RS per layer remain).  Non-divisible head counts (40, 28, 10 over
TP=16) pad intermediates only.

Three implementations (cfg.impl):
  ref     — naive (S,S) scores; oracle for tests.
  blocked — q-block x kv-block online-softmax scan; bounded memory; the
            dry-run lowers this one.  Sliding-window layers slice only the
            kv window per q block (sub-quadratic compute in the HLO).
  pallas  — TPU kernel (kernels/flash_attention.py) via kernels.ops.

Caches are stored FLAT (B, T, Kv*hd) so the "model" axis always divides
top-level cache shardings (kv-head counts like 8 don't divide TP=16).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import modules as nn
from repro.parallel.sharding import logical_constraint

NEG_INF = -2.0 ** 30  # large-negative that survives bf16 arithmetic


class AttnCache(NamedTuple):
    # FLAT (B, T, Kv*hd); T = max len (global) or window (local)
    k: jnp.ndarray
    v: jnp.ndarray


def init(key, cfg, dtype):
    ks = jax.random.split(key, 6)
    d, hd = cfg.d_model, cfg.head_dim
    p = {
        "wq": nn.dense_init(ks[0], d, cfg.n_heads * hd, dtype),
        "wk": nn.dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype),
        "wv": nn.dense_init(ks[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": nn.dense_init(ks[3], cfg.n_heads * hd, d, dtype,
                            scale=1.0 / max(1, cfg.n_layers) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def _scale(cfg) -> float:
    return cfg.attn_logit_scale if cfg.attn_logit_scale is not None \
        else cfg.head_dim ** -0.5


def expand_kv(k, n_heads: int):
    """(B,T,Kv,hd) -> (B,T,H,hd) by repeating each kv head G times."""
    B, T, Kv, hd = k.shape
    G = n_heads // Kv
    if G == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, T, Kv, G, hd))
    return k.reshape(B, T, n_heads, hd)


def _qkv(p, cfg, x, angles):
    """Project (fused column-parallel) + head-split + qk-norm + rope.

    x arrives sequence-sharded; one AG inside column_parallel.  Returns
    q (B,S,H,hd) sharded on heads, k/v (B,S,Kv,hd) replicated over the
    model axis so the blocked core stays shard-local.
    """
    from repro.parallel.collectives import column_parallel
    qf, kf, vf = column_parallel(x, [p["wq"], p["wk"], p["wv"]])
    q = _split_heads(qf, cfg.n_heads, cfg.head_dim)
    k = _split_heads(kf, cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(vf, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = nn.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = nn.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope and angles is not None:
        q = nn.apply_rope(q, angles)
        k = nn.apply_rope(k, angles)
    q = logical_constraint(q, "batch", None, "heads", None)
    k = logical_constraint(k, "batch", None, None, None)
    v = logical_constraint(v, "batch", None, None, None)
    return q, k, v


# ---------------------------------------------------------------------------
# reference implementation — full (S, S) scores, H-space
# ---------------------------------------------------------------------------
def _mask_bias(S: int, causal: bool, window: Optional[int]) -> jnp.ndarray:
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    ok = jnp.ones((S, S), bool)
    if causal:
        ok &= j <= i
    if window is not None:
        ok &= (i - j) < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attend_ref(q, k, v, *, causal, window, scale, softcap):
    """q (B,S,H,hd); k,v (B,S,H,hd) pre-expanded -> (B,S,H,hd)."""
    B, S, H, hd = q.shape
    s = jnp.einsum("bqhd,bthd->bhqt", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = nn.softcap(s, softcap)
    s = s + _mask_bias(S, causal, window)[None, None]
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bhqt,bthd->bqhd", w.astype(q.dtype), v,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o


# ---------------------------------------------------------------------------
# blocked flash — bounded memory, scan over q and kv blocks (H-space)
# ---------------------------------------------------------------------------
def attend_blocked(q, k, v, *, causal, window, scale, softcap,
                   block_q: int, block_kv: int):
    B, S, H, hd = q.shape
    bq = min(block_q, S)
    while S % bq:
        bq -= 1
    bkv = min(block_kv, S)
    while S % bkv:
        bkv -= 1
    nq, nkv = S // bq, S // bkv

    if window is not None and window + bq < S and causal:
        # sliding-window fast path: slices just the kv window per q block
        return _attend_local_blocked(q, k, v, causal=causal, window=window,
                                     scale=scale, softcap=softcap, bq=bq)

    qb = jnp.moveaxis(q.reshape(B, nq, bq, H, hd), 1, 0)     # (nq,B,bq,H,hd)
    kb = jnp.moveaxis(k.reshape(B, nkv, bkv, H, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nkv, bkv, H, hd), 1, 0)

    def q_block(qi, q_blk):
        m0 = jnp.full((B, H, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        a0 = jnp.zeros((B, H, bq, hd), jnp.float32)

        def kv_step(carry, inputs):
            m, l, acc = carry
            ki, k_blk, v_blk = inputs
            s = jnp.einsum("bqhd,bthd->bhqt", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            s = nn.softcap(s, softcap)
            iq = qi * bq + jnp.arange(bq)
            jk = ki * bkv + jnp.arange(bkv)
            ok = jnp.ones((bq, bkv), bool)
            if causal:
                ok &= jk[None, :] <= iq[:, None]
            if window is not None:
                ok &= (iq[:, None] - jk[None, :]) < window
            s = jnp.where(ok[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqt,bthd->bhqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (jnp.arange(nkv), kb, vb))
        out = acc / jnp.maximum(l[..., None], 1e-37)
        return jnp.moveaxis(out, 1, 2)  # (B, bq, H, hd)

    def scan_q(_, inputs):
        qi, q_blk = inputs
        return None, q_block(qi, q_blk)

    _, outs = jax.lax.scan(scan_q, None, (jnp.arange(nq), qb))
    o = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)
    return o.astype(q.dtype)


def _attend_local_blocked(q, k, v, *, causal, window, scale, softcap, bq):
    """Sliding-window attention: per q block, slice only the kv window.

    Compute in the HLO is O(S * (window + bq)) — genuinely sub-quadratic,
    which is what makes recurrentgemma long_500k-eligible.
    """
    B, S, H, hd = q.shape
    nq = S // bq
    span = window + bq
    qb = jnp.moveaxis(q.reshape(B, nq, bq, H, hd), 1, 0)

    def q_block(carry, inputs):
        qi, q_blk = inputs
        start = jnp.clip(qi * bq + bq - span, 0, S - span)
        k_w = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        v_w = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        s = jnp.einsum("bqhd,bthd->bhqt", q_blk, k_w,
                       preferred_element_type=jnp.float32) * scale
        s = nn.softcap(s, softcap)
        iq = qi * bq + jnp.arange(bq)
        jk = start + jnp.arange(span)
        ok = jnp.ones((bq, span), bool)
        if causal:
            ok &= jk[None, :] <= iq[:, None]
        ok &= (iq[:, None] - jk[None, :]) < window
        s = jnp.where(ok[None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqt,bthd->bqhd", w.astype(v_w.dtype), v_w,
                       preferred_element_type=jnp.float32)
        return carry, o

    _, outs = jax.lax.scan(q_block, None, (jnp.arange(nq), qb))
    o = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# decode — one query against the cache
# ---------------------------------------------------------------------------
# The production cache is sharded over the "model" axis on the TIME dim
# (split-T / flash-decoding): each shard scans its slice and the partial
# online-softmax stats (m, l, acc) merge with (B,H)-sized psums — the
# cache bytes never move.  (The first layout — flat features — made GSPMD
# repartition gigabytes of cache per token whenever kv_heads < TP; see
# EXPERIMENTS.md §Perf iteration 5.)

def _decode_partial(q, k, v, valid, *, scale, softcap, n_kv):
    """Partial attention over a cache slice. q (B,1,H,hd);
    k/v (B,Tl,Kv*hd); valid (Tl,) bool. Returns (m, l, acc)."""
    B, _, H, hd = q.shape
    Tl = k.shape[1]
    Kv = n_kv
    kk = k.reshape(B, Tl, Kv, hd)
    vv = v.reshape(B, Tl, Kv, hd)
    qg = q.reshape(B, Kv, H // Kv, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, kk,
                   preferred_element_type=jnp.float32) * scale
    s = nn.softcap(s, softcap)
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    m = s.max(-1)                                       # (B,Kv,G)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    acc = jnp.einsum("bkgt,btkd->bkgd", p.astype(vv.dtype), vv,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def attend_decode_sharded(q, cache: AttnCache, pos, *, window, scale,
                          softcap, n_kv: int, env):
    """Split-T decode via shard_map (cache time-sharded over "model")."""
    from repro.models.moe import _shard_map
    axes = env.resolve("seq_sp")
    axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
    B, _, H, hd = q.shape
    T = cache.k.shape[1]

    def body(q_l, k_l, v_l):
        idx = jax.lax.axis_index(axes[0])
        Tl = k_l.shape[1]
        slots = idx * Tl + jnp.arange(Tl)
        if window is None:
            valid = slots <= pos
        else:
            abs_pos = pos - ((pos - slots) % T)
            valid = (abs_pos >= 0) & (abs_pos > pos - window)
        m, l, acc = _decode_partial(q_l, k_l, v_l, valid, scale=scale,
                                    softcap=softcap, n_kv=n_kv)
        m_g = jax.lax.pmax(m, axes)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axes)
        acc_g = jax.lax.psum(acc * corr[..., None], axes)
        o = acc_g / jnp.maximum(l_g[..., None], 1e-37)
        return o.astype(q_l.dtype).reshape(q_l.shape[0], 1, H * hd)

    return _shard_map(
        body, mesh=env.mesh,
        in_specs=(env.spec("batch", None, None, None),
                  env.spec("batch", "seq_sp", None),
                  env.spec("batch", "seq_sp", None)),
        out_specs=env.spec("batch", None, None),
        check_vma=False)(q, cache.k, cache.v)


def _split_t_applicable(env, T: int) -> bool:
    if env is None:
        return False
    axes = env.resolve("seq_sp")
    axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
    if len(axes) != 1:
        return False
    n = env.mesh.shape[axes[0]]
    return n > 1 and T % n == 0


def attend_decode(q, cache: AttnCache, pos, *, window, scale, softcap,
                  n_kv: int):
    """q (B,1,H,hd); cache.k/v FLAT (B,T,Kv*hd); pos scalar int32.

    Global cache: slot = t, valid slots are <= pos.
    Local (ring) cache: slot = t % W; valid = slot abs-position in window.
    """
    from repro.parallel.sharding import current_env
    env = current_env()
    if _split_t_applicable(env, cache.k.shape[1]):
        return attend_decode_sharded(q, cache, pos, window=window,
                                     scale=scale, softcap=softcap,
                                     n_kv=n_kv, env=env)
    B, _, H, hd = q.shape
    T = cache.k.shape[1]
    Kv = n_kv
    G = H // Kv
    k = cache.k.reshape(B, T, Kv, hd)
    v = cache.v.reshape(B, T, Kv, hd)
    qg = q.reshape(B, Kv, G, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = nn.softcap(s, softcap)
    slots = jnp.arange(T)
    if window is None:
        ok = slots <= pos
    else:
        abs_pos = pos - ((pos - slots) % T)  # T == window for ring caches
        ok = (abs_pos >= 0) & (abs_pos > pos - window)
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o.reshape(B, 1, H * hd)


# ---------------------------------------------------------------------------
# paged decode — the KV cache as fixed-size pages named by a block table
# ---------------------------------------------------------------------------
# Swallow §X-B overlay of shared memory on distributed memory, applied to
# the KV cache: instead of one (B, T) slab per sequence, k/v live in a
# pool of (page_size, Kv*hd) pages and each sequence owns a block-index
# table row.  Page ownership follows core/memory_server.striped_owner —
# the serving allocator (repro.serving.paged_kv) is the host-side half.
# Physical page 0 is reserved as the null page: padded block-table slots
# point at it and their contribution is masked out exactly (the masked
# exp underflows to 0.0), so garbage there never reaches a real token.

class PagedAttnCache(NamedTuple):
    # k/v pools, FLAT features: (n_pages, page_size, Kv*hd), or
    # (n_layers, n_pages, page_size, Kv*hd) for a scanned segment's stack
    k: jnp.ndarray
    v: jnp.ndarray


def _at(layer, *idx):
    """Index of ``idx`` in one layer's pool: the layer's own (P, ps, F)
    pool when ``layer`` is None, else row ``layer`` of the stacked
    (L, P, ps, F) pool.  Indexing the stack in ONE scatter or gather is
    what keeps a scanned segment's pools in their buffer: slicing the
    layer's slab out first materialises it."""
    return idx if layer is None else (layer,) + idx


def gather_pages(leaf, block_tables, layer=None):
    """The rows of the pages ``block_tables`` (..., nmax) names, in
    order: (..., nmax * page_size, F) from a pool leaf (P, ps, F), or
    from row ``layer`` of its stack (``_at``), in one gather.  The
    paged decode of every pool kind (GQA keys and values, latent rows)
    reads its sequences through this."""
    rows = leaf[_at(layer, block_tables)]
    return rows.reshape(*block_tables.shape[:-1], -1, leaf.shape[-1])


def causal_valid(T: int, pos):
    """(…, T) mask of the first T key positions a query at ``pos`` (…,)
    attends to: key t is valid where ``t <= pos``."""
    return jnp.arange(T) <= pos[..., None]


def paged_row_width(cfg) -> int:
    """Elements one token occupies in a layer's pool: its k and v."""
    return 2 * cfg.n_kv_heads * cfg.head_dim


def paged_cache_init(cfg, n_pages: int, page_size: int, dtype):
    shape = (n_pages, page_size, cfg.n_kv_heads * cfg.head_dim)
    return PagedAttnCache(k=jnp.zeros(shape, dtype),
                          v=jnp.zeros(shape, dtype))


def paged_cache_update(pool: PagedAttnCache, k_new, v_new, block_tables,
                       pos, layer=None):
    """Write step-t k/v into page ``block_tables[b, t//ps]``, slot t%ps.

    k_new/v_new (B, 1, Kv, hd); block_tables (B, nmax) int32; pos (B,)
    int32 per-sequence write position; ``layer`` the row of a stacked
    pool (``_at``).  Inactive batch slots must point at the null page
    (their writes collide there harmlessly).
    """
    B = k_new.shape[0]
    ps = pool.k.shape[-2]
    k_new = k_new.reshape(B, -1)
    v_new = v_new.reshape(B, -1)
    page = jnp.take_along_axis(block_tables, (pos // ps)[:, None],
                               axis=1)[:, 0]
    slot = pos % ps
    idx = _at(layer, page, slot)
    return PagedAttnCache(k=pool.k.at[idx].set(k_new),
                          v=pool.v.at[idx].set(v_new))


def _paged_shard_axes(env, n_pages: int):
    """The mesh axis the page pools stripe over, or None when the
    single-device path applies (no env, trivial axis, or a page count
    the stripe cannot divide)."""
    if env is None:
        return None
    axes = env.resolve("pages")
    axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
    if len(axes) != 1:
        return None
    n = env.mesh.shape[axes[0]]
    if n <= 1 or n_pages % n:
        return None
    return axes


def attend_decode_paged_sharded(q, pool: PagedAttnCache, block_tables,
                                pos, *, scale, softcap, n_kv: int, env,
                                axes, impl=None, layer=None):
    """Striped-pool decode via shard_map (pages sharded over "model").

    Each stripe owner attends over only the pages whose physical slab
    rows fall inside its contiguous shard ``[j*P/n, (j+1)*P/n)`` (the
    ``stripe_slab_index`` layout: logical page p lives on node p % n),
    then the per-stripe online-softmax partials (m, l, acc) merge with
    (B,Kv,G)-sized psums — the split-T decode idiom applied to the page
    axis, so the pool bytes never leave their owner node.  Block-table
    entries arriving here are already *physical* slab rows (the engine
    translates at the device boundary), so ownership is a range test.
    Exactness: stripes a sequence doesn't touch contribute m = NEG_INF,
    and exp(NEG_INF - m_global) underflows to exactly 0.0 — the merge
    adds nothing, matching the single-device masked softmax on the
    valid slots.  A stacked pool stripes its page axis the same way and
    comes with ``layer`` (replicated): each owner gathers ``(layer, local
    page)`` from its shard of the stack in one gather.
    """
    from repro.models.moe import _shard_map
    B, _, H, hd = q.shape
    Pn, ps = pool.k.shape[-3], pool.k.shape[-2]
    Kv = n_kv
    n = env.mesh.shape[axes[0]]
    L = Pn // n
    nmax = block_tables.shape[1]
    T = nmax * ps

    def body(q_l, k_l, v_l, bt_l, pos_l, *lay):
        lay = lay[0] if lay else None
        j = jax.lax.axis_index(axes[0])
        local = bt_l - j * L
        mine = (local >= 0) & (local < L)          # (B, nmax)
        safe = jnp.where(mine, local, 0)
        if impl == "pallas":
            from repro.kernels import ops as kops
            if lay is not None:
                k_l, v_l = k_l[lay], v_l[lay]
            with jax.named_scope("attend"):
                acc, m, l = kops.paged_decode_attention(
                    q_l.reshape(B, H, hd), k_l.reshape(L, ps, Kv, hd),
                    v_l.reshape(L, ps, Kv, hd), safe, pos_l,
                    scale=scale, softcap=softcap,
                    page_mask=mine.astype(jnp.int32), partials=True)
        else:
            with jax.named_scope("kv_gather"):
                k = k_l[_at(lay, safe)].reshape(B, T, Kv, hd)
                v = v_l[_at(lay, safe)].reshape(B, T, Kv, hd)
            with jax.named_scope("attend"):
                qg = q_l.reshape(B, Kv, H // Kv, hd)
                s = jnp.einsum("bkgd,btkd->bkgt", qg, k,
                               preferred_element_type=jnp.float32) * scale
                s = nn.softcap(s, softcap)
                valid = (jnp.arange(T)[None, :] <= pos_l[:, None]) \
                    & jnp.repeat(mine, ps, axis=1)
                s = jnp.where(valid[:, None, None, :], s, NEG_INF)
                m = s.max(-1)                               # (B,Kv,G)
                p = jnp.exp(s - m[..., None])
                l = p.sum(-1)
                acc = jnp.einsum("bkgt,btkd->bkgd", p.astype(v.dtype), v,
                                 preferred_element_type=jnp.float32)
        with jax.named_scope("attend"):
            m_g = jax.lax.pmax(m, axes)
            corr = jnp.exp(m - m_g)
            l_g = jax.lax.psum(l * corr, axes)
            acc_g = jax.lax.psum(acc * corr[..., None], axes)
            o = acc_g / jnp.maximum(l_g[..., None], 1e-37)
            return o.astype(q_l.dtype).reshape(B, 1, H * hd)

    from jax.sharding import PartitionSpec
    pool_spec = env.spec(*(None,) * (pool.k.ndim - 3), "pages", None, None)
    repl = PartitionSpec()
    lay = () if layer is None else (layer,)
    return _shard_map(
        body, mesh=env.mesh,
        in_specs=(repl, pool_spec, pool_spec, repl, repl) + (repl,) * len(lay),
        out_specs=repl, check_vma=False)(q, pool.k, pool.v,
                                         block_tables, pos, *lay)


def attend_decode_paged(q, pool: PagedAttnCache, block_tables, pos, *,
                        scale, softcap, n_kv: int, impl=None, layer=None):
    """q (B,1,H,hd); pool pages (P,ps,Kv*hd), or a stack of them with
    ``layer`` naming the row (``_at``); pos (B,) int32.

    Gathers the sequence's pages through the block table and runs the
    same masked decode attention as the dense path — identical arithmetic
    on the valid slots, so paged and dense decode agree token-for-token.
    Under a mesh with a non-trivial "pages" stripe the owner-partial
    shard_map path runs instead (same math, per-stripe partials merged).
    """
    from repro.parallel.sharding import current_env
    env = current_env()
    axes = _paged_shard_axes(env, pool.k.shape[-3])
    if axes is not None:
        return attend_decode_paged_sharded(
            q, pool, block_tables, pos, scale=scale, softcap=softcap,
            n_kv=n_kv, env=env, axes=axes, impl=impl, layer=layer)
    B, _, H, hd = q.shape
    ps = pool.k.shape[-2]
    Kv = n_kv
    if impl == "pallas":
        from repro.kernels import ops as kops
        P_ = pool.k.shape[-3]
        kp, vp = (pool.k, pool.v) if layer is None else \
            (pool.k[layer], pool.v[layer])
        with jax.named_scope("attend"):
            o = kops.paged_decode_attention(
                q.reshape(B, H, hd), kp.reshape(P_, ps, Kv, hd),
                vp.reshape(P_, ps, Kv, hd), block_tables, pos,
                scale=scale, softcap=softcap)
            return o.reshape(B, 1, H * hd)
    nmax = block_tables.shape[1]
    T = nmax * ps
    G = H // Kv
    with jax.named_scope("kv_gather"):
        k = gather_pages(pool.k, block_tables, layer).reshape(B, T, Kv, hd)
        v = gather_pages(pool.v, block_tables, layer).reshape(B, T, Kv, hd)
    with jax.named_scope("attend"):
        qg = q.reshape(B, Kv, G, hd)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, k,
                       preferred_element_type=jnp.float32) * scale
        s = nn.softcap(s, softcap)
        ok = causal_valid(T, pos)
        s = jnp.where(ok[:, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        o = jnp.einsum("bkgt,btkd->bkgd", w.astype(v.dtype), v,
                       preferred_element_type=jnp.float32).astype(q.dtype)
        return o.reshape(B, 1, H * hd)


def paged_cache_scatter_suffix(pool: PagedAttnCache, k, v, block_row,
                               start, n_valid, layer=None):
    """Scatter a prompt *suffix*'s kv (1,W,Kv,hd) into the pool at
    logical positions start..start+W-1 (a prefix-cache hit: the first
    ``start`` positions already live in shared pages).  Slots at or past
    ``n_valid`` are padding — they are routed to the null page, whose
    garbage is masked by design, so one compile serves every suffix
    length in a bucket.  ``layer`` names the row of a stacked pool."""
    W = k.shape[1]
    ps = pool.k.shape[-2]
    k = k.reshape(W, -1)
    v = v.reshape(W, -1)
    t = start + jnp.arange(W)
    idx = jnp.clip(t // ps, 0, block_row.shape[0] - 1)
    # physical page 0 is the reserved null page (repro.serving.paged_kv):
    # padding lands there and its garbage is masked to exactly 0
    page = jnp.where(jnp.arange(W) < n_valid, block_row[idx], 0)
    slot = t % ps
    at = _at(layer, page, slot)
    return PagedAttnCache(k=pool.k.at[at].set(k), v=pool.v.at[at].set(v))


def attend_prefill_paged(q, pool: PagedAttnCache, block_row, start, *,
                         scale, softcap, n_kv: int, layer=None):
    """Suffix-prefill attention: q (1,W,H,hd) holds query positions
    start..start+W-1; keys/values are gathered from the sequence's pages
    (cached prefix + the just-scattered suffix) and masked causally at
    ``j <= start + w`` — one batched dispatch, same arithmetic as the
    decode path, no new kernel.  ``layer`` names the row of a stacked
    pool."""
    B, W, H, hd = q.shape
    ps = pool.k.shape[-2]
    nmax = block_row.shape[0]
    T = nmax * ps
    Kv = n_kv
    G = H // Kv
    with jax.named_scope("kv_gather"):
        k = gather_pages(pool.k, block_row, layer).reshape(B, T, Kv, hd)
        v = gather_pages(pool.v, block_row, layer).reshape(B, T, Kv, hd)
    with jax.named_scope("attend"):
        qg = q.reshape(B, W, Kv, G, hd)
        s = jnp.einsum("bwkgd,btkd->bkgwt", qg, k,
                       preferred_element_type=jnp.float32) * scale
        s = nn.softcap(s, softcap)
        ok = causal_valid(T, start + jnp.arange(W))
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        o = jnp.einsum("bkgwt,btkd->bwkgd", w.astype(v.dtype), v,
                       preferred_element_type=jnp.float32).astype(q.dtype)
        return o.reshape(B, W, H * hd)


def apply_prefill_paged(p, cfg, x, pool: PagedAttnCache, block_row, start,
                        n_valid, *, angles, layer=None):
    """Paged suffix-prefill path: x (1,W,D) at positions start..;
    scatters the suffix kv then attends over the whole page run.
    ``layer`` names the row of a stacked pool (``_at``).
    Returns (out (1,W,D'), new pool)."""
    with jax.named_scope("qkv"):
        q, k_new, v_new = _qkv(p, cfg, x, angles)
    with jax.named_scope("kv_append"):
        pool = paged_cache_scatter_suffix(pool, k_new, v_new, block_row,
                                          start, n_valid, layer)
    o = attend_prefill_paged(q, pool, block_row, start, scale=_scale(cfg),
                             softcap=cfg.attn_softcap, n_kv=cfg.n_kv_heads,
                             layer=layer)
    with jax.named_scope("out_proj"):
        return nn.matmul(o, p["wo"]), pool


def paged_cache_from_prefill(pool: PagedAttnCache, k, v, block_row,
                             start: int = 0):
    """Scatter prefill k/v (1,S,Kv,hd) of ONE sequence into the pool.

    ``block_row`` (nmax,) int32 is the sequence's block-table row; tokens
    land at logical slots start..start+S-1.  A scanned segment's stack
    (L,P,ps,F) takes every layer's k/v (L,1,S,Kv,hd) in one scatter at
    ``(layer, page, slot)``, in place (with the layer axis a window of
    the scatter instead, the chip's compiler relayouts the whole stack
    in and out).
    """
    S = k.shape[-3]
    ps = pool.k.shape[-2]
    t = start + jnp.arange(S)
    at = (block_row[t // ps], t % ps)
    if pool.k.ndim == 4:
        L = pool.k.shape[0]
        at = (jnp.repeat(jnp.arange(L), S),) + tuple(jnp.tile(i, L)
                                                     for i in at)
    k = k.reshape(-1, pool.k.shape[-1])
    v = v.reshape(-1, pool.v.shape[-1])
    return PagedAttnCache(k=pool.k.at[at].set(k), v=pool.v.at[at].set(v))


def apply_decode_paged(p, cfg, x, pool: PagedAttnCache, block_tables,
                       pos, *, angles, layer=None):
    """Paged decode path: x (B,1,D), pos (B,). Returns (out, new pool).
    ``layer`` names the row of a stacked pool (``_at``).  Its operations
    carry the scopes ``qkv``, ``kv_append``, ``kv_gather``, ``attend``
    and ``out_proj`` in the HLO metadata."""
    with jax.named_scope("qkv"):
        q, k_new, v_new = _qkv(p, cfg, x, angles)
    with jax.named_scope("kv_append"):
        pool = paged_cache_update(pool, k_new, v_new, block_tables, pos,
                                  layer)
    o = attend_decode_paged(q, pool, block_tables, pos, scale=_scale(cfg),
                            softcap=cfg.attn_softcap, n_kv=cfg.n_kv_heads,
                            impl=cfg.impl, layer=layer)
    with jax.named_scope("out_proj"):
        return nn.matmul(o, p["wo"]), pool


def cache_init(cfg, batch: int, max_len: int, window: Optional[int], dtype):
    T = min(window, max_len) if window is not None else max_len
    shape = (batch, T, cfg.n_kv_heads * cfg.head_dim)
    return AttnCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def cache_update_decode(cache: AttnCache, k_new, v_new, pos, window):
    """Write the step-t k/v into slot t (global) or t % W (ring).

    k_new/v_new arrive as (B, 1, Kv, hd); the cache stores them flat.
    """
    B = k_new.shape[0]
    k_new = k_new.reshape(B, 1, -1)
    v_new = v_new.reshape(B, 1, -1)
    T = cache.k.shape[1]
    slot = pos % T if window is not None else pos
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, slot, axis=1)
    return AttnCache(k, v)


def cache_from_prefill(k, v, window, max_len):
    """Build the flat decode cache from prefill k/v (B,S,Kv,hd)."""
    B, S, Kv, hd = k.shape
    k = k.reshape(B, S, Kv * hd)
    v = v.reshape(B, S, Kv * hd)
    if window is None:
        pad = max_len - S
        if pad > 0:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        return AttnCache(k, v)
    W = min(window, max_len)
    if S >= W:
        tail_k, tail_v = k[:, S - W:], v[:, S - W:]
        slots = (jnp.arange(S - W, S)) % W
        ck = jnp.zeros((B, W, Kv * hd), k.dtype).at[:, slots].set(tail_k)
        cv = jnp.zeros((B, W, Kv * hd), v.dtype).at[:, slots].set(tail_v)
        return AttnCache(ck, cv)
    ck = jnp.zeros((B, W, Kv * hd), k.dtype).at[:, :S].set(k)
    cv = jnp.zeros((B, W, Kv * hd), v.dtype).at[:, :S].set(v)
    return AttnCache(ck, cv)


# ---------------------------------------------------------------------------
# full layer entry points
# ---------------------------------------------------------------------------
def apply(p, cfg, x, *, kind: str, angles, impl: Optional[str] = None):
    """Train/prefill path. Returns (out, (k, v))."""
    impl = impl or cfg.impl
    window = cfg.sliding_window if kind == "local" else None
    q, k, v = _qkv(p, cfg, x, angles)
    kh = expand_kv(k, cfg.n_heads)
    vh = expand_kv(v, cfg.n_heads)
    kh = logical_constraint(kh, "batch", None, "heads", None)
    vh = logical_constraint(vh, "batch", None, "heads", None)
    kw = dict(causal=cfg.causal, window=window, scale=_scale(cfg),
              softcap=cfg.attn_softcap)
    if impl == "ref":
        o = attend_ref(q, kh, vh, **kw)
    elif impl == "blocked":
        o = attend_blocked(q, kh, vh, block_q=cfg.attn_block_q,
                           block_kv=cfg.attn_block_kv, **kw)
    elif impl == "pallas":
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, kh, vh, block_q=cfg.attn_block_q,
                                 block_kv=cfg.attn_block_kv, **kw)
    else:
        raise ValueError(impl)
    o = o.reshape(*x.shape[:-1], cfg.n_heads * cfg.head_dim)
    from repro.parallel.collectives import row_parallel
    out = row_parallel(o, p["wo"])
    return out, (k, v)


def apply_decode(p, cfg, x, cache: AttnCache, pos, *, kind: str, angles):
    """Decode path: x (B,1,D). Returns (out, new_cache)."""
    window = cfg.sliding_window if kind == "local" else None
    q, k_new, v_new = _qkv(p, cfg, x, angles)
    cache = cache_update_decode(cache, k_new, v_new, pos, window)
    o = attend_decode(q, cache, pos, window=window, scale=_scale(cfg),
                      softcap=cfg.attn_softcap, n_kv=cfg.n_kv_heads)
    out = nn.matmul(o, p["wo"])
    return out, cache
