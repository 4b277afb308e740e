"""Pallas TPU decode attention (single query vs KV cache), GQA-aware.

Grid (B, nT): each step DMAs one (block_t, Kv*hd) tile of the cache —
every KV head of ``block_t`` slots — and each head's G query heads
attend to their lane slice of it with an online softmax carried in VMEM
scratch.  The cache is read in its flat (B, T, Kv*hd) serving layout, so
a tile's last two dimensions are (block_t, Kv*hd): a multiple of the
TPU's (8, 128) tiling whenever hd is a multiple of 128, with no
H-expansion copy in HBM (decode is memory-bound; the cache read is the
roofline term).  Slots beyond ``pos`` are masked.

``paged_decode_attention`` is the paged-KV variant behind the serving
engine (Swallow §X-B: the KV cache as a striped distributed store): the
cache lives in fixed-size pages (P, ps, Kv*hd) and each sequence names
its pages through a block-index table.  The table is a scalar-prefetch
operand, so the BlockSpec index map DMAs exactly the pages the sequence
owns — one whole page (all KV heads) per block — the kernel never
assumes a contiguous cache, and per-sequence lengths replace the single
shared ``pos``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _attend_tile(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *, t_start,
                 pos, scale, softcap, n_kv, hd):
    """Fold one (n, Kv*hd) cache tile into the online softmax of every
    KV head: head h reads lanes [h*hd, (h+1)*hd) of the tile and updates
    m/l (Kv, G, 1) and acc (Kv, G, hd) at its leading index."""
    for h in range(n_kv):
        lanes = pl.ds(h * hd, hd)
        q = q_ref[0, h].astype(jnp.float32)                  # (G, hd)
        k = k_ref[0, :, lanes].astype(jnp.float32)           # (n, hd)
        v = v_ref[0, :, lanes].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        slots = t_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slots <= pos, s, NEG_INF)
        m_prev = m_ref[h]                                     # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _normalized(acc_ref, l_ref):
    return acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)


def _scratch(n_kv, G, hd):
    return [pltpu.VMEM((n_kv, G, 1), jnp.float32),
            pltpu.VMEM((n_kv, G, 1), jnp.float32),
            pltpu.VMEM((n_kv, G, hd), jnp.float32)]


def _dec_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                *, scale, softcap, block_t, nt, n_kv, hd):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    pos = pos_ref[0]
    t_start = ti * block_t

    @pl.when(t_start <= pos)
    def _compute():
        _attend_tile(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                     t_start=t_start, pos=pos, scale=scale, softcap=softcap,
                     n_kv=n_kv, hd=hd)

    @pl.when(ti == nt - 1)
    def _finish():
        o_ref[0] = _normalized(acc_ref, l_ref).astype(o_ref.dtype)


def _time_tile(T: int, block_t: int) -> int:
    """Largest tile <= block_t that divides T and keeps the (8, 128)
    tiling (a multiple of 8, or all of T)."""
    return next((b for b in range(min(block_t, T), 0, -1)
                 if T % b == 0 and b % 8 == 0), T)


def decode_attention(q, k, v, pos, *, scale=None, softcap=None,
                     block_t=512, interpret: bool):
    """q (B,H,hd); k,v (B,T,Kv,hd); pos () int32. Returns (B,H,hd)."""
    B, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    scale = hd ** -0.5 if scale is None else scale
    bt = _time_tile(T, block_t)
    nt = T // bt
    qg = q.reshape(B, Kv, G, hd)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(_dec_kernel, scale=scale, softcap=softcap,
                               block_t=bt, nt=nt, n_kv=Kv, hd=hd)
    tile = pl.BlockSpec((1, bt, Kv * hd), lambda b, t: (b, t, 0))
    heads = pl.BlockSpec((1, Kv, G, hd), lambda b, t: (b, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, nt),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), heads, tile, tile],
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, hd), q.dtype),
        scratch_shapes=_scratch(Kv, G, hd),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, qg, k.reshape(B, T, Kv * hd), v.reshape(B, T, Kv * hd))
    return out.reshape(B, H, hd)


def _paged_dec_kernel(*args, scale, softcap, page_size, pages_per_blk,
                      n_blocks, masked, partials, n_kv, hd):
    """Grid (B, n_blocks); each block sweeps ``pages_per_blk`` pages
    (block_t = pages_per_blk * page_size cache slots) with one online
    softmax per KV head carried in VMEM scratch.  Scalar-prefetch
    operands are the block table, per-sequence pos, and (when
    ``masked``) a page ownership mask; the remaining refs unpack as the
    q ref, pages_per_blk k page refs, pages_per_blk v page refs, the
    output(s), then scratch.  ``partials`` emits the raw online-softmax
    state (acc, m, l) instead of the normalized output — the sharded
    caller merges per-stripe partials with psums."""
    m_ = pages_per_blk
    if masked:
        bt_ref, pos_ref, pm_ref, q_ref, *refs = args
    else:
        bt_ref, pos_ref, q_ref, *refs = args
        pm_ref = None
    k_refs, v_refs = refs[:m_], refs[m_:2 * m_]
    if partials:
        o_acc_ref, o_m_ref, o_l_ref, m_ref, l_ref, acc_ref = refs[2 * m_:]
    else:
        o_ref, m_ref, l_ref, acc_ref = refs[2 * m_:]
    b = pl.program_id(0)
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    pos = pos_ref[b]
    for i in range(m_):
        t_start = (blk * m_ + i) * page_size
        live = t_start <= pos
        if masked:
            # an unowned page's slot in the safe table points at local
            # row 0 — skip it entirely, the merge recovers exactness
            live = live & (pm_ref[b, blk * m_ + i] != 0)

        @pl.when(live)
        def _compute(i=i, t_start=t_start):
            _attend_tile(q_ref, k_refs[i], v_refs[i], m_ref, l_ref, acc_ref,
                         t_start=t_start, pos=pos, scale=scale,
                         softcap=softcap, n_kv=n_kv, hd=hd)

    @pl.when(blk == n_blocks - 1)
    def _finish():
        if partials:
            o_acc_ref[0] = acc_ref[...]
            o_m_ref[0] = m_ref[...]
            o_l_ref[0] = l_ref[...]
        else:
            o_ref[0] = _normalized(acc_ref, l_ref).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, pos, *,
                           scale=None, softcap=None, block_t=None,
                           page_mask=None, partials=False,
                           interpret: bool):
    """q (B,H,hd); k_pages/v_pages (P,ps,Kv,hd); block_tables (B,nmax)
    int32 physical page ids; pos (B,) int32 per-sequence last valid slot.

    Logical slot t of sequence b lives at page ``block_tables[b, t//ps]``,
    offset ``t % ps``.  Pages past ``pos[b]`` must still name a real page
    (the serving engine points them at the reserved null page 0); their
    contribution is masked out exactly.  The pools are read as
    (P, ps, Kv*hd) — the engine's own pool layout, so the reshape is free
    there — and one grid step DMAs whole pages of every KV head.

    ``block_t`` is the time-tile sweep hook: a multiple of ``page_size``
    makes each grid step DMA ``block_t // page_size`` pages (each through
    its own scalar-prefetched index map) and sweep them in one kernel
    invocation — fewer grid steps against the same scattered pool.  The
    block table is padded with null pages when nmax doesn't divide.
    ``None`` keeps the one-page-per-step schedule.

    ``page_mask`` (B,nmax) int32 marks which table entries this caller
    owns (striped pools: a shard passes its local safe table plus the
    ownership mask; unowned entries are skipped, not attended).
    ``partials=True`` returns the raw online-softmax state
    ``(acc (B,Kv,G,hd) f32, m (B,Kv,G) f32, l (B,Kv,G) f32)`` instead of
    the normalized (B,H,hd) output, for cross-stripe psum merging.
    """
    B, H, hd = q.shape
    P, ps, Kv = k_pages.shape[:3]
    nmax = block_tables.shape[1]
    G = H // Kv
    scale = hd ** -0.5 if scale is None else scale
    m_ = 1 if block_t is None else max(1, block_t // ps)
    masked = page_mask is not None
    qg = q.reshape(B, Kv, G, hd)
    bt = jnp.asarray(block_tables, jnp.int32)
    pm = None if page_mask is None \
        else jnp.asarray(page_mask, jnp.int32)
    if nmax % m_:
        pad = m_ - nmax % m_
        # pad with the reserved null page (id 0); t_start > pos masks it
        bt = jnp.pad(bt, ((0, 0), (0, pad)), constant_values=0)
        if masked:
            pm = jnp.pad(pm, ((0, 0), (0, pad)), constant_values=0)
        nmax += pad
    n_blocks = nmax // m_
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(B)

    kernel = functools.partial(_paged_dec_kernel, scale=scale,
                               softcap=softcap, page_size=ps,
                               pages_per_blk=m_, n_blocks=n_blocks,
                               masked=masked, partials=partials,
                               n_kv=Kv, hd=hd)

    def page_spec(i):
        # the block-index table drives the page DMA: page i of block p
        # of sequence b is physical page bt[b, p*m_+i] (pref[0] is the
        # table whatever the scalar-prefetch arity)
        return pl.BlockSpec(
            (1, ps, Kv * hd),
            lambda b, p, *pref, i=i: (pref[0][b, p * m_ + i], 0, 0))

    def head_spec(shape):
        return pl.BlockSpec((1,) + shape, lambda b, p, *pref: (b,) +
                            (0,) * len(shape))

    if partials:
        out_specs = [head_spec((Kv, G, hd)), head_spec((Kv, G, 1)),
                     head_spec((Kv, G, 1))]
        out_shape = [jax.ShapeDtypeStruct((B, Kv, G, hd), jnp.float32),
                     jax.ShapeDtypeStruct((B, Kv, G, 1), jnp.float32),
                     jax.ShapeDtypeStruct((B, Kv, G, 1), jnp.float32)]
    else:
        out_specs = head_spec((Kv, G, hd))
        out_shape = jax.ShapeDtypeStruct((B, Kv, G, hd), q.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if masked else 2,
        grid=(B, n_blocks),
        in_specs=(
            [head_spec((Kv, G, hd))]
            + [page_spec(i) for i in range(m_)]      # k pages
            + [page_spec(i) for i in range(m_)]),    # v pages
        out_specs=out_specs,
        scratch_shapes=_scratch(Kv, G, hd),
    )
    scalars = (bt, pos_arr, pm) if masked else (bt, pos_arr)
    k_flat = k_pages.reshape(P, ps, Kv * hd)
    v_flat = v_pages.reshape(P, ps, Kv * hd)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*scalars, qg, *([k_flat] * m_), *([v_flat] * m_))
    if partials:
        acc, m, l = out
        return acc, m[..., 0], l[..., 0]
    return out.reshape(B, H, hd)
