"""Unified LM: init / forward / loss / prefill / decode over layer segments.

Layers are grouped into homogeneous *segments* (same cycle of layer kinds,
same FFN type) and scanned with ``lax.scan`` — the layer-streaming
structure that keeps the compiled HLO small and gives FSDP its
gather-per-layer (Swallow C3 "overlays") behaviour.  Heterogeneous
patterns (gemma2 local/global, recurrentgemma 2:1) scan whole cycles;
remainder layers form their own segments.
"""
from __future__ import annotations

import functools
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks, modules as nn
from repro.parallel.sharding import logical_constraint

LOSS_CHUNK = 512  # sequence chunk for the fused/chunked cross-entropy


class SegmentSpec(NamedTuple):
    kinds: Tuple[str, ...]
    is_moe: bool
    n_cycles: int
    scanned: bool
    start_layer: int


def make_segments(cfg: ModelConfig) -> List[SegmentSpec]:
    kinds = cfg.layer_kinds
    moe_flags = [cfg.moe is not None and i >= cfg.first_k_dense
                 for i in range(cfg.n_layers)]
    p = len(cfg.layer_pattern)
    segs: List[SegmentSpec] = []
    i = 0
    while i < cfg.n_layers:
        if i % p == 0 and i + p <= cfg.n_layers \
                and len(set(moe_flags[i:i + p])) == 1:
            # count consecutive full cycles with the same MoE signature
            n = 0
            j = i
            while j + p <= cfg.n_layers \
                    and kinds[j:j + p] == cfg.layer_pattern \
                    and len(set(moe_flags[j:j + p])) == 1 \
                    and moe_flags[j] == moe_flags[i]:
                n += 1
                j += p
            segs.append(SegmentSpec(cfg.layer_pattern, moe_flags[i], n,
                                    n > 1, i))
            i = j
        else:
            # remainder: group consecutive same-(kind, moe) layers
            k0, m0 = kinds[i], moe_flags[i]
            n = 0
            while i + n < cfg.n_layers and kinds[i + n] == k0 \
                    and moe_flags[i + n] == m0:
                n += 1
            segs.append(SegmentSpec((k0,), m0, n, n > 1, i))
            i += n
    assert sum(s.n_cycles * len(s.kinds) for s in segs) == cfg.n_layers
    return segs


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig):
    dtype = nn.dt(cfg.param_dtype)
    segs = make_segments(cfg)
    n_keys = len(segs) + 4
    ks = jax.random.split(key, n_keys)
    params: dict = {}
    if cfg.embed_inputs:
        params["embed"] = {"embed_table": nn.embed_init(
            ks[0], cfg.vocab_size, cfg.d_model, dtype)}

    def cycle_init(k, seg: SegmentSpec):
        kk = jax.random.split(k, len(seg.kinds))
        return [blocks.init(kk[j], cfg, seg.kinds[j], seg.is_moe, dtype)
                for j in range(len(seg.kinds))]

    seg_params = []
    for si, seg in enumerate(segs):
        if seg.scanned:
            seg_keys = jax.random.split(ks[1 + si], seg.n_cycles)
            seg_params.append(jax.vmap(
                functools.partial(cycle_init, seg=seg))(seg_keys))
        else:
            seg_params.append(cycle_init(ks[1 + si], seg))
    params["segments"] = seg_params
    params["final_norm"] = nn.rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings or not cfg.embed_inputs:
        params["head"] = {"head_w": nn.dense_init(
            ks[-1], cfg.d_model, cfg.vocab_size, dtype)}
    if cfg.mtp_depth:
        kk = jax.random.split(ks[-2], 2 + cfg.mtp_depth)
        last_seg = segs[-1]
        params["mtp"] = {
            "mtp_proj": nn.dense_init(kk[0], 2 * cfg.d_model, cfg.d_model,
                                      dtype),
            "norm_h": nn.rmsnorm_init(cfg.d_model),
            "norm_e": nn.rmsnorm_init(cfg.d_model),
            "final_norm": nn.rmsnorm_init(cfg.d_model),
            "block": blocks.init(kk[1], cfg, last_seg.kinds[-1],
                                 last_seg.is_moe, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------
def _rope_dim(cfg) -> int:
    return cfg.mla.qk_rope_head_dim if cfg.mla is not None else cfg.head_dim


def _angles(cfg, positions):
    if not cfg.rope:
        return None
    return nn.rope_angles(positions, _rope_dim(cfg), cfg.rope_theta,
                          cfg.mrope_sections, cfg.rope_scaling)


def default_positions(cfg, batch: int, seq: int, offset: int = 0):
    pos = jnp.arange(offset, offset + seq, dtype=jnp.int32)[None]
    pos = jnp.broadcast_to(pos, (batch, seq))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[None], (3, batch, seq))
    return pos


def embed_tokens(params, cfg, tokens):
    if cfg.embed_inputs:
        table = params["embed"]["embed_table"]
        x = jnp.take(table, tokens, axis=0).astype(nn.dt(cfg.activation_dtype))
    else:
        x = tokens.astype(nn.dt(cfg.activation_dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def head_logits(params, cfg, h):
    """h (..., D) -> fp32 logits (..., V), with final softcap."""
    if cfg.tie_embeddings and cfg.embed_inputs:
        w = params["embed"]["embed_table"]  # (V, D)
        logits = jax.lax.dot_general(
            h, w, (((h.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        logits = jax.lax.dot_general(
            h, params["head"]["head_w"], (((h.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    logits = logical_constraint(logits, "batch", None, "vocab")
    if cfg.logit_softcap is not None:
        logits = nn.softcap(logits, cfg.logit_softcap)
    return logits


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, tokens, *, mode: str,
            positions=None, impl: Optional[str] = None):
    """tokens: (B,S) int32 ids or (B,S,D) embeddings (stub frontends).

    Returns (h_final (B,S,D) pre-final-norm, caches, aux).
    caches is None in train mode; in prefill mode it is the raw per-segment
    cache pytree (convert with ``caches_from_prefill``).
    """
    assert mode in ("train", "prefill")
    x = embed_tokens(params, cfg, tokens)
    B, S = x.shape[:2]
    if positions is None:
        positions = default_positions(cfg, B, S)
    angles = _angles(cfg, positions)
    x = logical_constraint(x, "batch", "seq_sp", None)

    segs = make_segments(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    caches = []

    for seg, seg_p in zip(segs, params["segments"]):
        def cycle_apply(cyc_p, x):
            cache_list = []
            aux_c = jnp.zeros((), jnp.float32)
            for j, kind in enumerate(seg.kinds):
                x, c, a = blocks.apply(cyc_p[j], cfg, kind, x,
                                       angles=angles, mode=mode, impl=impl)
                if mode == "prefill":
                    cache_list.append(c)
                aux_c = aux_c + a
            x = logical_constraint(x, "batch", "seq_sp", None)
            return x, (tuple(cache_list) if mode == "prefill" else None), aux_c

        if seg.scanned:
            def scan_body(carry, cyc_p):
                x, aux = carry
                x, cache_c, aux_c = cycle_apply(cyc_p, x)
                return (x, aux + aux_c), cache_c

            if cfg.remat and mode == "train":
                scan_body = jax.checkpoint(scan_body)
            (x, aux_total), cache_seg = jax.lax.scan(
                scan_body, (x, aux_total), seg_p)
        else:
            x, cache_seg, aux_c = cycle_apply(seg_p, x)
            aux_total = aux_total + aux_c
        caches.append(cache_seg)

    return x, (caches if mode == "prefill" else None), aux_total


# ---------------------------------------------------------------------------
# loss (chunked fused cross-entropy over the sequence)
# ---------------------------------------------------------------------------
def _head_weight(params, cfg):
    """(D, V) head matrix (transposed embedding when tied)."""
    if cfg.tie_embeddings and cfg.embed_inputs:
        return params["embed"]["embed_table"].T
    return params["head"]["head_w"]


def _local_ce(logits, labels_c, mask_c, v_offset, v_local, softcap,
              axes=()):
    """Vocab-parallel CE on local logits (B,Sc,v_local) fp32.

    With ``axes`` (mesh axis names of the vocab shards) the reductions are
    explicit psums — exact, and only (B,Sc)-sized traffic on the wire.
    """
    if softcap is not None:
        logits = nn.softcap(logits, softcap)
    # stop_gradient on the max: pmax has no VJP, and d(lse)/d(logits) is
    # exactly softmax either way (the max terms cancel analytically)
    m = jax.lax.stop_gradient(logits.max(-1))
    if axes:
        m = jax.lax.stop_gradient(jax.lax.pmax(m, axes))
    s = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
    if axes:
        s = jax.lax.psum(s, axes)
    lse = m + jnp.log(s)
    loc = labels_c - v_offset
    in_range = (loc >= 0) & (loc < v_local)
    loc = jnp.clip(loc, 0, v_local - 1)
    correct = jnp.take_along_axis(logits, loc[..., None], axis=-1)[..., 0]
    correct = jnp.where(in_range, correct, 0.0)
    if axes:
        correct = jax.lax.psum(correct, axes)
    nll = (lse - correct) * mask_c
    return nll.sum(), mask_c.sum()


def _chunk_ce(params, cfg, h_c, labels_c, mask_c):
    """One sequence chunk of CE.  Under a mesh this is a shard_map with
    vocab-parallel logits: each shard computes (B,Sc,V/tp) locally and the
    only collectives are (B,Sc)-sized psums — never logits-sized."""
    from repro.parallel.sharding import current_env
    env = current_env()
    w = _head_weight(params, cfg)
    vocab_axes = env.resolve("vocab") if env is not None else None
    if env is None or vocab_axes is None:
        logits = jax.lax.dot_general(
            h_c, w, (((h_c.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return _local_ce(logits, labels_c, mask_c, 0, cfg.vocab_size,
                         cfg.logit_softcap)

    axes = (vocab_axes,) if isinstance(vocab_axes, str) else tuple(vocab_axes)
    tp = 1
    for a in axes:
        tp *= env.mesh.shape[a]
    if cfg.vocab_size % tp:
        logits = head_logits(params, cfg, h_c)
        return _local_ce(logits, labels_c, mask_c, 0, cfg.vocab_size,
                         None)  # softcap applied in head_logits

    v_local = cfg.vocab_size // tp

    def body(h_l, w_l, lab_l, mask_l):
        idx = jax.lax.axis_index(axes[0]) if len(axes) == 1 else 0
        logits = jax.lax.dot_general(
            h_l, w_l, (((h_l.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        tot, cnt = _local_ce(logits, lab_l, mask_l, idx * v_local, v_local,
                             cfg.logit_softcap, axes)
        batch_axes = [a for a in env.mesh.axis_names if a not in axes]
        if batch_axes:
            tot = jax.lax.psum(tot, tuple(batch_axes))
            cnt = jax.lax.psum(cnt, tuple(batch_axes))
        return tot, cnt

    from repro.models.moe import _shard_map
    tot, cnt = _shard_map(
        body, mesh=env.mesh,
        in_specs=(env.spec("batch", None, None),   # h replicated over model
                  env.spec(None, "vocab"),
                  env.spec("batch", None),
                  env.spec("batch", None)),
        out_specs=(env.spec(), env.spec()),
        check_vma=False)(h_c, w, labels_c, mask_c)
    return tot, cnt


def cross_entropy(params, cfg, h, labels, mask):
    """Chunked CE: never materializes (B,S,V) for the whole sequence."""
    B, S, D = h.shape
    c = min(LOSS_CHUNK, S)
    while S % c:
        c -= 1
    n = S // c
    if n == 1:
        tot, cnt = _chunk_ce(params, cfg, h, labels, mask)
        return tot / jnp.maximum(cnt, 1.0)

    hs = jnp.moveaxis(h.reshape(B, n, c, D), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, n, c), 1, 0)
    ms = jnp.moveaxis(mask.reshape(B, n, c), 1, 0)

    @jax.checkpoint
    def body(carry, inp):
        tot, cnt = carry
        h_c, l_c, m_c = inp
        t, k = _chunk_ce(params, cfg, h_c, l_c, m_c)
        return (tot + t, cnt + k), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                 (hs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, cfg: ModelConfig, batch, *, impl=None):
    """batch: tokens/embeds, labels (B,S), mask (B,S). Returns (loss, metrics)."""
    tokens = batch["tokens"]
    h, _, aux = forward(params, cfg, tokens, mode="train",
                        positions=batch.get("positions"), impl=impl)
    hn = nn.rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
    ce = cross_entropy(params, cfg, hn, batch["labels"], batch["mask"])
    loss = ce
    metrics = {"ce": ce, "moe_aux": aux}
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_coef * aux
    if cfg.mtp_depth and "mtp" in params:
        mtp_ce = _mtp_loss(params, cfg, h, tokens, batch["labels"],
                           batch["mask"], impl=impl)
        loss = loss + 0.3 * mtp_ce
        metrics["mtp_ce"] = mtp_ce
    metrics["loss"] = loss
    return loss, metrics


def _mtp_loss(params, cfg, h, tokens, labels, mask, *, impl=None):
    """DeepSeek multi-token prediction: one extra block predicts t+2."""
    p = params["mtp"]
    B, S = labels.shape
    # embedding of the next token (teacher-forced)
    e_next = embed_tokens(params, cfg, labels)          # (B,S,D) token t+1
    hn = nn.rmsnorm(h, p["norm_h"]["scale"], cfg.norm_eps)
    en = nn.rmsnorm(e_next, p["norm_e"]["scale"], cfg.norm_eps)
    h_in = nn.matmul(jnp.concatenate([hn, en], -1), p["mtp_proj"])
    positions = default_positions(cfg, B, S)
    angles = _angles(cfg, positions)
    seg = make_segments(cfg)[-1]
    h_mtp, _, _ = blocks.apply(p["block"], cfg, seg.kinds[-1], h_in,
                               angles=angles, mode="train", impl=impl)
    h_mtp = nn.rmsnorm(h_mtp, p["final_norm"]["scale"], cfg.norm_eps)
    # targets: token t+2 = labels shifted left by one
    labels2 = jnp.concatenate([labels[:, 1:], labels[:, -1:]], 1)
    mask2 = jnp.concatenate([mask[:, 1:], jnp.zeros_like(mask[:, -1:])], 1)
    return cross_entropy(params, cfg, h_mtp, labels2, mask2)


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------
def caches_from_prefill(cfg, raw_caches, max_len: int):
    segs = make_segments(cfg)
    out = []
    for seg, seg_c in zip(segs, raw_caches):
        def conv_cycle(cyc):
            return tuple(blocks.cache_from_prefill(cfg, seg.kinds[j], cyc[j],
                                                   max_len)
                         for j in range(len(seg.kinds)))
        if seg.scanned:
            out.append(jax.vmap(conv_cycle)(seg_c))
        else:
            out.append(conv_cycle(seg_c))
    return out


def prefill(params, cfg, tokens, *, max_len: int, positions=None, impl=None):
    """Returns (next-token logits (B,1,V), decode caches)."""
    h, raw, _ = forward(params, cfg, tokens, mode="prefill",
                        positions=positions, impl=impl)
    caches = caches_from_prefill(cfg, raw, max_len)
    h_last = h[:, -1:]
    h_last = nn.rmsnorm(h_last, params["final_norm"]["scale"], cfg.norm_eps)
    return head_logits(params, cfg, h_last), caches


def init_caches(cfg, batch: int, max_len: int):
    dtype = nn.dt(cfg.activation_dtype)
    segs = make_segments(cfg)
    out = []
    for seg in segs:
        cyc = tuple(blocks.cache_init(cfg, k, batch, max_len, dtype)
                    for k in seg.kinds)
        if seg.scanned:
            cyc = jax.tree.map(
                lambda l: jnp.zeros((seg.n_cycles,) + l.shape, l.dtype), cyc)
        out.append(cyc)
    return out


# ---------------------------------------------------------------------------
# paged decode — pools + block tables instead of per-sequence slabs
# ---------------------------------------------------------------------------
def paged_decodable(cfg) -> bool:
    """Paged serving needs causal, embedded-token configs whose every
    layer keeps a per-token cache: global attention (k/v) or latent
    attention (one latent row) — windows and recurrent states are
    constant-size, nothing to page — and no M-RoPE (per-sequence
    positions are scalar per step)."""
    return (cfg.supports_decode and cfg.embed_inputs
            and cfg.mrope_sections is None
            and all(k in blocks.PAGED for k in cfg.layer_kinds))


def init_paged_caches(cfg, n_pages: int, page_size: int):
    """Per-layer paged pools, mirroring the init_caches pytree: one pool
    of the layer kind's own (``PagedAttnCache``, ``PagedLatentCache``)
    per layer, stacked (n_cycles, ...) for scanned segments.  All layers
    share one block table — page ids are logical across the whole
    stack, exactly the vLLM layout."""
    assert paged_decodable(cfg), f"{cfg.name} is not paged-decodable"
    dtype = nn.dt(cfg.activation_dtype)
    segs = make_segments(cfg)
    out = []
    for seg in segs:
        cyc = tuple(blocks.paged_cache_init(cfg, k, n_pages, page_size,
                                            dtype)
                    for k in seg.kinds)
        if seg.scanned:
            cyc = jax.tree.map(
                lambda l: jnp.zeros((seg.n_cycles,) + l.shape, l.dtype), cyc)
        out.append(cyc)
    return out


def paged_bytes_per_token(cfg) -> int:
    """Bytes one token occupies in the paged pools, over every layer
    (each pool module's row width, ``blocks.paged_row_width``)."""
    item = jnp.dtype(nn.dt(cfg.activation_dtype)).itemsize
    return sum(blocks.paged_row_width(cfg, k) for k in cfg.layer_kinds) \
        * item


def paged_from_prefill(cfg, pools, raw_caches, block_row):
    """Scatter ONE sequence's prefill state (from forward(mode=
    "prefill"), batch 1) into the pools at the pages named by
    ``block_row``: one scatter per stacked pool covers all its layers,
    in place."""
    return [tuple(blocks.paged_cache_from_prefill(cfg, kind, seg_pool[j],
                                                  seg_raw[j], block_row)
                  for j, kind in enumerate(seg.kinds))
            for seg, seg_pool, seg_raw in zip(make_segments(cfg), pools,
                                              raw_caches)]


def _paged_layers(params, cfg, x, pools, apply):
    """Run x through every layer against the paged pools, in place.

    ``apply(p, kind, x, pool, layer) -> (x, pool, held)`` runs one
    layer (``held``: each token's pairs on held experts).  A
    scanned segment's layer scan carries the segment's stacked pools
    with x, and its ``xs`` are the weights and the layer index: each
    layer scatters into and gathers from the stack at ``(layer, ...)``
    (``layer`` a traced scalar), so the pools never leave their buffer.
    Passing the stack as ``xs``/``ys`` instead makes XLA slice every
    layer's slab out, write it into a fresh stack and copy that back,
    three whole-pool moves per step.  Unscanned segments hold one pool
    per layer (``layer`` None).  Returns (x, new pools, held pairs per
    token summed over the layers; None for a model without experts).
    """
    count = cfg.moe is not None
    held = jnp.zeros(x.shape[:-1], jnp.int32) if count else None
    new_pools = []
    for seg, seg_p, seg_pool in zip(make_segments(cfg), params["segments"],
                                    pools):
        def cycle(cyc_p, cyc_pool, x, held, layer):
            new_c = []
            for j, kind in enumerate(seg.kinds):
                x, c, h = apply(cyc_p[j], kind, x, cyc_pool[j], layer)
                new_c.append(c)
                if count:
                    held = held + h
            return x, tuple(new_c), held

        if seg.scanned:
            def scan_body(carry, inp):
                x, cyc_pool, held = carry
                cyc_p, layer = inp
                return cycle(cyc_p, cyc_pool, x, held, layer), None
            (x, new_seg, held), _ = jax.lax.scan(
                scan_body, (x, seg_pool, held),
                (seg_p, jnp.arange(seg.n_cycles, dtype=jnp.int32)))
        else:
            x, new_seg, held = cycle(seg_p, seg_pool, x, held, None)
        new_pools.append(new_seg)
    return x, new_pools, held


def moe_layers(cfg) -> int:
    """How many layers route experts."""
    return 0 if cfg.moe is None else cfg.n_layers - cfg.first_k_dense


def decode_step_paged(params, cfg, tokens, pools, block_tables, pos):
    """One paged decode step over a continuous batch.

    tokens (B,1) int32; block_tables (B,nmax) int32 physical page ids;
    pos (B,) int32 per-sequence positions (inactive slots: 0, with a
    null-page block row).  Returns (logits (B,1,V), new pools, held
    pairs per slot (B,) summed over the layers, or None without
    experts).
    """
    with jax.named_scope("embed"):
        x = embed_tokens(params, cfg, tokens)
        positions = pos[:, None].astype(jnp.int32)
        angles = _angles(cfg, positions)

    def layer_decode(p, kind, x, pool, layer):
        return blocks.apply_decode_paged(p, cfg, kind, x, pool, block_tables,
                                         pos, angles=angles, layer=layer)

    x, new_pools, held = _paged_layers(params, cfg, x, pools, layer_decode)
    with jax.named_scope("head"):
        h = nn.rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
        return head_logits(params, cfg, h), new_pools, \
            None if held is None else held[:, 0]


def decode_window_paged(params, cfg, tokens, pools, block_tables, pos,
                        active, k: int):
    """Fused K-step greedy decode window, entirely on device.

    ``lax.scan`` chains :func:`decode_step_paged` K times: the greedy
    argmax of step j feeds step j+1 without a host round-trip, KV pages
    are appended in place, and per-slot positions advance on device.
    The block tables must be fixed for the whole window — the scheduler
    pre-reserves the window's pages (``safe_horizon``) to guarantee it.

    tokens (B,1) int32 last emitted token per slot; pos (B,) int32 write
    positions; active (B,) int32 1 for occupied slots (inactive slots
    hold token/pos fixed so their null-page writes stay at slot 0).
    Returns (emitted (B,K) int32, last tokens (B,1), pos (B,), pools,
    counts): ``counts`` is None for a model without experts, else the
    window's int32 ``[moe_tokens, moe_pairs_held]`` over its occupied
    slots: tokens through expert layers (one per token and layer) and
    their (token, expert) pairs on held experts.
    """
    count = cfg.moe is not None

    def body(carry, _):
        tok, p, pl, n = carry
        logits, pl, held = decode_step_paged(params, cfg, tok, pl,
                                             block_tables, p)
        with jax.named_scope("head"):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B,1)
            nxt = jnp.where(active[:, None] > 0, nxt, tok)
            if count:
                n = n + jnp.stack([active.sum() * moe_layers(cfg),
                                   (active * held).sum()])
            p = p + active
        return (nxt, p, pl, n), nxt[:, 0]

    n0 = jnp.zeros((2,), jnp.int32) if count else None
    (tok, pos, pools, n), toks = jax.lax.scan(
        body, (tokens, pos, pools, n0), None, length=k)
    return jnp.moveaxis(toks, 0, 1), tok, pos, pools, n


def _suffix_forward_paged(params, cfg, tokens, pools, block_row, start,
                          n_valid):
    """Shared body of :func:`prefill_suffix_paged` and
    :func:`verify_window_paged`: run a W-token continuation (positions
    start..start+W-1) through every layer's ``apply_prefill_paged``,
    scattering its kv into the sequence's pages and attending causally
    over the whole page run.  Returns (hidden states (1,W,D), pools)."""
    B, W = tokens.shape
    with jax.named_scope("embed"):
        x = embed_tokens(params, cfg, tokens)
        positions = (start + jnp.arange(W, dtype=jnp.int32))[None]
        positions = jnp.broadcast_to(positions, (B, W))
        angles = _angles(cfg, positions)

    def layer_prefill(p, kind, x, pool, layer):
        return blocks.apply_prefill_paged(p, cfg, kind, x, pool, block_row,
                                          start, n_valid, angles=angles,
                                          layer=layer)

    x, new_pools, _ = _paged_layers(params, cfg, x, pools, layer_prefill)
    return x, new_pools


def prefill_suffix_paged(params, cfg, tokens, pools, block_row, start,
                         n_valid):
    """Chunked prefill of a prompt *suffix* against the paged pools — the
    prefix-cache hit path.  The cached prefix (positions 0..start-1)
    already lives in shared pages named by ``block_row``; only the
    uncached suffix runs through the model, in ONE batched dispatch:
    each layer scatters the suffix kv into the request's pages and
    attends causally over the whole page run (cached prefix + suffix),
    same arithmetic as the decode path, no new kernel.

    tokens (1,W) int32 suffix ids, padded to a bucket width W; ``start``
    scalar int32 cached-prefix length; ``n_valid`` scalar int32 true
    suffix length (padded slots scatter to the null page, whose garbage
    is masked by design).  Returns (next-token logits (1,1,V) at the
    last *valid* suffix position — the request's first generated token —
    and the updated pools).
    """
    x, new_pools = _suffix_forward_paged(params, cfg, tokens, pools,
                                         block_row, start, n_valid)
    with jax.named_scope("head"):
        h_last = jnp.take(x, n_valid - 1, axis=1)[:, None]  # (1,1,D)
        h_last = nn.rmsnorm(h_last, params["final_norm"]["scale"],
                            cfg.norm_eps)
        return head_logits(params, cfg, h_last), new_pools


def chunk_prefill_paged(params, cfg, tokens, pools, block_row, start,
                        n_valid):
    """One slice of a chunked prefill: positions ``start ..
    start+n_valid-1`` of a prompt whose earlier KV (cached prefix or
    previous chunks — the suffix body cannot tell the difference) is
    already in the pages named by ``block_row``.  A chunk at offset
    ``start`` IS a suffix continuation at ``start``, so this shares
    :func:`prefill_suffix_paged`'s body verbatim; composing k chunks
    writes the same KV, in the same order, with the same arithmetic, as
    one monolithic dispatch — the bit-identity the chunked oracle rung
    pins.  Only the final chunk's returned logits are consumed (the
    first generated token); intermediate chunks are dispatched for their
    pool side effect alone.
    """
    return prefill_suffix_paged(params, cfg, tokens, pools, block_row,
                                start, n_valid)


def verify_window_paged(params, cfg, tokens, pools, block_row, start,
                        n_valid):
    """Speculative-decoding verification: score K+1 continuation
    positions of ONE sequence in ONE batched dispatch.

    ``tokens`` (1,W) holds [last emitted token, draft_1..draft_K] padded
    to a pow2 bucket width W; ``start`` is the sequence's KV write
    position (the last emitted token's KV lands there, exactly as a
    decode step would place it) and ``n_valid`` = K+1.  The body is the
    same per-layer ``apply_prefill_paged`` path as the prefix-cache
    suffix prefill — kv for all K+1 inputs is scattered into the
    sequence's pages (padding routed to the null page) and every
    position attends causally over the whole page run — so scoring K+1
    positions costs one model pass instead of K+1 sequential decode
    steps, and the arithmetic matches the decode path token-for-token.

    Returns (logits (1,W,V) at every position — position j's greedy
    argmax is the model's true next token after input j, which the
    engine compares against draft j+1 to accept the longest matching
    prefix — and the updated pools).  Rejected positions' KV stays in
    the pages but is masked by position and overwritten before the
    write position reaches it; whole rejected pages are rolled back via
    ``PageAllocator.truncate_to``.
    """
    x, new_pools = _suffix_forward_paged(params, cfg, tokens, pools,
                                         block_row, start, n_valid)
    with jax.named_scope("head"):
        h = nn.rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
        return head_logits(params, cfg, h), new_pools


def decode_step(params, cfg, tokens, caches, pos, *, impl=None):
    """One decode step. tokens (B,1) ids or (B,1,D) embeds; pos scalar.

    Returns (logits (B,1,V), new caches).
    """
    x = embed_tokens(params, cfg, tokens)
    B = x.shape[0]
    if cfg.mrope_sections is not None:
        positions = jnp.full((3, B, 1), pos, jnp.int32)
    else:
        positions = jnp.full((B, 1), pos, jnp.int32)
    angles = _angles(cfg, positions)

    segs = make_segments(cfg)
    new_caches = []
    for seg, seg_p, seg_c in zip(segs, params["segments"], caches):
        def cycle_decode(cyc_p, cyc_c, x):
            new_c = []
            for j, kind in enumerate(seg.kinds):
                x, c = blocks.apply_decode(cyc_p[j], cfg, kind, x, cyc_c[j],
                                           pos, angles=angles)
                new_c.append(c)
            return x, tuple(new_c)

        if seg.scanned:
            def scan_body(x, inp):
                cyc_p, cyc_c = inp
                x, new_c = cycle_decode(cyc_p, cyc_c, x)
                return x, new_c
            x, new_seg = jax.lax.scan(scan_body, x, (seg_p, seg_c))
        else:
            x, new_seg = cycle_decode(seg_p, seg_c, x)
        new_caches.append(new_seg)

    h = nn.rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return head_logits(params, cfg, h), new_caches
