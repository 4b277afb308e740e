"""Mixture-of-Experts FFN with explicit shard_map communication.

Baseline strategy: **expert tensor parallelism** ("etp") — experts are
unsharded (works for any expert count: grok has 8 experts, deepseek 256),
the per-expert FFN hidden dim is sharded over the "model" mesh axis, and
tokens never move between data shards.  The residual stream arrives
sequence-sharded over "model" (Megatron-SP), is all-gathered inside the
shard_map region, dispatched locally (sort + fixed capacity), pushed
through a group-scanned grouped-GEMM, and the partial outputs are
reduce-scattered back to the sequence-sharded layout.  This is the
Swallow design rule made literal: every byte communicated is an explicit
collective in the program text.

The alternative "ep" strategy (experts striped over "model" — the paper's
address%n striping applied to the expert table) is selected by overriding
the logical axis rules {"expert": "model", "expert_ff": None}; the local
dispatch math is identical.  Evaluated in the perf hillclimb.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import modules as nn
from repro.parallel.sharding import current_env

from repro.parallel.sharding import compat_shard_map as _shard_map


def init(key, cfg, dtype):
    """The router over all ``n_experts`` (with ``noaux_tc`` its
    correction bias) and the weights of the experts this device holds
    (``MoEConfig.held``)."""
    m = cfg.moe
    d, fe = cfg.d_model, m.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router_w": nn.dense_init(ks[0], d, m.n_experts, jnp.float32),
        "e_up": _expert_init(ks[1], m.held, d, fe, dtype),
        "e_down": _expert_init(ks[2], m.held, fe, d, dtype,
                               scale=1.0 / max(1, cfg.n_layers) ** 0.5),
    }
    if cfg.gated_ffn:
        p["e_gate"] = _expert_init(ks[3], m.held, d, fe, dtype)
    if m.topk_method == "noaux_tc":
        p["router_bias"] = jnp.zeros((m.n_experts,), jnp.float32)
    return p


def _expert_init(key, e, d_in, d_out, dtype, scale: float = 1.0):
    std = scale * (d_in ** -0.5)
    return (jax.random.normal(key, (e, d_in, d_out), jnp.float32) * std
            ).astype(dtype)


# ---------------------------------------------------------------------------
# routing + dispatch (runs per-shard; pure local math)
# ---------------------------------------------------------------------------
def route(cfg, router_w, tokens, bias=None):
    """tokens (T, D) -> (weights (T,k), ids (T,k), aux_loss scalar).

    ``topk_method="noaux_tc"`` is DeepSeek-V3's rule (its ``MoEGate``):
    with scores ``s`` over all experts, the choice scores ``s + bias``;
    each of ``n_group`` groups scores the sum of its two best choice
    scores; the ``topk_group`` best groups are kept and the ``top_k``
    best choice scores among their experts taken.  The weights are
    ``s`` (not ``s + bias``) at those experts, normalised to sum 1
    (DeepSeek-V3's ``norm_topk_prob``), times ``routed_scaling_factor``.
    """
    m = cfg.moe
    logits = jnp.einsum("td,de->te", tokens.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    if m.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if m.topk_method == "noaux_tc":
        T = scores.shape[0]
        choice = scores if bias is None \
            else scores + bias.astype(jnp.float32)[None]
        groups = choice.reshape(T, m.n_group, m.n_experts // m.n_group)
        group_score = jax.lax.top_k(groups, 2)[0].sum(-1)    # (T, n_group)
        _, keep = jax.lax.top_k(group_score, m.topk_group)
        kept = jnp.zeros((T, m.n_group), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        kept = jnp.repeat(kept, m.n_experts // m.n_group, axis=1)
        _, ids = jax.lax.top_k(jnp.where(kept, choice, -jnp.inf), m.top_k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        if m.top_k > 1:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        w = w * m.routed_scaling_factor
    else:
        w, ids = jax.lax.top_k(scores, m.top_k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * sum_e f_e * p_e
    probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    f = jnp.zeros((m.n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = f / (ids.size)
    p_mean = probs.mean(0)
    aux = m.n_experts * jnp.sum(f * p_mean)
    return w, ids, aux


def capacity(cfg, n_tokens: int) -> int:
    m = cfg.moe
    c = int(math.ceil(n_tokens * m.top_k * m.capacity_factor / m.n_experts))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def dispatch_indices(ids, n_tokens: int, top_k: int, E: int, C: int):
    """Sort token->expert assignments into fixed-capacity slots.

    Returns slot_tok (E*C,) int32 token row per slot (sentinel n_tokens for
    empty), and slot (T*k,) destination slot per assignment (E*C = dropped).
    """
    flat_e = ids.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos_in_e = jnp.arange(flat_e.size, dtype=jnp.int32) - first[sorted_e]
    slot_of_sorted = jnp.where(pos_in_e < C, sorted_e * C + pos_in_e, E * C)
    slot = jnp.zeros((flat_e.size,), jnp.int32).at[order].set(slot_of_sorted)
    tok_ids = jnp.arange(flat_e.size, dtype=jnp.int32) // top_k
    slot_tok = jnp.full((E * C,), n_tokens, jnp.int32).at[slot].set(
        tok_ids, mode="drop")
    return slot_tok, slot


def _group_count(E: int, C: int, D: int, budget_bytes: int = 1 << 27) -> int:
    """Experts per scan step sized so gathered activations stay ~<=128MB."""
    per_expert = C * D * 4
    eg = max(1, min(E, budget_bytes // max(per_expert, 1)))
    while E % eg:
        eg -= 1
    return E // eg


def local_moe(cfg, tokens, router_w, e_gate, e_up, e_down, bias=None):
    """Dense-math MoE on local tokens. tokens (T, D) -> (out (T, D), aux).

    e_* weights may be sharded on the ffn dim (expert-TP): the result is
    then a partial sum the caller must psum/reduce-scatter.
    """
    m = cfg.moe
    T, D = tokens.shape
    E = m.n_experts
    C = capacity(cfg, T)
    act = nn.activation(cfg.act)

    w, ids, aux = route(cfg, router_w, tokens, bias)
    slot_tok, slot = dispatch_indices(ids, T, m.top_k, E, C)
    slot_w = jnp.zeros((E * C,), tokens.dtype).at[slot].set(
        w.reshape(-1).astype(tokens.dtype), mode="drop")

    x_pad = jnp.concatenate([tokens, jnp.zeros((1, D), tokens.dtype)], 0)
    n_g = _group_count(E, C, D)
    eg = E // n_g
    slot_tok_g = slot_tok.reshape(n_g, eg * C)
    slot_w_g = slot_w.reshape(n_g, eg * C)

    def group_step(out_acc, inputs):
        gi, st, sw = inputs
        xg = x_pad[st].reshape(eg, C, D)
        wg_up = jax.lax.dynamic_slice_in_dim(e_up, gi * eg, eg, axis=0)
        wg_dn = jax.lax.dynamic_slice_in_dim(e_down, gi * eg, eg, axis=0)
        up = jnp.einsum("ecd,edf->ecf", xg, wg_up,
                        preferred_element_type=jnp.float32)
        if e_gate is not None:
            wg_gt = jax.lax.dynamic_slice_in_dim(e_gate, gi * eg, eg, axis=0)
            gt = jnp.einsum("ecd,edf->ecf", xg, wg_gt,
                            preferred_element_type=jnp.float32)
            h = act(gt) * up
        else:
            h = act(up)
        y = jnp.einsum("ecf,efd->ecd", h.astype(tokens.dtype), wg_dn,
                       preferred_element_type=jnp.float32)
        y = (y.reshape(eg * C, D) * sw[:, None]).astype(jnp.float32)
        out_acc = out_acc.at[st].add(y, mode="drop")
        return out_acc, None

    out0 = jnp.zeros((T + 1, D), jnp.float32)
    out, _ = jax.lax.scan(group_step, out0,
                          (jnp.arange(n_g), slot_tok_g, slot_w_g))
    return out[:T].astype(tokens.dtype), aux


# ---------------------------------------------------------------------------
# dropless inference over the held experts
# ---------------------------------------------------------------------------
def apply_dropless(p, cfg, x):
    """Inference path: x (..., D) -> (out (..., D), held pairs (...,)).

    The router scores all ``n_experts`` (in float32, as the published
    gate does); of each token's ``top_k`` (token, expert) pairs those
    on the experts this device holds
    (``MoEConfig.held`` from ``first_held``) are sorted by expert and
    run through one grouped matmul per expert matrix
    (``jax.lax.ragged_dot``: on the chip a grouped-matmul kernel that
    visits only the rows of each group), weighted and added back to
    their tokens.  Nothing is dropped and no capacity is set, so a
    token's output does not depend on the other tokens of its batch.
    Pairs on experts held elsewhere are left out: on one device of an
    expert-parallel deployment this is the device's part of the layer.
    The second output counts each token's pairs on held experts."""
    m = cfg.moe
    lead, D = x.shape[:-1], x.shape[-1]
    tokens = x.reshape(-1, D)
    T, k, E_h = tokens.shape[0], m.top_k, m.held
    with jax.named_scope("router"):
        w, ids, _ = route(cfg, p["router_w"], tokens, p.get("router_bias"))
        local = ids - m.first_held
        mine = (local >= 0) & (local < E_h)
        expert = jnp.where(mine, local, E_h).reshape(-1)     # (T*k,)
        # a token has at most min(k, E_h) pairs on held experts, and the
        # stable sort puts the others last
        order = jnp.argsort(expert, stable=True)[:T * min(k, E_h)]
        sizes = jnp.zeros((E_h + 1,), jnp.int32).at[expert].add(1)[:E_h]
        tok = order // k
        weight = jnp.where(mine.reshape(-1), w.reshape(-1), 0.0)[order]
    with jax.named_scope("experts"):
        xs = tokens[tok]
        act = nn.activation(cfg.act)

        def grouped(a, wmat):
            return jax.lax.ragged_dot(a, wmat, sizes,
                                      preferred_element_type=jnp.float32)
        up = grouped(xs, p["e_up"])
        h = act(grouped(xs, p["e_gate"])) * up if "e_gate" in p \
            else act(up)
        y = grouped(h.astype(tokens.dtype), p["e_down"]) * weight[:, None]
        out = jnp.zeros((T, D), jnp.float32).at[tok].add(y)
    return (out.astype(x.dtype).reshape(x.shape),
            mine.sum(-1).astype(jnp.int32).reshape(lead))


# ---------------------------------------------------------------------------
# sharded entry point
# ---------------------------------------------------------------------------
def apply(p, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, D) -> (out (B, S, D), aux loss scalar): the training
    path, with capacity routing over all experts."""
    if cfg.moe.held != cfg.moe.n_experts:
        raise NotImplementedError(
            "capacity routing needs every expert held; a device holding "
            "part of them serves through apply_dropless")
    env = current_env()
    e_gate = p.get("e_gate")
    if env is None:
        B, S, D = x.shape
        out, aux = local_moe(cfg, x.reshape(B * S, D), p["router_w"],
                             e_gate, p["e_up"], p["e_down"],
                             p.get("router_bias"))
        return out.reshape(B, S, D), aux

    mesh = env.mesh
    B, S, D = x.shape
    tp = env.resolve("expert_ff")          # model axis (expert-TP) or None
    ep = env.resolve("expert")             # model axis (EP) or None
    fsdp = env.resolve("fsdp")
    batch = env.resolve("batch")
    model_size = 1
    for a in _axes_tuple(tp) + _axes_tuple(ep):
        model_size *= mesh.shape[a]
    # x arrives FULL-sequence (blocks gather after the pre-norm); the
    # output is reduce-scattered back to the seq-sharded residual layout.
    seq_shard = (S % max(model_size, 1) == 0) and model_size > 1 and S > 1
    seq_axes = (tp or ep) if seq_shard else None

    in_specs = (
        env.spec("batch", "seq_sp" if seq_shard else None, None),   # x
        env.spec("fsdp", None),                                     # router
        env.spec("expert", "fsdp", "expert_ff"),                    # gate
        env.spec("expert", "fsdp", "expert_ff"),                    # up
        env.spec("expert", "expert_ff", "fsdp"),                    # down
    )
    out_specs = (env.spec("batch", "seq_sp" if seq_shard else None, None),
                 env.spec())

    fn = partial(_sharded_moe, cfg=cfg, seq_axes=_axes_tuple(seq_axes),
                 tp_axes=_axes_tuple(tp), ep_axes=_axes_tuple(ep),
                 fsdp_axes=_axes_tuple(fsdp), batch_axes=_axes_tuple(batch))
    gate_arg = e_gate if e_gate is not None else jnp.zeros(
        (0,) + p["e_up"].shape[1:], p["e_up"].dtype)
    out, aux = _shard_map(fn, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)(
        x, p["router_w"], gate_arg, p["e_up"], p["e_down"])
    return out, aux


def _axes_tuple(a):
    if a is None:
        return ()
    return (a,) if isinstance(a, str) else tuple(a)


def _sharded_moe(x, router_w, e_gate, e_up, e_down, *, cfg, seq_axes,
                 tp_axes, ep_axes, fsdp_axes, batch_axes):
    """shard_map body: explicit AG / RS around the local MoE math."""
    # 1. gather sequence shards so each model shard sees its full tokens
    for ax in seq_axes:
        x = jax.lax.all_gather(x, ax, axis=1, tiled=True)
    # 2. gather weight FSDP shards (nodes-as-storage: fetch remote shards)
    for ax in fsdp_axes:
        router_w = jax.lax.all_gather(router_w, ax, axis=0, tiled=True)
        e_up = jax.lax.all_gather(e_up, ax, axis=1, tiled=True)
        e_down = jax.lax.all_gather(e_down, ax, axis=2, tiled=True)
        if e_gate.shape[0]:
            e_gate = jax.lax.all_gather(e_gate, ax, axis=1, tiled=True)
    gate = e_gate if e_gate.shape[0] else None

    B, S, D = x.shape
    tokens = x.reshape(B * S, D)

    if ep_axes:
        out, aux = _local_moe_ep(cfg, tokens, router_w, gate, e_up, e_down,
                                 ep_axes)
    else:
        out, aux = local_moe(cfg, tokens, router_w, gate, e_up, e_down)

    out = out.reshape(B, S, D)
    # 3. combine partial sums (expert-TP) / complete EP outputs, returning
    #    to the sequence-sharded residual layout
    comb_axes = tp_axes + ep_axes
    if seq_axes:
        for ax in comb_axes:
            out = jax.lax.psum_scatter(out, ax, scatter_dimension=1,
                                       tiled=True)
    elif comb_axes:
        out = jax.lax.psum(out, comb_axes)
    # average aux across data shards
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)
    return out, aux


def _local_moe_ep(cfg, tokens, router_w, e_gate, e_up, e_down, ep_axes):
    """EP variant: each shard holds E_local experts; tokens routed to local
    experts only (others contribute via the later psum over ep axes)."""
    m = cfg.moe
    T, D = tokens.shape
    E_local = e_up.shape[0]
    idx = jax.lax.axis_index(ep_axes[0]) if len(ep_axes) == 1 else \
        _linear_index(ep_axes)
    e_lo = idx * E_local

    w, ids, aux = route(cfg, router_w, tokens)
    # keep only assignments owned by this shard; remap to local expert ids
    local = (ids >= e_lo) & (ids < e_lo + E_local)
    ids_l = jnp.where(local, ids - e_lo, E_local)     # E_local = drop bucket
    w_l = jnp.where(local, w, 0.0)

    C = capacity(cfg, T)  # same global capacity per expert
    slot_tok, slot = dispatch_indices(ids_l, T, m.top_k, E_local + 1, C)
    # slots belonging to the drop bucket are masked via zero weights
    slot_w = jnp.zeros(((E_local + 1) * C,), tokens.dtype).at[slot].set(
        w_l.reshape(-1).astype(tokens.dtype), mode="drop")
    slot_tok = slot_tok[: E_local * C]
    slot_w = slot_w[: E_local * C]

    x_pad = jnp.concatenate([tokens, jnp.zeros((1, D), tokens.dtype)], 0)
    act = nn.activation(cfg.act)
    xg = x_pad[slot_tok].reshape(E_local, C, D)
    up = jnp.einsum("ecd,edf->ecf", xg, e_up,
                    preferred_element_type=jnp.float32)
    if e_gate is not None:
        gt = jnp.einsum("ecd,edf->ecf", xg, e_gate,
                        preferred_element_type=jnp.float32)
        h = act(gt) * up
    else:
        h = act(up)
    y = jnp.einsum("ecf,efd->ecd", h.astype(tokens.dtype), e_down,
                   preferred_element_type=jnp.float32)
    y = (y.reshape(E_local * C, D) * slot_w[:, None]).astype(jnp.float32)
    out = jnp.zeros((T + 1, D), jnp.float32).at[slot_tok].add(y, mode="drop")
    return out[:T].astype(tokens.dtype), aux


def _linear_index(axes):
    idx = 0
    for ax in axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx
