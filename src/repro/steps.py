"""Step builders: train_step / prefill_step / serve_step + abstract specs.

These are the units the launcher jits, the dry-run lowers, and the
roofline analyzer reads.  Everything here works on ShapeDtypeStructs
(no allocation) so a 671B-parameter model can be lowered on one CPU.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ATTN, LOCAL, MLA, RGLRU, RWKV6, ModelConfig,
                                ShapeConfig)
from repro.models import lm, modules as nn
from repro.optim import adam as adam_lib
from repro.parallel.sharding import (ShardingEnv, current_env, param_specs)


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, adam_cfg: adam_lib.AdamConfig,
                    schedule=None, impl: Optional[str] = None):
    schedule = schedule or (lambda s: 3e-4)

    def train_step(params, opt_state, batch):
        def lf(p):
            return lm.loss_fn(p, cfg, batch, impl=impl)
        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        lr = schedule(opt_state.step)
        params2, opt_state2, om = adam_lib.update(
            grads, opt_state, params, lr=lr, cfg=adam_cfg)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["lr"] = lr
        return params2, opt_state2, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, max_len: int,
                      impl: Optional[str] = None):
    def prefill_step(params, tokens, positions=None):
        return lm.prefill(params, cfg, tokens, max_len=max_len,
                          positions=positions, impl=impl)
    return prefill_step


def make_serve_step(cfg: ModelConfig, impl: Optional[str] = None):
    """One decode step: greedy-sample next token given the KV cache."""
    def serve_step(params, tokens, caches, pos):
        logits, caches = lm.decode_step(params, cfg, tokens, caches, pos,
                                        impl=impl)
        if cfg.embed_inputs:
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, caches
    return serve_step


def constrain_paged_pools(pools):
    """Pin the page-axis stripe of every KV pool leaf.

    No-op without a mesh.  Every pool-returning step ends with this so
    the scatters/gathers inside never let GSPMD resolve the output pools
    to a different (e.g. replicated) layout — the device-side analogue
    of ``StripedStore.write`` re-pinning its slab.  The page axis is
    third-from-last in both unstacked (P, ps, F) and scan-stacked
    (C, P, ps, F) leaves.  The pools reach it updated in place: a
    scanned segment's stack rides the layer scan's carry and each layer
    scatters into it at ``(layer, page, slot)`` (``lm._paged_layers``),
    so with the pools donated the output aliases the input buffer and
    no step copies a pool.
    """
    env = current_env()
    if env is None:
        return pools

    def pin(a):
        spec = ((None,) * (a.ndim - 3)) + ("pages", None, None)
        return jax.lax.with_sharding_constraint(a, env.sharding(*spec))
    return jax.tree.map(pin, pools)


def _scoped(fn):
    """Run a step function under a ``jax.named_scope`` of its own name,
    so every operation of the program carries it in the HLO metadata
    the profiler reports (metadata only: the compiled code is the
    same)."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(fn.__name__):
            return fn(*args, **kwargs)
    return scoped


def make_paged_prefill_step(cfg: ModelConfig, impl: Optional[str] = None):
    """Prefill ONE sequence straight into the paged KV pools.

    (params, tokens (1,S), pools, block_row (nmax,)) ->
    (next-token logits (1,1,V), updated pools).  Jit with the pools
    donated — the scatter is in-place on device.
    """
    @_scoped
    def prefill_paged(params, tokens, pools, block_row):
        h, raw, _ = lm.forward(params, cfg, tokens, mode="prefill",
                               impl=impl)
        with jax.named_scope("kv_append"):
            pools = lm.paged_from_prefill(cfg, pools, raw, block_row)
        with jax.named_scope("head"):
            h_last = nn.rmsnorm(h[:, -1:], params["final_norm"]["scale"],
                                cfg.norm_eps)
            logits = lm.head_logits(params, cfg, h_last)
        return logits, constrain_paged_pools(pools)
    return prefill_paged


def make_paged_serve_step(cfg: ModelConfig):
    """One continuous-batch paged decode step (greedy sampling).

    (params, tokens (B,1), pools, block_tables (B,nmax), pos (B,)) ->
    (next tokens (B,1), logits, updated pools).  Jit with the pools
    donated: the layer scan updates them in place.
    """
    @_scoped
    def serve_paged(params, tokens, pools, block_tables, pos):
        logits, pools, _ = lm.decode_step_paged(params, cfg, tokens, pools,
                                                block_tables, pos)
        with jax.named_scope("head"):
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, constrain_paged_pools(pools)
    return serve_paged


def make_paged_suffix_prefill(cfg: ModelConfig):
    """Batched suffix prefill for prefix-cache hits.

    (params, tokens (1,W) padded suffix ids, pools, block_row (nmax,),
     start, n_valid) -> (first-token logits (1,1,V), updated pools).
    Only the uncached suffix runs through the model — one dispatch,
    attending the shared-prefix KV through the block row.  Jit with the
    pools donated; the padded width W is the only retrace axis (the
    engine buckets it to powers of two).  Every layer writes the suffix
    kv into the donated pools in place, through the layer scan's carry.
    """
    @_scoped
    def suffix_prefill(params, tokens, pools, block_row, start, n_valid):
        logits, pools = lm.prefill_suffix_paged(params, cfg, tokens, pools,
                                                block_row, start, n_valid)
        return logits, constrain_paged_pools(pools)
    return suffix_prefill


def make_chunk_prefill(cfg: ModelConfig):
    """One page-aligned prefill chunk for a prefilling slot.

    (params, tokens (1,W) padded chunk ids, pools, block_row (nmax,),
     start, n_valid) -> (last-position logits (1,1,V), updated pools).
    The chunk is a suffix continuation — positions ``start ..
    start+n_valid-1`` run through ``lm.chunk_prefill_paged``
    (== ``prefill_suffix_paged``: same layer path, same paged scatter,
    same causal attention over the page run), which is why chunked
    prefill is bit-identical to monolithic: each chunk writes exactly
    the KV a single prefill would have written at those positions, and
    only the final chunk's logits are read (the first generated token).
    Jit with the pools donated (each layer scatters into them in place,
    through the layer scan's carry); the padded width W is the only
    retrace axis (the engine buckets it to powers of two), so a heavy-tailed
    prompt-length distribution compiles O(log max_chunk) kernels instead
    of one per length.
    """
    @_scoped
    def chunk_prefill(params, tokens, pools, block_row, start, n_valid):
        logits, pools = lm.chunk_prefill_paged(params, cfg, tokens, pools,
                                               block_row, start, n_valid)
        return logits, constrain_paged_pools(pools)
    return chunk_prefill


def make_verify_window(cfg: ModelConfig):
    """Speculative-decoding verification window (one sequence, one
    dispatch).

    (params, tokens (1,W) [last token + K padded drafts], pools,
     block_row (nmax,), start, n_valid) -> (logits (1,W,V) at every
    position, updated pools).  Reuses the suffix-prefill layer path
    (``attention.apply_prefill_paged``) so scoring K+1 positions costs
    one model pass with decode-identical arithmetic.  Jit with the pools
    donated (updated in place through the layer scan's carry); the
    padded width W is the only retrace axis (the engine buckets it to
    powers of two).
    """
    @_scoped
    def verify_window(params, tokens, pools, block_row, start, n_valid):
        logits, pools = lm.verify_window_paged(params, cfg, tokens, pools,
                                               block_row, start, n_valid)
        return logits, constrain_paged_pools(pools)
    return verify_window


def make_spec_draft_verify(cfg: ModelConfig):
    """Fused speculative draft+verify for ONE slot (device-resident
    drafting — no host materialization of candidate drafts).

    (params, history (B,H) device token-history rows, pools,
     block_tables (B,nmax), slot, start, k) ->
    (emitted (W,), n_emit, m, history, pools), with the verify width
    ``W`` static (the engine buckets it to powers of two) and
    ``max_n``/``min_n`` static n-gram bounds.  ``slot``/``start``/``k``
    are traced scalars: one compilation per width serves every slot,
    position and draft depth.

    One dispatch chains the whole speculation round on device:

    1. ``device_propose`` suffix-matches the slot's history row
       (``hist_len = start + 1`` — ``start`` is the next KV write
       position, whose token's KV is not yet written) for a draft of up
       to ``min(k, W-1)`` tokens;
    2. ``verify_window_paged`` scores last-token + draft (``n_valid =
       m+1`` positions) against the paged KV in one model pass;
    3. the greedy acceptance rule keeps the longest matching prefix and
       appends the verifier's bonus/correction token — ``emitted[:n_emit]``
       with ``n_emit = accepted + 1`` is exactly what non-speculative
       greedy decode would emit;
    4. the accepted tokens are appended to the slot's history row, so
       the next window drafts from an already-current device history.

    Jit with history and the pools donated (the verify pass updates the
    pools in place through the layer scan's carry); the host pulls only
    ``(emitted, n_emit, m)`` — one d2h event per verify.
    """
    from repro.serving.spec_decode import device_propose

    @_scoped
    def draft_verify(params, history, pools, block_tables, slot, start, k,
                     *, W: int, max_n: int, min_n: int):
        H = history.shape[-1]
        row = jax.lax.dynamic_index_in_dim(history, slot, 0,
                                           keepdims=False)
        block_row = jax.lax.dynamic_index_in_dim(block_tables, slot, 0,
                                                 keepdims=False)
        hist_len = jnp.asarray(start, jnp.int32) + 1
        draft, m = device_propose(row, hist_len, k, k_max=W - 1,
                                  max_n=max_n, min_n=min_n)
        last = row[jnp.clip(hist_len - 1, 0, H - 1)]
        tokens = jnp.concatenate([last[None], draft])[None, :]   # (1, W)
        logits, pools = lm.verify_window_paged(params, cfg, tokens, pools,
                                               block_row, start, m + 1)
        greedy = jnp.argmax(logits[0], -1).astype(jnp.int32)     # (W,)
        offs = jnp.arange(W, dtype=jnp.int32)
        draft_w = jnp.concatenate([draft, jnp.zeros((1,), jnp.int32)])
        ok = (offs < m) & (greedy == draft_w)
        a = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))   # accepted prefix
        bonus = greedy[a]                # correction (or bonus) token
        emitted = jnp.where(offs < a, draft_w, 0)
        emitted = jnp.where(offs == a, bonus, emitted)
        n_emit = a + 1
        # append the emission to the history row on device: positions
        # hist_len .. hist_len+n_emit-1 take emitted[0..n_emit-1]
        rel = jnp.arange(H, dtype=jnp.int32) - hist_len
        src = emitted[jnp.clip(rel, 0, W - 1)]
        new_row = jnp.where((rel >= 0) & (rel < n_emit), src, row)
        history = jax.lax.dynamic_update_index_in_dim(history, new_row,
                                                      slot, 0)
        return emitted, n_emit, m, history, constrain_paged_pools(pools)
    return draft_verify


def make_page_copy():
    """Copy-on-write: duplicate one physical page across every layer's
    k/v pool in a single device dispatch.

    (pools, src, dst) -> pools with page ``dst`` := page ``src``
    everywhere.  The page axis is third-from-last in both unstacked
    (P, ps, F) and scan-stacked (C, P, ps, F) pool leaves, so one
    ellipsis-indexed scatter covers the whole pytree.  The whole page is
    copied — slots past the shared fill point hold stale values the
    diverging request overwrites before its position ever reaches them.
    Jit with the pools donated; src/dst are traced scalars (one compile).
    """
    @_scoped
    def copy_page(pools, src, dst):
        pools = jax.tree.map(
            lambda a: a.at[..., dst, :, :].set(a[..., src, :, :]), pools)
        return constrain_paged_pools(pools)
    return copy_page


def make_paged_serve_scan(cfg: ModelConfig):
    """Fused K-step paged decode window (device-resident serving).

    (params, tokens (B,1), pools, block_tables (B,nmax), pos (B,),
     active (B,), k) -> (emitted (B,K), last tokens (B,1), pos (B,),
    updated pools), and for a model with routed experts also the
    window's counters ``[moe_tokens, moe_pairs_held]`` (int32 (2,),
    ``lm.decode_window_paged``), pulled with the emitted tokens in the
    window's one sync.  ``k`` is the scan length — jit with
    ``static_argnames=("k",)`` and the pools donated; one dispatch and
    one host sync then cover K decode steps instead of one.  The pools
    stay in the donated buffer for the whole window: the window scan
    carries them into the layer scan's carry, where every layer's
    append is one scatter into the stack and its gather reads the
    stack at ``(layer, block_tables)``.
    """
    @_scoped
    def serve_scan(params, tokens, pools, block_tables, pos, active, *,
                   k: int):
        emitted, last, pos, pools, counts = lm.decode_window_paged(
            params, cfg, tokens, pools, block_tables, pos, active, k)
        out = (emitted, last, pos, constrain_paged_pools(pools))
        return out if counts is None else out + (counts,)
    return serve_scan


# ---------------------------------------------------------------------------
# abstract state + sharding specs
# ---------------------------------------------------------------------------
def abstract_params(cfg: ModelConfig):
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(lambda k: lm.init_params(k, cfg), key)


def abstract_opt_state(cfg: ModelConfig, adam_cfg, params_shape):
    return jax.eval_shape(lambda p: adam_lib.init(p, adam_cfg), params_shape)


def with_shardings(shape_tree, spec_tree, mesh):
    """Attach NamedShardings to a ShapeDtypeStruct tree."""
    def mk(sds, spec):
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree.map(mk, shape_tree, spec_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _prepend_none(spec: P, n: int = 1) -> P:
    return P(*(((None,) * n) + tuple(spec)))


def cache_specs(cfg: ModelConfig, env: ShardingEnv):
    """PartitionSpec pytree mirroring lm.init_caches."""
    def kind_spec(kind):
        if kind in (ATTN, LOCAL):
            # split-T layout: time dim sharded over "model" so decode scans
            # the cache in place (flash-decoding; §Perf iteration 5)
            s = env.spec("batch", "seq_sp", None)
            from repro.models.attention import AttnCache
            return AttnCache(k=s, v=s)
        if kind == MLA:
            from repro.models.mla import MLACache
            return MLACache(ckv=env.spec("batch", "seq_sp", None),
                            k_rope=env.spec("batch", "seq_sp", None))
        if kind == RGLRU:
            from repro.models.rglru import RGLRUCache
            return RGLRUCache(h=env.spec("batch", "tp"),
                              conv=env.spec("batch", None, "tp"))
        if kind == RWKV6:
            from repro.models.rwkv6 import RWKVCache
            return RWKVCache(state=env.spec("batch", "heads", None, None),
                             x_tm=env.spec("batch", None),
                             x_cm=env.spec("batch", None))
        raise ValueError(kind)

    out = []
    for seg in lm.make_segments(cfg):
        cyc = tuple(kind_spec(k) for k in seg.kinds)
        if seg.scanned:
            cyc = jax.tree.map(lambda s: _prepend_none(s), cyc,
                               is_leaf=lambda x: isinstance(x, P))
        out.append(cyc)
    return out


def batch_specs(cfg: ModelConfig, env: ShardingEnv):
    tok = env.spec("batch", None) if cfg.embed_inputs \
        else env.spec("batch", None, None)
    b = {"tokens": tok,
         "labels": env.spec("batch", None),
         "mask": env.spec("batch", None)}
    if cfg.mrope_sections is not None:
        b["positions"] = env.spec(None, "batch", None)
    return b


def make_batch_struct(cfg: ModelConfig, shape: ShapeConfig, mesh,
                      env: ShardingEnv):
    B, S = shape.global_batch, shape.seq_len
    if cfg.embed_inputs:
        tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    else:
        tok = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                   nn.dt(cfg.activation_dtype))
    batch = {"tokens": tok,
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "mask": jax.ShapeDtypeStruct((B, S), jnp.float32)}
    if cfg.mrope_sections is not None:
        batch["positions"] = jax.ShapeDtypeStruct((3, B, S), jnp.int32)
    return with_shardings(batch, batch_specs(cfg, env), mesh)


def make_decode_structs(cfg: ModelConfig, shape: ShapeConfig, mesh,
                        env: ShardingEnv):
    """(tokens, caches, pos) ShapeDtypeStructs for serve_step."""
    B, T = shape.global_batch, shape.seq_len
    if cfg.embed_inputs:
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        tok_spec = env.spec("batch", None)
    else:
        tok = jax.ShapeDtypeStruct((B, 1, cfg.d_model),
                                   nn.dt(cfg.activation_dtype))
        tok_spec = env.spec("batch", None, None)
    tok = with_shardings(tok, tok_spec, mesh)
    caches_shape = jax.eval_shape(lambda: lm.init_caches(cfg, B, T))
    caches = with_shardings(caches_shape, cache_specs(cfg, env), mesh)
    pos = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    return tok, caches, pos


def make_state_structs(cfg: ModelConfig, adam_cfg, mesh, env: ShardingEnv):
    """(params, opt_state) ShapeDtypeStructs with shardings (no alloc)."""
    p_shape = abstract_params(cfg)
    p_spec = param_specs(p_shape, env)
    params = with_shardings(p_shape, p_spec, mesh)
    o_shape = abstract_opt_state(cfg, adam_cfg, p_shape)
    o_spec = adam_lib.state_specs(p_shape, adam_cfg, p_spec)
    opt = with_shardings(o_shape, o_spec, mesh)
    return params, opt


def adam_config_for(cfg: ModelConfig) -> adam_lib.AdamConfig:
    return adam_lib.AdamConfig(state_dtype=cfg.opt_state_dtype)
