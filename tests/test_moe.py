"""MoE dispatch invariants — unit + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_tiny_config
from repro.models import moe


def _cfg(E=8, k=2, cf=1.25):
    from repro.configs.base import MoEConfig
    return get_tiny_config("grok-1-314b").replace(
        moe=MoEConfig(n_experts=E, top_k=k, d_ff_expert=32,
                      capacity_factor=cf))


@settings(max_examples=25, deadline=None)
@given(T=st.integers(4, 64), E=st.integers(2, 16), k=st.integers(1, 4),
       seed=st.integers(0, 2 ** 16))
def test_dispatch_invariants(T, E, k, seed):
    k = min(k, E)
    ids = jax.random.randint(jax.random.PRNGKey(seed), (T, k), 0, E)
    C = moe.capacity(_cfg(E=E, k=k), T)
    slot_tok, slot = moe.dispatch_indices(ids, T, k, E, C)
    slot_tok = np.asarray(slot_tok)
    slot = np.asarray(slot)
    # every non-sentinel slot holds a valid token id
    valid = slot_tok[slot_tok < T]
    assert ((valid >= 0) & (valid < T)).all()
    # no slot is double-assigned: kept assignments map to unique slots
    kept = slot[slot < E * C]
    assert len(np.unique(kept)) == len(kept)
    # each expert receives at most C tokens
    for e in range(E):
        n_e = ((slot >= e * C) & (slot < (e + 1) * C)).sum()
        assert n_e <= C
    # slot round-trips: slot s holds the token that was routed there
    for i, s in enumerate(slot):
        if s < E * C:
            assert slot_tok[s] == i // k


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_route_weights_normalized(seed):
    cfg = _cfg()
    tokens = jax.random.normal(jax.random.PRNGKey(seed), (16, cfg.d_model))
    rw = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (cfg.d_model, cfg.moe.n_experts))
    w, ids, aux = moe.route(cfg, rw, tokens)
    assert np.allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
    assert float(aux) > 0.0
    assert (np.asarray(ids) < cfg.moe.n_experts).all()


def test_local_moe_matches_dense_when_capacity_huge():
    """With top_k == n_experts and huge capacity, MoE == sum of all expert
    FFNs weighted by (uniform) routing weights."""
    from repro.configs.base import MoEConfig
    cfg = get_tiny_config("grok-1-314b").replace(
        moe=MoEConfig(n_experts=2, top_k=2, d_ff_expert=16,
                      capacity_factor=4.0))
    key = jax.random.PRNGKey(0)
    p = moe.init(key, cfg, jnp.float32)
    T = 8
    tokens = jax.random.normal(jax.random.PRNGKey(1), (T, cfg.d_model))
    out, aux = moe.local_moe(cfg, tokens, p["router_w"], p.get("e_gate"),
                             p["e_up"], p["e_down"])
    # dense reference
    w, ids, _ = moe.route(cfg, p["router_w"], tokens)
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    import repro.models.modules as nn
    ref = jnp.zeros_like(out)
    for t in range(T):
        acc = jnp.zeros((cfg.d_model,))
        for j in range(cfg.moe.top_k):
            e = int(ids[t, j])
            h = nn.activation(cfg.act)(tokens[t] @ p["e_gate"][e]) \
                * (tokens[t] @ p["e_up"][e])
            acc += w[t, j] * (h @ p["e_down"][e])
        ref = ref.at[t].set(acc)
    assert jnp.abs(out - ref).max() < 1e-3


def test_moe_grad_flows():
    cfg = _cfg()
    p = moe.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))

    def loss(p):
        out, aux = moe.apply(p, cfg, x)
        return jnp.sum(out ** 2) + 0.01 * aux

    g = jax.grad(loss)(p)
    leaves = jax.tree.leaves(g)
    assert all(jnp.isfinite(l).all() for l in leaves)
    assert sum(float(jnp.abs(l).sum()) for l in leaves) > 0
