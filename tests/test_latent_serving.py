"""DeepSeek-V3's mechanisms on the paged engine, at a tiny size.

The paged latent (MLA) pool with absorbed decode and the two latent
prefill paths, the dropless expert layer over the experts a device
holds, ``noaux_tc`` routing and YaRN, each against the dense path, a
plain NumPy version or the written formula.

The configuration is DeepSeek-V3's tiny preset with three expert layers
after the dense one (so the expert segment is a layer scan) and latent
widths at which a 16-token slice takes the up-projected path and a
shorter one the absorbed path (``mla.absorbed_cheaper``); attention runs
in blocks of 8 keys, so a slice spans several blocks.  Token-for-token
comparisons run in float32 activations: in bfloat16 the dense path's
up-projected attention and the paged latent paths round differently and
random weights leave near-ties that the rounding flips.  The logit
comparisons run in both.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dense_oracle, make_engine, seeded_prompts

MAX_LEN = 48


def _cfg(act="float32", **moe):
    import dataclasses
    from repro.configs import get_tiny_config
    from repro.configs.base import MLAConfig
    cfg = get_tiny_config("deepseek-v3-671b")
    return cfg.replace(
        name=f"ds-latent-{act}-{sorted(moe.items())}", n_layers=4,
        activation_dtype=act,
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=8,
                      qk_rope_head_dim=8, v_head_dim=8),
        moe=dataclasses.replace(cfg.moe, **moe))


@pytest.fixture(autouse=True)
def _small_key_blocks(monkeypatch):
    from repro.models import mla
    monkeypatch.setattr(mla, "KEY_BLOCK", 8)


_PARAMS = {}


def _model(act="float32", **moe):
    from repro.models import lm
    cfg = _cfg(act, **moe)
    if cfg.name not in _PARAMS:
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        # a correction bias that moves the choice (the published one is
        # learned; zeros would leave noaux_tc's bias path untested)
        for seg in params["segments"]:
            for blk in seg:
                if "moe" in blk:
                    b = blk["moe"]["router_bias"]
                    blk["moe"]["router_bias"] = 0.3 * jax.random.normal(
                        jax.random.PRNGKey(9), b.shape)
        _PARAMS[cfg.name] = params
    return cfg, _PARAMS[cfg.name]


def _serve(cfg, params, prompts, gens, **kw):
    eng = make_engine(cfg, params, max_len=MAX_LEN, n_pages=80, **kw)
    rids = [eng.submit(p, g).rid for p, g in zip(prompts, gens)]
    done = {r.rid: list(r.tokens) for r in eng.run()}
    return {f"r{i}": done[rid] for i, rid in enumerate(rids)}, eng


FLAGS = {
    "decode": dict(),
    "chunked": dict(chunked_prefill=True, chunk_tokens=16),
    "prefix_cow": dict(prefix_cache=True),
    "prefix_chunked": dict(prefix_cache=True, chunked_prefill=True,
                           chunk_tokens=16),
    "speculation": dict(prefix_cache=True, spec_decode=True, spec_k=4),
}


@pytest.mark.parametrize("flags", sorted(FLAGS))
def test_paged_latent_serving_matches_dense_oracle(flags):
    """Every served path (fused decode windows, chunked prefill in
    up-projected 16-token slices, suffix prefill after a prefix hit
    that ends inside a page, which copies the page, and speculative
    verification) gives the dense path's greedy tokens."""
    cfg, params = _model()
    # 13 shared tokens end inside the second 4-token page
    prompts = seeded_prompts(cfg, 5, 22, shared=13)
    gens = [7, 5, 9, 4, 6]
    want = dense_oracle(cfg, params, prompts, gens, MAX_LEN)
    got, eng = _serve(cfg, params, prompts, gens, **FLAGS[flags])
    assert got == want
    if eng.cache is not None:
        assert eng.cache.stats.cow_copies > 0


@pytest.mark.parametrize("act,tol", [("float32", 1e-4), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("width", [4, 16])
def test_paged_latent_prefill_and_decode_logits_match_dense(act, tol,
                                                           width):
    """A prompt prefilled in slices of ``width`` tokens (4: absorbed, 16:
    up-projected, each over several key blocks), then one decode step,
    gives the dense path's logits.  Tolerance, relative to the largest
    logit: float32 1e-4 (summation order only); bfloat16 3e-2 (the two
    latent paths round the scores and values at other points than the
    dense up-projected attention, a few bf16 ulps of the logits)."""
    from repro import steps
    from repro.models import lm, mla
    cfg, params = _model(act)
    assert mla.absorbed_cheaper(cfg, 4) and not mla.absorbed_cheaper(cfg,
                                                                      16)
    S, ps = 28, 4
    toks = jnp.asarray(seeded_prompts(cfg, 1, S + 1, seed=3)[0])
    h, _, _ = lm.forward(params, cfg, toks[None], mode="prefill")
    from repro.models import modules as nn
    full = lm.head_logits(params, cfg, nn.rmsnorm(
        h, params["final_norm"]["scale"], cfg.norm_eps))[0]
    pools = lm.init_paged_caches(cfg, 40, ps)
    row = jnp.arange(1, 1 + MAX_LEN // ps, dtype=jnp.int32)
    chunk = jax.jit(steps.make_chunk_prefill(cfg))
    for start in range(0, S, width):
        n = min(width, S - start)
        pad = jnp.zeros((1, width), jnp.int32).at[0, :n].set(
            toks[start:start + n])
        logits, pools = chunk(params, pad, pools, row, start, n)
    scale = float(jnp.abs(full).max())
    assert float(jnp.abs(logits[0, 0] - full[S - 1]).max()) < tol * scale
    serve = jax.jit(steps.make_paged_serve_step(cfg))
    _, logits, _ = serve(params, toks[S:S + 1][None], pools, row[None],
                         jnp.array([S], jnp.int32))
    assert float(jnp.abs(logits[0, 0] - full[S]).max()) < tol * scale


def test_dropless_token_output_is_the_same_alone_and_in_any_batch():
    from repro.models import moe
    cfg, params = _model()
    p = params["segments"][1][0]["moe"]
    p = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(1), (12, cfg.d_model))
    whole, held = jax.jit(lambda x: moe.apply_dropless(p, cfg, x))(x)
    for i in range(12):
        one, one_held = moe.apply_dropless(p, cfg, x[i:i + 1])
        np.testing.assert_allclose(np.asarray(one[0]), np.asarray(whole[i]),
                                   rtol=1e-6, atol=1e-6)
        assert int(one_held[0]) == int(held[i])
    perm = np.random.default_rng(0).permutation(12)
    shuffled, _ = moe.apply_dropless(p, cfg, x[perm])
    np.testing.assert_allclose(np.asarray(shuffled),
                               np.asarray(whole)[perm], rtol=1e-6, atol=1e-6)


def _np_route(scores, bias, n_group, topk_group, top_k, scale):
    """DeepSeek-V3's ``MoEGate`` (``noaux_tc``) in plain NumPy, one token
    at a time."""
    E = scores.shape[-1]
    ws, ids = [], []
    for s in scores:
        c = s + bias
        g = c.reshape(n_group, E // n_group)
        gs = np.sort(g, axis=1)[:, -2:].sum(1)
        keep = np.argsort(-gs, kind="stable")[:topk_group]
        masked = np.full(E, -np.inf)
        for k in keep:
            sl = slice(k * E // n_group, (k + 1) * E // n_group)
            masked[sl] = c[sl]
        pick = np.argsort(-masked, kind="stable")[:top_k]
        w = s[pick] / (s[pick].sum() + 1e-20) * scale
        ws.append(w)
        ids.append(pick)
    return np.array(ws), np.array(ids)


def test_noaux_tc_routing_matches_numpy():
    from repro.configs.base import MoEConfig
    from repro.models import moe
    cfg, _ = _model()
    cfg = cfg.replace(moe=MoEConfig(
        n_experts=32, top_k=4, d_ff_expert=8, n_shared=1,
        score_func="sigmoid", topk_method="noaux_tc", n_group=8,
        topk_group=3, routed_scaling_factor=2.5))
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (40, cfg.d_model))
    rw = jax.random.normal(ks[1], (cfg.d_model, 32)) * 0.2
    bias = jax.random.normal(ks[2], (32,)) * 0.1
    w, ids, _ = moe.route(cfg, rw, x, bias)
    scores = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                               @ np.asarray(rw, np.float64))))
    w_np, ids_np = _np_route(scores, np.asarray(bias, np.float64), 8, 3, 4,
                             2.5)
    order = np.argsort(np.asarray(ids), 1)
    order_np = np.argsort(ids_np, 1)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(ids), order,
                                                     1),
                                  np.take_along_axis(ids_np, order_np, 1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, 1),
                               np.take_along_axis(w_np, order_np, 1),
                               rtol=1e-5)


def _np_layer(cfg, p, x, experts):
    """The expert layer in plain NumPy, one token at a time, over the
    routed experts ``first .. first + held - 1`` of ``p``'s (all the
    experts') weights."""
    m = cfg.moe
    x = np.asarray(x, np.float64)
    s = 1 / (1 + np.exp(-(x @ np.asarray(p["router_w"], np.float64))))
    w, ids = _np_route(s, np.asarray(p["router_bias"], np.float64),
                       m.n_group, m.topk_group, m.top_k,
                       m.routed_scaling_factor)
    silu = lambda a: a / (1 + np.exp(-a))   # noqa: E731
    out = np.zeros_like(x)
    first, held = experts
    for t in range(x.shape[0]):
        for j in range(m.top_k):
            e = ids[t, j]
            if first <= e < first + held:
                g = x[t] @ np.asarray(p["e_gate"][e], np.float64)
                u = x[t] @ np.asarray(p["e_up"][e], np.float64)
                out[t] += w[t, j] * (silu(g) * u) @ np.asarray(
                    p["e_down"][e], np.float64)
    return out


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """Four devices of 2 of the 8 experts each: their parts, with the
    shared expert counted once, add up to the uncut layer (a plain NumPy
    version over all experts); each part is the NumPy layer over its own
    experts, and its held-pair count is the NumPy routing's."""
    import dataclasses
    from repro.models import moe
    from repro.models import modules as nn
    cfg, params = _model()
    blk = jax.tree.map(lambda a: a[0], params["segments"][1][0])
    x = jax.random.normal(jax.random.PRNGKey(2), (10, cfg.d_model))
    uncut = _np_layer(cfg, blk["moe"], x, (0, 8))
    shared_cfg = cfg.replace(d_ff=cfg.moe.d_ff_expert * cfg.moe.n_shared)
    total = np.asarray(nn.ffn_apply(blk["shared"], shared_cfg, x),
                       np.float64)
    pairs = 0
    for first in range(0, 8, 2):
        share = cfg.replace(moe=dataclasses.replace(cfg.moe, n_held=2,
                                                    first_held=first))
        p = {k: (v[first:first + 2] if k.startswith("e_") else v)
             for k, v in blk["moe"].items()}
        part, held = moe.apply_dropless(p, share, x)
        np.testing.assert_allclose(np.asarray(part),
                                   _np_layer(cfg, blk["moe"], x,
                                             (first, 2)),
                                   rtol=1e-4, atol=1e-5)
        total += np.asarray(part, np.float64)
        pairs += np.asarray(held)
    np.testing.assert_array_equal(pairs, np.full(10, cfg.moe.top_k))
    whole, _ = moe.apply_dropless(blk["moe"], cfg, x)
    whole = whole + nn.ffn_apply(blk["shared"], shared_cfg, x)
    np.testing.assert_allclose(total - np.asarray(
        nn.ffn_apply(blk["shared"], shared_cfg, x), np.float64), uncut,
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole), total, rtol=1e-4,
                               atol=1e-5)


def test_yarn_frequencies_and_scale_follow_the_written_formula():
    """DeepSeek-V3's published YaRN: the blended inverse frequencies of
    its 64 rotary dimensions and the attention scale
    ``192^-1/2 * (0.1 ln 40 + 1)^2``."""
    from repro.configs import get_config
    from repro.models import mla, modules as nn
    cfg = get_config("deepseek-v3-671b")
    r, dim, base = cfg.rope_scaling, 64, cfg.rope_theta

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (
            2 * math.log(base))
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)),
                                                  dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(np.asarray(nn.rope_freqs(dim, base, r)),
                               want, rtol=1e-6)
    assert (low, high) == (10, 23)
    assert mla._scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2, rel=1e-12)
    assert mla._scale(cfg.replace(rope_scaling=None)) == 192 ** -0.5


def test_window_counters_count_the_routed_pairs():
    """``moe_tokens`` counts one token per occupied slot, decode step and
    expert layer; with every expert held each token brings ``top_k``
    held pairs, and a device holding 2 of the 8 sees fewer; each window
    hands its counts back with its tokens (no extra device sync)."""
    cfg, params = _model()
    prompts = seeded_prompts(cfg, 4, 10, seed=5)
    gens = [6, 3, 8, 5]
    _, eng = _serve(cfg, params, prompts, gens)
    decoded = sum(g - 1 for g in gens)
    assert eng.decode_tokens == decoded
    assert eng.moe_tokens == decoded * 3
    assert eng.moe_pairs_held == cfg.moe.top_k * eng.moe_tokens
    assert eng.d2h_syncs == eng.windows_run + len(prompts)
    share, p = _model(n_held=2, first_held=4)
    p = jax.tree.map(lambda a: a, params)
    for blk in p["segments"][1]:
        blk["moe"] = {k: (v[:, 4:6] if k.startswith("e_") else v)
                      for k, v in blk["moe"].items()}
    _, part = _serve(share, p, prompts, gens)
    assert part.moe_tokens == decoded * 3
    assert 0 < part.moe_pairs_held < cfg.moe.top_k * part.moe_tokens


def test_latent_pool_sizes_and_the_striped_refusal():
    """The pool is one (kv_lora + rope)-wide row per token and layer,
    its page axis third from last; a mesh that would stripe it is
    refused, not served."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.serving.engine import PagedEngine
    full = get_config("deepseek-v3-671b").replace(n_layers=5,
                                                  first_k_dense=1)
    assert lm.paged_decodable(full)
    assert lm.paged_bytes_per_token(full) == 5 * 1152
    cfg, params = _model()
    pools = lm.init_paged_caches(cfg, 12, 4)
    assert [tuple(a.shape) for a in jax.tree.leaves(pools)] == \
        [(12, 4, 40), (3, 12, 4, 40)]

    class Mesh:
        axis_names = ("data", "model")
        shape = {"data": 1, "model": 2}
    with pytest.raises(NotImplementedError, match="latent"):
        PagedEngine(cfg, params, max_batch=2, page_size=4, n_pages=12,
                    max_len=16, mesh=Mesh())
