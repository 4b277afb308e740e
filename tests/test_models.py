"""Model-stack behaviour: ref-vs-blocked equivalence, decode-vs-forward
consistency, segment construction."""
import jax
import jax.numpy as jnp
import pytest

from conftest import make_batch
from repro.configs import get_config, get_tiny_config
from repro.models import attention, lm, modules as nn, rglru, rwkv6


def test_attention_ref_vs_blocked():
    k = jax.random.PRNGKey(0)
    B, S, H, hd = 2, 128, 4, 16
    ks = jax.random.split(k, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    kk = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    for causal, window, cap in [(True, None, None), (True, 32, None),
                                (False, None, None), (True, None, 30.0),
                                (True, 32, 30.0)]:
        r = attention.attend_ref(q, kk, v, causal=causal, window=window,
                                 scale=0.25, softcap=cap)
        b = attention.attend_blocked(q, kk, v, causal=causal, window=window,
                                     scale=0.25, softcap=cap,
                                     block_q=16, block_kv=32)
        assert jnp.abs(r - b).max() < 1e-4, (causal, window, cap)


def test_rglru_assoc_matches_ref():
    k = jax.random.PRNGKey(1)
    B, S, W = 2, 128, 64
    ks = jax.random.split(k, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.2 + 0.79
    b = jax.random.normal(ks[1], (B, S, W)) * 0.1
    h0 = jax.random.normal(ks[2], (B, W))
    hs1, hT1 = rglru._scan_ref(a, b, h0)
    hs2, hT2 = rglru._scan_assoc(a, b, h0)
    assert jnp.abs(hs1 - hs2).max() < 1e-4
    assert jnp.abs(hT1 - hT2).max() < 1e-4


def test_rwkv_chunked_matches_ref():
    k = jax.random.PRNGKey(2)
    B, S, H, K = 2, 128, 2, 16
    ks = jax.random.split(k, 6)
    r = jax.random.normal(ks[0], (B, S, H, K))
    kk = jax.random.normal(ks[1], (B, S, H, K))
    v = jax.random.normal(ks[2], (B, S, H, K))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, K)) - 1.0)
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    S0 = jax.random.normal(ks[5], (B, H, K, K)).astype(jnp.float32)
    o1, s1 = rwkv6._wkv_ref(r, kk, v, lw, u, S0)
    o2, s2 = rwkv6._wkv_chunked(r, kk, v, lw, u, S0)
    assert jnp.abs(o1 - o2).max() < 1e-3
    assert jnp.abs(s1 - s2).max() < 1e-3


@pytest.mark.parametrize("arch", ["qwen3-14b", "gemma2-27b",
                                  "recurrentgemma-2b", "rwkv6-1.6b",
                                  "deepseek-v3-671b"])
def test_decode_matches_forward(arch):
    """Prefill(S) + decode(token S) logits == forward(S+1) last logits.
    The training forward routes experts into a fixed capacity and drops
    the overflow, the inference path drops nothing: the capacity here
    holds every token, so the two compute the same function."""
    cfg = get_tiny_config(arch).replace(impl="ref")
    if cfg.moe is not None:
        import dataclasses
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=cfg.moe.n_experts / cfg.moe.top_k))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, S + 1), 0,
                              cfg.vocab_size)
    h, _, _ = lm.forward(params, cfg, toks, mode="train")
    hn = nn.rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
    full = lm.head_logits(params, cfg, hn)[:, S]
    _, caches = lm.prefill(params, cfg, toks[:, :S], max_len=S + 8)
    dl, _ = lm.decode_step(params, cfg, toks[:, S:S + 1], caches, S)
    rel = jnp.abs(full - dl[:, 0]).max() / (jnp.abs(full).max() + 1e-9)
    assert rel < 2e-2, (arch, float(rel))


def test_multi_step_decode_consistency():
    """Greedy decode step-by-step == teacher-forced forward argmaxes."""
    cfg = get_tiny_config("qwen3-14b").replace(impl="ref")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, S, G = 2, 12, 6
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0,
                              cfg.vocab_size)
    logits, caches = lm.prefill(params, cfg, toks, max_len=S + G)
    seq = [jnp.argmax(logits, -1).astype(jnp.int32)]
    for i in range(G - 1):
        logits, caches = lm.decode_step(params, cfg, seq[-1], caches, S + i)
        seq.append(jnp.argmax(logits, -1).astype(jnp.int32))
    gen = jnp.concatenate(seq, axis=1)
    # teacher-force the generated tokens through the full forward
    full = jnp.concatenate([toks, gen], axis=1)
    h, _, _ = lm.forward(params, cfg, full, mode="train")
    hn = nn.rmsnorm(h, params["final_norm"]["scale"], cfg.norm_eps)
    logits_all = lm.head_logits(params, cfg, hn)
    want = jnp.argmax(logits_all[:, S - 1:S + G - 1], -1)
    assert (want == gen).mean() > 0.95  # ties under fp tolerance


def test_segments():
    segs = lm.make_segments(get_config("deepseek-v3-671b"))
    assert [(s.n_cycles, s.is_moe) for s in segs] == [(3, False), (58, True)]
    segs = lm.make_segments(get_config("recurrentgemma-2b"))
    assert segs[0].kinds == ("rglru", "rglru", "local")
    assert segs[0].n_cycles == 8
    assert sum(s.n_cycles * len(s.kinds) for s in segs) == 26
    segs = lm.make_segments(get_config("gemma2-27b"))
    assert segs[0].kinds == ("local", "attn") and segs[0].n_cycles == 23


def test_loss_decreases_under_training():
    from repro.configs.base import ShapeConfig
    from repro.runtime import train_loop
    cfg = get_tiny_config("qwen3-14b")
    shape = ShapeConfig("t", 64, 4, "train")
    job = train_loop.TrainJobConfig(steps=30, log_every=10, peak_lr=3e-3,
                                    warmup=5)
    out = train_loop.run(cfg, shape, job=job)
    first = out["history"][0]["loss"]
    last = out["history"][-1]["loss"]
    assert last < first - 0.2, (first, last)
