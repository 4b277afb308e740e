"""Pallas kernel allclose sweeps vs ref.py oracles (deliverable c).

Every kernel is swept over shapes and dtypes in interpret mode (the
kernel body executes with jnp semantics on CPU; on TPU the same tiling
lowers natively)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(11)


def _rand(shape, dtype, k):
    x = jax.random.normal(k, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 128, 2, 64), (2, 256, 4, 64),
                                   (1, 192, 3, 128)])
@pytest.mark.parametrize("mode", ["causal", "window", "bidir", "softcap"])
def test_flash_attention_sweep(shape, dtype, mode):
    B, S, H, hd = shape
    ks = jax.random.split(KEY, 3)
    q = _rand(shape, dtype, ks[0])
    k = _rand(shape, dtype, ks[1])
    v = _rand(shape, dtype, ks[2])
    kw = dict(causal=mode != "bidir",
              window=64 if mode == "window" else None,
              softcap=30.0 if mode == "softcap" else None)
    o_ref = ref.flash_attention(q, k, v, **kw)
    o = ops.flash_attention(q, k, v, block_q=64, block_kv=64, **kw)
    err = jnp.abs(o.astype(jnp.float32) - o_ref.astype(jnp.float32)).max()
    assert err < _tol(dtype), (shape, dtype, mode, float(err))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,Kv,G,pos", [(128, 2, 4, 17), (256, 1, 8, 255),
                                        (192, 4, 1, 100)])
def test_decode_attention_sweep(T, Kv, G, pos, dtype):
    B, hd = 2, 64
    H = Kv * G
    ks = jax.random.split(KEY, 3)
    q = _rand((B, H, hd), dtype, ks[0])
    k = _rand((B, T, Kv, hd), dtype, ks[1])
    v = _rand((B, T, Kv, hd), dtype, ks[2])
    o_ref = ref.decode_attention(q, k, v, pos)
    o = ops.decode_attention(q, k, v, jnp.int32(pos), block_t=64)
    err = jnp.abs(o.astype(jnp.float32) - o_ref.astype(jnp.float32)).max()
    assert err < _tol(dtype), float(err)


@pytest.mark.parametrize("S,W", [(64, 128), (256, 256), (128, 512)])
def test_rglru_scan_sweep(S, W):
    B = 2
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.2 + 0.79
    b = jax.random.normal(ks[1], (B, S, W)) * 0.1
    h0 = jax.random.normal(ks[2], (B, W))
    hs_r, hT_r = ref.rglru_scan(a, b, h0)
    hs, hT = ops.rglru_scan(a, b, h0)
    assert jnp.abs(hs - hs_r).max() < 1e-5
    assert jnp.abs(hT - hT_r).max() < 1e-5


@pytest.mark.parametrize("S,H,K,chunk", [(64, 2, 32, 16), (128, 1, 64, 32),
                                         (96, 3, 16, 32)])
def test_rwkv6_scan_sweep(S, H, K, chunk):
    B = 2
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, S, H, K))
    k = jax.random.normal(ks[1], (B, S, H, K))
    v = jax.random.normal(ks[2], (B, S, H, K))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, K)) - 1.0)
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    S0 = jax.random.normal(ks[5], (B, H, K, K)).astype(jnp.float32)
    o_r, s_r = ref.rwkv6_scan(r, k, v, lw, u, S0)
    o, s = ops.rwkv6_scan(r, k, v, lw, u, S0, chunk=chunk)
    assert jnp.abs(o - o_r).max() < 2e-3, float(jnp.abs(o - o_r).max())
    assert jnp.abs(s - s_r).max() < 2e-3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F", [(2, 64, 128, 256), (4, 32, 256, 128)])
def test_moe_gemm_sweep(E, C, D, F, dtype):
    ks = jax.random.split(KEY, 2)
    x = _rand((E, C, D), dtype, ks[0])
    w = _rand((E, D, F), dtype, ks[1])
    o_ref = ref.moe_gemm(x, w)
    o = ops.moe_gemm(x, w, block_c=32, block_f=128, block_d=64)
    rel = (jnp.abs(o.astype(jnp.float32) - o_ref.astype(jnp.float32)).max()
           / jnp.abs(o_ref.astype(jnp.float32)).max())
    assert rel < (3e-2 if dtype == jnp.bfloat16 else 1e-5), float(rel)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,D", [(64, 128), (96, 256)])
def test_rmsnorm_sweep(N, D, dtype):
    ks = jax.random.split(KEY, 2)
    x = _rand((N, D), dtype, ks[0])
    s = jax.random.normal(ks[1], (D,))
    o_ref = ref.rmsnorm(x, s)
    o = ops.rmsnorm(x, s, block_rows=32)
    err = jnp.abs(o.astype(jnp.float32) - o_ref.astype(jnp.float32)).max()
    assert err < _tol(dtype)


def test_model_pallas_impl_matches_blocked():
    """The full model gives the same loss under impl=pallas vs blocked."""
    from conftest import make_batch
    from repro.configs import get_tiny_config
    from repro.models import lm
    for arch in ("qwen3-14b", "recurrentgemma-2b", "rwkv6-1.6b"):
        cfg = get_tiny_config(arch)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        batch = make_batch(cfg)
        l_b, _ = lm.loss_fn(params, cfg.replace(impl="blocked"), batch)
        l_p, _ = lm.loss_fn(params, cfg.replace(impl="pallas"), batch)
        assert abs(float(l_b) - float(l_p)) < 5e-3, (arch, l_b, l_p)


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_interpret_only_on_cpu(monkeypatch, backend, want):
    """Interpret mode is the CPU's alone; another backend is refused, not
    silently interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops._interpret()
    else:
        assert ops._interpret() is want
