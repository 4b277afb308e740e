"""The plain reference's attention in blocks of query rows agrees with
the whole score matrix at once, to float32 rounding."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

from benchkit import cell  # noqa: E402

REF = cell.load_module(BENCH / "configs" / "qwen3-1.7b.ref.py")


@pytest.mark.parametrize("block", [1, 7, 16, 64])
def test_blocked_attention_matches_whole(block):
    import jax
    import jax.numpy as jnp
    S, Kv, G, hd = 45, 2, 3, 16
    k1, k2, k3 = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(k1, (S, Kv, G, hd), jnp.float32)
    k = jax.random.normal(k2, (S, Kv, hd), jnp.float32)
    v = jax.random.normal(k3, (S, Kv, hd), jnp.float32)
    whole = REF.attend(q, k, v, block=S)
    # the same sum written out: causal softmax over keys t <= s
    s = jnp.einsum("skgd,tkd->kgst", q, k,
                   precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    plain = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(s, -1), v,
                       precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(whole, plain, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(REF.attend(q, k, v, block=block), whole,
                               rtol=1e-5, atol=1e-6)


def test_blocked_hidden_states_match_whole():
    import jax.numpy as jnp
    cfg = cell.load_json(DATA / "tiny-qwen3.json")
    w = REF.make_weights(cfg, 11)
    tokens = jnp.arange(40, dtype=jnp.int32) * 37 % cfg["vocab_size"]
    np.testing.assert_allclose(REF.hidden(w, cfg, tokens, block=8),
                               REF.hidden(w, cfg, tokens, block=40),
                               rtol=1e-5, atol=1e-5)
