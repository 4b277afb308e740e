"""Plain reference of Qwen3's dense decoder, and its weights.

The forward pass of ``Qwen3ForCausalLM`` as published (Hugging Face
``transformers``' ``modeling_qwen3``): token embedding; per layer
RMSNorm -> attention with per-head RMSNorm on queries and keys before
rotary embedding (half-split rotation, ``rope_theta``), grouped-query
attention (query head h reads key/value head h // (heads / kv_heads)),
causal softmax at scale 1/sqrt(head_dim) -> residual; RMSNorm ->
SwiGLU MLP (down(silu(gate(x)) * up(x))) -> residual; final RMSNorm and
the output head (the embedding matrix when ``tie_word_embeddings``).

It runs in float32 with every matrix product at ``HIGHEST`` precision,
imports nothing of the program, and scans over the layers so that one
layer's weights are widened at a time.  Attention is computed in blocks
of ``Q_BLOCK`` query rows, each over all of its keys, so that the scores
of a long sequence never exist whole (16,896 positions would take 18 GB
a layer).  ``quant="fp8"`` is the control:
the same pass with every weight matrix rounded to float8 (e4m3) with a
scale per output channel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
Q_BLOCK = 512


def make_weights(cfg: dict, seed: int) -> dict:
    """Random weights from ``seed``, made on the device in one jitted
    call with the chip's own bit generator (``rbg``; threefry takes tens
    of seconds for 1.7 B numbers on a v5e), in the configuration's
    ``torch_dtype``, stacked over layers.
    Matrices are (in, out) and drawn as the published initializer does,
    N(0, initializer_range**2); norm scales are 1 + 0.1 N(0, 1) (not all
    ones) so that a missing or misplaced norm shows."""
    dt = jnp.dtype(cfg["torch_dtype"])
    std = cfg["initializer_range"]
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    f, V, hd = cfg["intermediate_size"], cfg["vocab_size"], cfg["head_dim"]
    shapes = {"embed": (V, d), "wq": (L, d, q), "wk": (L, d, kv),
              "wv": (L, d, kv), "wo": (L, q, d), "w_gate": (L, d, f),
              "w_up": (L, d, f), "w_down": (L, f, d)}
    norms = {"ln1": (L, d), "ln2": (L, d), "q_norm": (L, hd),
             "k_norm": (L, hd), "final_norm": (d,)}
    if not cfg["tie_word_embeddings"]:
        shapes["head"] = (d, V)

    def make(key):
        names = sorted(shapes) + sorted(norms)
        keys = dict(zip(names, jax.random.split(key, len(names))))
        out = {n: (jax.random.normal(keys[n], s, jnp.float32) * std
                   ).astype(dt) for n, s in shapes.items()}
        out.update({n: (1.0 + 0.1 * jax.random.normal(keys[n], s,
                                                      jnp.float32)).astype(dt)
                    for n, s in norms.items()})
        return out

    key = jax.random.fold_in(jax.random.key(seed % 2 ** 31, impl="rbg"),
                             seed // 2 ** 31)
    return jax.jit(make)(key)


def _fp8(w, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis`` (the
    contraction axis), back in float32."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(q, k, v, block: int = Q_BLOCK):
    """Causal softmax attention at scale 1/sqrt(head_dim) of queries
    (S, Kv, G, hd) over keys and values (S, Kv, hd), ``block`` query rows
    at a time; returns (S, Kv, G, hd)."""
    S, Kv, G, hd = q.shape
    nb = -(-S // block)
    qb = jnp.pad(q, ((0, nb * block - S), (0, 0), (0, 0), (0, 0))
                 ).reshape(nb, block, Kv, G, hd)
    rows = jnp.arange(nb * block).reshape(nb, block)
    keys = jnp.arange(S)

    def one(args):
        qi, ri = args
        s = jnp.einsum("skgd,tkd->kgst", qi, k, precision=HI) * hd ** -0.5
        s = jnp.where(keys[None, None, None, :] <= ri[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgst,tkd->skgd", p, v, precision=HI)

    return jax.lax.map(one, (qb, rows)).reshape(nb * block, Kv, G, hd)[:S]


def hidden(weights: dict, cfg: dict, tokens, quant: str = "none",
           block: int = Q_BLOCK):
    """Final-normed hidden states (S, d) of one causal sequence."""
    H, Kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    S = tokens.shape[0]
    pos = jnp.arange(S)
    widen = (lambda w: _fp8(w.astype(jnp.float32), -2)) if quant == "fp8" \
        else (lambda w: w.astype(jnp.float32))
    if quant not in ("none", "fp8"):
        raise ValueError(quant)
    emb = weights["embed"]
    x = (_fp8(emb.astype(jnp.float32), -1) if quant == "fp8"
         else emb.astype(jnp.float32))[tokens]

    def layer(x, lw):
        w = {k: widen(lw[k]) for k in MATRICES}
        h = _rms(x, lw["ln1"], eps)
        q = jnp.matmul(h, w["wq"], precision=HI).reshape(S, H, hd)
        k = jnp.matmul(h, w["wk"], precision=HI).reshape(S, Kv, hd)
        v = jnp.matmul(h, w["wv"], precision=HI).reshape(S, Kv, hd)
        q = _rope(_rms(q, lw["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, lw["k_norm"], eps), pos, theta)
        o = attend(q.reshape(S, Kv, H // Kv, hd), k, v, block
                   ).reshape(S, H * hd)
        x = x + jnp.matmul(o, w["wo"], precision=HI)
        h = _rms(x, lw["ln2"], eps)
        g = jnp.matmul(h, w["w_gate"], precision=HI)
        u = jnp.matmul(h, w["w_up"], precision=HI)
        x = x + jnp.matmul(jax.nn.silu(g) * u, w["w_down"], precision=HI)
        return x, None

    per_layer = {k: weights[k] for k in MATRICES + ("ln1", "ln2", "q_norm",
                                                    "k_norm")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    return _rms(x, weights["final_norm"], eps)


def logits(weights: dict, cfg: dict, h, quant: str = "none"):
    """Output-head logits (n, V) in float32 of hidden states ``h``."""
    if cfg["tie_word_embeddings"]:
        w = weights["embed"].astype(jnp.float32)
        w = (_fp8(w, -1) if quant == "fp8" else w).T
    else:
        w = weights["head"].astype(jnp.float32)
        w = _fp8(w, -2) if quant == "fp8" else w
    return jnp.matmul(h, w, precision=HI)
