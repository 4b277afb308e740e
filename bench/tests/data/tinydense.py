"""A model family the benchmark itself does not run (``model_type``
``tinydense``), for the benchmark's own tests: Qwen3's layer equations
with every mechanism fixed by the family rather than named in the
configuration file, an output head of its own, and a work count of its
own.  It shows that a family is one new module, found by name."""
from __future__ import annotations

from dataclasses import dataclass


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=False, qk_norm=True, gated_ffn=True, act="silu",
        param_dtype=cfg["torch_dtype"], activation_dtype=cfg["torch_dtype"])


def program_params(weights: dict, mcfg):
    import jax.numpy as jnp
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    attn = {k: weights[k] for k in ("wq", "wk", "wv", "wo")}
    attn.update(q_norm=f32(weights["q_norm"]), k_norm=f32(weights["k_norm"]))
    block = {"ln1": {"scale": f32(weights["ln1"])}, "attn": attn,
             "ln2": {"scale": f32(weights["ln2"])},
             "ffn": {k: weights[k] for k in ("w_gate", "w_up", "w_down")}}
    return {"embed": {"embed_table": weights["embed"]},
            "segments": [[block]],
            "final_norm": {"scale": f32(weights["final_norm"])},
            "head": {"head_w": weights["head"]}}


@dataclass(frozen=True)
class Work:
    layer_params: int
    layers: int
    attn_per_key: int
    head_params: int
    kv_bytes_per_token: int

    @property
    def weight_bytes(self) -> int:
        return 2 * (self.layers * self.layer_params + self.head_params)

    def token_flops(self, context: int) -> int:
        return self.layers * (2 * self.layer_params
                              + self.attn_per_key * context)

    @property
    def head_flops(self) -> int:
        return 2 * self.head_params

    def prefill_flops(self, start: int, n: int) -> int:
        return sum(self.token_flops(p + 1) for p in range(start, start + n))


def work(cfg: dict) -> Work:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return Work(layer_params=2 * d * q + 2 * d * kv
                + 3 * d * cfg["intermediate_size"],
                layers=cfg["num_hidden_layers"], attn_per_key=4 * q,
                head_params=d * cfg["vocab_size"],
                kv_bytes_per_token=cfg["num_hidden_layers"] * 2 * kv * 2)
