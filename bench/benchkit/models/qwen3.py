"""Qwen3's dense decoder (``model_type`` ``qwen3``): the program's
``ModelConfig``, its parameter tree, and the work one token needs.

The configuration file's published keys give the sizes; the mechanisms
they imply that the program names as options (qk-norm, a gated MLP, the
activation) come from the file's ``model_options`` group.
"""
from __future__ import annotations

from dataclasses import dataclass


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"], activation_dtype=cfg["torch_dtype"],
        **cfg["model_options"])


def program_params(weights: dict, mcfg):
    """The benchmark's layer-stacked weights in the program's parameter
    tree (matrices shared, not copied; norm scales in float32 as the
    program keeps them), holding only the leaves the model options ask
    for."""
    import jax.numpy as jnp
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    attn = {k: weights[k] for k in ("wq", "wk", "wv", "wo")}
    if mcfg.qk_norm:
        attn.update(q_norm=f32(weights["q_norm"]),
                    k_norm=f32(weights["k_norm"]))
    ffn = {k: weights[k] for k in (("w_gate",) if mcfg.gated_ffn else ())
           + ("w_up", "w_down")}
    block = {"ln1": {"scale": f32(weights["ln1"])}, "attn": attn,
             "ln2": {"scale": f32(weights["ln2"])}, "ffn": ffn}
    params = {"embed": {"embed_table": weights["embed"]},
              "segments": [[block]],
              "final_norm": {"scale": f32(weights["final_norm"])}}
    if not mcfg.tie_embeddings:
        params["head"] = {"head_w": weights["head"]}
    return params


def work(cfg: dict) -> "Shape":
    return Shape.of(cfg)


@dataclass(frozen=True)
class Shape:
    """The sizes that set a dense GQA decoder's work: every matrix is read
    once per decode step and used once per token."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    gated: bool
    bytes_per_param: int = 2
    bytes_per_kv: int = 2

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        return cls(layers=cfg["num_hidden_layers"],
                   d_model=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"],
                   ffn=cfg["intermediate_size"],
                   vocab=cfg["vocab_size"],
                   gated=cfg["model_options"]["gated_ffn"],
                   bytes_per_param=_itemsize(cfg["torch_dtype"]),
                   bytes_per_kv=_itemsize(cfg["torch_dtype"]))

    @property
    def layer_params(self) -> int:
        """Matrix parameters of one layer (norm scales are negligible)."""
        d, q, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return 2 * d * q + 2 * d * kv + (3 if self.gated else 2) * d * self.ffn

    @property
    def head_params(self) -> int:
        return self.vocab * self.d_model

    @property
    def weight_bytes(self) -> int:
        """Bytes a step must read: every layer's matrices and the output
        head (the embedding rows looked up are negligible)."""
        return (self.layers * self.layer_params + self.head_params) \
            * self.bytes_per_param

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim \
            * self.bytes_per_kv

    def token_flops(self, context: int) -> int:
        """Operations of one token through every layer, attending to
        ``context`` keys (itself included), without the output head."""
        attn = 4 * self.heads * self.head_dim * context
        return self.layers * (2 * self.layer_params + attn)

    @property
    def head_flops(self) -> int:
        return 2 * self.head_params

    def prefill_flops(self, start: int, n: int) -> int:
        """Operations of prompt positions ``start .. start + n - 1``
        (position p attends to p + 1 keys), without the output head."""
        keys = n * start + n * (n + 1) // 2
        return self.layers * (2 * self.layer_params * n
                              + 4 * self.heads * self.head_dim * keys)


def _itemsize(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}[dtype]
