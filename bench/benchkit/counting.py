"""Operations and bytes the served model needs, from shapes alone, and
the chips' published peaks.

The work is what the algorithm needs, not what an implementation does:
a decode step reads every weight once and the live keys and values of
each sequence, and writes one new key/value entry per sequence.  Padding
that an implementation gathers or computes is never counted, so a
roofline share read against these numbers stays the same whatever
implements the step, and a faster kernel shows as a gain.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable

# Published peaks per chip, keyed by ``device_kind`` as JAX reports it.
# TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


@dataclass(frozen=True)
class Shape:
    """The sizes that set a dense decoder's work (from a configuration
    file's published keys)."""
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


def _itemsize(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}[dtype]


def decode_step(shape: Shape, contexts: Iterable[int]) -> tuple:
    """(operations, bytes) of one decode step for sequences whose new
    token attends to ``contexts[i]`` keys (itself included): each token
    runs every layer and the head; the step reads the weights once, each
    sequence's ``context - 1`` stored entries, and writes one entry."""
    contexts = list(contexts)
    flops = sum(shape.token_flops(c) + shape.head_flops for c in contexts)
    nbytes = shape.weight_bytes + sum(contexts) * shape.kv_bytes_per_token
    return flops, nbytes


def prefill(shape: Shape, start: int, n: int, logits: bool) -> int:
    """Operations of prompt positions ``start .. start + n - 1`` (position
    p attends to p + 1 keys), plus the head once when the slice ends the
    prompt and its logits are read."""
    keys = n * start + n * (n + 1) // 2
    flops = shape.layers * (2 * shape.layer_params * n
                            + 4 * shape.heads * shape.head_dim * keys)
    return flops + (shape.head_flops if logits else 0)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of operations over peak rate and bytes
    over peak bandwidth."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
