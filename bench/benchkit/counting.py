"""Operations and bytes the served model needs, from shapes alone, and
the chips' published peaks.

Each model family's module (``benchkit/models/<model_type>.py``) counts
the work of one token from its configuration's published sizes, in a
work count with these members:

* ``weight_bytes``: the bytes a decode step reads once, whatever its
  batch;
* ``kv_bytes_per_token``: the cache one token stores (and a later token
  reads);
* ``token_flops(context)``: operations of one token through every
  layer, attending to ``context`` keys (itself included), without the
  output head;
* ``head_flops``: operations of the output head for one token;
* ``prefill_flops(start, n)``: operations of prompt positions ``start ..
  start + n - 1``, without the head.

The work is what the algorithm needs, not what an implementation does:
a decode step reads every weight once and the live keys and values of
each sequence, and writes one new key/value entry per sequence.  Padding
that an implementation gathers or computes is never counted, so a
roofline share read against these numbers stays the same whatever
implements the step, and a faster kernel shows as a gain.
"""
from __future__ import annotations

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


def decode_step(work, contexts: Iterable[int]) -> tuple:
    """(operations, bytes) of one decode step for sequences whose new
    token attends to ``contexts[i]`` keys (itself included): each token
    runs every layer and the head; the step reads the weights once, each
    sequence's ``context - 1`` stored entries, and writes one entry."""
    contexts = list(contexts)
    flops = sum(work.token_flops(c) + work.head_flops for c in contexts)
    nbytes = work.weight_bytes + sum(contexts) * work.kv_bytes_per_token
    return flops, nbytes


def prefill(work, start: int, n: int, logits: bool) -> int:
    """Operations of prompt positions ``start .. start + n - 1`` (position
    p attends to p + 1 keys), plus the head once when the slice ends the
    prompt and its logits are read."""
    return work.prefill_flops(start, n) + (work.head_flops if logits else 0)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of operations over peak rate and bytes
    over peak bandwidth."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
