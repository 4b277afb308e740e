"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the engine finished, drawn from the seed and holding the
request with the most served tokens, is run through the configuration's
plain reference (float32, ``HIGHEST`` precision), teacher-forced on each
prompt and its served tokens.  At every served position the gap is the
reference's best logit minus the reference's logit of the token the
engine served; the widest gap over the sample is compared with the
configuration's limit.  A correct greedy server reads only the rounding
of its own precision there; a wrong page, position, mask, merge or token
reads a large part of the logit range.

The control (``control=True``) puts the reference itself in the
program's place at the next precision down (float8 weights): at the same
positions it reads the gap of the token that the float8 pass puts
first.  It is run by ``bench/limits.py`` and the tests, never by a
benchmark run.
"""
from __future__ import annotations

import functools
from typing import List

import numpy as np


def sample(records, n: int, seed: int) -> List:
    """``n`` finished requests drawn from ``seed``, the one with the most
    served tokens among them."""
    done = [r for r in records if r.done is not None and not r.failed]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.tokens), r.item.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


@functools.lru_cache(maxsize=8)
def _scorer(ref, cfg_key, seq_len: int, n_read: int, control: bool):
    import jax
    import jax.numpy as jnp
    cfg = dict(cfg_key)

    def score(weights, tokens, start, served, n):
        h = ref.hidden(weights, cfg, tokens)
        idx = jnp.minimum(start + jnp.arange(n_read), seq_len - 1)
        lg = ref.logits(weights, cfg, h[idx])
        if control:
            hq = ref.hidden(weights, cfg, tokens, "fp8")
            pick = jnp.argmax(ref.logits(weights, cfg, hq[idx], "fp8"), -1)
        else:
            pick = served
        gap = lg.max(-1) - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        return jnp.max(jnp.where(jnp.arange(n_read) < n, gap, -jnp.inf))

    return jax.jit(score)


def widest_gap(ref, weights, cfg: dict, seqs, seq_len: int, n_read: int,
               control: bool = False) -> float:
    """Widest gap over ``seqs``, a list of (prompt, served tokens), each
    padded to ``seq_len`` positions and ``n_read`` served tokens (one
    compiled program for every sequence).  Where ``weights`` has a whole
    copy on each of several chips, the sequences are dealt out over the
    chips in turn."""
    import jax.numpy as jnp
    fn = _scorer(ref, _freeze(cfg), seq_len, n_read, control)
    copies = _copies(weights)
    gaps = []
    for i, (prompt, served) in enumerate(seqs):
        served = np.asarray(served, np.int32)
        toks = np.zeros(seq_len, np.int32)
        full = np.concatenate([prompt, served[:-1]])
        toks[:len(full)] = full
        pad = np.zeros(n_read, np.int32)
        pad[:len(served)] = served
        gaps.append(fn(copies[i % len(copies)], jnp.asarray(toks),
                       len(prompt) - 1, jnp.asarray(pad), len(served)))
    return max((float(g) for g in gaps), default=-np.inf)


def _copies(weights) -> list:
    """One whole copy of ``weights`` per device that holds one."""
    import jax
    devices = sorted(jax.tree.leaves(weights)[0].sharding.device_set,
                     key=lambda d: d.id)
    if len(devices) == 1:
        return [weights]
    return [jax.tree.map(lambda a: next(
        s.data for s in a.addressable_shards if s.device == d), weights)
        for d in devices]


def _freeze(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def served_problems(records, vocab: int) -> List[str]:
    """Finished requests that served the wrong number of tokens or a
    token outside the vocabulary."""
    bad = []
    for r in records:
        if r.done is None or r.failed:
            continue
        toks = r.req.tokens
        if len(toks) != r.item.gen:
            bad.append(f"request {r.item.index}: {len(toks)} tokens, "
                       f"asked {r.item.gen}")
        elif not all(0 <= t < vocab for t in toks):
            bad.append(f"request {r.item.index}: a token outside [0, "
                       f"{vocab})")
    return bad
