"""The one load generator.

Every traffic mix is a JSON file under ``bench/traffic/`` whose keys are
parameters of this generator; a new mix is a new data file.  Keys:

* ``arrivals``: ``{"kind": <name>, ...}``.  ``kind`` names the arrival
  process, a module ``bench/benchkit/arrivals/<kind>.py`` (see
  :func:`arrival_process`); the other keys are its parameters.
* ``block``: the number of consecutive requests that form one block
  (default 1).  Every block holds one draw from each of ``block`` equal
  strata of every distribution: its gaps, prompt and answer lengths and
  tenants.  So each block offers the stated mix at the stated rate, with
  no burst the stated process would not make.
* ``order_seed``: draws which values of a stratum go to which block and
  the order within each block.  It is the mix's, not the run's, so
  every run offers the same sizes at the same times; the run's seed
  draws the token ids (and the weights).
* ``warm_in_s``: seconds of the same load offered before the measured
  window opens, so the window starts with the batch in its steady state.
* ``prompt_tokens`` and ``output_tokens``: a distribution, ``{"dist":
  <name>, ...}``, where ``dist`` names a module
  ``bench/benchkit/dists/<name>.py`` with ``quantile(spec, u)``, and
  optional ``min``/``max`` clip it.  With ``prefix``, ``prompt_tokens``
  is the unique turn that follows the tenant's shared prefix.
* ``prefix`` (optional): ``{"tenants": t, "tokens": <distribution>,
  "zipf_s": s}``: t tenants, each with a shared prefix (a system prompt)
  that set-up prefills into the prefix cache; each request belongs to a
  tenant, tenants sending in proportion to Zipf skew s.

Lengths, gaps and tenant counts are stratified: they sit at the midpoint
quantiles of the stated distributions.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from typing import List

import numpy as np


@dataclass
class Item:
    """One request as the load generator offers it.  It is due
    ``due_s`` seconds after the load starts, or, where ``after`` names
    another request, ``due_s`` seconds after that one finished."""
    index: int
    due_s: float
    prompt: np.ndarray      # int32 token ids (shared prefix included)
    gen: int                # output tokens asked for
    tenant: str = "default"
    after: int = -1


@dataclass
class Schedule:
    items: List[Item]
    prefixes: List[np.ndarray] = field(default_factory=list)
    warm_in_s: float = 0.0
    # longest prompt plus output the mix's stated bounds allow, whatever
    # the number of requests drawn (so a longer run sizes nothing anew)
    max_tokens: int = 0


def arrival_process(name: str):
    """``bench/benchkit/arrivals/<name>.py``: ``count(spec, seconds,
    block)``, the number of requests (a multiple of ``block``) that
    ``seconds`` of load take, and ``due(spec, n, block, rng)``, the
    arrays ``due_s`` and ``after`` of :class:`Item`."""
    return importlib.import_module(f"benchkit.arrivals.{name}")


def stratified(dist: dict, n: int) -> np.ndarray:
    """``n`` values at the midpoint quantiles ``(i + 0.5) / n`` of
    ``dist``, ascending, clipped to its ``min``/``max``."""
    u = (np.arange(n) + 0.5) / n
    mod = importlib.import_module(f"benchkit.dists.{dist['dist']}")
    x = np.asarray(mod.quantile(dist, u), np.float64)
    return np.clip(x, dist.get("min", -np.inf), dist.get("max", np.inf))


def blocked(values, block: int, rng) -> np.ndarray:
    """``values`` (a multiple of ``block`` long) in an order drawn from
    ``rng`` in which each run of ``block`` consecutive values holds one
    from each of ``block`` strata (the sorted values cut in ``block``
    equal parts)."""
    v = np.sort(np.asarray(values))
    n = len(v)
    if n % block:
        raise ValueError(f"{n} values do not fill blocks of {block}")
    per = n // block
    strata = np.stack([rng.permutation(v[s * per:(s + 1) * per])
                       for s in range(block)])        # (block, per)
    return np.concatenate([strata[rng.permutation(block), b]
                           for b in range(per)])


def zipf_counts(n: int, tenants: int, s: float) -> np.ndarray:
    """How many of ``n`` requests each tenant sends, in proportion to
    ``1 / rank**s`` (largest remainder, so the counts sum to ``n``)."""
    w = 1.0 / np.arange(1, tenants + 1) ** s
    share = n * w / w.sum()
    counts = np.floor(share).astype(np.int64)
    rest = n - counts.sum()
    counts[np.argsort(-(share - counts), kind="stable")[:rest]] += 1
    return counts


def _top(dist: dict, drawn) -> int:
    """The stated upper bound of a distribution, else the largest drawn."""
    return int(dist["max"]) if "max" in dist else int(max(drawn))


def build(traffic: dict, seed: int, seconds: float, vocab: int) -> Schedule:
    """The whole offered load of one run: ``seconds`` of load after the
    warm-in, in the mix's order, with token ids from ``seed``."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(int(traffic["order_seed"]))
    arr = traffic["arrivals"]
    proc = arrival_process(arr["kind"])
    block = int(traffic.get("block", 1))
    warm = float(traffic.get("warm_in_s", 0.0))
    n = proc.count(arr, seconds + warm, block)
    due, after = proc.due(arr, n, block, order)

    def lengths(dist):
        return blocked(np.rint(stratified(dist, n)).astype(np.int64),
                       block, order)
    turn = lengths(traffic["prompt_tokens"])
    gen = lengths(traffic["output_tokens"])
    prefixes: List[np.ndarray] = []
    tenant_of = np.full(n, -1)
    if "prefix" in traffic:
        pre = traffic["prefix"]
        t = int(pre["tenants"])
        # tenant k (by popularity) always has the k-th prefix length
        plen = np.rint(stratified(pre["tokens"], t)).astype(np.int64)
        prefixes = [rng.integers(0, vocab, int(m), dtype=np.int32)
                    for m in plen]
        tenant_of = blocked(np.repeat(
            np.arange(t), zipf_counts(n, t, float(pre["zipf_s"]))),
            block, order)
    bound = (_top(traffic["prompt_tokens"], turn) + _top(
        traffic["output_tokens"], gen)
        + (max(len(p) for p in prefixes) if prefixes else 0))
    items = []
    for i in range(n):
        own = rng.integers(0, vocab, int(turn[i]), dtype=np.int32)
        k = int(tenant_of[i])
        prompt = np.concatenate([prefixes[k], own]) if k >= 0 else own
        items.append(Item(index=i, due_s=float(due[i]), prompt=prompt,
                          gen=int(gen[i]),
                          tenant=f"t{k}" if k >= 0 else "default",
                          after=int(after[i])))
    return Schedule(items=items, prefixes=prefixes, warm_in_s=warm,
                    max_tokens=int(bound))
