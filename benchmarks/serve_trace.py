"""Bursty multi-tenant Poisson trace through the paged serving engine.

The serving-side companion of cost_sweep.py: where that benchmark replays
whole *jobs* through the cost-aware nOS, this one replays individual
*requests* through :mod:`repro.serving` — the paged-KV continuous-batching
engine — and emits a throughput / TTFT / page-occupancy table per tenant,
plus the nOS fleet serving view (pages, energy, queue latency).

Arrivals are Poisson per tenant in units of engine steps (the engine
step is the farmer's clock), with a burst tenant that dumps its whole
load at once — the mixed pattern that makes continuous batching and
page-pressure preemption visible.

``--trace shared-prefix`` switches to the multi-tenant shared-prefix
trace (N tenants x M requests sharing per-tenant system prompts) and
``--prefix-cache on`` serves it through the copy-on-write prefix cache
(docs/PREFIX_CACHE.md); ``bench_prefix_comparison`` replays it twice —
cache on vs off — into BENCH_prefix.json (token identity, hit rate,
prefill-token reduction).

``--trace repetitive`` is the speculative-decoding exemplar: a single
latency-bound stream (batch 1) of motif-tiled prompts whose greedy
continuations loop, so device-resident n-gram drafting
(``--spec-decode on``, with ``--spec-k auto`` adaptive depth or a
fixed integer) verifies many tokens per model pass;
``bench_spec_comparison`` replays it twice — speculation on vs off —
into BENCH_spec.json (token identity, dispatches per token, accept
rate, and the wall-clock split wall_s = scan_s + draft_verify_s +
host_s with the spec_speedup verdict).

``--trace overload`` is the load harness (docs/LOAD_TESTING.md): an
interactive tenant under diurnal-modulated Poisson arrivals, a batch
tenant with heavy-tailed Pareto prompt lengths, and a surge tenant that
dumps a pile at once (overload-and-recover).  Per-request TTFT/TPOT is
recorded on both the deterministic step clock and the wall clock, and
``bench_slo_comparison`` replays it twice — ``--chunk-prefill on`` vs
monolithic — into BENCH_slo.json: p50/p95/p99 TTFT per SLO class,
goodput (tokens from deadline-met requests), token identity, and the
gated ``p99_ttft_ratio`` / ``goodput_ratio`` verdicts
(scripts/check_bench.py::check_slo).

``--trace-out`` arms the step-clock flight recorder
(docs/OBSERVABILITY.md) on any replay and exports Chrome trace-event
JSON; ``bench_obs_comparison`` replays the overload trace with tracing
off vs on into BENCH_obs.json (token bit-identity, the gated
``overhead_ratio`` <= 1.05x, the per-phase predicted-vs-measured
model-error rollup, and an embedded schema-validated trace excerpt),
gated by ``scripts/check_bench.py::check_obs``.

``--trace chaos`` is the fault-injection harness
(docs/FAULT_TOLERANCE.md): interactive + batch tenants whose requests
stripe across every node of the paged pool, driven under a seeded
:class:`repro.serving.FaultPlan` (``--fault-plan chaos``) of node
failures, transient admission errors and straggler slowdowns;
``bench_chaos_comparison`` replays it twice — fault-free vs chaos —
into BENCH_chaos.json (survivor token bit-identity, goodput retained,
recovery-step percentiles, zero stale reads), gated by
``scripts/check_bench.py::check_chaos``.

Run:  PYTHONPATH=src python benchmarks/serve_trace.py [--quick]
      PYTHONPATH=src python benchmarks/serve_trace.py --quick \
          --trace shared-prefix --prefix-cache on
      PYTHONPATH=src python benchmarks/serve_trace.py --quick \
          --trace repetitive --batch 1 --spec-decode on
      PYTHONPATH=src python benchmarks/serve_trace.py --quick \
          --trace overload --chunk-prefill on
      PYTHONPATH=src python benchmarks/serve_trace.py --quick \
          --trace chaos --nodes 4 --fault-plan chaos
"""
from __future__ import annotations

import argparse
import math
import sys
from dataclasses import dataclass, replace as dc_replace
from typing import List, Optional, Union

import numpy as np

sys.path.insert(0, "src")

from repro.serving.telemetry import (HistogramDigest,  # noqa: E402
                                     rollup_dispatch_events)

# Pareto-drawn prompt lengths are quantized to this grid so the
# monolithic prefill path (which retraces per prompt length) compiles a
# bounded kernel set; the heavy tail survives quantization
LEN_QUANTUM = 8


@dataclass(frozen=True)
class Tenant:
    name: str
    n_requests: int
    rate: float          # mean arrivals per engine step (Poisson); 0 = burst
    prompt_len: int
    gen: int
    at_step: int = 0     # burst tenants: every request arrives here
    shared_prefix: int = 0   # leading tokens all of the tenant's requests
                             # share (its "system prompt"); 0 = fully unique
    motif: int = 0           # > 0: prompts are a per-request motif of this
                             # many tokens tiled to prompt_len (repetitive
                             # text — speculative-decoding fodder)
    slo: str = "standard"    # repro.serving.slo class for every request
    pareto_alpha: float = 0.0   # > 0: prompt lengths are heavy-tailed —
                                # prompt_len * (1 + Pareto(alpha)), capped
                                # at max_prompt_len, LEN_QUANTUM-quantized
    max_prompt_len: int = 0     # Pareto cap (0: 4x prompt_len)
    rate_period: int = 0     # > 0: diurnal arrivals — the Poisson rate is
                             # modulated by a sine of this period (steps)
    rate_amp: float = 0.0    # diurnal modulation depth in [0, 1)


def default_tenants(quick: bool = False) -> List[Tenant]:
    if quick:
        return [Tenant("chat", 6, 0.5, 12, 6),
                Tenant("burst", 4, 0.0, 8, 4, at_step=5)]
    return [
        Tenant("chat", 12, 0.4, 16, 8),          # steady interactive load
        Tenant("batch", 8, 0.15, 32, 16),        # long-prompt background
        Tenant("burst", 8, 0.0, 12, 6, at_step=10),  # arrives all at once
    ]


def shared_prefix_tenants(quick: bool = False) -> List[Tenant]:
    """The multi-tenant shared-prefix trace (BENCH_prefix.json): N
    tenants x M requests, each tenant's requests sharing a per-tenant
    "system prompt".  The prefix length is deliberately NOT page aligned
    (22 tokens over 8-token pages) so every hit diverges inside a page
    and exercises the copy-on-write path, not just whole-page sharing."""
    if quick:
        return [Tenant("tenantA", 4, 0.5, 30, 6, shared_prefix=22),
                Tenant("tenantB", 4, 0.5, 30, 6, shared_prefix=22),
                Tenant("tenantC", 4, 0.5, 30, 6, shared_prefix=22)]
    return [Tenant(f"tenant{c}", 8, 0.4, 46, 10, shared_prefix=38)
            for c in "ABCD"]


def repetitive_tenants(quick: bool = False) -> List[Tenant]:
    """The repetitive-text trace (BENCH_spec.json): one latency-bound
    stream (run it with max_batch=1 — the regime where batching cannot
    amortize dispatches and speculation is the only lever) of long-gen
    requests whose prompts tile a short motif.  Greedy decode on such
    prompts falls into loops, which is exactly the repetitive-output
    regime (templates, code, retrieval) n-gram drafting exploits."""
    if quick:
        return [Tenant("loop", 3, 0.0, 24, 40, at_step=0, motif=4)]
    return [Tenant("loop", 6, 0.0, 32, 64, at_step=0, motif=4)]


def overload_tenants(quick: bool = False) -> List[Tenant]:
    """The heavy-traffic trace (BENCH_slo.json, docs/LOAD_TESTING.md):

    * ``interactive`` — short prompts under a diurnal-modulated Poisson
      stream (the sine-modulated rate is the burst pattern a day of chat
      traffic shows), SLO class ``interactive`` (tight TTFT deadline);
    * ``batch`` — heavy-tailed Pareto prompt lengths (alpha ~1.1: most
      prompts near the floor, rare prompts many times longer — the
      long-prompt head-of-line hazard), class ``batch``;
    * ``surge`` — the overload-and-recover phase: a pile of standard
      requests lands at one step, far over slot capacity, and the queue
      must drain without starving anyone.

    Full mode is thousands of requests; ``--quick`` keeps the same shape
    at CI scale."""
    if quick:
        return [
            Tenant("interactive", 18, 0.6, 8, 6, slo="interactive",
                   rate_period=24, rate_amp=0.8),
            Tenant("batch", 6, 0.08, 24, 4, slo="batch",
                   pareto_alpha=1.1, max_prompt_len=96),
            Tenant("surge", 10, 0.0, 8, 4, at_step=30, slo="standard"),
        ]
    return [
        Tenant("interactive", 1200, 0.8, 12, 8, slo="interactive",
               rate_period=200, rate_amp=0.8),
        Tenant("batch", 500, 0.12, 32, 8, slo="batch",
               pareto_alpha=1.1, max_prompt_len=256),
        Tenant("surge", 300, 0.0, 12, 6, at_step=400, slo="standard"),
    ]


def chaos_tenants(quick: bool = False) -> List[Tenant]:
    """The fault-injection trace (BENCH_chaos.json,
    docs/FAULT_TOLERANCE.md): an interactive tenant under steady Poisson
    arrivals plus a long-prompt batch tenant, both sized so every
    request's pages span all stripes of a 4-node pool (prompt + gen
    >= 4 pages at 8-token pages) — a node failure therefore always
    lands on live requests, exercising quarantine + exact-recompute
    recovery rather than only free-list shrinkage."""
    if quick:
        return [
            Tenant("interactive", 8, 0.4, 16, 12, slo="interactive"),
            Tenant("batch", 4, 0.12, 32, 8, slo="batch"),
        ]
    return [
        Tenant("interactive", 48, 0.5, 24, 16, slo="interactive"),
        Tenant("batch", 16, 0.1, 48, 12, slo="batch"),
    ]


TRACES = {
    "mixed": default_tenants,
    "shared-prefix": shared_prefix_tenants,
    "repetitive": repetitive_tenants,
    "overload": overload_tenants,
    "chaos": chaos_tenants,
}


def resolve_tenants(tenants, quick: bool = False) -> List[Tenant]:
    """Fail-fast trace validation: ``tenants`` may be a trace name, a
    list of :class:`Tenant`, or None (the default trace).  Anything else
    — or tenant fields that would only blow up deep inside ``prompt_for``
    / the engine — exits 2 listing the valid traces, so programmatic
    callers get the same contract as ``--trace`` argparse choices."""
    from repro.serving.slo import SLO_CLASSES
    valid = ", ".join(sorted(TRACES))

    def bail(msg: str):
        print(f"serve_trace: {msg}; valid traces: {valid}",
              file=sys.stderr)
        raise SystemExit(2)

    if tenants is None:
        return default_tenants(quick)
    if isinstance(tenants, str):
        if tenants not in TRACES:
            bail(f"unknown trace {tenants!r}")
        return TRACES[tenants](quick)
    try:
        tenants = list(tenants)
    except TypeError:
        bail(f"tenants must be a trace name or a list of Tenant, "
             f"got {type(tenants).__name__}")
    if not tenants:
        bail("empty tenants list")
    for t in tenants:
        if not isinstance(t, Tenant):
            bail(f"tenants list holds a {type(t).__name__}, not a Tenant")
        if t.n_requests <= 0 or t.prompt_len <= 0 or t.gen <= 0:
            bail(f"tenant {t.name!r} has non-positive "
                 f"n_requests/prompt_len/gen")
        if t.shared_prefix > t.prompt_len:
            bail(f"tenant {t.name!r} shared_prefix {t.shared_prefix} "
                 f"exceeds prompt_len {t.prompt_len}")
        if t.slo not in SLO_CLASSES:
            bail(f"tenant {t.name!r} has unknown SLO class {t.slo!r} "
                 f"(valid: {', '.join(sorted(SLO_CLASSES))})")
    return tenants


def prompt_for(cfg, t: Tenant, rid: int, plen: Optional[int] = None):
    """Request ``rid``'s prompt: the tenant's system prompt (stable
    per-tenant seed) + a unique per-request tail — or, for ``motif``
    tenants, a per-request motif tiled to the length.  ``plen``
    overrides the tenant's nominal length (Pareto draws)."""
    import jax
    import zlib
    plen = t.prompt_len if plen is None else plen
    if t.motif > 0:
        pat = np.asarray(jax.random.randint(jax.random.PRNGKey(rid),
                                            (t.motif,), 2, cfg.vocab_size),
                         np.int32)
        return np.tile(pat, -(-plen // t.motif))[:plen]
    tail_len = plen - t.shared_prefix
    parts = []
    if t.shared_prefix > 0:
        seed = zlib.crc32(t.name.encode()) % (2 ** 31)
        parts.append(jax.random.randint(jax.random.PRNGKey(seed),
                                        (t.shared_prefix,), 2,
                                        cfg.vocab_size))
    if tail_len > 0:
        parts.append(jax.random.randint(jax.random.PRNGKey(rid),
                                        (tail_len,), 2, cfg.vocab_size))
    return np.concatenate([np.asarray(p, np.int32) for p in parts])


def _draw_len(t: Tenant, rng: np.random.Generator) -> int:
    """Prompt length for one request: the nominal length, or a
    heavy-tailed Pareto draw quantized to LEN_QUANTUM (bounded compile
    set) and capped (bounded pool demand)."""
    if t.pareto_alpha <= 0.0:
        return t.prompt_len
    cap = t.max_prompt_len or 4 * t.prompt_len
    raw = t.prompt_len * (1.0 + rng.pareto(t.pareto_alpha))
    q = (int(raw) // LEN_QUANTUM) * LEN_QUANTUM
    return min(cap, max(t.prompt_len, q))


def arrivals_for(t: Tenant, rng: np.random.Generator):
    """(step, prompt_len) arrival list — Poisson gaps (optionally
    diurnal-modulated), or one burst."""
    if t.rate <= 0.0:
        return [(t.at_step, _draw_len(t, rng))
                for _ in range(t.n_requests)]
    out, now = [], 0.0
    for _ in range(t.n_requests):
        r = t.rate
        if t.rate_period > 0 and t.rate_amp > 0.0:
            # inhomogeneous Poisson via per-gap rate: the day/night sine
            r = t.rate * (1.0 + t.rate_amp
                          * math.sin(2.0 * math.pi * now / t.rate_period))
            r = max(r, 0.05 * t.rate)       # night floor, never zero
        now += rng.exponential(1.0 / r)
        out.append((t.at_step + int(now), _draw_len(t, rng)))
    return out


def replay(tenants: Union[str, List[Tenant], None] = None, *,
           quick: bool = False, seed: int = 0,
           max_batch: int = 4, page_size: int = 8, n_pages: int = 0,
           arch: str = "tiny-100m", link_mode: str = "circuit",
           prefill_budget: float = 2.0, fused: bool = True,
           max_window: int = 8, warmup: bool = False, params=None,
           prefix_cache: bool = False, spec_decode: bool = False,
           spec_k="auto", chunk_prefill: bool = False,
           chunk_tokens: int = 0, n_nodes: int = 1, fault_plan=None,
           trace: bool = False, trace_capacity: int = 4096):
    """Drive the engine window by window, injecting arrivals between
    dispatches.  With ``fused`` the engine decodes multi-token windows,
    capped to the next pending arrival so the trace's admission clock
    stays faithful; ``fused=False`` is the legacy per-step loop.

    ``tenants`` is a trace name from :data:`TRACES`, an explicit
    ``Tenant`` list, or None (the default trace); anything malformed
    exits 2 up front (see :func:`resolve_tenants`) instead of failing
    deep inside ``prompt_for``.

    ``fault_plan`` arms the deterministic fault plane
    (:class:`repro.serving.FaultPlan`) AFTER warmup and the metrics
    reset, so plan step 0 is the first measured step and warmup never
    consumes fault events; ``n_nodes`` stripes the page pool so a node
    failure quarantines a real fraction of it.

    ``trace`` arms the step-clock flight recorder (docs/OBSERVABILITY
    .md): request-lifecycle + dispatch spans with predicted-vs-measured
    attribution, exportable via ``eng.tracer.write_chrome``.  Tracing
    never changes scheduling (the engine's clock is the step index, not
    the wall), so traced and untraced replays emit identical tokens —
    ``bench_obs_comparison`` gates exactly that.

    Returns (engine, per-tenant rows, totals).
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.models import lm
    from repro.serving import PagedEngine

    tenants = resolve_tenants(tenants, quick)
    rng = np.random.default_rng(seed)
    # materialize the whole trace up front — (step, tenant, rid, plen)
    # — BEFORE sizing the engine: Pareto tenants only reveal their
    # worst-case length once drawn
    arrivals = sorted([(step, t, plen)
                       for t in tenants
                       for (step, plen) in arrivals_for(t, rng)],
                      key=lambda a: a[0])
    max_len = max(plen + t.gen for (_, t, plen) in arrivals)
    if not n_pages:
        # ~75% of worst-case demand: page pressure without thrash — but
        # never below one request's peak need (batch-1 traces would
        # otherwise be rejected at submit)
        worst = max_batch * (-(-max_len // page_size))
        n_pages = max(int(worst * 0.75), -(-max_len // page_size), 2) + 1

    cfg = get_tiny_config(arch)
    if params is None:
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
    # materialize every arrival's prompt up front too: trace
    # construction is not serving work, and jax.random compiles per
    # prompt shape
    pending = [(step, t, i, prompt_for(cfg, t, i, plen))
               for i, (step, t, plen) in enumerate(arrivals)]
    eng = PagedEngine(cfg, params, max_batch=max_batch,
                      page_size=page_size, n_pages=n_pages,
                      max_len=max_len, link_mode=link_mode,
                      prefill_budget=prefill_budget, fused=fused,
                      max_window=max_window, prefix_cache=prefix_cache,
                      spec_decode=spec_decode, spec_k=spec_k,
                      chunked_prefill=chunk_prefill,
                      chunk_tokens=chunk_tokens, n_nodes=n_nodes,
                      trace=trace, trace_capacity=trace_capacity)
    if warmup:
        # compile every window bucket + a prefill per DISTINCT
        # materialized prompt length (prefill retraces per length;
        # chunked engines compile their pow2 chunk buckets the same way)
        # outside the timed region
        eng.warmup_windows()
        lens = sorted({p.shape[0] for (_, _, _, p) in pending})
        for i, plen in enumerate(lens):
            warm = jax.random.randint(jax.random.PRNGKey(10_000 + i),
                                      (plen,), 2, cfg.vocab_size)
            eng.submit(np.asarray(warm), min(2, max_len - plen),
                       rid=f"warmup{i}")
        eng.run()
        # compile the COW copy + suffix-prefill buckets the trace will
        # hit: one miss/hit pair per (prompt_len, shared_prefix)
        for plen, sp in sorted({(t.prompt_len, t.shared_prefix)
                                for t in tenants}):
            eng.warmup_prefix(plen, sp)
        eng.reset_metrics()
        if eng.cache is not None:
            eng.cache.clear()      # measured run starts with a cold tree
        eng.sched.step_idx = 0
    if fault_plan is not None:
        # arm AFTER warmup/reset: the plane's epoch pins plan step 0 to
        # the current scheduler step, so the fault schedule replays
        # identically whether or not compiles were warmed
        eng.install_faults(fault_plan)

    occupancy = []
    while pending or eng.sched.waiting or eng.sched.prefilling \
            or eng.sched.running:
        while pending and pending[0][0] <= eng.sched.step_idx:
            _, t, rid, prompt = pending.pop(0)
            eng.submit(prompt, t.gen, tenant=t.name,
                       rid=f"{t.name}/{rid}", slo=t.slo)
        before = eng.steps_run
        if eng.sched.waiting or eng.sched.prefilling or eng.sched.running:
            # never decode past the next arrival: windows respect the
            # trace's clock, not just the scheduler's safe horizon
            cap = pending[0][0] - eng.sched.step_idx if pending else None
            eng.step(max_window=cap)
        else:
            eng.sched.step_idx += 1   # idle gap before the next arrival
        # one sample per *scheduler* step (a fused window covers several)
        # so fused and per-step occupancy means weight phases identically
        occupancy += [eng.alloc.pages_in_use] * max(eng.steps_run - before,
                                                    1)

    rows = []
    for t in tenants:
        fin = [r for r in eng.sched.finished if r.tenant == t.name]
        ttft = HistogramDigest.of(r.first_token_step - r.arrived_step
                                  for r in fin)
        met = [r for r in fin if r.first_token_step <= r.deadline_step]
        rows.append(dict(
            tenant=t.name, slo=t.slo, requests=len(fin),
            tokens=sum(len(r.tokens) for r in fin),
            ttft_mean=ttft.mean,
            ttft_p95=ttft.percentile(95),
            ttft_p99=ttft.percentile(99),
            slo_met_frac=len(met) / max(len(fin), 1),
            preemptions=sum(r.preemptions for r in fin)))
    m = eng.metrics()
    totals = dict(
        steps=eng.steps_run, windows=m["windows"], tokens=m["tokens_out"],
        tokens_finished=m["tokens_finished"],
        tok_per_s=m["tok_per_s"], decode_tok_per_s=m["decode_tok_per_s"],
        wall_s=m["wall_s"], decode_s=m["decode_s"],
        h2d_syncs=m["h2d_syncs"], d2h_syncs=m["d2h_syncs"],
        syncs_per_token=m["syncs_per_token"],
        occupancy_mean=float(np.mean(occupancy)) / max(n_pages - 1, 1),
        occupancy_peak=m["peak_pages"] / max(n_pages - 1, 1),
        preemptions=m["preemptions"], n_pages=n_pages,
        page_size=page_size, prefill_tokens=m["prefill_tokens"],
        model_passes=m["model_passes"],
        dispatches_per_token=m["dispatches_per_token"])
    if eng.spec is not None:
        totals.update(
            accept_rate=m["accept_rate"], spec_drafted=m["spec_drafted"],
            spec_accepted=m["spec_accepted"],
            spec_verifies=m["spec_verifies"],
            spec_rollbacks=m["spec_rollbacks"],
            spec_k_mean=m["spec_k_mean"],
            spec_verify_s=m["spec_verify_s"])
    if eng.cache is not None:
        totals.update(
            hit_rate=m["prefix_hit_rate"],
            prefill_tokens_cached=m["prefill_tokens_cached"],
            cow_copies=m["cow_copies"], shared_pages=m["shared_pages"],
            prefix_evictions=m["prefix_evictions"],
            bytes_deduped=m["bytes_deduped"])
    if eng.sched.chunked:
        totals.update(
            chunk_dispatches=m["chunk_dispatches"],
            chunk_rounds=m["chunk_rounds"],
            chunk_tasks=m["chunk_tasks"],
            chunk_preemptions=m["chunk_preemptions"])
    if eng.faults is not None:
        totals.update(
            node_failures=m["node_failures"],
            node_joins=m["node_joins"],
            pages_quarantined=m["pages_quarantined"],
            requests_recovered=m["requests_recovered"],
            requests_shed=m["requests_shed"],
            tokens_recomputed=m["tokens_recomputed"],
            transient_rejections=m["transient_rejections"],
            quarantined_served=m["quarantined_served"],
            recovery_steps_p50=m["recovery_steps_p50"],
            recovery_steps_p99=m["recovery_steps_p99"])
    return eng, rows, totals


def slo_stats(eng) -> dict:
    """Per-SLO-class percentile digest of a finished replay.

    TTFT percentiles are reported on two clocks: the deterministic
    engine-step clock (``ttft_steps_*`` — what check_bench gates, stable
    across machines) and the wall clock (``ttft_wall_*_s`` —
    informational).  ``goodput_tokens`` counts only tokens from requests
    whose first token landed by their class deadline — the "useful work"
    number an overloaded fleet optimizes, as opposed to raw throughput
    that happily burns pages on requests nobody is waiting for any more.

    Percentiles come from the shared streaming
    :class:`repro.serving.telemetry.HistogramDigest` — in its exact
    regime (every trace this repo ships) bit-equal to the
    ``np.percentile`` calls it replaced, and bounded-memory beyond.
    """
    from repro.serving.slo import get_slo

    out = {}
    for r in eng.sched.finished:
        out.setdefault(r.slo, []).append(r)
    digest = {}
    for name, reqs in sorted(out.items()):
        slo = get_slo(name)
        ttft = HistogramDigest.of(r.first_token_step - r.arrived_step
                                  for r in reqs)
        wall = HistogramDigest.of(r.first_token_at - r.submitted_at
                                  for r in reqs)
        tpot = HistogramDigest.of((r.finished_at - r.first_token_at)
                                  / max(len(r.tokens) - 1, 1)
                                  for r in reqs)
        met = [r for r in reqs
               if r.first_token_step <= r.deadline_step]
        digest[name] = dict(
            requests=len(reqs),
            ttft_target_steps=slo.ttft_steps,
            ttft_steps_p50=ttft.percentile(50),
            ttft_steps_p95=ttft.percentile(95),
            ttft_steps_p99=ttft.percentile(99),
            ttft_wall_p50_s=wall.percentile(50),
            ttft_wall_p99_s=wall.percentile(99),
            tpot_wall_mean_s=tpot.mean,
            slo_met_frac=len(met) / max(len(reqs), 1),
            goodput_tokens=sum(len(r.tokens) for r in met),
            tokens=sum(len(r.tokens) for r in reqs))
    return digest


def bench_slo_comparison(*, quick: bool = True, seed: int = 0,
                         max_batch: int = 4, page_size: int = 8,
                         max_window: int = 8, chunk_tokens: int = 0,
                         arch: str = "tiny-100m"):
    """Replay the overload trace twice — chunked prefill (SLO-aware EDF
    admission, deadline-budgeted chunk rounds) vs the monolithic priced
    FIFO — with shared params and warmed-up compiles, asserting
    per-request token identity (chunking is a KV-composition transform,
    not a sampler change).

    Returns the BENCH_slo.json payload (see
    scripts/check_bench.py::check_slo).  The gated verdicts are
    deterministic: ``p99_ttft_ratio`` compares the interactive class's
    p99 TTFT on the engine-step clock (chunked must not be worse — the
    whole point of slicing long prefills is that short interactive
    requests stop waiting behind them), and ``goodput_ratio`` compares
    deadline-met tokens (chunking must not win latency by throwing away
    throughput).
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.models import lm

    tenants = overload_tenants(quick)
    cfg = get_tiny_config(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    out, toks = {}, {}
    for mode, chunked in (("chunked", True), ("monolithic", False)):
        eng, rows, totals = replay(tenants, seed=seed,
                                   max_batch=max_batch,
                                   page_size=page_size,
                                   max_window=max_window,
                                   chunk_prefill=chunked,
                                   chunk_tokens=chunk_tokens,
                                   warmup=True, params=params, arch=arch)
        toks[mode] = {r.rid: list(r.tokens) for r in eng.sched.finished}
        per_class = slo_stats(eng)
        out[mode] = dict(
            tokens=totals["tokens"], steps=totals["steps"],
            tok_per_s=totals["tok_per_s"],
            prefill_tokens=totals["prefill_tokens"],
            preemptions=totals["preemptions"],
            goodput_tokens=sum(c["goodput_tokens"]
                               for c in per_class.values()),
            slo=per_class)
        if chunked:
            out[mode].update(
                chunk_dispatches=totals["chunk_dispatches"],
                chunk_rounds=totals["chunk_rounds"],
                chunk_tasks=totals["chunk_tasks"],
                chunk_preemptions=totals["chunk_preemptions"])
    inter_c = out["chunked"]["slo"]["interactive"]
    inter_m = out["monolithic"]["slo"]["interactive"]
    return {
        "schema": "swallow.bench.slo/v1",
        "arch": arch, "batch": max_batch, "page_size": page_size,
        "max_window": max_window, "trace": "overload",
        "quick": quick, "seed": seed,
        "chunked": out["chunked"], "monolithic": out["monolithic"],
        "tokens_match": toks["chunked"] == toks["monolithic"],
        "p99_ttft_ratio": inter_c["ttft_steps_p99"]
        / max(inter_m["ttft_steps_p99"], 1e-9),
        "goodput_ratio": out["chunked"]["goodput_tokens"]
        / max(out["monolithic"]["goodput_tokens"], 1),
    }


def bench_chaos_comparison(*, quick: bool = True, seed: int = 0,
                           max_batch: int = 4, page_size: int = 8,
                           max_window: int = 8, n_nodes: int = 4,
                           arch: str = "tiny-100m"):
    """Replay the chaos trace twice — fault-free vs a seeded
    :class:`repro.serving.FaultPlan` with >= 2 node failures — with
    shared params and warmed-up compiles, asserting that every request
    the chaos run finishes (the survivors) emits tokens bit-identical
    to the fault-free run.  Greedy recompute through the preemption
    machinery is exact, so fault recovery is a *placement* event, not a
    sampler change — the same invariant every other serving transform
    in this file is held to.

    The fault schedule is sized from the fault-free run's own step
    count, so failures always land while requests are in flight, and
    the plan's heartbeat/straggler detection runs on the deterministic
    step clock — the whole chaos run replays bit-identically from
    (seed, trace).

    Returns the BENCH_chaos.json payload (see
    scripts/check_bench.py::check_chaos).  Gated verdicts:
    ``tokens_match`` (survivor bit-identity), ``goodput_retained``
    (deadline-met tokens, chaos/fault-free — recovery must degrade
    gracefully, not collapse), ``quarantined_served == 0`` (no dispatch
    ever read a dead stripe) and ``node_failures >= 2`` both planned
    and detected."""
    import jax
    from repro.configs import get_tiny_config
    from repro.models import lm
    from repro.serving import FaultPlan

    tenants = chaos_tenants(quick)
    cfg = get_tiny_config(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    common = dict(seed=seed, max_batch=max_batch, page_size=page_size,
                  max_window=max_window, n_nodes=n_nodes, warmup=True,
                  params=params, arch=arch)

    base_eng, _, base_totals = replay(tenants, **common)
    base_toks = {r.rid: list(r.tokens) for r in base_eng.sched.finished}
    base_good = sum(c["goodput_tokens"]
                    for c in slo_stats(base_eng).values())

    # size the schedule from the fault-free run: failures land in the
    # first ~half of the trace, while the pool is under live load
    horizon = max(int(base_totals["steps"]) * 3 // 4, 16)
    plan = FaultPlan.seeded(seed, n_nodes=n_nodes, horizon=horizon,
                            n_fails=2, n_transients=2, n_slow=1)
    chaos_eng, _, chaos_totals = replay(tenants, fault_plan=plan,
                                        **common)
    chaos_toks = {r.rid: list(r.tokens)
                  for r in chaos_eng.sched.finished}
    chaos_good = sum(c["goodput_tokens"]
                     for c in slo_stats(chaos_eng).values())
    survivors_match = all(toks == base_toks[rid]
                          for rid, toks in chaos_toks.items())

    def block(totals, good, n_finished):
        out = dict(tokens=totals["tokens"], steps=totals["steps"],
                   tok_per_s=totals["tok_per_s"],
                   preemptions=totals["preemptions"],
                   requests_finished=n_finished, goodput_tokens=good)
        return out

    chaos_blk = block(chaos_totals, chaos_good, len(chaos_toks))
    chaos_blk.update(
        node_failures=chaos_totals["node_failures"],
        node_joins=chaos_totals["node_joins"],
        pages_quarantined=chaos_totals["pages_quarantined"],
        requests_recovered=chaos_totals["requests_recovered"],
        requests_shed=chaos_totals["requests_shed"],
        tokens_recomputed=chaos_totals["tokens_recomputed"],
        transient_rejections=chaos_totals["transient_rejections"],
        quarantined_served=chaos_totals["quarantined_served"],
        recovery_steps_p50=chaos_totals["recovery_steps_p50"],
        recovery_steps_p99=chaos_totals["recovery_steps_p99"])
    return {
        "schema": "swallow.bench.chaos/v1",
        "arch": arch, "batch": max_batch, "page_size": page_size,
        "max_window": max_window, "n_nodes": n_nodes,
        "trace": "chaos", "quick": quick, "seed": seed,
        "planned_failures": plan.n_node_failures,
        "planned_events": len(plan.events),
        "fault_free": block(base_totals, base_good, len(base_toks)),
        "chaos": chaos_blk,
        "tokens_match": bool(survivors_match),
        "survivors": len(chaos_toks),
        "goodput_retained": chaos_good / max(base_good, 1),
    }


def bench_obs_comparison(*, quick: bool = True, seed: int = 0,
                         max_batch: int = 4, page_size: int = 8,
                         max_window: int = 8, repeats: int = 3,
                         arch: str = "tiny-100m"):
    """Replay the overload trace with the flight recorder off vs on —
    shared params, warmed-up compiles — and price what observability
    costs.

    Scheduling runs on the deterministic step clock and the tracer only
    *reads* it, so the traced replay must emit per-request tokens
    bit-identical to the untraced one (``tokens_match``); the wall-clock
    ``overhead_ratio`` (min-of-``repeats`` traced wall / min untraced
    wall, alternated to decorrelate host drift) is gated at
    ``PERF_SMOKE_MAX_OBS_OVERHEAD`` (default 1.05 — a flight recorder
    that taxes serving >5% would never stay armed in production).

    The payload embeds the traced run's model-error rollup (per-phase
    cost-engine predicted vs measured wall) and a truncated copy of the
    Chrome trace events, which ``scripts/check_bench.py::check_obs``
    validates against the trace-event schema — the same document
    ``--trace-out`` ships to Perfetto.

    Returns the BENCH_obs.json payload.
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.models import lm

    tenants = overload_tenants(quick)
    cfg = get_tiny_config(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    common = dict(seed=seed, max_batch=max_batch, page_size=page_size,
                  max_window=max_window, warmup=True, params=params,
                  arch=arch)

    walls = {"off": [], "on": []}
    stats, toks, traced_eng = {}, {}, None
    for rep in range(repeats):
        for mode in ("off", "on"):
            eng, rows, totals = replay(tenants, trace=mode == "on",
                                       **common)
            walls[mode].append(totals["wall_s"])
            t = {r.rid: list(r.tokens) for r in eng.sched.finished}
            assert toks.setdefault(mode, t) == t, \
                f"{mode} replay is not deterministic across repeats"
            stats[mode] = dict(tokens=totals["tokens"],
                               steps=totals["steps"],
                               tok_per_s=totals["tok_per_s"],
                               wall_s=min(walls[mode]))
            if mode == "on":
                traced_eng = eng

    tracer = traced_eng.tracer
    tracer.finalize(traced_eng.sched.step_idx)
    report = tracer.model_error_report()
    stats["on"].update(spans_recorded=tracer.recorded,
                       spans_dropped=tracer.dropped)
    doc = tracer.chrome_trace()
    events = doc["traceEvents"]
    keep = 600                     # enough for schema validation without
    return {                       # bloating the committed artifact
        "schema": "swallow.bench.obs/v1",
        "arch": arch, "batch": max_batch, "page_size": page_size,
        "max_window": max_window, "trace": "overload",
        "quick": quick, "seed": seed, "repeats": repeats,
        "off": stats["off"], "on": stats["on"],
        "tokens_match": toks["off"] == toks["on"],
        "overhead_ratio": min(walls["on"]) / max(min(walls["off"]), 1e-9),
        "model_error": report,
        "trace_events_total": len(events),
        "trace_events": events[:keep],
    }


def bench_tenants() -> List[Tenant]:
    """Decode-heavy pinned trace for BENCH_serve.json: one burst of
    long-gen requests at batch pressure, so fused windows actually reach
    ``max_window``.  (The docs quick trace is arrival-dominated — its
    windows are capped near K=1 by the admission clock, which makes it a
    TTFT exemplar, not a decode-throughput one.)"""
    return [Tenant("decode", 8, 0.0, 16, 24, at_step=0)]


def bench_fused_comparison(*, quick: bool = True, seed: int = 0,
                           max_batch: int = 4, page_size: int = 8,
                           max_window: int = 8, arch: str = "tiny-100m"):
    """Replay the pinned decode-burst trace twice — fused windows vs
    legacy per-step — with shared params, warmed-up compiles and an
    uncontended pool (speedup A/B, not a preemption stressor), asserting
    token identity per request.

    Returns the BENCH_serve.json payload (see scripts/check_bench.py).
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.models import lm

    tenants = bench_tenants()
    if not quick:
        tenants = [Tenant("decode", 16, 0.0, 32, 48, at_step=0)]
    max_len = max(t.prompt_len + t.gen for t in tenants)
    n_pages = max_batch * (-(-max_len // page_size)) + 1
    cfg = get_tiny_config(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    out = {}
    toks = {}
    for mode, fused in (("fused", True), ("perstep", False)):
        eng, rows, totals = replay(tenants, seed=seed,
                                   max_batch=max_batch,
                                   page_size=page_size, n_pages=n_pages,
                                   fused=fused,
                                   max_window=max_window, warmup=True,
                                   params=params, arch=arch)
        toks[mode] = {r.rid: list(r.tokens) for r in eng.sched.finished}
        out[mode] = dict(
            tokens=totals["tokens"], steps=totals["steps"],
            windows=totals["windows"],
            decode_tok_per_s=totals["decode_tok_per_s"],
            tok_per_s=totals["tok_per_s"],
            h2d_syncs=totals["h2d_syncs"], d2h_syncs=totals["d2h_syncs"],
            syncs_per_token=totals["syncs_per_token"],
            preemptions=totals["preemptions"])
    return {
        "schema": "swallow.bench.serve/v1",
        "arch": arch, "batch": max_batch, "page_size": page_size,
        "max_window": max_window, "trace": "decode-burst",
        "quick": quick, "seed": seed,
        "fused": out["fused"], "perstep": out["perstep"],
        "tokens_match": toks["fused"] == toks["perstep"],
        "speedup_decode": out["fused"]["decode_tok_per_s"]
        / max(out["perstep"]["decode_tok_per_s"], 1e-9),
        "sync_reduction": out["perstep"]["syncs_per_token"]
        / max(out["fused"]["syncs_per_token"], 1e-9),
    }


def bench_prefix_comparison(*, quick: bool = True, seed: int = 0,
                            max_batch: int = 4, page_size: int = 8,
                            arch: str = "tiny-100m"):
    """Replay the shared-prefix multi-tenant trace twice — prefix cache
    on vs off — with shared params and warmed-up compiles, asserting
    per-request token identity (sharing is a placement transform, not a
    sampler change).

    Returns the BENCH_prefix.json payload (see scripts/check_bench.py):
    hit rate, prefill tokens saved, TTFT, tokens/s, and the headline
    ``prefill_token_reduction`` (>= 2x on this trace — the §X-B sharing
    overlay as a throughput lever).
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.models import lm

    tenants = shared_prefix_tenants(quick)
    max_len = max(t.prompt_len + t.gen for t in tenants)
    # room for every slot's worst case + the donated radix branches
    n_pages = 2 * max_batch * (-(-max_len // page_size)) \
        + len(tenants) * (-(-max_len // page_size)) + 1
    cfg = get_tiny_config(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    out, toks, ttft = {}, {}, {}
    for mode, cached in (("on", True), ("off", False)):
        eng, rows, totals = replay(tenants, seed=seed, max_batch=max_batch,
                                   page_size=page_size, n_pages=n_pages,
                                   prefix_cache=cached, warmup=True,
                                   params=params, arch=arch)
        toks[mode] = {r.rid: list(r.tokens) for r in eng.sched.finished}
        ttft[mode] = [r.first_token_step - r.arrived_step
                      for r in eng.sched.finished]
        out[mode] = dict(
            tokens=totals["tokens"], steps=totals["steps"],
            prefill_tokens=totals["prefill_tokens"],
            tok_per_s=totals["tok_per_s"],
            ttft_steps_mean=float(np.mean(ttft[mode])),
            preemptions=totals["preemptions"])
        if cached:
            out[mode].update(
                hit_rate=totals["hit_rate"],
                prefill_tokens_cached=totals["prefill_tokens_cached"],
                cow_copies=totals["cow_copies"],
                shared_pages=totals["shared_pages"],
                evictions=totals["prefix_evictions"],
                bytes_deduped=totals["bytes_deduped"])
    payload = {
        "schema": "swallow.bench.prefix/v1",
        "arch": arch, "batch": max_batch, "page_size": page_size,
        "trace": "shared-prefix", "quick": quick, "seed": seed,
        "tenants": len(tenants),
        "requests_per_tenant": tenants[0].n_requests,
        "on": out["on"], "off": out["off"],
        "tokens_match": toks["on"] == toks["off"],
        "prefill_token_reduction": out["off"]["prefill_tokens"]
        / max(out["on"]["prefill_tokens"], 1),
        "ttft_ratio": out["on"]["ttft_steps_mean"]
        / max(out["off"]["ttft_steps_mean"], 1e-9),
    }
    return payload


def bench_spec_comparison(*, quick: bool = True, seed: int = 0,
                          page_size: int = 8, max_window: int = 8,
                          spec_k="auto", arch: str = "tiny-100m"):
    """Replay the repetitive single-stream trace twice — speculative
    decoding on vs off — with shared params and warmed-up compiles,
    asserting per-request token identity (acceptance only ever keeps
    the verifier's own greedy tokens, so speculation is a dispatch
    transform, not a sampler change).

    Runs at max_batch=1: the latency-bound regime where cross-request
    batching cannot amortize model passes, so ``dispatches_per_token``
    isolates what drafting+verification buys (off is ~1.0 pass/token
    even with fused windows — a K-step scan is K sequential passes; a
    K+1-wide verify is ONE).  Speculation runs the device-resident
    fused draft+verify chain with ``spec_k="auto"`` adaptive depth by
    default — the configuration the engine ships.

    Returns the BENCH_spec.json payload (see scripts/check_bench.py):
    the headline ``spec_speedup`` (on/off wall tok_per_s, >= 1.0 is the
    bar — speculation must WIN wall-clock, not just dispatch counts),
    ``on.dispatches_per_token`` (< 0.7 — >= 1.4x fewer model dispatches
    per emitted token), and the honesty split of where each run's wall
    time went: ``scan_s`` (plain fused-scan device time),
    ``draft_verify_s`` (the speculative dispatch chain), ``host_s``
    (everything that is not a device dispatch — scheduling, accounting,
    h2d/d2h marshalling).  PR 5 hid a 5.6x wall-clock REGRESSION behind
    a 5x dispatch-count win precisely because this split was missing.
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.models import lm

    tenants = repetitive_tenants(quick)
    max_len = max(t.prompt_len + t.gen for t in tenants)
    n_pages = (-(-max_len // page_size)) + 1       # exact single-slot pool
    cfg = get_tiny_config(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    out, toks = {}, {}
    for mode, spec in (("on", True), ("off", False)):
        eng, rows, totals = replay(tenants, seed=seed, max_batch=1,
                                   page_size=page_size, n_pages=n_pages,
                                   fused=True, max_window=max_window,
                                   spec_decode=spec, spec_k=spec_k,
                                   warmup=True, params=params, arch=arch)
        toks[mode] = {r.rid: list(r.tokens) for r in eng.sched.finished}
        verify_s = totals.get("spec_verify_s", 0.0)
        out[mode] = dict(
            tokens=totals["tokens"], steps=totals["steps"],
            model_passes=totals["model_passes"],
            dispatches_per_token=totals["dispatches_per_token"],
            tok_per_s=totals["tok_per_s"],
            decode_tok_per_s=totals["decode_tok_per_s"],
            # the wall-clock honesty split: wall = scan + draft/verify
            # + host-side overhead
            wall_s=totals["wall_s"],
            scan_s=totals["decode_s"] - verify_s,
            draft_verify_s=verify_s,
            host_s=totals["wall_s"] - totals["decode_s"],
            h2d_syncs=totals["h2d_syncs"],
            d2h_syncs=totals["d2h_syncs"],
            preemptions=totals["preemptions"])
        if spec:
            out[mode].update(
                accept_rate=totals["accept_rate"],
                spec_drafted=totals["spec_drafted"],
                spec_accepted=totals["spec_accepted"],
                spec_verifies=totals["spec_verifies"],
                spec_rollbacks=totals["spec_rollbacks"],
                spec_k_mean=totals["spec_k_mean"])
    return {
        "schema": "swallow.bench.spec/v2",
        "arch": arch, "batch": 1, "page_size": page_size,
        "max_window": max_window, "spec_k": spec_k,
        "trace": "repetitive", "quick": quick, "seed": seed,
        "on": out["on"], "off": out["off"],
        "tokens_match": toks["on"] == toks["off"],
        "dispatch_reduction": out["off"]["dispatches_per_token"]
        / max(out["on"]["dispatches_per_token"], 1e-9),
        "spec_speedup": out["on"]["tok_per_s"]
        / max(out["off"]["tok_per_s"], 1e-9),
    }


TP_LAYOUTS = ((1, 1), (1, 2), (2, 2))


def _tp_replay(cfg, params, data: int, model: int, seed: int, devices):
    """Replay the pinned prefix-sharing workload at one (data, model)
    layout — a mesh over the first data*model of ``devices`` (1x1: no
    mesh) — and return its traced payload plus per-request tokens."""
    from repro.launch.mesh import make_test_mesh
    from repro.serving import PagedEngine

    mesh = make_test_mesh(data, model, devices=devices) \
        if data * model > 1 else None
    n_nodes = max(model, 1)
    eng = PagedEngine(cfg, params, max_batch=3, page_size=4, n_pages=48,
                      max_len=32, n_nodes=n_nodes, mesh=mesh,
                      prefix_cache=True, trace=True)
    rng = np.random.default_rng(seed)
    shared = rng.integers(2, cfg.vocab_size, 6, dtype=np.int32)
    prompts = []
    for i in range(6):
        tail = rng.integers(2, cfg.vocab_size, 6, dtype=np.int32)
        head = shared if i >= 4 else rng.integers(2, cfg.vocab_size, 6,
                                                  dtype=np.int32)
        prompts.append(np.concatenate([head, tail]))
    gens = [6, 9, 4, 7, 8, 5]
    owner_steps = np.zeros(n_nodes, np.int64)
    for i, (p, g) in enumerate(zip(prompts, gens)):
        eng.submit(p, g, rid=f"r{i}")
    while eng.sched.waiting or eng.sched.running or eng.sched.prefilling:
        eng.step()
        for pages in eng.alloc.held.values():   # page-steps per owner node
            for pg in pages:
                owner_steps[pg % n_nodes] += 1
    eng.tracer.finalize(eng.sched.step_idx)
    report = eng.tracer.model_error_report()
    tot = int(owner_steps.sum())
    out = {
        "predicted_s": sum(r["predicted_s"] for r in report.values()),
        "measured_s": sum(r["measured_s"] for r in report.values()),
        "predicted_comms_s": sum(r.get("predicted_comms_s", 0.0)
                                 for r in report.values()),
        "comms_bytes": sum(r.get("comms_bytes", 0.0)
                           for r in report.values()),
        "measured_remote_frac": (1.0 - owner_steps[0] / tot) if tot else 0.0,
        "steps": eng.steps_run,
        "cow_copies": eng.cache.stats.cow_copies,
        "preemptions": eng.metrics()["preemptions"],
    }
    tokens = {r.rid: [int(t) for t in r.tokens] for r in eng.sched.finished}
    return out, tokens


def bench_tp_comparison(*, quick: bool = True, seed: int = 0,
                        arch: str = "tiny-100m"):
    """Replay a pinned prefix-sharing workload through the paged engine
    at every serving layout — 1x1 single device, then 1x2 and 2x2
    striped meshes — all in this process, on subsets of one
    ``jax.devices()`` list (four chips, or four virtual CPU devices
    from ``--xla_force_host_platform_device_count=4``), asserting
    per-request greedy-token bit-identity against the 1x1 baseline
    (sharding is a placement transform, never a sampler change).

    Per layout the payload records the traced run's predicted vs
    measured seconds, the window-level predicted interconnect cost
    (``predicted_comms_s`` / ``comms_bytes`` — the §V link model priced
    per dispatch span), and ``measured_remote_frac``: the fraction of
    page-steps held on nodes other than node 0, measured from the live
    allocator each engine step.  The §V model predicts (n-1)/n for a
    striped store; ``scripts/check_bench.py::check_tp`` gates the
    measured/predicted ratio at ``PERF_SMOKE_MAX_TP_MODEL_ERROR``.

    Returns the BENCH_tp.json payload.
    """
    import jax
    from repro.configs import get_tiny_config
    from repro.models import lm

    devices = jax.devices()
    cfg = get_tiny_config(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    layouts = []
    base_tokens = None
    for data, model in TP_LAYOUTS:
        child, tokens = _tp_replay(cfg, params, data, model, seed, devices)
        if base_tokens is None:
            base_tokens = tokens
        n = max(model, 1)
        predicted_remote = (n - 1) / n
        layouts.append(dict(
            layout=f"{data}x{model}", data=data, model=model,
            tokens_match=tokens == base_tokens,
            predicted_remote_frac=predicted_remote,
            remote_frac_ratio=(child["measured_remote_frac"]
                               / predicted_remote if predicted_remote
                               else 1.0),
            **child))
    return {
        "schema": "swallow.bench.tp/v1",
        "arch": arch, "batch": 3, "page_size": 4, "n_pages": 48,
        "trace": "tp-pinned", "quick": quick, "seed": seed,
        "layouts": layouts,
        "tokens_match": all(l["tokens_match"] for l in layouts),
    }


def format_table(rows, totals) -> str:
    out = [f"# paged serve trace — {len(rows)} tenants, "
           f"{totals['n_pages']} pages x {totals['page_size']} tokens",
           f"{'tenant':<12} {'slo':<11} {'reqs':>5} {'tokens':>7} "
           f"{'ttft_mean':>10} {'ttft_p95':>9} {'ttft_p99':>9} "
           f"{'met%':>5} {'preempt':>8}"]
    for r in rows:
        out.append(f"{r['tenant']:<12} {r['slo']:<11} {r['requests']:>5} "
                   f"{r['tokens']:>7} {r['ttft_mean']:>10.1f} "
                   f"{r['ttft_p95']:>9.1f} {r['ttft_p99']:>9.1f} "
                   f"{r['slo_met_frac'] * 100:>4.0f}% "
                   f"{r['preemptions']:>8}")
    t = totals
    out.append(f"{t['steps']} engine steps in {t['windows']} device "
               f"dispatches, {t['tokens']} tokens "
               f"({t['tok_per_s']:.0f} tok/s wall, "
               f"{t['decode_tok_per_s']:.0f} decode tok/s); "
               f"host<->device syncs {t['h2d_syncs']} h2d + "
               f"{t['d2h_syncs']} d2h ({t['syncs_per_token']:.2f}/token); "
               f"page occupancy "
               f"mean {t['occupancy_mean'] * 100:.0f}% / peak "
               f"{t['occupancy_peak'] * 100:.0f}%; "
               f"{t['preemptions']} preemptions")
    if "accept_rate" in t:
        out.append(f"spec decode: {t['model_passes']} model passes "
                   f"({t['dispatches_per_token']:.2f}/token), "
                   f"{t['accept_rate'] * 100:.0f}% accept rate "
                   f"({t['spec_accepted']}/{t['spec_drafted']} drafts, "
                   f"{t['spec_verifies']} verifies, "
                   f"{t['spec_rollbacks']} page rollbacks); "
                   f"mean K {t['spec_k_mean']:.1f}, draft+verify "
                   f"{t['spec_verify_s']:.3f}s of {t['decode_s']:.3f}s "
                   f"decode")
    if "hit_rate" in t:
        out.append(f"prefix cache: {t['hit_rate'] * 100:.0f}% hit rate, "
                   f"{t['prefill_tokens_cached']} prefill tokens served "
                   f"from shared pages ({t['prefill_tokens']} computed), "
                   f"{t['cow_copies']} COW copies, {t['shared_pages']} "
                   f"tree pages, {t['prefix_evictions']} evictions, "
                   f"{t['bytes_deduped'] / 1024:.0f} KiB deduped")
    if "chunk_dispatches" in t:
        out.append(f"chunked prefill: {t['chunk_tasks']} chunks in "
                   f"{t['chunk_rounds']} rounds "
                   f"({t['chunk_dispatches']} dispatches), "
                   f"{t['chunk_preemptions']} mid-prefill preemptions")
    if "node_failures" in t:
        out.append(f"fault plane: {t['node_failures']} node failures / "
                   f"{t['node_joins']} re-joins, "
                   f"{t['pages_quarantined']} pages quarantined, "
                   f"{t['requests_recovered']} requests recovered "
                   f"({t['tokens_recomputed']} tokens recomputed), "
                   f"{t['requests_shed']} shed, "
                   f"{t['transient_rejections']} transient rejections, "
                   f"recovery p50/p99 {t['recovery_steps_p50']:.0f}/"
                   f"{t['recovery_steps_p99']:.0f} steps, "
                   f"{t['quarantined_served']} stale reads")
    return "\n".join(out)


def fleet_view(eng) -> str:
    """Per-tenant gauges through the nOS serving surface.  The
    speculative-decoding gauges are engine-wide (acceptance is not
    tracked per tenant), so every tenant row shows the same pair.  When
    the flight recorder is armed, each tenant's share of the
    predicted-vs-measured attribution rides along (split by token
    share — dispatches are batched across tenants, so per-tenant wall
    is an apportionment, not a measurement) and the nOS attribution
    table is appended."""
    from repro.core import nos as nos_mod
    from repro.serving.slo import get_slo
    pod = nos_mod.NOS(data_rows=4, model_cols=1)
    est = eng.decode_estimate      # engine-priced step time & energy
    j_per_token = est.energy.total_j / max(eng.max_batch, 1)
    m = eng.metrics()
    report = None
    pred_s = meas_s = pred_j = 0.0
    if eng.tracer is not None:
        report = eng.tracer.model_error_report()
        pred_s = sum(r["predicted_s"] for r in report.values())
        meas_s = sum(r["measured_s"] for r in report.values())
        pred_j = sum(r["predicted_j"] for r in report.values())
    all_tokens = sum(len(r.tokens) for r in eng.sched.finished)
    tenants = sorted({r.tenant for r in eng.sched.finished})
    for name in tenants:
        fin = [r for r in eng.sched.finished if r.tenant == name]
        ttft = [r.first_token_step - r.arrived_step for r in fin]
        tokens = sum(len(r.tokens) for r in fin)
        met_tokens = sum(len(r.tokens) for r in fin
                         if r.first_token_step <= r.deadline_step)
        # a trace tenant's requests share one SLO class; price its
        # step-clock deadline to seconds with the engine's own estimate
        slo = get_slo(fin[0].slo) if fin else None
        pod.submit(nos_mod.Job(name, rows_needed=1))
        pod.update_serving(
            name,
            pages_held=max((eng.alloc.pages_for(r.prompt_len + r.gen)
                            for r in fin), default=0),
            tokens_out=tokens,
            queue_latency_s=(float(np.mean(ttft)) if ttft else 0.0)
            * est.step_time_s,
            preemptions=sum(r.preemptions for r in fin),
            energy_j=tokens * j_per_token,
            accept_rate=m.get("accept_rate"),
            dispatches_per_token=m.get("dispatches_per_token"),
            ttft_p99_s=(float(np.percentile(ttft, 99)) if ttft else 0.0)
            * est.step_time_s,
            ttft_target_s=(slo.ttft_steps * est.step_time_s
                           if slo else None),
            goodput_frac=met_tokens / max(tokens, 1),
            # fault gauges are engine-wide, like accept_rate: every
            # tenant row shows the same recovery story
            pages_quarantined=m.get("pages_quarantined"),
            requests_recovered=m.get("requests_recovered"),
            tokens_recomputed=m.get("tokens_recomputed"),
            recovery_steps_p99=m.get("recovery_steps_p99"),
            **({"predicted_s": pred_s * tokens / max(all_tokens, 1),
                "measured_s": meas_s * tokens / max(all_tokens, 1),
                "predicted_j": pred_j * tokens / max(all_tokens, 1)}
               if report else {}))
    table = pod.serving_table()
    if report:
        table += ("\n[nOS] predicted-vs-measured attribution:\n"
                  + pod.attribution_table())
    return table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small trace for CI / docs examples")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--pages", type=int, default=0)
    ap.add_argument("--link-mode", default="circuit",
                    choices=["circuit", "packet"])
    ap.add_argument("--fused", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="fused multi-token decode windows "
                         "(--no-fused = legacy per-step loop)")
    ap.add_argument("--window", type=int, default=8,
                    help="max fused window (tokens per device dispatch)")
    ap.add_argument("--trace", default="mixed",
                    choices=sorted(TRACES),
                    help="mixed: the bursty Poisson tenants; "
                         "shared-prefix: N tenants x M requests sharing "
                         "per-tenant system prompts; repetitive: the "
                         "single-stream motif trace speculation feeds on; "
                         "overload: the heavy-traffic SLO harness "
                         "(diurnal interactive + Pareto batch + surge)")
    ap.add_argument("--prefix-cache", default="off", choices=["on", "off"],
                    help="radix-tree prefix sharing on the page store")
    ap.add_argument("--spec-decode", default="off", choices=["on", "off"],
                    help="n-gram speculative decoding (draft from the "
                         "sequence's own history, verify K+1 positions "
                         "in one dispatch)")
    ap.add_argument("--spec-k", default="auto",
                    help="max draft tokens per verification dispatch, or "
                         "'auto' for per-request adaptive depth from the "
                         "acceptance EWMA (the default)")
    ap.add_argument("--chunk-prefill", default="off", choices=["on", "off"],
                    help="page-aligned chunked prefill with SLO-aware EDF "
                         "admission (off = monolithic priced FIFO)")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="tokens per prefill chunk (0 = 2 pages)")
    ap.add_argument("--nodes", type=int, default=1,
                    help="memory nodes striping the page pool (a node "
                         "failure quarantines its stripe)")
    ap.add_argument("--fault-plan", default="off", choices=["off", "chaos"],
                    help="chaos: arm a seeded FaultPlan (node failures + "
                         "transient rejections + a straggler) against "
                         "the run")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the chaos FaultPlan")
    ap.add_argument("--fault-horizon", type=int, default=48,
                    help="steps the chaos schedule spans")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="arm the flight recorder and export the replay "
                         "as Chrome trace-event JSON "
                         "(docs/OBSERVABILITY.md)")
    ap.add_argument("--metrics-out", default=None,
                    metavar="METRICS.json",
                    help="dump the unified metrics registry snapshot")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    spec_k = args.spec_k if args.spec_k == "auto" else int(args.spec_k)
    fault_plan = None
    if args.fault_plan == "chaos":
        from repro.serving import FaultPlan
        if args.nodes < 2:
            print("serve_trace: --fault-plan chaos needs --nodes >= 2 "
                  "(node 0 never fails)", file=sys.stderr)
            raise SystemExit(2)
        fault_plan = FaultPlan.seeded(args.fault_seed, n_nodes=args.nodes,
                                      horizon=args.fault_horizon)
    eng, rows, totals = replay(args.trace, quick=args.quick,
                               seed=args.seed, max_batch=args.batch,
                               page_size=args.page_size, n_pages=args.pages,
                               link_mode=args.link_mode, fused=args.fused,
                               max_window=args.window,
                               prefix_cache=args.prefix_cache == "on",
                               spec_decode=args.spec_decode == "on",
                               spec_k=spec_k,
                               chunk_prefill=args.chunk_prefill == "on",
                               chunk_tokens=args.chunk_tokens,
                               n_nodes=args.nodes, fault_plan=fault_plan,
                               trace=bool(args.trace_out))
    print(format_table(rows, totals))
    if eng.tracer is not None:
        from repro.serving.telemetry import format_model_error
        eng.tracer.finalize(eng.sched.step_idx)
        report = eng.tracer.model_error_report()
        if report:
            print("per-phase model error (cost-engine predicted vs "
                  "measured wall):")
            print(format_model_error(report))
        if args.trace_out:
            eng.tracer.write_chrome(args.trace_out)
            print(f"[trace] wrote {args.trace_out} "
                  f"({eng.tracer.recorded} spans recorded, "
                  f"{eng.tracer.dropped} evicted)")
    if args.metrics_out:
        import json
        with open(args.metrics_out, "w") as f:
            json.dump(eng.registry.snapshot(), f, indent=2,
                      sort_keys=True)
        print(f"[metrics] wrote {args.metrics_out}")
    if args.trace == "overload":
        for cls, d in slo_stats(eng).items():
            print(f"slo[{cls}]: p50/p95/p99 ttft "
                  f"{d['ttft_steps_p50']:.0f}/{d['ttft_steps_p95']:.0f}/"
                  f"{d['ttft_steps_p99']:.0f} steps "
                  f"(target {d['ttft_target_steps']}), "
                  f"met {d['slo_met_frac'] * 100:.0f}%, goodput "
                  f"{d['goodput_tokens']}/{d['tokens']} tokens")
    print("[nOS] fleet serving view:")
    print(fleet_view(eng))


if __name__ == "__main__":
    main()
