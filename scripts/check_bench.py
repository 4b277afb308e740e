#!/usr/bin/env python
"""Perf-smoke CI gate: validate the machine-readable BENCH_*.json files
that ``benchmarks/run.py --json`` emits.

* ``BENCH_micro.json`` (swallow.bench.micro/v1): non-empty ``rows`` of
  {name, us_per_call, derived} with finite positive timings, including
  the serve rows the fused-decode PR pinned.
* ``BENCH_serve.json`` (swallow.bench.serve/v1): fused + perstep stat
  blocks, ``tokens_match`` must be true (fused windows are a perf
  transform, not a sampler change), syncs-per-token must drop, and
  ``speedup_decode`` must clear ``PERF_SMOKE_MIN_SPEEDUP`` (default 1.0
  — the 1.5x acceptance bar is checked on dedicated hosts, CI runners
  only guard against regressions to parity).
* ``BENCH_prefix.json`` (swallow.bench.prefix/v1): prefix-cache on/off
  stat blocks on the shared-prefix trace.  ``tokens_match`` must be
  true (sharing is a placement transform), ``on.hit_rate`` must be
  positive, and ``prefill_token_reduction`` must clear
  ``PERF_SMOKE_MIN_PREFIX_REDUCTION`` (default 2.0 — the reduction is a
  token *count* ratio, deterministic on any host).
* ``BENCH_spec.json`` (swallow.bench.spec/v2): speculative-decoding
  on/off stat blocks on the repetitive single-stream trace, including
  the wall-clock honesty split (``wall_s`` = ``scan_s`` +
  ``draft_verify_s`` + ``host_s``).  ``tokens_match`` must be true
  (speculation is a dispatch transform, not a sampler change),
  ``on.accept_rate`` must be positive, ``on.dispatches_per_token``
  must stay under ``PERF_SMOKE_MAX_SPEC_DISPATCHES`` (default 0.7),
  ``dispatch_reduction`` must clear ``PERF_SMOKE_MIN_SPEC_REDUCTION``
  (default 1.4) — both are model-pass *count* ratios, deterministic on
  any host — and ``spec_speedup`` (on.tok_per_s / off.tok_per_s, the
  wall-clock verdict) must clear ``PERF_SMOKE_SPEC_SPEEDUP_MIN``
  (default 1.0: speculation must never lose to the plain scan it
  replaces).

* ``BENCH_slo.json`` (swallow.bench.slo/v1): chunked-prefill vs
  monolithic stat blocks on the overload trace (diurnal interactive +
  Pareto batch + surge), each with per-SLO-class TTFT percentile
  digests.  ``tokens_match`` must be true (chunking is a KV-composition
  transform, not a sampler change), ``p99_ttft_ratio`` (the interactive
  class's p99 TTFT on the deterministic engine-step clock,
  chunked/monolithic) must stay under ``PERF_SMOKE_MAX_P99_TTFT_RATIO``
  (default 1.0: slicing prefills must never make the interactive tail
  WORSE), and ``goodput_ratio`` (deadline-met tokens,
  chunked/monolithic) must clear ``PERF_SMOKE_MIN_GOODPUT_RATIO``
  (default 1.0: the latency win must not be bought with thrown-away
  throughput).  All three are deterministic on any host.

* ``BENCH_chaos.json`` (swallow.bench.chaos/v1): fault-free vs chaos
  stat blocks on the fault-injection trace (a seeded FaultPlan of node
  failures + transient rejections + a straggler against the striped
  page pool).  ``tokens_match`` must be true (every request the chaos
  run finishes is bit-identical to the fault-free run — recovery is
  exact greedy recompute, not resampling), ``chaos.node_failures``
  must be >= 2 both planned and detected, ``quarantined_served`` must
  be 0 (no dispatch ever read a dead stripe), recovery percentiles
  must be finite, and ``goodput_retained`` (deadline-met tokens,
  chaos/fault-free) must clear ``PERF_SMOKE_MIN_GOODPUT_RETAINED``
  (default 0.25 — degradation must be graceful; the whole chain is on
  the deterministic step clock, so the value is host-independent).

* ``BENCH_obs.json`` (swallow.bench.obs/v1): flight-recorder off vs on
  stat blocks on the overload trace.  ``tokens_match`` must be true
  (the tracer only *reads* the deterministic step clock — observing a
  run must never change it), ``overhead_ratio`` (min traced wall / min
  untraced wall) must stay under ``PERF_SMOKE_MAX_OBS_OVERHEAD``
  (default 1.05 — a flight recorder that taxes serving >5% would never
  stay armed in production), the embedded ``trace_events`` excerpt
  must validate against the Chrome trace-event schema
  (``repro.serving.telemetry.validate_chrome_trace`` — the same
  document ``--trace-out`` ships to Perfetto), at least one dispatch
  span must carry the full attribution triple
  (predicted_s/predicted_j/measured_s), and the ``model_error`` rollup
  must be finite.

* ``BENCH_tp.json`` (swallow.bench.tp/v1): the pinned prefix-sharing
  workload replayed at every serving layout — the 1x1 single-device
  baseline plus striped (data, model) meshes, all in one process on
  subsets of its devices.  ``tokens_match`` must be true per layout and overall
  (striping the page pools is a placement transform — greedy tokens are
  bit-identical across meshes), every striped layout must price
  interconnect traffic (``predicted_comms_s`` / ``comms_bytes`` > 0 —
  the §V link model applied per dispatch window), and the measured
  remote page fraction must track the predicted (n-1)/n stripe model:
  ``remote_frac_ratio`` within ``PERF_SMOKE_MAX_TP_MODEL_ERROR``
  (default 0.25) of 1.0.  All gated values ride the deterministic step
  clock and allocator state, so they are host-independent.

Run from the repo root:
    python benchmarks/run.py --only micro --json
    python scripts/check_bench.py BENCH_micro.json BENCH_serve.json \
        BENCH_prefix.json BENCH_spec.json BENCH_slo.json \
        BENCH_chaos.json BENCH_obs.json BENCH_tp.json
"""
from __future__ import annotations

import json
import math
import os
import sys

# telemetry is pure host-side (stdlib + numpy) — importable without jax
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

REQUIRED_SERVE_KEYS = ("tokens", "steps", "windows", "decode_tok_per_s",
                       "tok_per_s", "h2d_syncs", "d2h_syncs",
                       "syncs_per_token", "preemptions")
REQUIRED_MICRO_ROWS = ("micro/serve_fused_window_", "micro/serve_perstep_",
                       "micro/paged_attn_kernel_")


def _finite_pos(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x) and x >= 0


def check_micro(doc: dict) -> list:
    errs = []
    if doc.get("schema") != "swallow.bench.micro/v1":
        errs.append(f"bad schema: {doc.get('schema')!r}")
    rows = doc.get("rows", [])
    if not rows:
        errs.append("rows is empty")
    for r in rows:
        if set(r) != {"name", "us_per_call", "derived"}:
            errs.append(f"bad row keys: {sorted(r)}")
            break
        if not _finite_pos(r["us_per_call"]):
            errs.append(f"{r['name']}: non-finite us_per_call "
                        f"{r['us_per_call']!r}")
    names = [r.get("name", "") for r in rows]
    for prefix in REQUIRED_MICRO_ROWS:
        if not any(n.startswith(prefix) for n in names):
            errs.append(f"missing required micro row {prefix}*")
    return errs


def check_serve(doc: dict) -> list:
    errs = []
    if doc.get("schema") != "swallow.bench.serve/v1":
        errs.append(f"bad schema: {doc.get('schema')!r}")
    for mode in ("fused", "perstep"):
        blk = doc.get(mode)
        if not isinstance(blk, dict):
            errs.append(f"missing {mode} block")
            continue
        for key in REQUIRED_SERVE_KEYS:
            if not _finite_pos(blk.get(key)):
                errs.append(f"{mode}.{key}: non-finite {blk.get(key)!r}")
    if doc.get("tokens_match") is not True:
        errs.append("tokens_match is not true: fused windows changed "
                    "the emitted tokens")
    if not errs:
        if doc["fused"]["syncs_per_token"] \
                > doc["perstep"]["syncs_per_token"]:
            errs.append(
                "fused syncs_per_token "
                f"({doc['fused']['syncs_per_token']:.3f}) did not drop "
                f"below per-step ({doc['perstep']['syncs_per_token']:.3f})")
        min_speedup = float(os.environ.get("PERF_SMOKE_MIN_SPEEDUP", "1.0"))
        speedup = doc.get("speedup_decode")
        if not _finite_pos(speedup):
            errs.append(f"speedup_decode: non-finite {speedup!r}")
        elif speedup < min_speedup:
            errs.append(f"speedup_decode {speedup:.3f} "
                        f"< required {min_speedup}")
    return errs


REQUIRED_PREFIX_ON_KEYS = ("tokens", "steps", "prefill_tokens",
                           "tok_per_s", "ttft_steps_mean", "hit_rate",
                           "prefill_tokens_cached", "cow_copies",
                           "shared_pages", "bytes_deduped")
REQUIRED_PREFIX_OFF_KEYS = ("tokens", "steps", "prefill_tokens",
                            "tok_per_s", "ttft_steps_mean")


def check_prefix(doc: dict) -> list:
    errs = []
    if doc.get("schema") != "swallow.bench.prefix/v1":
        errs.append(f"bad schema: {doc.get('schema')!r}")
    for mode, keys in (("on", REQUIRED_PREFIX_ON_KEYS),
                       ("off", REQUIRED_PREFIX_OFF_KEYS)):
        blk = doc.get(mode)
        if not isinstance(blk, dict):
            errs.append(f"missing {mode} block")
            continue
        for key in keys:
            if not _finite_pos(blk.get(key)):
                errs.append(f"{mode}.{key}: non-finite {blk.get(key)!r}")
    if doc.get("tokens_match") is not True:
        errs.append("tokens_match is not true: prefix sharing changed "
                    "the emitted tokens")
    if not errs:
        if doc["on"]["hit_rate"] <= 0.0:
            errs.append("on.hit_rate is 0: the shared-prefix trace "
                        "never hit the cache")
        min_red = float(os.environ.get("PERF_SMOKE_MIN_PREFIX_REDUCTION",
                                       "2.0"))
        red = doc.get("prefill_token_reduction")
        if not _finite_pos(red):
            errs.append(f"prefill_token_reduction: non-finite {red!r}")
        elif red < min_red:
            errs.append(f"prefill_token_reduction {red:.3f} "
                        f"< required {min_red}")
    return errs


REQUIRED_SPEC_ON_KEYS = ("tokens", "steps", "model_passes",
                         "dispatches_per_token", "accept_rate",
                         "spec_drafted", "spec_accepted", "spec_verifies",
                         "spec_k_mean", "tok_per_s", "wall_s", "scan_s",
                         "draft_verify_s", "host_s")
REQUIRED_SPEC_OFF_KEYS = ("tokens", "steps", "model_passes",
                          "dispatches_per_token", "tok_per_s", "wall_s",
                          "scan_s", "draft_verify_s", "host_s")


def check_spec(doc: dict) -> list:
    errs = []
    if doc.get("schema") != "swallow.bench.spec/v2":
        errs.append(f"bad schema: {doc.get('schema')!r}")
    for mode, keys in (("on", REQUIRED_SPEC_ON_KEYS),
                       ("off", REQUIRED_SPEC_OFF_KEYS)):
        blk = doc.get(mode)
        if not isinstance(blk, dict):
            errs.append(f"missing {mode} block")
            continue
        for key in keys:
            if not _finite_pos(blk.get(key)):
                errs.append(f"{mode}.{key}: non-finite {blk.get(key)!r}")
    if doc.get("tokens_match") is not True:
        errs.append("tokens_match is not true: speculative decoding "
                    "changed the emitted tokens")
    if not errs:
        if doc["on"]["accept_rate"] <= 0.0:
            errs.append("on.accept_rate is 0: the repetitive trace never "
                        "accepted a draft")
        max_dpt = float(os.environ.get("PERF_SMOKE_MAX_SPEC_DISPATCHES",
                                       "0.7"))
        dpt = doc["on"]["dispatches_per_token"]
        if dpt >= max_dpt:
            errs.append(f"on.dispatches_per_token {dpt:.3f} "
                        f">= allowed {max_dpt}")
        min_red = float(os.environ.get("PERF_SMOKE_MIN_SPEC_REDUCTION",
                                       "1.4"))
        red = doc.get("dispatch_reduction")
        if not _finite_pos(red):
            errs.append(f"dispatch_reduction: non-finite {red!r}")
        elif red < min_red:
            errs.append(f"dispatch_reduction {red:.3f} "
                        f"< required {min_red}")
        # the wall-clock verdict: fewer dispatches must actually buy
        # wall time, or speculation is a pessimization on this host
        min_speedup = float(os.environ.get("PERF_SMOKE_SPEC_SPEEDUP_MIN",
                                           "1.0"))
        speedup = doc.get("spec_speedup")
        if not _finite_pos(speedup):
            errs.append(f"spec_speedup: non-finite {speedup!r}")
        elif speedup < min_speedup:
            errs.append(f"spec_speedup {speedup:.3f} "
                        f"< required {min_speedup}: speculation lost "
                        "wall-clock to the plain scan")
    return errs


REQUIRED_SLO_KEYS = ("tokens", "steps", "tok_per_s", "prefill_tokens",
                     "goodput_tokens")
REQUIRED_SLO_CLASS_KEYS = ("requests", "ttft_steps_p50", "ttft_steps_p95",
                           "ttft_steps_p99", "slo_met_frac",
                           "goodput_tokens", "tokens")


def check_slo(doc: dict) -> list:
    errs = []
    if doc.get("schema") != "swallow.bench.slo/v1":
        errs.append(f"bad schema: {doc.get('schema')!r}")
    for mode in ("chunked", "monolithic"):
        blk = doc.get(mode)
        if not isinstance(blk, dict):
            errs.append(f"missing {mode} block")
            continue
        for key in REQUIRED_SLO_KEYS:
            if not _finite_pos(blk.get(key)):
                errs.append(f"{mode}.{key}: non-finite {blk.get(key)!r}")
        classes = blk.get("slo")
        if not isinstance(classes, dict) or not classes:
            errs.append(f"{mode}.slo: missing per-class digest")
            continue
        if "interactive" not in classes:
            errs.append(f"{mode}.slo: no interactive class (the gated "
                        "ratio needs one)")
        for cls, digest in classes.items():
            for key in REQUIRED_SLO_CLASS_KEYS:
                if not _finite_pos(digest.get(key)):
                    errs.append(f"{mode}.slo.{cls}.{key}: non-finite "
                                f"{digest.get(key)!r}")
    if doc.get("tokens_match") is not True:
        errs.append("tokens_match is not true: chunked prefill changed "
                    "the emitted tokens")
    if not errs:
        max_ratio = float(os.environ.get("PERF_SMOKE_MAX_P99_TTFT_RATIO",
                                         "1.0"))
        ratio = doc.get("p99_ttft_ratio")
        if not _finite_pos(ratio):
            errs.append(f"p99_ttft_ratio: non-finite {ratio!r}")
        elif ratio > max_ratio:
            errs.append(f"p99_ttft_ratio {ratio:.3f} > allowed "
                        f"{max_ratio}: chunked prefill made the "
                        "interactive p99 TTFT worse")
        min_good = float(os.environ.get("PERF_SMOKE_MIN_GOODPUT_RATIO",
                                        "1.0"))
        good = doc.get("goodput_ratio")
        if not _finite_pos(good):
            errs.append(f"goodput_ratio: non-finite {good!r}")
        elif good < min_good:
            errs.append(f"goodput_ratio {good:.3f} < required "
                        f"{min_good}: chunking bought latency with "
                        "thrown-away throughput")
    return errs


REQUIRED_CHAOS_KEYS = ("tokens", "steps", "tok_per_s",
                       "requests_finished", "goodput_tokens")
REQUIRED_CHAOS_FAULT_KEYS = ("node_failures", "node_joins",
                             "pages_quarantined", "requests_recovered",
                             "requests_shed", "tokens_recomputed",
                             "transient_rejections", "quarantined_served",
                             "recovery_steps_p50", "recovery_steps_p99")


def check_chaos(doc: dict) -> list:
    errs = []
    if doc.get("schema") != "swallow.bench.chaos/v1":
        errs.append(f"bad schema: {doc.get('schema')!r}")
    for mode in ("fault_free", "chaos"):
        blk = doc.get(mode)
        if not isinstance(blk, dict):
            errs.append(f"missing {mode} block")
            continue
        for key in REQUIRED_CHAOS_KEYS:
            if not _finite_pos(blk.get(key)):
                errs.append(f"{mode}.{key}: non-finite {blk.get(key)!r}")
    chaos = doc.get("chaos")
    if isinstance(chaos, dict):
        for key in REQUIRED_CHAOS_FAULT_KEYS:
            if not _finite_pos(chaos.get(key)):
                errs.append(f"chaos.{key}: non-finite {chaos.get(key)!r}")
    if doc.get("tokens_match") is not True:
        errs.append("tokens_match is not true: fault recovery changed "
                    "a survivor's emitted tokens")
    if not errs:
        if doc.get("planned_failures", 0) < 2:
            errs.append(f"planned_failures "
                        f"{doc.get('planned_failures')!r} < 2: the "
                        "chaos schedule must inject >= 2 node failures")
        if chaos["node_failures"] < 2:
            errs.append(f"chaos.node_failures {chaos['node_failures']} "
                        "< 2: the watchdog missed injected failures")
        if chaos["quarantined_served"] != 0:
            errs.append(f"chaos.quarantined_served "
                        f"{chaos['quarantined_served']} != 0: a dispatch "
                        "read a quarantined page")
        min_good = float(os.environ.get("PERF_SMOKE_MIN_GOODPUT_RETAINED",
                                        "0.25"))
        good = doc.get("goodput_retained")
        if not _finite_pos(good):
            errs.append(f"goodput_retained: non-finite {good!r}")
        elif good < min_good:
            errs.append(f"goodput_retained {good:.3f} < required "
                        f"{min_good}: recovery did not degrade "
                        "gracefully")
    return errs


REQUIRED_TP_KEYS = ("predicted_s", "measured_s", "predicted_comms_s",
                    "comms_bytes", "measured_remote_frac", "steps",
                    "cow_copies", "preemptions")


def check_tp(doc: dict) -> list:
    errs = []
    if doc.get("schema") != "swallow.bench.tp/v1":
        errs.append(f"bad schema: {doc.get('schema')!r}")
    layouts = doc.get("layouts")
    if not isinstance(layouts, list) or len(layouts) < 2:
        errs.append("layouts: need the 1x1 baseline plus at least one "
                    "striped mesh")
        return errs
    for blk in layouts:
        tag = blk.get("layout", "?")
        for key in REQUIRED_TP_KEYS:
            if not _finite_pos(blk.get(key)):
                errs.append(f"{tag}.{key}: non-finite {blk.get(key)!r}")
        if blk.get("tokens_match") is not True:
            errs.append(f"{tag}: tokens_match is not true — sharding the "
                        "page pools changed the emitted tokens")
    if doc.get("tokens_match") is not True:
        errs.append("tokens_match is not true: some layout diverged from "
                    "the 1x1 baseline")
    if not any(blk.get("model", 1) > 1 for blk in layouts):
        errs.append("no striped layout (model > 1) in the sweep")
    if not errs:
        # the §V stripe model: measured remote page fraction vs the
        # predicted (n-1)/n, gated as a ratio around 1.0
        max_err = float(os.environ.get("PERF_SMOKE_MAX_TP_MODEL_ERROR",
                                       "0.25"))
        for blk in layouts:
            if blk.get("model", 1) <= 1:
                continue
            tag = blk["layout"]
            ratio = blk.get("remote_frac_ratio")
            if not _finite_pos(ratio):
                errs.append(f"{tag}.remote_frac_ratio: non-finite "
                            f"{ratio!r}")
            elif abs(ratio - 1.0) > max_err:
                errs.append(f"{tag}.remote_frac_ratio {ratio:.3f} "
                            f"deviates from the (n-1)/n stripe model by "
                            f"more than {max_err}")
            if blk.get("predicted_comms_s", 0.0) <= 0.0:
                errs.append(f"{tag}.predicted_comms_s is 0: the striped "
                            "run priced no interconnect traffic")
            if blk.get("comms_bytes", 0.0) <= 0.0:
                errs.append(f"{tag}.comms_bytes is 0: the striped run "
                            "priced no wire bytes")
    return errs


REQUIRED_OBS_KEYS = ("tokens", "steps", "tok_per_s", "wall_s")


def check_obs(doc: dict) -> list:
    from repro.serving.telemetry import validate_chrome_trace

    errs = []
    if doc.get("schema") != "swallow.bench.obs/v1":
        errs.append(f"bad schema: {doc.get('schema')!r}")
    for mode in ("off", "on"):
        blk = doc.get(mode)
        if not isinstance(blk, dict):
            errs.append(f"missing {mode} block")
            continue
        for key in REQUIRED_OBS_KEYS:
            if not _finite_pos(blk.get(key)):
                errs.append(f"{mode}.{key}: non-finite {blk.get(key)!r}")
    if doc.get("tokens_match") is not True:
        errs.append("tokens_match is not true: arming the flight "
                    "recorder changed the emitted tokens")
    events = doc.get("trace_events")
    if not isinstance(events, list) or not events:
        errs.append("trace_events: missing or empty")
    else:
        for e in validate_chrome_trace({"traceEvents": events}):
            errs.append(f"trace_events: {e}")
        dispatch = [e for e in events
                    if e.get("ph") == "X" and e.get("cat") == "dispatch"]
        if not dispatch:
            errs.append("trace_events: no dispatch spans in the excerpt")
        elif not any({"predicted_s", "predicted_j", "measured_s"}
                     <= set(e.get("args", {})) for e in dispatch):
            errs.append("trace_events: no dispatch span carries the "
                        "predicted_s/predicted_j/measured_s attribution "
                        "triple")
    report = doc.get("model_error")
    if not isinstance(report, dict) or not report:
        errs.append("model_error: missing or empty rollup")
    else:
        for phase, r in report.items():
            for key in ("count", "predicted_s", "measured_s",
                        "predicted_j"):
                if not _finite_pos(r.get(key)):
                    errs.append(f"model_error.{phase}.{key}: non-finite "
                                f"{r.get(key)!r}")
    if not errs:
        if doc["on"].get("spans_recorded", 0) <= 0:
            errs.append("on.spans_recorded is 0: the traced run "
                        "recorded nothing")
        max_over = float(os.environ.get("PERF_SMOKE_MAX_OBS_OVERHEAD",
                                        "1.05"))
        over = doc.get("overhead_ratio")
        if not _finite_pos(over):
            errs.append(f"overhead_ratio: non-finite {over!r}")
        elif over > max_over:
            errs.append(f"overhead_ratio {over:.3f} > allowed "
                        f"{max_over}: the flight recorder taxes "
                        "serving too much to stay armed")
    return errs


def main() -> None:
    paths = sys.argv[1:] or ["BENCH_micro.json", "BENCH_serve.json",
                             "BENCH_prefix.json", "BENCH_spec.json",
                             "BENCH_slo.json", "BENCH_chaos.json",
                             "BENCH_obs.json", "BENCH_tp.json"]
    failures = []
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            failures.append(f"{path}: unreadable ({e})")
            continue
        schema = doc.get("schema", "")
        if "micro" in schema or "micro" in os.path.basename(path):
            errs = check_micro(doc)
        elif "prefix" in schema or "prefix" in os.path.basename(path):
            errs = check_prefix(doc)
        elif "spec" in schema or "spec" in os.path.basename(path):
            errs = check_spec(doc)
        elif "slo" in schema or "slo" in os.path.basename(path):
            errs = check_slo(doc)
        elif "chaos" in schema or "chaos" in os.path.basename(path):
            errs = check_chaos(doc)
        elif "obs" in schema or "obs" in os.path.basename(path):
            errs = check_obs(doc)
        elif "tp" in schema \
                or os.path.basename(path).startswith("BENCH_tp"):
            errs = check_tp(doc)
        else:
            errs = check_serve(doc)
        for e in errs:
            failures.append(f"{path}: {e}")
        if not errs:
            print(f"[bench] {path}: ok ({schema})")
    if failures:
        print(f"\n{len(failures)} bench check(s) FAILED:", file=sys.stderr)
        for f in failures:
            print(" -", f, file=sys.stderr)
        sys.exit(1)
    print("all bench checks passed")


if __name__ == "__main__":
    main()
