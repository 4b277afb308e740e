"""Telemetry-plane tests: the streaming percentile digest, the unified
metrics registry, the step-clock flight recorder, and the engine-level
guarantees the observability PR rests on — tracing never changes the
emitted tokens, every opened span closes, the Chrome export validates,
``reset_metrics`` really zeroes the registry, and invariant violations
dump the flight recorder before raising (docs/OBSERVABILITY.md,
docs/FAULT_TOLERANCE.md).
"""
import glob
import json
import os
import time

import numpy as np
import pytest

from conftest import dense_oracle, get_tiny_model, make_engine, \
    seeded_prompts

from repro.serving.telemetry import (HistogramDigest, MetricsRegistry,
                                     StepTracer, counter_attr,
                                     format_model_error,
                                     rollup_dispatch_events,
                                     validate_chrome_trace)


# --- HistogramDigest -------------------------------------------------------
def test_digest_exact_regime_matches_numpy_percentile():
    rng = np.random.default_rng(0)
    vals = rng.exponential(5.0, size=500)
    d = HistogramDigest.of(vals)
    assert d.exact
    for q in (0, 25, 50, 90, 95, 99, 100):
        assert d.percentile(q) == float(np.percentile(vals, q))
    assert d.count == 500
    assert d.mean == pytest.approx(float(np.mean(vals)))
    assert d.vmin == float(np.min(vals))
    assert d.vmax == float(np.max(vals))


def test_digest_spill_stays_within_relative_error():
    rng = np.random.default_rng(1)
    vals = rng.lognormal(0.0, 2.0, size=20_000)
    d = HistogramDigest.of(vals, exact_max=1024, rel_err=0.01)
    assert not d.exact          # spilled into log buckets
    for q in (50, 95, 99):
        true = float(np.percentile(vals, q))
        # DDSketch guarantee: the representative is within rel_err of
        # the true sample; nearest-rank vs interpolation adds at most
        # one bucket of slack on a 20k sample
        assert d.percentile(q) == pytest.approx(true, rel=0.03)
    assert d.count == 20_000


def test_digest_empty_and_reset():
    d = HistogramDigest()
    assert d.percentile(99) == 0.0 and d.mean == 0.0 and d.count == 0
    d.observe_many([1.0, 2.0, 3.0])
    assert d.count == 3
    d.reset()
    assert d.count == 0 and d.percentile(50) == 0.0 and d.exact


def test_digest_handles_nonpositive_values_after_spill():
    d = HistogramDigest(exact_max=4)
    d.observe_many([0.0, 0.0, 0.0, 5.0, 5.0, 5.0, 5.0])  # forces spill
    assert not d.exact
    assert d.percentile(10) == 0.0          # underflow bucket
    assert d.percentile(99) == pytest.approx(5.0, rel=0.03)


def test_digest_snapshot_schema():
    snap = HistogramDigest.of([1.0, 2.0, 4.0]).snapshot()
    assert set(snap) == {"count", "mean", "min", "max",
                         "p50", "p95", "p99"}
    assert snap["count"] == 3 and snap["min"] == 1.0 and snap["max"] == 4.0


# --- MetricsRegistry -------------------------------------------------------
def test_registry_counters_gauges_hists_snapshot_reset():
    r = MetricsRegistry()
    r.inc("steps")
    r.inc("steps", 4)
    r.set_counter("tokens", 12)
    r.set_gauge("load", 0.5)
    r.register_gauge("pool", lambda: 7)
    r.observe("lat", 3.0)
    r.observe("lat", 9.0)
    snap = r.snapshot()
    assert snap["counters"] == {"steps": 5, "tokens": 12}
    assert snap["gauges"] == {"load": 0.5, "pool": 7}
    assert snap["histograms"]["lat"]["count"] == 2
    assert r.percentile("lat", 50) == pytest.approx(6.0)
    assert r.percentile("missing", 99, default=-1.0) == -1.0

    r.reset()
    snap = r.snapshot()
    # keys survive a reset (dashboards keep their columns); stored
    # values zero; gauge CALLABLES are wiring, not state — untouched
    assert snap["counters"] == {"steps": 0, "tokens": 0}
    assert snap["gauges"] == {"load": 0.0, "pool": 7}
    assert snap["histograms"]["lat"]["count"] == 0


def test_counter_attr_descriptor_reads_and_writes_registry():
    class Thing:
        hits = counter_attr()
        renamed = counter_attr("external_name")

        def __init__(self):
            self.registry = MetricsRegistry()
            self.hits = 0
            self.renamed = 0

    t = Thing()
    t.hits += 3
    t.renamed = 9
    assert t.hits == 3 and t.renamed == 9
    assert t.registry.counters == {"hits": 3, "external_name": 9}
    t.registry.reset()
    assert t.hits == 0 and t.renamed == 0


# --- StepTracer ------------------------------------------------------------
def test_tracer_ring_evicts_oldest_and_counts_drops():
    tr = StepTracer(capacity=8)
    for i in range(20):
        tr.record_dispatch("scan", i, float(i), i + 0.5)
    assert tr.recorded == 20 and tr.dropped == 12 and len(tr.spans) == 8
    # FIFO eviction: the ring holds exactly the newest 8, in order
    assert [s.start_step for s in tr.spans] == list(range(12, 20))


def test_tracer_lifecycle_spans_close_and_never_overlap():
    tr = StepTracer()
    for rid in ("a", "b"):
        tr.request_event(rid, "queued", 0, tenant="t1")
    tr.request_event("a", "prefilling", 2, tenant="t1")
    tr.request_event("a", "running", 3, tenant="t1")
    tr.request_event("b", "prefilling", 4, tenant="t1")
    tr.request_event("a", "finished", 6, tenant="t1")
    assert set(tr.open_spans) == {"b"}      # b still mid-flight
    tr.finalize(7)
    assert not tr.open_spans                # every opened span closed
    lanes = {}
    for s in tr.spans:
        lanes.setdefault((s.group, s.track), []).append(s)
    for spans in lanes.values():
        spans.sort(key=lambda s: s.t0)
        for prev, cur in zip(spans, spans[1:]):
            assert cur.t0 >= prev.t1        # no overlap on a lane
    states = [s.name for s in lanes[("tenant:t1", "a")]]
    assert states == ["queued", "prefilling", "running", "finished"]


def test_tracer_chrome_export_is_schema_valid():
    tr = StepTracer()
    tr.request_event("r0", "queued", 0)
    t = time.perf_counter()
    tr.record_dispatch("prefill", 1, t, t + 2e-3, predicted_s=1e-3,
                       predicted_j=0.5)
    tr.request_event("r0", "finished", 2)
    tr.counter_sample(2, [3, 1])
    doc = tr.chrome_trace()
    assert validate_chrome_trace(doc) == []
    # round-trips through JSON (what write_chrome ships to Perfetto)
    assert validate_chrome_trace(json.loads(json.dumps(doc))) == []
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert phs == {"X", "M", "C"}


def test_validate_chrome_trace_flags_malformed_docs():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [{"ph": "Q"}]}) != []
    assert validate_chrome_trace(
        {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1,
                          "ts": 0.0, "dur": -1.0, "args": {}}]}) != []


def test_rollup_and_format_model_error():
    tr = StepTracer()
    for step in range(3):
        tr.record_dispatch("scan", step, float(step), step + 0.25,
                           predicted_s=0.5, predicted_j=2.0)
    report = tr.model_error_report()
    assert set(report) == {"scan"}
    row = report["scan"]
    assert row["count"] == 3
    assert row["predicted_s"] == pytest.approx(1.5)
    assert row["predicted_j"] == pytest.approx(6.0)
    assert row["measured_s"] > 0.0
    assert row["err_ratio"] == pytest.approx(
        row["measured_s"] / row["predicted_s"])
    table = format_model_error(report)
    assert "scan" in table and "meas/pred" in table
    # chrome events feed the same rollup (the offline report tool path)
    via_chrome = rollup_dispatch_events(tr.chrome_trace()["traceEvents"])
    assert via_chrome["scan"]["count"] == 3


def test_flight_dump_contents(tmp_path):
    tr = StepTracer(capacity=4, dump_dir=str(tmp_path))
    for i in range(6):
        tr.record_dispatch("scan", i, float(i), i + 0.5)
    tr.request_event("r0", "queued", 6)
    reg = MetricsRegistry()
    reg.inc("steps", 6)
    path = tr.flight_dump("test-reason", registry=reg)
    doc = json.load(open(path))
    assert doc["reason"] == "test-reason"
    assert len(doc["spans"]) == 4 and doc["spans_dropped"] == 2
    assert [s["name"] for s in doc["open_spans"]] == ["queued"]
    assert doc["metrics"]["counters"]["steps"] == 6


# --- engine integration ----------------------------------------------------
GEN = 6


def _run_traced(**kw):
    cfg, params = get_tiny_model()
    eng = make_engine(cfg, params, trace=True, **kw)
    prompts = seeded_prompts(cfg, 4, 8)
    for i, p in enumerate(prompts):
        eng.submit(p, GEN, rid=f"r{i}")
    fin = eng.run()
    return eng, {r.rid: list(r.tokens) for r in fin}


def test_tracing_does_not_change_tokens():
    cfg, params = get_tiny_model()
    prompts = seeded_prompts(cfg, 4, 8)
    eng_off = make_engine(cfg, params)
    for i, p in enumerate(prompts):
        eng_off.submit(p, GEN, rid=f"r{i}")
    off = {r.rid: list(r.tokens) for r in eng_off.run()}
    eng_on, on = _run_traced()
    assert on == off
    assert on == dense_oracle(cfg, params, prompts, GEN, 32)


def test_engine_trace_reconstructs_lifecycle_and_attribution():
    eng, _ = _run_traced()
    eng.tracer.finalize(eng.sched.step_idx)
    doc = eng.tracer.chrome_trace()
    assert validate_chrome_trace(doc) == []
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    states = {e["name"] for e in spans if e["cat"] in ("request",
                                                       "marker")}
    assert {"queued", "prefilling", "running", "finished"} <= states
    dispatch = [e for e in spans if e["cat"] == "dispatch"]
    assert {e["name"] for e in dispatch} >= {"prefill", "scan"}
    for e in dispatch:
        assert e["args"]["predicted_s"] > 0.0
        assert e["args"]["predicted_j"] > 0.0
        assert e["args"]["measured_s"] >= 0.0
    # per-node occupancy counter track rode along
    assert any(e.get("ph") == "C" for e in doc["traceEvents"])
    report = eng.tracer.model_error_report()
    assert {"prefill", "scan"} <= set(report)


def test_reset_metrics_zeroes_registry_digests_and_tracer():
    eng, _ = _run_traced()
    assert eng.steps_run > 0 and eng.tokens_emitted > 0
    assert eng.tracer.recorded > 0
    assert any(eng.registry.counters.values())
    eng.registry.observe("recovery_steps", 5.0)
    eng.reset_metrics()
    assert all(v == 0 for v in eng.registry.counters.values())
    assert eng.steps_run == 0 and eng.tokens_emitted == 0
    assert eng.tracer.recorded == 0 and not eng.tracer.spans
    assert not eng.tracer.open_spans
    # digests drained too: warmup traffic never pollutes chaos/SLO
    # percentiles (the PR-9 regression this test pins)
    assert eng.registry.hists["recovery_steps"].count == 0
    assert eng.metrics()["recovery_steps_p99"] == 0.0
    # live gauge callables keep reporting pool truth through a reset
    assert eng.registry.gauge("free_pages") > 0


def test_quarantine_invariant_dumps_flight_recorder(tmp_path):
    cfg, params = get_tiny_model()
    eng = make_engine(cfg, params, n_nodes=2, trace=True)
    eng.tracer.dump_dir = str(tmp_path)
    eng.submit(seeded_prompts(cfg, 1, 8)[0], GEN, rid="victim")
    eng.step()                                  # prefill: victim holds pages
    held = next(iter(eng.alloc.held["victim"]))
    eng.alloc.quarantined.add(held)             # corrupt: fake a stale page
    with pytest.raises(RuntimeError, match="quarantined"):
        eng._assert_no_quarantined()
    assert eng.quarantined_served == 1
    dumps = glob.glob(os.path.join(str(tmp_path), "flight-*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert doc["reason"] == "quarantined-served"
    assert doc["spans"]                          # history rode along
    assert doc["metrics"]["counters"]["quarantined_served"] == 1


def test_untraced_engine_pays_no_tracer_and_skips_dump():
    cfg, params = get_tiny_model()
    eng = make_engine(cfg, params)
    assert eng.tracer is None
    assert eng._flight_dump("whatever") is None  # no dump, no crash
    # the _span fast path returns the shared null context: predfn (the
    # cost-engine pricing lambda) must never run when tracing is off
    ctx = eng._span("scan", lambda: 1 / 0)
    with ctx:
        pass


def test_registry_snapshot_is_json_serializable():
    eng, _ = _run_traced()
    snap = eng.registry.snapshot()
    rt = json.loads(json.dumps(snap))
    assert rt["counters"]["steps_run"] == eng.steps_run
    assert "pages_in_use" in rt["gauges"]
