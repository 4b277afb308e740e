"""A whole benchmark run rehearsed on the CPU at a tiny size.

Each traffic kind goes through the harness loop for about a second; the
end-to-end arithmetic is recomputed from the loop's records; the traced
run reports its counters and no device metric (the CPU has no device
trace); the chip-only entry point refuses the CPU and a directory with
no program; the control (the reference at float8) fails the check that
the program passes; and a token altered where the engine produces it,
or a decode window that returns the KV cache unchanged, turns
``correct`` false.
"""
import json
import math
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from benchkit import cell, harness  # noqa: E402

SPEC = cell.load_json(ROOT / "BENCHMARK.json")
CELL = SPEC["workloads"][0]["name"]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "check"}


def rehearse(mix, trace=False, seed=2 ** 31 + 99, seconds=1.0, **kw):
    """One run of ``mix`` on the tiny configuration; returns the result
    and the harness's own outcome."""
    seen = {}
    run = harness.Loop.run

    def keep(self, *a, **k):
        seen["out"] = run(self, *a, **k)
        return seen["out"]
    harness.Loop.run = keep
    try:
        res = cell.run(SPEC, CELL, seed, seconds, trace,
                       t_start=time.perf_counter(), platform="cpu",
                       compile_cache=False,
                       config_file=DATA / "tiny-qwen3.json",
                       mix=cell.load_json(DATA / f"{mix}.json"),
                       log=lambda m: None, **kw)
    finally:
        harness.Loop.run = run
    return res, seen["out"]


@pytest.mark.parametrize("mix", ["open", "closed", "prefix"])
def test_each_traffic_kind_runs_and_adds_up(mix):
    res, out = rehearse(mix)
    assert set(res) == KEYS
    assert list(res)[-1] == "check"
    json.dumps(res, allow_nan=False)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in SPEC["end_to_end"]}
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert out.t0 <= out.opened < out.t1 <= out.closed
    assert m["output_tok_per_s"] == out.window_tokens / (
        out.closed - out.opened) > 0
    assert m["setup_s"] > 0
    due = [r for r in out.records
           if out.t0 <= r.due < out.t1]
    ttft = sorted(r.first - r.due for r in due)
    assert m["ttft_p90_s"] == ttft[math.ceil(0.9 * len(ttft)) - 1]
    assert 0 < m["tpot_p90_s"] < 1
    assert out.compiles_in_window == 0
    if mix == "prefix":
        assert out.counters["prefill_tokens_cached"] > 0


def test_traced_run_reports_counters_and_no_device_metric_on_cpu():
    res, out = rehearse("open", trace=True)
    assert res["correct"]
    names = set(res["metrics"])
    assert {"batch_occupancy_pct", "decode_step_ms",
            "arrival_late_p99_ms", "prefix_cached_pct"} <= names
    device = {m["name"] for m in SPEC["per_layer"]
              if m["source"] == "device_trace"}
    assert not names & device
    assert "busy_s" not in res["device"] and "breakdown" not in res
    c = out.counters
    assert res["metrics"]["batch_occupancy_pct"]["value"] == pytest.approx(
        100 * c["decode_tokens"] / (c["decode_steps"] * 4))
    assert out.work is not None and out.work.decode_steps > 0


def test_control_fails_the_check_the_program_passes():
    limit = cell.load_json(DATA / "tiny-qwen3.json")["check"][
        "logit_gap_limit"]
    for seed in (1, 2, 3):
        res, _ = rehearse("short", seed=seed, control=True)
        assert res["check"]["logit_gap"]["value"] <= limit
        assert res["control"]["logit_gap"] > limit


def test_a_token_altered_where_produced_is_caught(monkeypatch):
    from repro.serving import engine as engine_mod
    steps = engine_mod._jitted_steps

    def broken(cfg, mesh=None):
        d = dict(steps(cfg, mesh))
        scan = d["scan"]

        def altered(*a, **k):
            toks, *rest = scan(*a, **k)
            B = a[1].shape[0]
            t = toks.reshape(B, -1)
            t = t.at[:, 0].set((t[:, 0] + 1) % cfg.vocab_size)
            return (t.reshape(toks.shape), *rest)
        d["scan"] = altered
        return d
    monkeypatch.setattr(engine_mod, "_jitted_steps", broken)
    res, _ = rehearse("open")
    assert not res["correct"]
    assert res["check"]["logit_gap"]["value"] > \
        res["check"]["logit_gap"]["limit"]


def test_entry_point_refuses_the_cpu(capsys):
    entry = cell.load_module(BENCH / "run.py")
    rc = entry.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                     "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_entry_point_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELL,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


def test_a_decode_window_that_leaves_the_cache_unchanged_is_caught(
        monkeypatch):
    import jax
    from repro import steps as steps_mod
    from repro.serving import engine as engine_mod
    steps = engine_mod._jitted_steps

    def broken(cfg, mesh=None):
        d = dict(steps(cfg, mesh))
        scan = jax.jit(steps_mod.make_paged_serve_scan(cfg),
                       static_argnames=("k",))        # no donation

        def unchanged(params, tokens, pools, *a, **k):
            toks, last, pos, _ = scan(params, tokens, pools, *a, **k)
            return toks, last, pos, pools
        d["scan"] = unchanged
        return d
    monkeypatch.setattr(engine_mod, "_jitted_steps", broken)
    res, _ = rehearse("open")
    assert not res["correct"]
