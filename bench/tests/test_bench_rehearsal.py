"""A whole benchmark run rehearsed on the CPU at a tiny size.

Each traffic kind goes through the harness loop for about a second; the
end-to-end arithmetic is recomputed from the loop's records; the traced
run reports its counters and no device metric (the CPU has no device
trace); the chip-only entry point refuses the CPU and a directory with
no program; the control (the reference at float8) fails the check that
the program passes; and a token altered where the engine produces it,
or a decode window that returns the KV cache unchanged, turns
``correct`` false.  A family the benchmark does not run goes through
the same run by its own module; a four-chip cell runs on four virtual
CPU devices with its pools striped over them, and turns ``correct``
false where the exchange between the chips is left out.
"""
import json
import math
import os
import shutil
import subprocess
import sys
import textwrap
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


def rehearse(mix, trace=False, seed=2 ** 31 + 99, seconds=1.0,
             config="tiny-qwen3", **kw):
    """One run of ``mix`` on a tiny configuration; returns the result
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
                       config_file=DATA / f"{config}.json",
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


def test_a_family_the_benchmark_does_not_run_goes_through_a_whole_run(
        monkeypatch):
    """``tinydense`` (a module beside the tests, on the family package's
    search path) is found by its configuration's ``model_type``, and a
    whole run serves it, counts its work with its own work count and
    checks it against the reference."""
    from benchkit import models
    monkeypatch.setattr(models, "__path__", [*models.__path__, str(DATA)])
    res, out = rehearse("prefix", trace=True, config="tiny-dense")
    assert res["correct"], res["check"]
    assert out.work is not None and out.work.decode_flops > 0
    assert out.counters["prefill_tokens_cached"] > 0


def test_chained_windows_are_warmed_on_striped_pools_only(monkeypatch):
    """On one chip the engine's own warm-up compiles every decode window
    as the window dispatches it, so the benchmark's chained warm-up,
    which only striped pools need, is not run there."""
    from benchkit import engine, traffic
    called = []
    monkeypatch.setattr(engine, "warm_chained_windows", called.append)
    cfg = cell.load_json(DATA / "tiny-qwen3.json")
    ref = cell.load_module(DATA / cfg["reference"])
    mcfg = engine.model_config(cfg)
    eng = engine.build(cfg, mcfg, engine.program_params(
        cfg, ref.make_weights(cfg, 5), mcfg), 64)
    sched = traffic.build(cell.load_json(DATA / "prefix.json"), 5, 1.0,
                          cfg["vocab_size"])
    engine.warm(eng, sched, cfg["vocab_size"])
    assert eng.mesh is None and called == []


# a four-chip cell of the tiny configuration and the tiny shared-prefix
# mix, whose prefixes mostly end inside a page, so that questions about
# them copy that page on the striped pools in the window
X4 = {"name": "tiny.prefix-x4", "config": "tiny-qwen3", "traffic": "prefix",
      "chips": 4, "why": "pools striped 1x4"}


def test_four_chip_cell_on_virtual_devices():
    """On four virtual CPU devices: the engine the harness builds for
    four chips stripes its pools over all four, rounds its pages up to a
    multiple of four, replicates the parameters, and serves the same
    greedy tokens as the one-chip engine (in float32, where no two
    logits tie to rounding as bf16's do); and a whole run of a four-chip
    cell with shared prefixes that end inside a page compiles nothing in
    its window, the page copies included, copies pages, and passes its
    check, the reference dealt out over the four chips."""
    code = textwrap.dedent(f"""
        import json, sys, time
        sys.path[:0] = [{str(BENCH)!r}, {str(ROOT / "src")!r}]
        import jax
        import numpy as np
        from benchkit import cell, engine, harness
        devs = jax.devices()
        assert len(devs) == 4
        data = cell.BENCH / "tests" / "data"
        cfg = dict(cell.load_json(data / "tiny-qwen3.json"),
                   torch_dtype="float32")
        ref = cell.load_module(data / cfg["reference"])
        mcfg = engine.model_config(cfg)
        served = {{}}
        for n in (1, 4):
            w = engine.replicated(ref.make_weights(cfg, 5), devs[:n])
            eng = engine.build(cfg, mcfg, engine.program_params(cfg, w, mcfg),
                               64, devs[:n])
            pools = jax.tree.leaves(eng.pools)
            assert eng.alloc.n_pages % n == 0
            assert all(len(p.sharding.device_set) == n for p in pools)
            if n == 4:
                assert eng.alloc.n_pages == 36       # 4 x 8 pages + 1, to 36
                assert all(p.addressable_shards[0].data.shape[-3]
                           == p.shape[-3] // 4 for p in pools)
                assert all(len(a.sharding.device_set) == 4
                           and a.sharding.is_fully_replicated
                           for a in jax.tree.leaves(eng.params))
            rng = np.random.default_rng(3)
            reqs = [eng.submit(rng.integers(0, cfg["vocab_size"], int(n_),
                                            dtype=np.int32), 12)
                    for n_ in (5, 17, 30, 44, 9, 50)]
            eng.run()
            served[n] = [list(r.tokens) for r in reqs]
        assert served[1] == served[4], served
        compiled = []
        count = harness.CompileCounter._on
        def named(self, event, duration, **kw):
            if self.armed and event == self.EVENT:
                compiled.append(kw.get("fun_name"))
            count(self, event, duration, **kw)
        harness.CompileCounter._on = named
        copies = []
        loop = harness.Loop.run
        def run(self, *a, **kw):
            out = loop(self, *a, **kw)
            copies.append(self.eng.cache.stats.cow_copies)
            return out
        harness.Loop.run = run
        spec = cell.load_json(cell.ROOT / "BENCHMARK.json")
        spec["workloads"].append({X4!r})
        res = cell.run(spec, {X4["name"]!r}, 2 ** 31 + 41, 2.0,
                       False, t_start=time.perf_counter(), platform="cpu",
                       compile_cache=False,
                       config_file=data / "tiny-qwen3.json",
                       mix=cell.load_json(data / "prefix.json"),
                       log=lambda m: None)
        assert res["correct"], res["check"]
        assert res["device"]["count"] == 4 and res["attempted"] > 0
        assert copies[0] > 0, copies
        assert compiled == [], compiled
    """)
    on_four_devices(code)


def test_four_chip_cell_without_the_exchange_between_chips_is_caught():
    """A four-chip cell's striped decode merges each chip's partial
    attention over the pages it owns across the chips; with that merge
    left out (each chip keeps its own partial), a whole run of the cell
    on four virtual CPU devices turns ``correct`` false."""
    code = textwrap.dedent(f"""
        import sys, time
        sys.path[:0] = [{str(BENCH)!r}, {str(ROOT / "src")!r}]
        import jax
        from benchkit import cell
        # the partials' merge: a max and two sums over the stripe axis
        jax.lax.pmax = lambda x, axes: x
        jax.lax.psum = lambda x, axes: x
        data = cell.BENCH / "tests" / "data"
        spec = cell.load_json(cell.ROOT / "BENCHMARK.json")
        spec["workloads"].append({X4!r})
        res = cell.run(spec, {X4["name"]!r}, 2 ** 31 + 43, 2.0, False,
                       t_start=time.perf_counter(), platform="cpu",
                       compile_cache=False,
                       config_file=data / "tiny-qwen3.json",
                       mix=cell.load_json(data / "prefix.json"),
                       log=lambda m: None)
        gap = res["check"]["logit_gap"]
        assert not res["correct"] and gap["value"] > gap["limit"], gap
    """)
    on_four_devices(code)


def on_four_devices(code: str) -> None:
    """Runs ``code`` in a process of its own on four virtual CPU
    devices; it has to end without an error."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code + "\nprint('OK')\n"],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    assert p.stdout.strip().endswith("OK")
