"""The requests' lifecycle stamps and the scope report, read by the
benchmark.

The scheduler stamps each request on ``perf_counter``, the harness's
clock, so a request's time to first token splits into how late it was
submitted, how long it waited for a slot, how long its prefill took and
how late its first token was seen; a program that stamps nothing gives
no parts (as a parent commit without the stamps).  The scope report
keeps a traced run's profile and compiled decode program and reads them
again."""
import json
import math
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_bench_rehearsal import CELL, DATA, SPEC, rehearse  # noqa: E402
from benchkit import cell, harness, scopes, stamps  # noqa: E402


def _due_with_first(out):
    return [r for r in out.records if out.t0 <= r.due < out.t1
            and not r.failed and r.first is not None]


@pytest.mark.parametrize("mix", ["open", "prefix"])
def test_time_to_first_token_adds_up_from_the_stamps(mix):
    _, out = rehearse(mix)
    due = _due_with_first(out)
    assert due
    for r in due:
        q = r.req
        assert r.due <= r.submitted <= q.submitted_at <= q.admitted_at \
            <= q.first_token_at <= r.first
        # the harness reads its clock just before it calls submit
        assert q.submitted_at - r.submitted < 0.05
    rows = stamps.parts(out)
    assert len(rows) == len(due)
    for row, r in zip(rows, due):
        assert row["ttft"] == r.first - r.due
        assert sum(row[p] for p in stamps.PARTS[:-1]) == pytest.approx(
            row["ttft"], abs=1e-6)


def test_stamp_parts_read_nothing_without_stamps():
    recs = [harness.Record(item=None, due=0.5, req=SimpleNamespace(tokens=[1]),
                           submitted=0.5, first=0.7)]
    out = SimpleNamespace(records=recs, t0=0.0, t1=1.0)
    assert stamps.parts(out) == [] and stamps.summary([]) == {}


def test_summary_is_nearest_rank_over_the_window():
    rows = [dict.fromkeys(stamps.PARTS, float(i)) for i in range(1, 11)]
    s = stamps.summary(rows)
    assert set(s) == set(stamps.PARTS)
    assert s["queue"] == {"p50": 5.0, "p90": 9.0, "mean": 5.5}
    assert s["ttft"]["p90"] == rows[math.ceil(0.9 * 10) - 1]["ttft"]


def test_scope_report_keeps_a_traced_run_and_reads_it_again(tmp_path):
    sys.path.insert(0, str(BENCH))
    import scope_report
    doc = scope_report.collect(
        SPEC, CELL, 2 ** 31 + 5, 1.0, tmp_path, platform="cpu",
        compile_cache=False, config_file=DATA / "tiny-qwen3.json",
        mix=cell.load_json(DATA / "open.json"), log=lambda m: None)
    assert doc["result"]["correct"]
    assert (tmp_path / "trace.xplane.pb").exists()
    texts = sorted(tmp_path.glob("serve_scan.k*.hlo"))
    assert len(texts) >= 2
    maps = [scopes.hlo_scopes(t.read_text()) for t in texts]
    assert {"qkv", "kv_append", "kv_gather", "attend", "mlp", "-"} \
        <= set(maps[-1].values())
    # the window sizes' programs number their instructions differently:
    # one name, two scopes
    names = {}
    for m in maps:
        for (name, _), sc in m.items():
            names.setdefault(name, set()).add(sc)
    assert any(len(v) > 1 for v in names.values())
    rep = scope_report.report(tmp_path)
    json.dumps(rep, allow_nan=False)
    # the CPU has no device plane: nothing to map, no decode share
    assert rep["mapped"] == 0 and rep["matched"] == {}
    assert rep["decode_kv_move_pct"] is None
    assert rep["engine_spans"] > 0
    c = doc["counters"]
    assert rep["host_pass_ms"] == 1e3 * c["host_pass_s"] / c["step_calls"]
    assert {"engine.pull_s", "engine.dispatch_s"} <= set(rep["engine_s"])
    n = rep["requests"]
    assert 0 < n["before_profiler"] < n["window"]
    assert set(rep["ttft"]["window"]) == set(stamps.PARTS)


def test_scope_report_reads_a_kept_directory_from_the_command_line(
        tmp_path, capsys):
    sys.path.insert(0, str(BENCH))
    import scope_report
    run = {"cell": CELL, "seed": 3, "result": {},
           "counters": {"step_calls": 4, "host_pass_s": 0.02,
                        "engine.pull_s": 0.5, "decode_steps": 9},
           "ttft": {}, "requests": {"window": 0, "before_profiler": 0}}
    (tmp_path / "run.json").write_text(json.dumps(run))
    t0 = time.perf_counter()
    assert scope_report.main(["--from", str(tmp_path)]) == 0
    assert time.perf_counter() - t0 < 30
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep["host_pass_ms"] == pytest.approx(5.0)
    assert rep["engine_s"] == {"engine.pull_s": 0.5}
    assert "scope_s" not in rep        # no profile kept


def test_untraced_scope_report_keeps_only_the_stamps(tmp_path):
    """Without the profiler nothing holds the loop, so every request of
    the window counts; no trace and no program text are kept."""
    sys.path.insert(0, str(BENCH))
    import scope_report
    doc = scope_report.collect(
        SPEC, CELL, 2 ** 31 + 6, 1.0, tmp_path, trace=False, platform="cpu",
        compile_cache=False, config_file=DATA / "tiny-qwen3.json",
        mix=cell.load_json(DATA / "prefix.json"), log=lambda m: None)
    assert doc["result"]["correct"]
    assert sorted(p.name for p in tmp_path.iterdir()) == ["run.json"]
    rep = scope_report.report(tmp_path)
    assert "scope_s" not in rep and "mapped" not in rep
    n = rep["requests"]
    assert n["before_profiler"] == n["window"] > 0
    assert rep["ttft"]["window"] == rep["ttft"]["before_profiler"]
