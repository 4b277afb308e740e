"""The reduction from a profiler trace to device times, on a record
worked by hand and on a small trace recorded on a TPU v5e."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

from benchkit import trace  # noqa: E402

HAND = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit_serve_scan(3)", 100, 400],
                    ["jit_chunk_prefill(5)", 500, 600]],
        "ops": [["fusion.1", 100, 250], ["fusion.2", 250, 400],
                ["all-reduce.7", 500, 520], ["convolution.3", 520, 600],
                ["fusion.9", 1100, 1200]]}},
    "host": [["bench.traced", 0, 1000], ["bench.step", 50, 650],
             ["bench.wait", 650, 1000]],
}


def test_reduce_by_hand():
    r = trace.reduce(HAND)
    assert r["window_s"] == pytest.approx(1000e-9)
    # ops inside the interval: 150 + 150 + 20 + 80 ns; the last op lies
    # past the traced interval and is clipped away
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["program_s"] == pytest.approx({"serve_scan": 300e-9,
                                            "chunk_prefill": 100e-9})
    assert r["device_ops"][0][0] in ("serve_scan/fusion.1",
                                     "serve_scan/fusion.2")
    assert ["chunk_prefill/all-reduce.7", pytest.approx(20e-9)] in \
        r["device_ops"]
    # idle gaps, each named after the span open at its midpoint: [0, 100)
    # at 50 and [400, 500) at 450 in bench.step, [600, 1000) at 800 in
    # bench.wait
    assert r["idle_gaps"] == [["bench.wait", pytest.approx(400e-9)],
                              ["bench.step", pytest.approx(100e-9)],
                              ["bench.step", pytest.approx(100e-9)]]


def test_collective_time_per_program_averaged_over_devices():
    """Self time of the operations that move data between chips, per
    program, averaged over the devices; the keys the reduction already
    returned are unchanged."""
    assert trace.reduce(HAND)["collective_s"] == pytest.approx(
        {"chunk_prefill": 20e-9})
    dev = {"modules": [["jit_serve_scan(3)", 100, 500]],
           "ops": [["while.1", 100, 500], ["fusion.1", 100, 300],
                   ["all-reduce-start.2", 300, 310],
                   ["all-reduce-done.2", 310, 350],
                   ["collective-permute.4", 350, 400],
                   ["fusion.3", 400, 500]]}
    other = {"modules": dev["modules"],
             "ops": [["fusion.1", 100, 480], ["all-gather.5", 480, 500]]}
    rec = {"devices": {"/device:TPU:0": dev, "/device:TPU:1": other},
           "host": [["bench.traced", 0, 1000]]}
    r = trace.reduce(rec)
    assert r["collective_s"] == pytest.approx({"serve_scan": (
        10e-9 + 40e-9 + 50e-9 + 20e-9) / 2})
    assert r["program_s"] == pytest.approx({"serve_scan": 400e-9})
    assert set(r) == {"window_s", "busy_s", "idle_share", "program_s",
                      "collective_s", "device_ops", "idle_gaps"}


def test_program_names():
    assert trace.program_name("jit_serve_scan(17)") == "serve_scan"
    assert trace.program_name("copy_page") == "copy_page"


def test_no_traced_span_is_an_error():
    with pytest.raises(ValueError, match="bench.traced"):
        trace.reduce({"devices": HAND["devices"], "host": []})


def test_recorded_v5e_trace_against_a_timeline():
    """250 ms recorded on a TPU v5e: busy time and program times from the
    reduction agree with a plain 1-microsecond timeline of the same
    events."""
    import numpy as np
    rec = json.loads((DATA / "v5e_trace.json").read_text())
    r = trace.reduce(rec)
    t0, t1 = trace.traced_interval(rec)
    dev = rec["devices"]["/device:TPU:0"]
    on = np.zeros((t1 - t0) // 1000, bool)
    for _, s, e in dev["ops"]:
        on[max(s - t0, 0) // 1000:max(min(e, t1) - t0, 0) // 1000] = True
    assert r["busy_s"] == pytest.approx(on.sum() * 1e-6, rel=1e-3)
    assert r["window_s"] == pytest.approx(0.25)
    assert r["idle_share"] == pytest.approx(1 - on.mean(), abs=1e-3)
    scan = sum(min(e, t1) - max(s, t0) for n, s, e in dev["modules"]
               if n.startswith("jit_serve_scan") and e > t0 and s < t1)
    assert r["program_s"]["serve_scan"] == pytest.approx(scan / 1e9)
    # operations' self times never exceed the busy time
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] + 1e-9
    assert {g[0] for g in r["idle_gaps"]} <= {"bench.step", "bench.wait",
                                               "bench.submit", "bench.loop"}
