"""Engine phases and model scopes read from a trace: the reduction on a
record worked by hand, the scope of an HLO op_name, and the scopes read
from compiled HLO text."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchkit import scopes  # noqa: E402

HAND = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit_serve_scan(3)", 100, 400],
                    ["jit_chunk_prefill(5)", 500, 600]],
        "ops": [["while.1", 100, 400], ["copy.47", 100, 160],
                ["fusion.2", 160, 250], ["fusion.3", 250, 300],
                ["fusion.9", 500, 600]],
        "scopes": ["-", "-", "kv_gather", "attend", "mlp"]}},
    "host": [["bench.traced", 0, 1000], ["bench.step", 50, 650]],
    "engine": [["engine.plan", 20, 60], ["engine.dispatch", 60, 90],
               ["engine.pull", 90, 420], ["engine.complete", 420, 450],
               ["engine.chunk", 480, 520], ["engine.plan", 700, 720]],
}


def test_reduce_by_hand():
    r = scopes.reduce(HAND)
    # the while's self time is its 300 ns less its body's 200 ns
    assert r["scope_s"] == pytest.approx({
        "serve_scan/-": 160e-9, "serve_scan/kv_gather": 90e-9,
        "serve_scan/attend": 50e-9, "chunk_prefill/mlp": 100e-9})
    # idle: [0, 100) under plan, dispatch and the pull's first 10 ns;
    # [400, 500) under the pull's last 20, complete and chunk; [600,
    # 1000) under plan 700-720; the rest is outside any phase
    assert r["idle_by_phase"] == pytest.approx({
        "engine.plan": 60e-9, "engine.dispatch": 30e-9,
        "engine.pull": 30e-9, "engine.complete": 30e-9,
        "engine.chunk": 20e-9, "outside": 430e-9})
    assert sum(r["idle_by_phase"].values()) == pytest.approx(600e-9)


def test_recorded_v5e_trace_agrees_with_trace_reduce():
    """On the committed v5e record (no scopes, no engine spans) the
    scope times add up to the operations' self times and the idle time
    to the interval less ``trace.reduce``'s busy time."""
    import json

    from benchkit import trace
    rec = json.loads((Path(__file__).resolve().parent / "data"
                      / "v5e_trace.json").read_text())
    r, t = scopes.reduce(rec), trace.reduce(rec, top=10 ** 6)
    assert set(r["idle_by_phase"]) == {"outside"}
    assert r["idle_by_phase"]["outside"] == pytest.approx(
        t["window_s"] - t["busy_s"])
    assert {k.split("/")[1] for k in r["scope_s"]} == {"-"}
    assert sum(r["scope_s"].values()) == pytest.approx(
        sum(v for _, v in t["device_ops"]))


@pytest.mark.parametrize("op_name,scope", [
    ("jit(serve_scan)/serve_scan/while/body/kv_gather/gather", "kv_gather"),
    ("jit(serve_scan)/serve_scan/while/body/mlp/qkv/dot_general", "qkv"),
    ("jit(serve_scan)/serve_scan/while/body/dynamic_update_slice", "-"),
    ("", "-"), (None, "-"),
])
def test_scope_is_the_innermost_named(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_hlo_scopes_read_from_compiled_text():
    text = "\n".join([
        "ENTRY %main {",
        '  %fusion.5 = bf16[2]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(serve_scan)/serve_scan/kv_append/scatter"'
        ' source_file="a.py" source_line=3}',
        "  %copy.47 = bf16[2]{0} copy(%fusion.5)",
        '  ROOT %dot.1 = f32[2]{0} dot(%copy.47), '
        'metadata={op_name="jit(serve_scan)/serve_scan/head/dot_general"}',
        "}"])
    assert scopes.hlo_scopes(text) == {("fusion.5", "bf16[2]"): "kv_append",
                                       ("copy.47", "bf16[2]"): "-",
                                       ("dot.1", "f32[2]"): "head"}


@pytest.mark.parametrize("line,sig", [
    ('%copy.85 = bf16[4096,8,8,128]{3,2,1,0:T(8,128)(2,1)} copy('
     'bf16[4096,8,8,128]{3,2,1,0:T(8,128)(2,1)} %bitcast.3)',
     ("copy.85", "bf16[4096,8,8,128]")),
    ('%copy-start.4 = (s32[1,512]{1,0:T(1,128)S(1)}, s32[1,512]{1,0}, '
     'u32[]{:S(2)}) copy-start(s32[1,512]{1,0:T(1,128)} %tokens.1)',
     ("copy-start.4", "s32[1,512]")),
    ("  ROOT %tuple.9 = () tuple()", ("tuple.9", "")),
    ("ENTRY %main.3 (p: f32[2]) -> f32[2] {", None),
])
def test_signature_of_a_trace_event_or_an_hlo_line(line, sig):
    assert scopes.signature(line) == sig


def test_a_module_is_matched_to_the_variant_that_knows_its_ops():
    """Two window sizes number their instructions differently; a
    module's operations pick the variant whose (name, shape) pairs they
    are, and a module neither knows is left unmatched."""
    k1 = {("fusion.1", "s32[16,1]"): "head", ("fusion.2", "bf16[8]"): "mlp",
          ("copy.3", "bf16[8]"): "-"}
    k8 = {("fusion.1", "s32[16,8]"): "head", ("fusion.2", "bf16[8]"): "qkv",
          ("copy.3", "bf16[8]"): "-", ("copy.4", "bf16[8]"): "-"}
    ops = [("fusion.1", "s32[16,8]"), ("fusion.2", "bf16[8]"),
           ("copy.4", "bf16[8]"), ("fusion.2", "bf16[8]")]
    assert scopes.match(ops, [k1, k8]) == (1, 1.0)
    # a module three quarters of which one variant knows is no match
    assert scopes.match(ops[:1] + [("fusion.1", "s32[16,1]")] * 3,
                        [k1, k8]) == (None, 0.75)
    i, share = scopes.match([("add.7", "f32[2]")] * 9 + ops[:1], [k1, k8])
    assert i is None and share == pytest.approx(0.1)


def test_extract_reads_the_engine_phases_of_a_cpu_trace(tmp_path):
    """On the CPU there is no device plane, so no op is mapped; the
    engine's phases are read from the host plane all the same."""
    import glob

    import jax
    import numpy as np
    sys.path.insert(0, str(BENCH.parent / "src"))
    from repro.configs import get_tiny_config
    from repro.models import lm
    from repro.serving import PagedEngine
    cfg = get_tiny_config("tiny-100m")
    eng = PagedEngine(cfg, lm.init_params(jax.random.PRNGKey(0), cfg),
                      max_batch=2, page_size=4, n_pages=16, max_len=16)
    prompt = np.arange(2, 10, dtype=np.int32)
    eng.submit(prompt, 3)
    eng.run()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit(prompt + 1, 3)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    rec = scopes.extract(path)
    phases = [name for name, s, e in rec["engine"]]
    assert {"engine.plan", "engine.prefill", "engine.pull"} <= set(phases)
    assert all(s <= e for _, s, e in rec["engine"])
    assert rec["devices"] == {} and rec["mapped"] == 0
