"""One run of one cell: load its files by name, set up, measure, check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json``: the configuration (published keys,
  the engine options, the check's limit) and, named by its
  ``reference`` key, the plain reference beside it;
* ``bench/benchkit/models/<model_type>.py``: the configuration's model
  family, named by its published ``model_type`` (see
  :mod:`benchkit.engine`);
* ``bench/traffic/<traffic>.json``: the traffic mix, read by
  :mod:`benchkit.traffic`;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric,
  ``read(ctx) -> float | None``.
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import math
import os
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
# the persistent compile cache: a fixed directory inside the checkout
CACHE_DIR = ROOT / ".jax_cache" / "bench"
# extra seconds of offered load past the window, for the drain
SCHEDULE_MARGIN_S = 10.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r}; known: "
                   f"{[c['name'] for c in bench['workloads']]}")


def config_path(bench: dict, name: str) -> Path:
    for c in bench["configs"]:
        if c["name"] == name:
            return ROOT / c["file"]
    raise KeyError(f"no configuration {name!r}")


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``trace`` its per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def quantile(values, q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least ``q`` of
    the sample at or below it."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def end_to_end(out, setup_s: float) -> dict:
    """The end-to-end metrics of one window, from the harness's clock."""
    due = [r for r in out.records
           if out.t0 <= r.due < out.t1 and not r.failed]
    # a request still without a token when the loop stopped waiting counts
    # the whole wait, a lower bound of its time to first token
    ttft = [(r.first if r.first is not None else out.t_stop) - r.due
            for r in due]
    tpot = [(r.last_in_window - r.first) / (r.seen_in_window - 1)
            for r in out.records
            if r.seen_in_window >= 2 and r.last_in_window >= out.t0]
    return {
        "ttft_p90_s": quantile(ttft, 0.9) if ttft else None,
        "tpot_p90_s": quantile(tpot, 0.9) if tpot else None,
        "output_tok_per_s": out.window_tokens / (out.closed - out.opened),
        "setup_s": setup_s,
    }


def host_summary(out) -> str:
    """Where the host stood still in the window: the longest engine steps,
    the longest gaps between them, the garbage collections, and the
    tail of the per-request times per output token."""
    st = out.steps
    took = sorted((b - a for a, b in st), reverse=True)
    gaps = sorted((b[0] - a[1] for a, b in zip(st, st[1:])), reverse=True)
    gcs = out.gc_pauses
    tpot = sorted(((r.last_in_window - r.first) / (r.seen_in_window - 1),
                   r.seen_in_window) for r in out.records
                  if r.seen_in_window >= 2 and r.last_in_window >= out.t0)
    top = lambda xs: ", ".join(f"{x:.4f}" for x in xs[:5])  # noqa: E731
    return (f"{len(st)} steps, longest {top(took)} s; gaps between steps "
            f"{top(gaps)} s; {len(gcs)} garbage collections, "
            f"{sum(g == 2 for g, _ in gcs)} of generation 2, longest "
            f"{top(sorted((s for _, s in gcs), reverse=True))} s; "
            f"tpot over {len(tpot)} requests, top (s, tokens): "
            + ", ".join(f"{v:.4f}/{n}" for v, n in tpot[::-1][:12]))


def _xplane(d: str) -> Optional[str]:
    found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    return found[0] if found else None


def run(bench: dict, cell_name: str, seed: int, seconds: float,
        trace: bool, *, t_start: float, platform: str = "tpu",
        compile_cache: bool = True, config_file: Optional[Path] = None,
        mix: Optional[dict] = None, control: bool = False,
        log=print) -> dict:
    """One run; returns the result line's object.  Raises :class:`NoChip`
    when JAX finds another platform than ``platform`` or too few
    devices.  ``config_file`` and ``mix`` stand in for the cell's own
    configuration and traffic (the tests, ``bench/sweep.py``).
    ``control`` (``bench/limits.py`` and the tests only) also reads the
    control's widest gap over the same sample, under ``"control"``."""
    import jax
    if compile_cache:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        # no eviction: the LRU eviction keeps an access-time file beside
        # each entry, and fails every write where one has gone missing
        # (as in a cache directory copied without them)
        jax.config.update("jax_compilation_cache_max_size", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from benchkit import engine
    cell = find_cell(bench, cell_name)
    engine.stage_on_host(cell["chips"])
    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell["chips"]:
        raise NoChip(f"JAX found {len(devices)} {devices[0].platform} "
                     f"device(s); the cell needs {cell['chips']} "
                     f"{platform} chip(s)")
    from benchkit import check, counting, harness, traffic, trace as tr
    kind = devices[0].device_kind
    peak = counting.peaks(kind) if platform == "tpu" else None
    cfg_file = config_file or config_path(bench, cell["config"])
    cfg = load_json(cfg_file)
    ref = load_module(cfg_file.parent / cfg["reference"])
    mix = mix or load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    shape = engine.work(cfg)
    vocab = cfg["vocab_size"]
    used = devices[:cell["chips"]]

    compiles = harness.CompileCounter()
    marks = [("start", time.perf_counter())]
    # on several chips one replica per chip, shared by the program's
    # parameters and the check
    weights = jax.block_until_ready(engine.replicated(
        ref.make_weights(cfg, seed), used))
    marks.append(("weights", time.perf_counter()))
    mcfg = engine.model_config(cfg)
    params = engine.program_params(cfg, weights, mcfg)
    sched = traffic.build(mix, seed, seconds + SCHEDULE_MARGIN_S, vocab)
    eng = engine.build(cfg, mcfg, params, sched.max_tokens, used)
    marks.append(("engine", time.perf_counter()))
    engine.warm(eng, sched, vocab)
    marks.append(("warm", time.perf_counter()))
    for p in sched.prefixes:           # shared prefixes: into the cache
        eng.submit(p, 1)
    eng.run()
    eng.reset_metrics()
    marks.append(("prefixes", time.perf_counter()))
    with tempfile.TemporaryDirectory() as tdir:
        loop = harness.Loop(eng, sched, shape,
                            trace_dir=tdir if trace else None)
        out = loop.run(seconds, compiles)
        setup_s = out.t0 - t_start
        marks.append(("warm-in", out.t0))
        jax.block_until_ready(eng.pools)
        mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
        reduced = None
        if trace:
            path = _xplane(tdir)
            rec = tr.extract(path) if path else {"devices": {}, "host": []}
            reduced = tr.reduce(rec) if rec["devices"] else None
    log("[bench] set-up s: " + ", ".join(
        f"{b[0]} {b[1] - a[1]:.2f}" for a, b in zip(
            [("", t_start)] + marks, marks))
        + f"; persistent cache {compiles.cache}")
    log(f"[bench] compiles_in_window {out.compiles_in_window} "
        f"preemptions {out.preemptions} requests {len(out.records)}; "
        f"prompt tokens in the window cached "
        f"{out.counters['prefill_tokens_cached']} computed "
        f"{out.counters['prefill_tokens']}; cache evictions "
        f"{eng.cache.stats.evictions if eng.cache is not None else 0}")
    log("[bench] host: " + host_summary(out))
    engine_opts = dict(cfg["engine"])
    max_len = eng.max_len
    problems = check.served_problems(out.records, vocab)
    picked = check.sample(out.records, cfg["check"]["requests"], seed)
    seqs = [(r.item.prompt, list(r.req.tokens)) for r in picked]
    del eng, params, loop
    gc.collect()

    # -- the comparison, after the window and with the program freed ------
    t_check = time.perf_counter()
    n_read = max(i.gen for i in sched.items)
    gap = (check.widest_gap(ref, weights, cfg, seqs, max_len, n_read)
           if seqs else None)
    limit = cfg["check"]["logit_gap_limit"]
    tokens_checked = sum(len(s) for _, s in seqs)
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "bad_requests": {"value": len(problems), "limit": 0},
    }
    correct = gap is not None and gap <= limit and not problems
    ctrl = (check.widest_gap(ref, weights, cfg, seqs, max_len, n_read,
                             control=True) if control and seqs else None)
    log(f"[bench] checked {tokens_checked} served tokens of {len(seqs)} "
        f"requests against the reference in "
        f"{time.perf_counter() - t_check:.2f} s")
    for p in problems[:5]:
        log(f"[bench] {p}")

    ctx = SimpleNamespace(out=out, trace=reduced, shape=shape, peak=peak,
                          chips=cell["chips"], engine=engine_opts)
    metrics = {}
    e2e = end_to_end(out, setup_s)
    for m in cell_metrics(bench, cell_name, trace):
        if trace:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        else:
            value = e2e[m["name"]]
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    due = [r for r in out.records if out.t0 <= r.due < out.t1]
    result = {
        "correct": bool(correct),
        "attempted": len(due),
        "failed": sum(r.failed for r in due),
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices),
                   "memory_peak_bytes": max((m for m in mem if m), default=0)},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if ctrl is not None:
        result["control"] = {"logit_gap": ctrl, "tokens": tokens_checked}
    result["check"] = checks
    return result
