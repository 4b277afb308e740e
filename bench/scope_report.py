"""Where a cell's time goes, by engine phase and by model scope.

    python3 bench/scope_report.py --workload <cell> --seed <n> --seconds <s> --out <dir> [--trace 0]
    python3 bench/scope_report.py --from <dir>

The first form runs the cell once, as ``bench/run.py --trace 1`` does,
and keeps in ``<dir>`` what that run throws away: the profiler's
``.xplane.pb``, the compiled text of the ``serve_scan`` program at each
window size the engine dispatches (``serve_scan.k<k>.hlo``), and
``run.json`` (the result line, the engine's counters over the run, and
the requests' time to first token split by their lifecycle stamps).
With ``--trace 0`` it runs as ``--trace 0`` does and keeps only
``run.json``: the profiler's start and stop hold the load loop for
seconds, and the requests due then wait behind it.  The second form
reads such a directory again.  Both print one JSON object:

* ``scope_s`` and ``idle_by_phase``: :func:`benchkit.scopes.reduce`
  over the traced interval (device self time per ``program/scope``;
  idle time per ``engine.*`` phase open on the host);
* ``decode_kv_move_pct``: the share of ``serve_scan``'s self time in
  operations scoped ``kv_append`` or ``kv_gather`` or under no model
  scope (``-``: the compiler's copies of the layer scan's carry);
* ``mapped``: device operations whose scope was found in the HLO text,
  and ``matched``: each ``serve_scan`` module met in the trace, with the
  window size's text it was matched to and the share of its operations
  that text knows (:func:`benchkit.scopes.match`);
* ``host_pass_ms``: ``host_pass_s / step_calls`` over the run, and
  ``engine_s``: the ``engine.<phase>_s`` counters;
* ``ttft``: :func:`benchkit.stamps.summary` of the window's requests,
  all of them and those whose first token came before the profiler
  started.

A TPU v5e trace names no operation's scope, so the scopes come from the
compiled HLO text, by instruction name and result shape; operations
of other programs read as ``?``.
"""
import argparse
import glob
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
PROGRAM = "serve_scan"
KV_MOVE = ("kv_append", "kv_gather", "-")


def _scan_texts(eng) -> dict:
    """Compiled text of the engine's decode-window program at each
    window size, compiled afresh (a program loaded from the persistent
    cache may carry no HLO text)."""
    import jax
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    args = jax.tree.map(shape, (eng.params, eng.d_tokens, eng.pools,
                                eng.d_block, eng.d_pos, eng.d_active))
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return {k: eng._scan.lower(*args, k=k).compile().as_text()
                for k in eng.window_sizes()}
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)


def collect(bench: dict, workload: str, seed: int, seconds: float,
            out_dir: Path, trace: bool = True, **run_kw) -> dict:
    """One run of ``workload``, kept in ``out_dir``; returns the
    contents of its ``run.json``.  ``run_kw`` goes to
    :func:`benchkit.cell.run` (the tests run the tiny configuration on
    the CPU)."""
    from benchkit import cell, harness, stamps
    out_dir.mkdir(parents=True, exist_ok=True)
    kept = {}
    run = harness.Loop.run

    def keep(self, *a, **kw):
        out = run(self, *a, **kw)
        if self.trace_dir is not None:
            for f in glob.glob(str(Path(self.trace_dir) / "**"
                                   / "*.xplane.pb"), recursive=True):
                shutil.copy(f, out_dir / "trace.xplane.pb")
            for k, text in _scan_texts(self.eng).items():
                (out_dir / f"{PROGRAM}.k{k}.hlo").write_text(text)
        kept["counters"] = dict(self.eng.registry.counters)
        cut = min((s for s, _ in out.pauses), default=None)
        kept["ttft"] = {"window": stamps.summary(stamps.parts(out)),
                        "before_profiler": stamps.summary(
                            stamps.parts(out, before=cut))}
        kept["requests"] = {"window": len(stamps.parts(out)),
                            "before_profiler": len(stamps.parts(
                                out, before=cut))}
        return out
    harness.Loop.run = keep
    try:
        result = cell.run(bench, workload, seed, seconds, trace,
                          t_start=time.perf_counter(), **run_kw)
    finally:
        harness.Loop.run = run
    doc = {"cell": workload, "seed": seed, "result": result, **kept}
    (out_dir / "run.json").write_text(json.dumps(doc, indent=1))
    return doc


def report(out_dir: Path) -> dict:
    """The report of a directory :func:`collect` filled."""
    from benchkit import scopes
    doc = json.loads((out_dir / "run.json").read_text())
    files = sorted(out_dir.glob(f"{PROGRAM}.k*.hlo"),
                   key=lambda f: int(f.name.split(".k")[1].split(".")[0]))
    variants = [scopes.hlo_scopes(f.read_text()) for f in files]
    rep = {"cell": doc["cell"], "seed": doc["seed"]}
    path = out_dir / "trace.xplane.pb"
    if path.exists():
        rec = scopes.extract(str(path), {PROGRAM: variants})
        rep["mapped"] = rec["mapped"]
        rep["matched"] = {m: [files[i].name if i is not None else None, s]
                          for m, (i, s) in rec["matched"].items()}
        rep["engine_spans"] = len(rec["engine"])
        rep.update(scopes.reduce(rec))
        scan = {k.split("/", 1)[1]: v for k, v in rep["scope_s"].items()
                if k.startswith(PROGRAM + "/")}
        total = sum(scan.values())
        rep["decode_kv_move_pct"] = (
            100.0 * sum(scan.get(s, 0.0) for s in KV_MOVE) / total
            if total else None)
    c = doc["counters"]
    calls = c.get("step_calls", 0)
    rep["host_pass_ms"] = 1e3 * c.get("host_pass_s", 0.0) / calls \
        if calls else None
    rep["engine_s"] = {k: v for k, v in c.items()
                       if k.startswith("engine.") and k.endswith("_s")}
    rep["ttft"] = doc["ttft"]
    rep["requests"] = doc["requests"]
    return rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", type=Path)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--from", dest="src", type=Path)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    if args.src is None:
        if not (args.workload and args.out):
            ap.error("give --workload and --out, or --from")
        from benchkit import cell
        bench = cell.load_json(ROOT / "BENCHMARK.json")
        try:
            collect(bench, args.workload, args.seed, args.seconds, args.out,
                    bool(args.trace),
                    log=lambda m: print(m, file=sys.stderr, flush=True))
        except cell.NoChip as e:
            print(f"scope_report: {e}", file=sys.stderr)
            return 3
    print(json.dumps(report(args.src or args.out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
