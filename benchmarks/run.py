"""Benchmark driver: one section per paper table/figure + micro timings +
the roofline table.  Prints ``name,us_per_call,derived`` CSV.

``--json`` additionally emits the machine-readable perf trajectory:
``BENCH_micro.json`` (every micro row), ``BENCH_serve.json`` (the
fused-vs-per-step serving comparison with token-identity check),
``BENCH_prefix.json`` (the prefix-cache on-vs-off shared-prefix trace:
hit rate, prefill-token reduction, token identity) and
``BENCH_spec.json`` (speculative decoding on-vs-off on the repetitive
trace: dispatches per token, accept rate, token identity) and
``BENCH_slo.json`` (chunked prefill vs monolithic on the overload
trace: per-SLO-class TTFT percentiles, goodput, token identity) and
``BENCH_chaos.json`` (fault-free vs seeded-chaos on the
fault-injection trace: survivor token identity, goodput retained,
recovery percentiles) and ``BENCH_obs.json`` (flight recorder off vs
on on the overload trace: token identity, tracing overhead ratio, the
predicted-vs-measured model-error rollup, a schema-validated trace
excerpt) and ``BENCH_tp.json`` (the pinned workload replayed at every
serving layout — 1x1 vs striped 1x2/2x2 meshes on subsets of this
process's devices: token bit-identity across layouts, predicted
interconnect cost per window, and the measured remote page fraction
against the (n-1)/n stripe model) into ``--json-dir``.  ``--only PATTERN`` filters sections by substring (an
unknown pattern is an error listing the valid titles) — the CI
perf-smoke job runs ``--only micro --json`` and validates the files
with ``scripts/check_bench.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import traceback


def main() -> None:
    sys.path.insert(0, "src")
    sys.path.insert(0, ".")
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="run only sections whose title contains this")
    ap.add_argument("--json", action="store_true",
                    help="emit BENCH_micro.json + BENCH_serve.json")
    ap.add_argument("--json-dir", default=".",
                    help="directory for the BENCH_*.json files")
    args = ap.parse_args()
    # the layout comparison (BENCH_tp.json) meshes four devices in this
    # one process; on a CPU host they are virtual (the flag touches only
    # the CPU backend)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import cost_sweep as cs
    from benchmarks import paper_tables as pt
    from benchmarks import perf_micro as pm
    from benchmarks import roofline_table as rt
    from benchmarks import serve_trace as st

    sections = [
        ("Table II (link energies)", pt.table2_link_energy),
        ("Table III (e/c, E/C)", pt.table3_ec_ratio),
        ("Table IV (power/core)", pt.table4_power),
        ("Fig 3 (memory/task)", pt.fig3_memory_per_task),
        ("Fig 5 (thread throughput)", pt.fig5_thread_throughput),
        ("Fig 9/10 (DVFS)", pt.fig9_fig10_dvfs),
        ("Fig 11 (neuron scaling)", pt.fig11_neuron_scaling),
        ("Fig 8/9 (nOS cost sweep)", cs.sweep_rows),
        ("micro: train grad", pm.micro_train_steps),
        ("micro: kernels", pm.micro_kernels),
        ("micro: serve", pm.micro_serve),
        ("micro: data", pm.micro_data_pipeline),
        ("micro: checkpoint", pm.micro_checkpoint),
        ("roofline table", rt.roofline_rows),
    ]
    if args.only:
        all_titles = [t for t, _ in sections]
        sections = [(t, f) for t, f in sections if args.only in t]
        if not sections:
            print(f"--only {args.only!r} matches no section; valid "
                  f"titles (substring match):", file=sys.stderr)
            for t in all_titles:
                print(f"  {t}", file=sys.stderr)
            raise SystemExit(2)
    print("name,us_per_call,derived")
    failures = 0
    micro_rows = []
    for title, fn in sections:
        print(f"# --- {title} ---")
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
                if name.startswith("micro/"):
                    micro_rows.append(
                        {"name": name, "us_per_call": float(us),
                         "derived": str(derived)})
        except Exception:
            traceback.print_exc()
            failures += 1
    if args.json:
        os.makedirs(args.json_dir, exist_ok=True)
        micro = {
            "schema": "swallow.bench.micro/v1",
            "host": platform.machine(),
            "python": platform.python_version(),
            "rows": micro_rows,
        }
        micro_path = os.path.join(args.json_dir, "BENCH_micro.json")
        with open(micro_path, "w") as f:
            json.dump(micro, f, indent=1)
        print(f"# wrote {micro_path} ({len(micro_rows)} rows)")
        comparisons = [
            ("BENCH_serve.json", st.bench_fused_comparison,
             lambda d: f"tokens_match={d['tokens_match']}, "
                       f"speedup_decode={d['speedup_decode']:.2f}x"),
            ("BENCH_prefix.json", st.bench_prefix_comparison,
             lambda d: f"tokens_match={d['tokens_match']}, "
                       f"hit_rate={d['on']['hit_rate']:.2f}, "
                       f"prefill_token_reduction="
                       f"{d['prefill_token_reduction']:.2f}x"),
            ("BENCH_spec.json", st.bench_spec_comparison,
             lambda d: f"tokens_match={d['tokens_match']}, "
                       f"dispatches_per_token="
                       f"{d['on']['dispatches_per_token']:.3f} vs "
                       f"{d['off']['dispatches_per_token']:.3f}, "
                       f"accept_rate={d['on']['accept_rate']:.2f}, "
                       f"spec_speedup={d['spec_speedup']:.2f}x"),
            ("BENCH_slo.json", st.bench_slo_comparison,
             lambda d: f"tokens_match={d['tokens_match']}, "
                       f"p99_ttft_ratio={d['p99_ttft_ratio']:.2f}, "
                       f"goodput_ratio={d['goodput_ratio']:.2f}"),
            ("BENCH_chaos.json", st.bench_chaos_comparison,
             lambda d: f"tokens_match={d['tokens_match']}, "
                       f"node_failures={d['chaos']['node_failures']}, "
                       f"requests_recovered="
                       f"{d['chaos']['requests_recovered']}, "
                       f"goodput_retained={d['goodput_retained']:.2f}"),
            ("BENCH_obs.json", st.bench_obs_comparison,
             lambda d: f"tokens_match={d['tokens_match']}, "
                       f"overhead_ratio={d['overhead_ratio']:.3f}, "
                       f"spans={d['on']['spans_recorded']}"),
            ("BENCH_tp.json", st.bench_tp_comparison,
             lambda d: f"tokens_match={d['tokens_match']}, "
                       f"layouts={[l['layout'] for l in d['layouts']]}, "
                       f"remote_frac_ratio="
                       f"{d['layouts'][-1]['remote_frac_ratio']:.3f}"),
        ]
        for fname, bench_fn, summarize in comparisons:
            try:
                doc = bench_fn(quick=True)
                path = os.path.join(args.json_dir, fname)
                with open(path, "w") as f:
                    json.dump(doc, f, indent=1)
                print(f"# wrote {path} ({summarize(doc)})")
            except Exception:
                traceback.print_exc()
                failures += 1
    if not args.only:
        print("# --- full roofline table ---")
        try:
            rt.print_full_table()
        except Exception:
            traceback.print_exc()
            failures += 1
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
