"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, traffic mix
and metrics are named in ``BENCHMARK.json``.  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics and a breakdown of the traced interval.  The last line
of standard output is the result, one JSON object; the last lines of
standard error are the numbers the correctness check compared, each
beside its limit.  Exits nonzero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for, or when the checkout holds
no program to measure.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}; run from a checkout "
              "of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from benchkit import cell
    bench = cell.load_json(ROOT / "BENCHMARK.json")
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        result = cell.run(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START, log=log)
    except cell.NoChip as e:
        log(f"bench: {e}")
        return 3
    for name, c in result["check"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
