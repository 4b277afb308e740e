"""Read the two numbers a cell's ``logit_gap_limit`` is set between.

    python3 bench/limits.py --workload <cell> --seeds 1,2,3 --seconds 15

For each seed, in one process, one run of the cell exactly as
``bench/run.py`` makes it (its own traffic at its own load, the timed
path, the same sample of finished requests), which reads the program's
widest logit gap, and the control on the same sample: the reference at
float8 in the program's place.  One JSON line per seed.  The limit lies
above the largest program reading over a dozen seeds or more and below
the smallest control reading.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from benchkit import cell
    bench = cell.load_json(ROOT / "BENCHMARK.json")
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            res = cell.run(bench, args.workload, seed, args.seconds, False,
                           t_start=time.perf_counter(), control=True,
                           log=lambda m: print(m, file=sys.stderr))
        except cell.NoChip as e:
            print(f"limits: {e}", file=sys.stderr)
            return 3
        print(json.dumps({"seed": seed,
                          "program": res["check"]["logit_gap"]["value"],
                          "control": res["control"]["logit_gap"],
                          "tokens": res["control"]["tokens"],
                          "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
