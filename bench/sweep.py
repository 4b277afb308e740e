"""Find a cell's knee once: offer its traffic at several Poisson rates.

    python3 bench/sweep.py --workload <cell> --rates 1,1.5,2 --seconds 20

Each rate is one run of the cell as ``bench/run.py`` makes it, in one
process, with only the arrival rate changed; one JSON line per rate.
Past the knee the queue grows all through the window, so the time to
first token jumps from about a second to many and the output rate stops
rising.  A cell's traffic file states about four fifths of the highest
rate at which it did not.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from benchkit import cell
    bench = cell.load_json(ROOT / "BENCHMARK.json")
    c = cell.find_cell(bench, args.workload)
    mix = cell.load_json(BENCH / "traffic" / f"{c['traffic']}.json")
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
        try:
            res = cell.run(bench, args.workload, args.seed, args.seconds,
                           False, t_start=time.perf_counter(), mix=m,
                           log=lambda msg: print(msg, file=sys.stderr))
        except cell.NoChip as e:
            print(f"sweep: {e}", file=sys.stderr)
            return 3
        print(json.dumps({"rate": rate, "attempted": res["attempted"],
                          **{k: v["value"] for k, v in
                             res["metrics"].items() if k != "setup_s"}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
