#!/usr/bin/env python3
"""Find the knee of an open-loop cell: short runs at several fixed rates,
in one process.

    python3 bench/sweep.py --workload <cell> --rates 0.2,0.4,0.8 --seconds 30

For each rate it runs the cell with the mix's ``rate_per_s`` replaced and
prints one JSON line: the requests outstanding when the window opened and
when it closed (a queue that grows means the rate is past the knee), the
answers in the window and the cell's end-to-end metrics. The cell's rate is
then fixed in its traffic file at about four fifths of the knee.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from sartbench.cell import NoChip, run_cell
    from sartbench.spec import load_cell
    cell = load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_per_s=rate)
        t0 = time.perf_counter()
        try:
            res = run_cell(dataclasses.replace(cell, traffic=traffic),
                           args.seed, args.seconds, False, t0)
        except NoChip as exc:
            print(f"sweep.py: {exc}", file=sys.stderr)
            return 1
        print(json.dumps({"workload": args.workload, "rate_per_s": rate,
                          "correct": res["correct"], **res["load"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
