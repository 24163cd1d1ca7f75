#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, metrics and bounds are in BENCHMARK.json at the root of the
checkout; a cell's configuration, traffic mix and metric readers are files
under bench/ found by name. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` with ``--trace 1``), then ``check``, each compared number
beside its limit. Exits non-zero, printing no result, without a TPU or
with fewer chips than the cell asks for.

``--control 1`` holds the control to the same limits in the program's
place (the reference at fp8 over the same sample), so ``correct`` has to
come out false; the program's numbers follow under ``program_check``. The
check's limits are set from these readings (PERF.md); the benchmark's own
runs leave it at 0.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from sartbench.spec import SpecError, load_cell
    try:
        cell = load_cell(args.workload)
    except (SpecError, KeyError) as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401
    except ImportError as exc:
        print(f"run.py: the program is not in this checkout ({exc})",
              file=sys.stderr)
        return 2
    from sartbench.cell import NoChip, run_cell
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, control=bool(args.control))
    except NoChip as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return 1
    check = result.get("check", {})
    for name, c in check.items():
        print(f"{name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
