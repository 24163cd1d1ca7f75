"""CLI: ``python -m tools.stepcheck [options]`` — reprolint conventions.

Exit code 1 only for findings not covered by the committed baseline
(``tools/stepcheck/baseline.txt``); ``--write-baseline`` regenerates it
(re-add justification comments by hand), ``--write-manifest``
regenerates the compile-count manifest after an intentional shape
change. ``--self-test`` seeds violations (an un-clamped index map, a
tampered manifest) and exits 0 only if stepcheck catches both — the CI
step that proves the checker itself works.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

from tools.reprolint.framework import Baseline, render_json, repo_root

from . import RULES

BASELINE_PATH = repo_root() / "tools" / "stepcheck" / "baseline.txt"


def self_test() -> int:
    """Negative controls: stepcheck must catch seeded violations."""
    import numpy as np

    from repro.kernels import paged_attention_grid
    from repro.kernels.introspect import BlockMapping

    from . import bounds, manifest

    failures = []

    # 1) un-clamp flash-decode's KV index map: the sentinel-table case
    #    must produce a STEP007 out-of-bounds finding
    num_pages, page_size, pps = 16, 4, 5
    kg = paged_attention_grid(3, 4, 8, 2, num_pages, page_size, pps)
    import dataclasses
    unclamped = tuple(
        dataclasses.replace(
            m, index_map=lambda b, i, bt, ln: (0, bt[b, i], 0, 0))
        if m.name in ("k_pages", "v_pages") else m
        for m in kg.in_mappings)
    broken = dataclasses.replace(kg, in_mappings=unclamped)
    cases = bounds.paged_attention_cases(num_pages, page_size, pps, 3)
    caught = bounds.verify_kernel_grid(broken, cases)
    if not any(f.rule == "STEP007" for f in caught):
        failures.append("un-clamped index map NOT caught by STEP007")
    if bounds.verify_kernel_grid(kg, cases):
        failures.append("clamped index map wrongly flagged by STEP007")

    # 2) tamper a manifest signature: the ratchet must flag the change,
    #    an off-manifest variant, and a stale entry
    per_target = {"engine[t]": {"decode": {"sig": "aaaa", "out": []},
                                "mixed:b8xl1": {"sig": "bbbb", "out": []}}}
    tampered = {"targets": {"engine[t]": {
        "decode": {"sig": "XXXX", "out": []},
        "mixed:b8xl2": {"sig": "cccc", "out": []}}}}
    flagged = manifest.check_manifest(per_target, tampered)
    symbols = {(f.rule, f.symbol) for f in flagged}
    for want in [("STEP002", "decode"), ("STEP002", "mixed:b8xl1"),
                 ("STEP002", "mixed:b8xl2")]:
        if want not in symbols:
            failures.append(f"manifest tampering NOT caught: {want}")

    if failures:
        for msg in failures:
            print(f"self-test FAILED: {msg}")
        return 1
    print("self-test OK: seeded violations caught "
          f"({len(caught)} bounds finding(s), "
          f"{len(flagged)} manifest finding(s))")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.stepcheck",
        description="trace-level semantic verifier for the serving step")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable findings (CI artifact)")
    parser.add_argument("--baseline", type=Path, default=BASELINE_PATH,
                        help="baseline file (default: committed)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="report every finding as new")
    parser.add_argument("--write-baseline", action="store_true",
                        help="regenerate the baseline from this run")
    parser.add_argument("--manifest", type=Path, default=None,
                        help="manifest file (default: committed)")
    parser.add_argument("--write-manifest", action="store_true",
                        help="regenerate tools/stepcheck/manifest.json")
    parser.add_argument("--list-rules", action="store_true")
    parser.add_argument("--self-test", action="store_true",
                        help="seed violations; exit 0 iff caught")
    args = parser.parse_args(argv)

    if args.list_rules:
        for code, (name, summary) in sorted(RULES.items()):
            print(f"{code}  {name}: {summary}")
        return 0
    if args.self_test:
        return self_test()

    from . import manifest as manifest_mod
    from .runner import run_all

    committed = None
    if args.manifest is not None:
        committed = manifest_mod.load_manifest(args.manifest)
    result = run_all(committed_manifest=committed)

    if args.write_manifest:
        path = args.manifest or manifest_mod.MANIFEST_PATH
        manifest_mod.write_manifest(result.manifest, path)
        print(f"wrote {path}")
        # findings computed against the stale manifest no longer apply
        result.findings = [f for f in result.findings
                           if f.rule != "STEP002"]

    baseline = (Baseline() if args.no_baseline
                else Baseline.load(args.baseline))
    old, new = baseline.partition(result.findings)

    if args.write_baseline:
        args.baseline.write_text(
            Baseline.render(result.findings).replace(
                "# reprolint baseline", "# stepcheck baseline"),
            encoding="utf-8")
        print(f"wrote {args.baseline} ({len(result.findings)} entries)")
        return 0

    if args.json:
        print(render_json(result.findings, new))
    else:
        new_ids = {id(f) for f in new}
        for f in result.findings:
            marker = "" if id(f) in new_ids else " [baselined]"
            print(f.render() + marker)
        print(f"stepcheck: {len(result.findings)} finding(s) "
              f"({len(old)} baselined, {len(new)} new) over "
              f"{result.targets_analyzed} engine target(s), "
              f"{result.variants_traced} traced variant(s)")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
