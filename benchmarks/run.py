"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  REPRO_BENCH_FAST=1 shrinks settings.
``--fast`` is the smoke mode (tiny volumes, 2 epochs) used by
tests/test_bench_smoke.py so benchmark scripts can't silently rot; ``--only``
restricts which modules run (all modules are still imported, so import rot is
always caught).  ``--fast`` is process-wide: it sets env vars that
benchmarks.common freezes at first import, so run it in its own process (the
CLI), not interleaved with full-size runs via main().  Roofline terms for the TPU target come from the compiled
dry-run (``python -m repro.launch.dryrun`` + ``python -m repro.launch.roofline``).
"""
from __future__ import annotations

import argparse
import os
import traceback


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="smoke mode: tiny settings so the full harness runs in seconds")
    ap.add_argument("--only", nargs="+", default=None, metavar="MODULE",
                    help="run only these modules (throughput, fig5_losscurves, "
                         "table3_groups, table2_psnr)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump every emitted row as JSON (CI uploads the "
                         "--fast run as a workflow artifact)")
    args = ap.parse_args(argv)
    if args.fast:
        os.environ["REPRO_BENCH_FAST"] = "1"
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    # import after the env is set: benchmarks.common reads it at import time
    from benchmarks import fig5_losscurves, table2_psnr, table3_groups, throughput

    modules = (throughput, fig5_losscurves, table3_groups, table2_psnr)
    if args.only is not None:
        wanted = set(args.only)
        modules = tuple(m for m in modules if m.__name__.split(".")[-1] in wanted)
        missing = wanted - {m.__name__.split(".")[-1] for m in modules}
        if missing:
            ap.error(f"unknown module(s): {sorted(missing)}")

    print("name,us_per_call,derived")
    failures = 0
    for mod in modules:
        try:
            mod.main()
        except Exception as e:  # keep the harness going; failures are visible
            failures += 1
            print(f"{mod.__name__},0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc()
    if args.json:
        import json

        from benchmarks import common

        with open(args.json, "w") as f:
            json.dump({"fast": args.fast, "failures": failures,
                       "rows": common.ROWS}, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
