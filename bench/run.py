#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; both are data files found by name (``bench/registry.py``).
With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the metrics are
its per-layer ones.  Earlier lines report the programs compiled inside the
window, the peak device memory, the entropy lane paths, the mix's counters
and the reference readings; the numbers compared close standard error, and
the last line of standard output is the result object.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, registry

    cell = registry.load_cell(args.workload)
    cache = harness.enable_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    harness.log(f"devices {len(devs)} x {devs[0].device_kind}; compile cache {cache}")
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), start_age=harness.process_age_s())
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
