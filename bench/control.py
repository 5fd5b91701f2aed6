#!/usr/bin/env python3
"""Readings for the limits of a cell's compared numbers, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]
                             [--fault unchanged]

For each seed, in one process: one run of the cell as ``run.py`` makes it
(set-up, a window of ``--seconds``, the check), then the same comparison
with each control in the program's place: the plain reference computed one
precision lower (bfloat16 for the float32 configurations), all of it, and
for an enhanced container the enhancer alone.  Prints one JSON line per
seed with the program's numbers and each control's.  The program's largest
reading over sound runs is a limit's lower end; the control's smallest is
its upper end (PERF.md, "How correct is decided").

``--fault unchanged`` runs the program with a training step that returns
its state unchanged, so the numbers printed are that fault's readings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def plant_unchanged_step() -> None:
    """Every enhancer training step returns its parameters and state as it
    got them."""
    import jax.numpy as jnp

    from repro.core import trainer

    trainer.train_step = lambda p, bn, opt, *a, **k: (
        p, bn, opt, jnp.zeros(p["b2"].shape[0]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=("unchanged",))
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    harness.enable_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    if args.fault:
        plant_unchanged_step()
    for seed in args.seeds:
        r = harness.run_cell(args.workload, seed, args.seconds, False,
                             control=not args.fault)
        print("control_line " + json.dumps(
            {"seed": seed, "fault": args.fault, "correct": r["correct"],
             "checks": r["checks"], "control": r.get("control"),
             "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
