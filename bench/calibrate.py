"""Readings that the limits of ``correct`` are set from, for one cell, in one process.

    python3 bench/calibrate.py --workload bt-sum-d8192.dev-b256 \
        --seeds 101 102 103 --control-seeds 201 202 203 --fault-seeds 301 302 303

For every seed it builds the cell's state and traffic, drives the first steps
through the program's own calls as a run's set-up does, and compares them with
the plain reference (``sound``).  It also reads, against the same reference:

- ``control``: the reference itself in the program's place, computed one
  precision below the configuration's (bfloat16 in place of float32);
- each fault of the cell's program module (``faults(prog, n)``), on the fault
  seeds; for ``ssl_mlp``: ``half_batch``, half of each batch left out and the
  mean taken over the rest, and ``reg_grad_zero`` and ``reg_grad_double``, the
  gradient that flows through the regularizer zeroed or doubled, the loss
  unchanged.

A step that returns its state unchanged reads 1 on ``change_gap`` by
construction and needs no run.  Each reading is one JSON line on standard
output; the last line sums them up: per number, the largest sound reading and
the smallest reading of the control and of each fault.  The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[],
                    help="seeds for each of the program's faults")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench import cells, correct, harness
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = cells.load_cell(args.workload)
    n = int(cell.traffic["batch"])
    program = cells.program(cell)
    prog = program.build(cell.config, n)
    kinds = {"sound": (args.seeds, prog),
             **{name: (args.fault_seeds, p) for name, p in program.faults(prog, n).items()},
             "control": (args.control_seeds, prog)}
    summary: dict = {}
    for kind, (seeds, p) in kinds.items():
        for seed in seeds:
            run = harness.Run(cell, seed, p, harness.Spans(annotate=False))
            if kind == "control":
                run.state = None
                read = run.reference(dtype=jnp.bfloat16, precision="default")
            else:
                read = run.first_steps()
                run.state = None
            ref = run.reference()
            nums = correct.numbers(read, ref)
            read.pop("delta"), ref.pop("delta")
            print(json.dumps({"kind": kind, "seed": seed, **nums, "read": read, "ref": ref}), flush=True)
            pick = max if kind == "sound" else min
            for k, v in nums.items():
                summary.setdefault(kind, {})[k] = pick(summary.get(kind, {}).get(k, v), v)
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
