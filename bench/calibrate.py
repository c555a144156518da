"""Readings that the limits of ``correct`` are set from, for one cell, in one process.

    python3 bench/calibrate.py --workload bt-sum-d8192.dev-b256 \
        --seeds 101 102 103 --control-seeds 201 202 203 --fault-seeds 301 302 303

For every seed it builds the cell's state and traffic, drives the first steps
through the program's own calls as a run's set-up does, and compares them with
the plain reference (``sound``).  It also reads, against the same reference:

- ``control``: the reference itself in the program's place, computed one
  precision below the configuration's (bfloat16 in place of float32);
- ``half_batch``: the program with half of each batch left out, the mean taken
  over the rest;
- ``reg_grad_zero`` and ``reg_grad_double``: the program with the gradient that
  flows through its regularizer zeroed or doubled, its loss unchanged.

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
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def half_batch(prog, n: int):
    """The program's step with the second half of every batch left out."""
    return SimpleNamespace(make_state=prog.make_state,
                           step=lambda s, b: prog.step(s, {k: v[: n // 2] for k, v in b.items()}))


def reg_grad(prog, scale: float):
    """The program's step with the gradient through the regularizer times
    ``scale``: the loss ``make_ssl_train_step`` differentiates becomes
    ``L + lam (scale - 1) (R - stop_gradient(R))``, equal to ``L`` in value.
    The step's loss is looked up in ``repro.train.ssl`` when it is traced, so
    it is swapped for the trace alone."""
    import jax

    import repro.train.ssl as ssl

    def faulty(z1, z2, cfg, perm_key=None):
        loss, metrics = real(z1, z2, cfg, perm_key=perm_key)
        r = metrics[f"{cfg.style}_reg"]
        return loss + cfg.lam * (scale - 1.0) * (r - jax.lax.stop_gradient(r)), metrics

    real = ssl.ssl_loss

    def step_fn(state, batch):
        ssl.ssl_loss = faulty
        try:
            return prog.step_fn(state, batch)
        finally:
            ssl.ssl_loss = real

    return SimpleNamespace(make_state=prog.make_state, step=jax.jit(step_fn))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[],
                    help="seeds for each fault: half_batch, reg_grad_zero, reg_grad_double")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench import cells, correct, harness, program
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = cells.load_cell(args.workload)
    n = int(cell.traffic["batch"])
    prog = program.build(cell.config, n)
    kinds = {"sound": (args.seeds, prog), "half_batch": (args.fault_seeds, half_batch(prog, n)),
             "reg_grad_zero": (args.fault_seeds, reg_grad(prog, 0.0)),
             "reg_grad_double": (args.fault_seeds, reg_grad(prog, 2.0)),
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
