"""Smoke test of the SSL pre-training path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded step on four chips

With no option it builds ``examples/ssl_pretrain.py``'s default run at the
paper's width (3072 -> 4096 -> 4096 backbone, 8192 -> 8192 projector, batch
256, Barlow Twins with the grouped R_sum regularizer: q=2, b=128, feature
permutation, LARS), compiles its train step, checks that the step holds the
Pallas kernels, takes five steps through ``run_training`` and checks every
loss is finite.  It then computes the step-0 regularizer three ways (Pallas
route, jnp FFT route, the exact O(d^2) matrix form) and checks they agree.

``--four-chips`` runs only the sharded SSL step of ``docs/distributed.md`` at
the same width, in ``global`` mode over ``data=4`` and in ``tp`` mode over
``(data=2, model=2)``, and compares the loss and each gradient leaf with the
single-device step on the full batch, all under "highest" matmul precision.

The last line of standard output is one JSON object naming the device; it is
printed only when every check passed.  Without a TPU the script exits
non-zero: there is no CPU fallback.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import ssl_pretrain  # noqa: E402
from repro.core import permutation as perm_lib  # noqa: E402
from repro.core import regularizers as regs  # noqa: E402
from repro.decorr import engine  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.train import LoopConfig, run_training  # noqa: E402
from repro.train.ssl import (  # noqa: E402
    embed,
    make_sharded_ssl_train_step,
    make_ssl_train_step,
    shard_ssl_batch,
)

STEPS = 5
# The three step-0 regularizer routes share the same standardized, permuted
# embeddings and differ only in the regularizer's arithmetic, all of it f32
# (the Pallas kernels contract at full f32, the matrix form runs under
# "highest").  On a v5e the Pallas route read 0 and the jnp FFT route 2.1e-6
# from the exact form; a Pallas route that contracted at Mosaic's reduced
# default precision read 1.078e-3.  The bound sits ~50x above the sound
# readings and ~10x below that fault.
REG_RTOL = 1e-4
# Sharded vs single-device step, both traced under "highest" matmul precision
# so that only the order of f32 sums differs (psums of per-shard partials):
# this phase checks the sharding, while the one-chip phase checks precision.
# Loss as in the repo's CPU oracle test.  Each gradient leaf is measured
# against its own largest reference entry, so a leaf hundreds of times
# smaller than the others is held to its own scale; a missing or doubled
# psum, or half the batch, moves a leaf by 0.5 or more.  How far reordered
# f32 sums alone move a leaf is measured, not assumed: the single-device step
# on the batch with its rows reversed (the loss does not depend on row order)
# gives each leaf's reorder noise, which tells rounding from a fault (on a
# v5e: 3e-7 to 5e-7 in every leaf).  A leaf passes
# within REORDER_X times its noise (at least GRAD_RTOL); a leaf whose noise
# reaches MAX_NOISE could hide a fault and fails.  A leaf whose reference
# stays below ZERO_GRAD of the tree's largest entry is zero up to rounding
# (the projector's output bias, which standardization cancels) and has no
# scale of its own: it is listed, not compared.
LOSS_RTOL = 5e-4
GRAD_RTOL = 1e-3
REORDER_X = 10.0
MAX_NOISE = 5e-2
ZERO_GRAD = 1e-5


class SmokeFailure(RuntimeError):
    """A check of the smoke test did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _rel(a, b) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-12)


def one_chip() -> None:
    args = ssl_pretrain.parse_args(["--steps", str(STEPS)])
    run = ssl_pretrain.build(args)
    state0, batch0 = run.state, run.batch_fn(0)

    t0 = time.perf_counter()
    step = jax.jit(run.step_fn).lower(state0, batch0).compile()
    print(f"compile_s={time.perf_counter() - t0:.3f}")
    check("tpu_custom_call" in step.as_text(), "compiled train step holds no Pallas kernel")

    losses, regs_seen, stamps = [], [], [time.perf_counter()]

    def log_fn(i, m):
        stamps.append(time.perf_counter())
        losses.append(m["bt_loss"])
        regs_seen.append(m["bt_reg"])
        print(f"step={i} loss={m['bt_loss']!r} reg={m['bt_reg']!r} "
              f"step_s={stamps[-1] - stamps[-2]:.4f}")

    state = run_training(
        state0, step, run.batch_fn, LoopConfig(total_steps=STEPS, log_interval=1), log_fn=log_fn
    )
    check(int(state.step) == STEPS and len(losses) == STEPS, f"ran {int(state.step)} of {STEPS} steps")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")

    # step-0 regularizer three ways, on the embeddings the step itself saw
    cfg, n = run.loss_cfg, run.data.batch
    key = jax.random.fold_in(state0.rng, 0)

    @jax.jit
    def views(params, batch):
        z1 = engine.standardize(embed(params, batch["view1"]), cfg)
        z2 = engine.standardize(embed(params, batch["view2"]), cfg)
        return perm_lib.permute_views(key, z1, z2)

    z1, z2 = views(state0.params, batch0)
    b, q = cfg.block_size, cfg.q
    grouped = jax.jit(regs.r_sum_grouped, static_argnames=("block_size", "q", "scale", "impl"))
    pallas = grouped(z1, z2, block_size=b, q=q, scale=float(n), impl="pallas")
    fft = grouped(z1, z2, block_size=b, q=q, scale=float(n), impl="jnp")
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(
            lambda a, c: regs.r_sum_grouped_from_matrix(regs.cross_correlation_matrix(a, c, scale=float(n)), b, q)
        )(z1, z2)
    print(f"reg0 pallas={float(pallas)!r} jnp_fft={float(fft)!r} exact={float(exact)!r} "
          f"train_step={regs_seen[0]!r}")
    for name, v in (("pallas", pallas), ("jnp_fft", fft), ("train_step", regs_seen[0])):
        err = _rel(v, exact)
        print(f"reg0 {name} rel_err={err:.3e}")
        check(err < REG_RTOL, f"step-0 regularizer: {name} is {err:.3e} from the exact form")


def _leaf_errors(grads, ref_grads) -> dict:
    """Per-leaf max |g - r| over the leaf's max |r|; None for a leaf whose
    reference is zero up to rounding (below ZERO_GRAD of the tree's max)."""
    named = jax.tree_util.tree_flatten_with_path(ref_grads)[0]
    scale = max(float(jnp.max(jnp.abs(r))) for _, r in named)
    errs = {}
    for (path, r), g in zip(named, jax.tree.leaves(grads)):
        r_max = float(jnp.max(jnp.abs(r)))
        name = jax.tree_util.keystr(path)
        errs[name] = None if r_max < ZERO_GRAD * scale else float(jnp.max(jnp.abs(g - r))) / r_max
    return errs


def four_chips() -> None:
    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found {len(devices)}")
    args = ssl_pretrain.parse_args(["--steps", str(STEPS)])
    run = ssl_pretrain.build(args)
    params, batch = run.state.params, run.batch_fn(0)
    rng = jax.random.fold_in(run.state.rng, 0)
    local = dataclasses.replace(run.loss_cfg, distributed="local")

    _, loss_fn = make_ssl_train_step(run.model, local, run.opt, run.sched)
    ref_step = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        (ref_loss, _), ref_grads = ref_step(params, batch, rng)
        print(f"single_device loss={float(ref_loss)!r} s={time.perf_counter() - t0:.3f}")
        _, reversed_grads = ref_step(params, jax.tree.map(lambda v: v[::-1], batch), rng)
    noise = _leaf_errors(reversed_grads, ref_grads)

    for mode, shape, axes in (("global", (4,), ("data",)), ("tp", (2, 2), ("data", "model"))):
        mesh = make_mesh(shape, axes)
        cfg = dataclasses.replace(run.loss_cfg, distributed=mode)
        _, lag = make_sharded_ssl_train_step(run.model, cfg, run.opt, run.sched, mesh)
        sb = shard_ssl_batch(batch, mesh)
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            compiled = jax.jit(lag).lower(params, sb, rng).compile()
        compile_s = time.perf_counter() - t0
        hlo = compiled.as_text()
        check("tpu_custom_call" in hlo, f"{mode}: sharded step holds no Pallas kernel")
        check("all-reduce" in hlo, f"{mode}: sharded step has no cross-device reduction")
        t0 = time.perf_counter()
        loss, _, grads = compiled(params, sb, rng)
        jax.block_until_ready(grads)
        run_s = time.perf_counter() - t0

        # the work is spread over all four chips: the batch is split across
        # them and every output lives on the whole mesh
        shard_devs = {s.device for s in sb["view1"].addressable_shards}
        check(len(shard_devs) == 4, f"{mode}: batch shards on {len(shard_devs)} devices")
        rows = {s.data.shape[0] for s in sb["view1"].addressable_shards}
        check(rows == {batch["view1"].shape[0] // mesh.shape["data"]}, f"{mode}: shard rows {rows}")
        out_devs = set().union(*(g.sharding.device_set for g in jax.tree.leaves(grads)))
        check(out_devs == set(devices[:4]), f"{mode}: grads on {len(out_devs)} devices")

        loss_err = _rel(loss, ref_loss)
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices[:4]]
        print(f"{mode} mesh={dict(mesh.shape)} loss={float(loss)!r} loss_rel_err={loss_err:.3e} "
              f"compile_s={compile_s:.3f} run_s={run_s:.3f} peak_bytes_per_device={peaks}")
        check(loss_err < LOSS_RTOL, f"{mode}: loss {loss_err:.3e} from the single-device step")
        errs = _leaf_errors(grads, ref_grads)
        check(sum(e is None for e in errs.values()) <= 1, f"{mode}: leaves left uncompared: {errs}")
        for name, err in errs.items():
            if err is None:
                print(f"{mode} grad{name} zero up to rounding")
                continue
            bound = max(GRAD_RTOL, REORDER_X * noise[name])
            print(f"{mode} grad{name} rel_err={err:.3e} reorder_noise={noise[name]:.3e} bound={bound:.3e}")
            check(noise[name] < MAX_NOISE, f"{mode}: grad{name} reorder noise {noise[name]:.3e} could hide a fault")
            check(err < bound, f"{mode}: grad{name} is {err:.3e} from the single-device step")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded global/tp step on four chips")
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    print(f"device_kind={dev.device_kind} count={len(jax.devices())}")
    try:
        four_chips() if args.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
