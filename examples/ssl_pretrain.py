"""End-to-end SSL pretraining driver (the paper's training setup, scaled to
this container) with the full production envelope: sharded-ready step,
checkpoint/restart, preemption flag, straggler watchdog.

Default config is a ~100M-parameter backbone+projector trained for a few
hundred steps — the assignment's end-to-end driver.  Use --tiny for a
seconds-scale run.

    PYTHONPATH=src python examples/ssl_pretrain.py --tiny
    PYTHONPATH=src python examples/ssl_pretrain.py \
        --steps 300 --ckpt-dir /tmp/ssl_ckpt          # ~100M params
    # kill it mid-run and re-run: it resumes from the newest checkpoint.
    # distributed (shard_map over all local devices; see docs/distributed.md):
    PYTHONPATH=src python examples/ssl_pretrain.py --tiny --distributed global
    PYTHONPATH=src python examples/ssl_pretrain.py --tiny --distributed tp \
        --model-parallel 2
"""

import argparse
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from repro.core.losses import DecorrConfig, normalized_bt_regularizer
from repro.data import SSLDataConfig, ssl_batch
from repro.decorr import warmup_tune_cache
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh_for_devices
from repro.launch.obs_args import add_obs_args, build_train_obs, finish_train_obs
from repro.optim import lars, warmup_cosine
from repro.train import LoopConfig, create_train_state, run_training
from repro.train.ssl import (
    SSLModelConfig,
    embed,
    init_ssl_params,
    make_sharded_ssl_train_step,
    make_ssl_train_step,
    shard_ssl_batch,
)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--d", type=int, default=8192, help="projector width (paper: 8192)")
    ap.add_argument("--block-size", type=int, default=128)
    ap.add_argument("--reg", default="sum", choices=["sum", "off"])
    ap.add_argument("--no-permute", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--preempt-flag", default=None)
    ap.add_argument(
        "--distributed",
        default=None,
        choices=["local", "global", "tp"],
        help="run the step under shard_map over all local devices "
        "(decorr engine mode; default: single-device step)",
    )
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="model-axis size for --distributed tp")
    add_obs_args(ap)
    ap.add_argument(
        "--pretune",
        default="analytic",
        choices=["off", "analytic", "dry", "measure"],
        help="warm the repro.tune cache for the shard-local decorr shapes "
        "before the first step is traced",
    )
    return ap.parse_args(argv)


def build(args: argparse.Namespace) -> SimpleNamespace:
    """Everything ``main`` trains with: model, data, loss config, optimizer,
    schedule, initial state, the un-jitted train step and the batch
    function (``mesh`` is None for the single-device step)."""
    if args.tiny:
        model = SSLModelConfig(input_dim=256, backbone_widths=(128,), projector_widths=(256, 256))
        data = SSLDataConfig(input_dim=256, batch=128)
        args.steps = min(args.steps, 120)
    else:
        # ~100M params: 3072 -> 4096 -> 4096 backbone, d-wide projector
        model = SSLModelConfig(
            input_dim=3072,
            backbone_widths=(4096, 4096),
            projector_widths=(args.d, args.d),
        )
        data = SSLDataConfig(input_dim=3072, batch=args.batch)

    n_params = sum(
        a * b
        for a, b in zip(
            (model.input_dim,) + model.backbone_widths + (model.backbone_widths[-1],) + model.projector_widths[:-1],
            model.backbone_widths + (model.backbone_widths[-1],) + model.projector_widths,
        )
    )
    print(f"[ssl_pretrain] ~{n_params/1e6:.1f}M params, d={model.projector_widths[-1]}, "
          f"batch={data.batch}, reg={args.reg}, permute={not args.no_permute}")

    loss_cfg = DecorrConfig(
        style="bt", reg=args.reg, q=2,
        block_size=args.block_size if args.reg == "sum" else None,
        lam=2.0**-10, permute=not args.no_permute,
        distributed=args.distributed or "local",
    )
    params = init_ssl_params(jax.random.PRNGKey(0), model)
    opt = lars(weight_decay=1e-4)  # the paper's optimizer
    state = create_train_state(params, opt)
    sched = warmup_cosine(0.2, max(args.steps // 10, 1), args.steps)

    mesh = None
    if args.distributed is not None:
        mesh = make_mesh_for_devices(len(jax.devices()), args.model_parallel)
        print(f"[ssl_pretrain] mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
              f"mode={args.distributed}")
        step_fn, _ = make_sharded_ssl_train_step(model, loss_cfg, opt, sched, mesh)
    else:
        step_fn, _ = make_ssl_train_step(model, loss_cfg, opt, sched)

    if args.pretune != "off":
        # warm the kernel-config cache for the SHARD-LOCAL shapes so the
        # first jitted step doesn't pay the search (ROADMAP open item).
        t_tune = time.time()
        n_jobs = len(warmup_tune_cache(
            data.batch, model.projector_widths[-1], loss_cfg,
            mesh=mesh, mode=args.pretune,
        ))
        print(f"[ssl_pretrain] pre-tuned {n_jobs} kernel shapes "
              f"({args.pretune}, {time.time()-t_tune:.1f}s)")

    def batch_fn(step):
        v1, v2 = ssl_batch(data, step)
        b = {"view1": jnp.asarray(v1), "view2": jnp.asarray(v2)}
        return shard_ssl_batch(b, mesh) if mesh is not None else b

    return SimpleNamespace(
        model=model, data=data, loss_cfg=loss_cfg, opt=opt, sched=sched,
        state=state, step_fn=step_fn, batch_fn=batch_fn, mesh=mesh,
    )


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    run = build(args)
    state, batch_fn = run.state, run.batch_fn
    step_fn = jax.jit(run.step_fn)

    t0 = time.time()

    def log_fn(step, m):
        loss_key = next(k for k in m if k.endswith("loss"))
        print(f"  step {step:5d}  loss={m[loss_key]:10.4f}  "
              f"({(time.time()-t0):6.1f}s, stragglers={m.get('stragglers', 0)})")

    lcfg = LoopConfig(
        total_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_interval=max(args.steps // 6, 10),
        log_interval=max(args.steps // 15, 1),
        preempt_flag=args.preempt_flag,
    )
    obs = build_train_obs(args)
    monitor = None
    if obs is not None:
        from repro.obs import DecorrHealthMonitor

        # probe the projector output of view1 — the matrix the decorrelation
        # objective acts on — for collapse / relaxation-gap health
        monitor = DecorrHealthMonitor(lambda params, batch: embed(params, batch["view1"]))
    state = run_training(
        state, step_fn, batch_fn, lcfg, log_fn=log_fn,
        registry=obs.registry if obs is not None else None,
        monitor=monitor,
    )
    finish_train_obs(args, obs)

    v1, v2 = ssl_batch(run.data, 10_000)
    q16 = normalized_bt_regularizer(
        embed(state.params, jnp.asarray(v1)), embed(state.params, jnp.asarray(v2))
    )
    print(f"[ssl_pretrain] final step={int(state.step)}  "
          f"normalized R_off (Eq.16) = {float(q16):.4f}  total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
