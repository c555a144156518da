"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Full-size configs target the production mesh (run under a real TPU runtime;
on this container use --reduced, which runs the same code path on 1 CPU
device).  The paper's decorrelation aux loss is enabled with --decorr.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.decorrelation import LMDecorrConfig
from repro.core.losses import DecorrConfig
from repro.data import LMDataConfig, lm_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.obs_args import (
    add_obs_args,
    attach_train_step,
    build_train_obs,
    finish_train_obs,
)
from repro.models import init_params
from repro.optim import adamw, warmup_cosine
from repro.train import LoopConfig, create_train_state, make_train_step, run_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--decorr", action="store_true", help="enable the paper's aux loss")
    ap.add_argument("--decorr-block", type=int, default=None)
    ap.add_argument(
        "--pretune",
        default="analytic",
        choices=["off", "analytic", "dry", "measure"],
        help="warm the repro.tune cache for the decorr kernel shapes before "
        "the first step is traced (ROADMAP: tune-cache warm-up hook)",
    )
    ap.add_argument("--seed", type=int, default=0)
    add_obs_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.decorr:
        cfg = dataclasses.replace(
            cfg,
            decorr=LMDecorrConfig(
                enabled=True,
                decorr=DecorrConfig(style="vic", reg="sum", block_size=args.decorr_block, q=2),
                nu=0.04,
            ),
        )

    print(f"[train] arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"devices={len(jax.devices())}")
    if args.decorr and args.pretune != "off":
        from repro.decorr import warmup_tune_cache

        # the aux-loss statistic has batch * tokens_per_seq rows of width
        # d_model — pre-tune those shapes so the first jitted step is warm.
        t_tune = time.time()
        n_jobs = len(warmup_tune_cache(
            args.batch * cfg.decorr.tokens_per_seq, cfg.d_model, cfg.decorr.decorr,
            mode=args.pretune,
        ))
        print(f"[train] pre-tuned {n_jobs} decorr kernel shapes "
              f"({args.pretune}, {time.time()-t_tune:.1f}s)")
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    opt = adamw()
    sched = warmup_cosine(args.lr, max(args.steps // 10, 1), args.steps)
    state = create_train_state(params, opt, seed=args.seed)
    step_fn = jax.jit(make_train_step(cfg, opt, sched, num_microbatches=args.microbatches))

    dcfg = LMDataConfig(
        vocab_size=cfg.vocab_size,
        batch=args.batch,
        seq_len=args.seq,
        seed=args.seed,
        n_codebooks=cfg.n_codebooks if cfg.frontend == "audio_codes" else 0,
    )

    def batch_fn(step):
        b = lm_batch(dcfg, step)
        out = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.frontend == "vision_stub":
            # frontend stub: tokens -> pseudo patch embeddings + M-RoPE ids
            tok = out.pop("tokens")
            key = jax.random.fold_in(jax.random.PRNGKey(dcfg.seed), step)
            out["embeds"] = jax.random.normal(key, (*tok.shape, cfg.d_model), jnp.float32) * 0.02
            pos = jnp.arange(tok.shape[1], dtype=jnp.int32)[None, None, :]
            out["positions"] = jnp.broadcast_to(pos, (3, *tok.shape))
        return out

    lcfg = LoopConfig(
        total_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_interval=args.ckpt_interval,
        log_interval=max(args.steps // 10, 1),
    )

    t0 = time.time()

    def log_fn(step, m):
        print(f"  step {step:5d} loss={m.get('loss', 0):.4f} ce={m.get('ce', 0):.4f} "
              f"decorr={m.get('decorr_aux', 0):.5f} ({time.time()-t0:.1f}s)")

    obs = build_train_obs(args)
    if obs is not None:
        attach_train_step(obs, step_fn, state, batch_fn(0))
    state = run_training(
        state, step_fn, batch_fn, lcfg, log_fn=log_fn,
        registry=obs.registry if obs is not None else None,
        perf=obs.perf if obs is not None else None,
    )
    print(f"[train] done at step {int(state.step)} in {time.time()-t0:.1f}s")
    finish_train_obs(args, obs)


if __name__ == "__main__":
    main()
