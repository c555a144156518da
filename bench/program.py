"""The system under test, built from a configuration file as
``examples/ssl_pretrain.build`` builds it: the MLP encoder of ``repro.train.ssl``,
the loss through ``repro.decorr.engine``, LARS with ``warmup_cosine``, the
jitted step of ``make_ssl_train_step`` and ``run_training`` as the loop.

``ssl_pretrain.build`` fixes its model and takes no seed, so this module
repeats its few lines of wiring for a configuration file.  It stands in until
the program has one function, called by both, that makes the step and its
state from a configuration and a seed.  Weights and optimizer state are made
on the device in one jitted call from the seed.  This module is the only one of
the benchmark that imports the program.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp

from repro.core.losses import DecorrConfig
from repro.decorr import warmup_tune_cache
from repro.optim import lars, warmup_cosine
from repro.train import LoopConfig, create_train_state, run_training
from repro.train.ssl import SSLModelConfig, init_ssl_params, make_ssl_train_step

__all__ = ["LoopConfig", "build", "run_training"]


def model_config(cfg: dict) -> SSLModelConfig:
    return SSLModelConfig(
        input_dim=int(cfg["input_dim"]),
        backbone_widths=tuple(int(w) for w in cfg["backbone_widths"]),
        projector_widths=(int(cfg["projector_width"]),) * int(cfg["projector_layers"]),
    )


def loss_config(cfg: dict) -> DecorrConfig:
    return DecorrConfig(
        style=cfg["style"], reg=cfg["reg"], q=int(cfg["q"]),
        block_size=int(cfg["block_size"]) if cfg["reg"] == "sum" else None,
        lam=float(cfg["lam"]), permute=bool(cfg["permute"]), eps=float(cfg["eps"]),
    )


def build(cfg: dict, batch: int) -> SimpleNamespace:
    """The train step (``step_fn``, and ``step`` jitted as ``ssl_pretrain.main``
    jits it) and the jitted maker of its initial state.

    ``make_state(key, perm_key)`` returns the TrainState: weights from ``key``
    by ``init_ssl_params``, zero LARS momentum, and ``perm_key`` as the key the
    step folds its step number into for the feature permutation.
    """
    if cfg["precision"]["matmul"] != "default" or cfg["precision"]["params"] != "float32":
        raise ValueError(f"the program runs float32 parameters at default matmul precision, "
                         f"not {cfg['precision']}")
    model, loss = model_config(cfg), loss_config(cfg)
    o, s = cfg["optimizer"], cfg["schedule"]
    opt = lars(momentum=o["momentum"], weight_decay=o["weight_decay"],
               trust_coefficient=o["trust_coefficient"], eps=o["eps"])
    sched = warmup_cosine(s["lr"], s["warmup_steps"], s["total_steps"], s["min_ratio"])
    step_fn, _ = make_ssl_train_step(model, loss, opt, sched)
    warmup_tune_cache(batch, model.projector_widths[-1], loss, mode="analytic")

    @jax.jit
    def make_state(key, perm_key):
        state = create_train_state(init_ssl_params(key, model), opt)
        return state._replace(rng=perm_key)

    return SimpleNamespace(step_fn=step_fn, step=jax.jit(step_fn), make_state=make_state)


@jax.jit
def leaf_norms(tree):
    """Per-leaf float32 L2 norms, in ``jax.tree.leaves`` order."""
    return [jnp.linalg.norm(x.astype(jnp.float32)) for x in jax.tree.leaves(tree)]


@jax.jit
def change(new, old):
    """Per-leaf float32 ``new - old``, in ``jax.tree.leaves`` order."""
    return [a.astype(jnp.float32) - b.astype(jnp.float32)
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]
