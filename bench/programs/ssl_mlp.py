"""The system under test, built from a configuration file as
``examples/ssl_pretrain.build`` builds it: the MLP encoder of ``repro.train.ssl``,
the loss through ``repro.decorr.engine``, LARS with ``warmup_cosine``, the
jitted step of ``make_ssl_train_step`` and ``run_training`` as the loop.

``ssl_pretrain.build`` fixes its model and takes no seed, so this module
repeats its few lines of wiring for a configuration file.  It stands in until
the program has one function, called by both, that makes the step and its
state from a configuration and a seed.  Weights and optimizer state are made
on the device in one jitted call from the seed.  What a program module
provides is in ``bench/programs/__init__.py``.

Operations a step requires count matrix products only (2 per multiply-add);
nothing recomputed counts.  Encoder, per view: forward ``2 n sum(a_i b_i)``
over the layers, backward twice that less the first layer's unneeded input
gradient.  R_off: ``6 n d^2``.  Grouped R_sum (Eq. 13): per view a real DFT of
every b-block as one product with the (b, b + 2) basis, ``2 n d (b + 2)``, the
cross-spectra over nb x nb block pairs and nf = b/2 + 1 frequencies,
``8 n nb^2 nf``; backward the spectra's two input gradients and the DFT's; for
q = 1 the synthesis, ``6 nb^2 nf b`` in all.  VICReg (``style`` "vic") applies
the regularizer to each view against itself: two calls of one DFT each.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp

import repro.train.ssl as ssl_train
from repro.core.losses import DecorrConfig
from repro.decorr import warmup_tune_cache
from repro.optim import lars, warmup_cosine
from repro.train import LoopConfig, create_train_state, run_training
from repro.train.ssl import SSLModelConfig, init_ssl_params, make_ssl_train_step

__all__ = ["LoopConfig", "batch_spec", "build", "faults", "regularizer_flops", "run_training", "step_flops",
           "tiny"]

VIEWS = ("view1", "view2")


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

    # the logged loss is <style>_loss; the first gradient as LARS keeps it, its momentum
    return SimpleNamespace(step_fn=step_fn, step=jax.jit(step_fn), make_state=make_state,
                           loss_key=f"{cfg['style']}_loss", first_gradient=lambda state: state.opt_state["mu"])


def batch_spec(cfg: dict, traffic: dict) -> dict:
    """One step's batch: two (batch, input_dim) float32 views."""
    shape = (int(traffic["batch"]), int(cfg["input_dim"]))
    return {v: jax.ShapeDtypeStruct(shape, jnp.float32) for v in VIEWS}


def layers(cfg: dict) -> list[tuple[int, int]]:
    dims = [int(cfg["input_dim"])] + [int(w) for w in cfg["backbone_widths"]]
    dims += [int(cfg["projector_width"])] * int(cfg["projector_layers"])
    return list(zip(dims[:-1], dims[1:]))


def param_count(cfg: dict) -> int:
    return sum(a * b + b for a, b in layers(cfg))


def encoder_flops(cfg: dict, n: int) -> int:
    ab = layers(cfg)
    per_view = 6 * n * sum(a * b for a, b in ab) - 2 * n * ab[0][0] * ab[0][1]
    return 2 * per_view


def regularizer_flops(cfg: dict, n: int) -> int:
    d = int(cfg["projector_width"])
    calls, views = (2, 1) if cfg["style"] == "vic" else (1, 2)
    if cfg["reg"] == "off":
        return calls * 6 * n * d * d
    b = int(cfg["block_size"])
    nb, nf = -(-d // b), b // 2 + 1
    per_call = views * 2 * (2 * n * nb * b * (b + 2)) + 24 * n * nb * nb * nf
    if int(cfg["q"]) == 1:
        per_call += 6 * nb * nb * nf * b
    return calls * per_call


def step_flops(cfg: dict, n: int) -> int:
    """Required operations of one train step at batch ``n``."""
    return encoder_flops(cfg, n) + regularizer_flops(cfg, n)


def _with_step(prog: SimpleNamespace, step) -> SimpleNamespace:
    return SimpleNamespace(**dict(vars(prog), step=step))


def half_batch(prog: SimpleNamespace, n: int) -> SimpleNamespace:
    """The program's step with the second half of every batch left out."""
    return _with_step(prog, lambda s, b: prog.step(s, {k: v[: n // 2] for k, v in b.items()}))


def reg_grad(prog: SimpleNamespace, scale: float) -> SimpleNamespace:
    """The program's step with the gradient through the regularizer times
    ``scale``: the loss becomes ``L + lam (scale - 1) (R - stop_gradient(R))``,
    equal to ``L`` in value, swapped in ``repro.train.ssl`` for the trace alone."""

    def faulty(z1, z2, cfg, perm_key=None):
        loss, metrics = real(z1, z2, cfg, perm_key=perm_key)
        r = metrics[f"{cfg.style}_reg"]
        return loss + cfg.lam * (scale - 1.0) * (r - jax.lax.stop_gradient(r)), metrics

    real = ssl_train.ssl_loss

    def step_fn(state, batch):
        ssl_train.ssl_loss = faulty
        try:
            return prog.step_fn(state, batch)
        finally:
            ssl_train.ssl_loss = real

    return _with_step(prog, jax.jit(step_fn))


def faults(prog: SimpleNamespace, n: int) -> dict:
    """Half of each batch left out; the regularizer's gradient zeroed or doubled."""
    return {"half_batch": half_batch(prog, n), "reg_grad_zero": reg_grad(prog, 0.0),
            "reg_grad_double": reg_grad(prog, 2.0)}


def tiny(cfg: dict, traffic: dict) -> tuple[dict, dict]:
    """The same configuration and traffic with small widths and batch."""
    cfg = dict(cfg, input_dim=32, backbone_widths=[32], projector_width=512)
    if cfg["reg"] == "sum":
        cfg["block_size"] = 32
    traffic = dict(traffic, batch=16, log_every=5)
    if "pool" in traffic:
        traffic["pool"] = 4
    return cfg, traffic
