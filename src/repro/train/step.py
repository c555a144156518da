"""Train-step factories.

``make_train_step`` builds the jit-able LM step:
  loss = CE + router-balance aux (MoE) + decorrelation aux (the paper's
  regularizer on final hidden states, core/decorrelation.py)

Features:
  * gradient accumulation: ``num_microbatches`` splits the per-step batch and
    accumulates grads in f32 under one ``lax.scan`` (required to fit the
    100B+ archs' activations; see DESIGN.md §7),
  * global-norm clipping,
  * deterministic per-step RNG (fold_in of step — restart-safe),
  * all cross-device reduction is implicit through pjit shardings; the
    explicit shard_map variant with compressed gradient all-reduce lives in
    ``make_compressed_dp_step``.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.decorrelation import lm_decorrelation_loss
from repro.models.common import ArchConfig
from repro.models.transformer import forward
from repro.optim.optimizers import Optimizer, clip_by_global_norm
from repro.parallel.sharding import on_mesh
from repro.train.train_state import TrainState

Array = jax.Array


def cross_entropy(logits: Array, labels: Array) -> Array:
    """Mean CE. logits (..., V) f32; labels (...) int32 (extra dims ok)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # a masked sum, not a gather: it needs no rule for where the vocab axis lives
    hit = labels[..., None] == jnp.arange(logits.shape[-1], dtype=labels.dtype)
    gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    return jnp.mean(logz - gold)


def _lm_loss_fn(params, batch, cfg: ArchConfig, rng: Array):
    kwargs = {}
    if "embeds" in batch:
        kwargs["embeds"] = batch["embeds"]
    else:
        kwargs["tokens"] = batch["tokens"]
    if "positions" in batch:
        kwargs["positions"] = batch["positions"]
    out = forward(params, cfg, **kwargs)
    ce = cross_entropy(out.logits, batch["labels"])
    decorr, dmetrics = lm_decorrelation_loss(out.hidden, cfg.decorr, perm_key=rng)
    moe_aux = out.aux["moe_aux"] * cfg.router_aux_weight
    loss = ce + decorr + moe_aux
    metrics = {"loss": loss, "ce": ce, "moe_aux": moe_aux, **dmetrics}
    return loss, metrics


def make_train_step(
    cfg: ArchConfig,
    optimizer: Optimizer,
    schedule: Callable[[Array], Array],
    num_microbatches: int = 1,
    clip_norm: Optional[float] = 1.0,
    loss_fn=None,
    grad_shardings=None,
):
    """``grad_shardings``: optional pytree of NamedShardings (matching
    params) to constrain the gradient ACCUMULATOR under microbatching.
    Without it the accumulator is replicated and GSPMD all-reduces every
    microbatch's full gradient; with it each microbatch reduce-scatters into
    the FSDP shards — 2x(data-1)/data less collective volume per microbatch
    (EXPERIMENTS.md §Perf, arctic cell)."""
    loss_fn = loss_fn or functools.partial(_lm_loss_fn, cfg=cfg)

    def _constrain(tree):
        if grad_shardings is None:
            return tree
        return jax.tree.map(
            lambda x, sh: jax.lax.with_sharding_constraint(x, sh), tree, grad_shardings
        )

    def train_step(state: TrainState, batch: Dict[str, Array]) -> Tuple[TrainState, Dict]:
        state = on_mesh(state)
        rng = jax.random.fold_in(state.rng, state.step)

        if num_microbatches <= 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch, rng=rng
            )
        else:
            def split(x):
                return x.reshape((num_microbatches, x.shape[0] // num_microbatches) + x.shape[1:])

            micro = jax.tree.map(split, batch)
            zero_g = _constrain(
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            )

            def body(acc, mb):
                g_acc, m_acc = acc
                (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params, mb, rng=rng
                )
                g_acc = _constrain(
                    jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                )
                m_acc = jax.tree.map(lambda a, b: a + b, m_acc, m)
                return (g_acc, m_acc), None

            mb0 = jax.tree.map(lambda x: x[0], micro)
            m_shapes = jax.eval_shape(
                lambda p, b: loss_fn(p, b, rng=rng)[1], state.params, mb0
            )
            zero_m = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), m_shapes)
            (grads, metrics), _ = jax.lax.scan(body, (zero_g, zero_m), micro)
            grads = jax.tree.map(lambda g: g / num_microbatches, grads)
            metrics = jax.tree.map(lambda m: m / num_microbatches, metrics)

        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            metrics["grad_norm"] = gnorm
        lr = schedule(state.step)
        metrics["lr"] = lr
        new_params, new_opt = optimizer.update(grads, state.opt_state, state.params, lr)
        return (
            TrainState(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                rng=state.rng,
            ),
            metrics,
        )

    return train_step


# ---------------------------------------------------------------------------
# Explicit-DP variant with compressed gradient all-reduce (shard_map)
# ---------------------------------------------------------------------------


def make_compressed_dp_step(
    loss_fn,
    optimizer: Optimizer,
    schedule,
    axis_name: str = "data",
    compression: str = "int8_ef",  # none | bf16 | int8_ef
):
    """Per-shard loss + explicit compressed psum of grads.  Used inside
    shard_map over the data axis; state.opt_state carries the error-feedback
    buffers for int8_ef."""
    from repro.optim import compression as comp

    def step(state: TrainState, batch, ef_errors):
        rng = jax.random.fold_in(state.rng, state.step)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch, rng=rng
        )
        if compression == "bf16":
            grads = comp.bf16_psum(grads, axis_name)
            grads = jax.tree.map(lambda g: g / jax.lax.psum(1, axis_name), grads)
        elif compression == "int8_ef":
            grads, ef_errors = comp.int8_psum_ef(grads, ef_errors, axis_name)
            grads = jax.tree.map(lambda g: g / jax.lax.psum(1, axis_name), grads)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis_name), grads)
        lr = schedule(state.step)
        new_params, new_opt = optimizer.update(grads, state.opt_state, state.params, lr)
        new_state = TrainState(state.step + 1, new_params, new_opt, state.rng)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axis_name), metrics)
        return new_state, metrics, ef_errors

    return step
