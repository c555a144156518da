"""The paper's own setting: Siamese MLP backbone + projector, trained with
Barlow Twins-style / VICReg-style losses (baseline R_off or proposed R_sum).

The backbone is deliberately simple (the paper's contribution is the loss,
not the ResNet); the projector is the standard 3-layer MLP with BN-like
standardization handled inside the loss.  ``make_ssl_train_step`` plugs into
the same optimizer/checkpoint machinery as the LM path.

``make_sharded_ssl_train_step`` is the mesh-aware variant: the loss+grad
computation runs under ``shard_map`` with the batch data-parallel over the
``data`` axis and — in the engine's ``tp`` mode — the projector OUTPUT layer
feature-sharded over the ``model`` axis, so each shard only materializes
(n_local, d / P) projections and the engine's all_to_all transpose does the
rest.  Partition specs come from ``parallel/sharding.py`` logical axes
("batch", "feature").
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.losses import DecorrConfig, ssl_loss
from repro.obs import profiling
from repro.optim.optimizers import Optimizer, clip_by_global_norm
from repro.parallel import sharding as shd
from repro.train.train_state import TrainState

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SSLModelConfig:
    input_dim: int = 3072
    backbone_widths: Tuple[int, ...] = (512, 512)
    projector_widths: Tuple[int, ...] = (2048, 2048, 2048)


def init_ssl_params(key: Array, cfg: SSLModelConfig) -> Dict:
    params = {"backbone": [], "projector": []}
    dims_b = (cfg.input_dim,) + cfg.backbone_widths
    dims_p = (cfg.backbone_widths[-1],) + cfg.projector_widths
    keys = jax.random.split(key, len(dims_b) + len(dims_p))
    ki = 0
    for i in range(len(dims_b) - 1):
        w = jax.random.normal(keys[ki], (dims_b[i], dims_b[i + 1]), jnp.float32)
        params["backbone"].append(
            {"w": w / jnp.sqrt(dims_b[i]), "b": jnp.zeros((dims_b[i + 1],))}
        )
        ki += 1
    for i in range(len(dims_p) - 1):
        w = jax.random.normal(keys[ki], (dims_p[i], dims_p[i + 1]), jnp.float32)
        params["projector"].append(
            {"w": w / jnp.sqrt(dims_p[i]), "b": jnp.zeros((dims_p[i + 1],))}
        )
        ki += 1
    return params


def backbone_apply(params: Dict, x: Array) -> Array:
    h = x
    for layer in params["backbone"]:
        h = jax.nn.relu(h @ layer["w"] + layer["b"])
    return h


def projector_apply(params: Dict, h: Array) -> Array:
    n = len(params["projector"])
    for i, layer in enumerate(params["projector"]):
        h = h @ layer["w"] + layer["b"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def embed(params: Dict, x: Array) -> Array:
    with jax.named_scope(profiling.ENCODER):
        return projector_apply(params, backbone_apply(params, x))


def make_ssl_train_step(
    model_cfg: SSLModelConfig,
    loss_cfg: DecorrConfig,
    optimizer: Optimizer,
    schedule,
    clip_norm=None,
):
    def loss_fn(params, batch, rng):
        v1, v2 = batch["view1"], batch["view2"]
        z1 = embed(params, v1)
        z2 = embed(params, v2)
        loss, metrics = ssl_loss(z1, z2, loss_cfg, perm_key=rng)
        return loss, metrics

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        rng = jax.random.fold_in(state.rng, state.step)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch, rng
        )
        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            metrics["grad_norm"] = gnorm
        lr = schedule(state.step)
        metrics["lr"] = lr
        new_params, new_opt = optimizer.update(grads, state.opt_state, state.params, lr)
        return TrainState(state.step + 1, new_params, new_opt, state.rng), metrics

    return train_step, loss_fn


# ---------------------------------------------------------------------------
# Mesh-aware variant: loss + grads under shard_map
# ---------------------------------------------------------------------------


def ssl_param_specs(model_cfg: SSLModelConfig, loss_cfg: DecorrConfig, mesh: Mesh):
    """PartitionSpec tree for ``init_ssl_params`` output.

    Everything is replicated except — in ``tp`` mode — the projector OUTPUT
    layer, whose weight columns / bias are feature-sharded over the logical
    "feature" axis (-> "model" mesh axis per ``parallel/sharding.py`` rules).
    """
    with shd.sharding_context(mesh):
        w_spec = shd.logical_to_spec((None, "feature"))
        b_spec = shd.logical_to_spec(("feature",))
    specs = {
        "backbone": [{"w": P(), "b": P()} for _ in model_cfg.backbone_widths],
        "projector": [{"w": P(), "b": P()} for _ in model_cfg.projector_widths],
    }
    if loss_cfg.distributed == "tp":
        specs["projector"][-1] = {"w": w_spec, "b": b_spec}
    return specs


def make_sharded_ssl_train_step(
    model_cfg: SSLModelConfig,
    loss_cfg: DecorrConfig,
    optimizer: Optimizer,
    schedule,
    mesh: Mesh,
    clip_norm=None,
    data_axis: str = "data",
    model_axis: str = "model",
):
    """``make_ssl_train_step`` running end-to-end under ``shard_map``.

    The batch is data-parallel over ``data_axis`` in every mode.  The loss
    semantics follow ``loss_cfg.distributed``:

      * ``local``  — each data shard computes the paper-faithful shard-local
        loss; grads (and reported metrics) are the DDP mean over shards.
      * ``global`` — the engine psums the O(d) accumulators, so loss and
        grads equal a single-device run on the full concatenated batch.
      * ``tp``     — additionally the projector output layer (and hence z)
        is feature-sharded over ``model_axis``; the engine's all_to_all
        transpose + psums reassemble the exact unsharded loss.

    The permutation key is computed OUTSIDE shard_map and passed in
    replicated, so every shard applies the identical feature permutation.
    Returns ``(train_step, loss_and_grads)`` where ``loss_and_grads(params,
    batch, rng) -> (loss, metrics, grads)`` (grads already cross-shard
    reduced; jit it for repeated use).
    """
    if data_axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no data axis {data_axis!r}")
    tp = loss_cfg.distributed == "tp"
    if tp:
        if model_axis not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no model axis {model_axis!r}")
        d_out = model_cfg.projector_widths[-1]
        p_model = int(mesh.shape[model_axis])
        if d_out % p_model:
            raise ValueError(f"projector width {d_out} not divisible by model={p_model}")

    cfg = loss_cfg
    if cfg.distributed in ("global", "tp"):
        cfg = dataclasses.replace(cfg, axis_name=data_axis)
    if tp:
        cfg = dataclasses.replace(cfg, model_axis=model_axis)
    mode = cfg.distributed

    pspecs = ssl_param_specs(model_cfg, loss_cfg, mesh)
    with shd.sharding_context(mesh):
        batch_spec = shd.logical_to_spec(("batch", None))

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(pspecs, {"view1": batch_spec, "view2": batch_spec}, P()),
        out_specs=(P(), P()),
    )
    def sharded_loss(params, batch, rng):
        z1 = embed(params, batch["view1"])
        z2 = embed(params, batch["view2"])
        loss, metrics = ssl_loss(z1, z2, cfg, perm_key=rng)
        if mode == "local":
            # DDP objective: the mean over shard-local losses.
            loss, metrics = jax.tree.map(
                lambda x: jax.lax.pmean(x, data_axis), (loss, metrics)
            )
        # metrics are reporting-only; detaching them keeps shard_map's
        # transpose free of symbolic-Zero cotangents on collective outputs.
        return loss, jax.lax.stop_gradient(metrics)

    def loss_and_grads(params, batch, rng):
        # Differentiating THROUGH shard_map (rather than per-shard inside it)
        # makes JAX's collective transposes accumulate each parameter's
        # cotangent across shards with exactly the loss's own semantics — no
        # hand-rolled grad psums to keep in sync with the engine's modes.
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: sharded_loss(p, batch, rng), has_aux=True
        )(params)
        return loss, metrics, grads

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        rng = jax.random.fold_in(state.rng, state.step)
        loss, metrics, grads = loss_and_grads(state.params, batch, rng)
        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            metrics["grad_norm"] = gnorm
        lr = schedule(state.step)
        metrics["lr"] = lr
        new_params, new_opt = optimizer.update(grads, state.opt_state, state.params, lr)
        return TrainState(state.step + 1, new_params, new_opt, state.rng), metrics

    return train_step, loss_and_grads


def shard_ssl_batch(batch: Dict[str, Array], mesh: Mesh) -> Dict[str, Array]:
    """device_put a {view1, view2} batch with its data-parallel sharding."""
    with shd.sharding_context(mesh):
        spec = shd.logical_to_spec(("batch", None))
    sh = NamedSharding(mesh, spec)
    return {k: jax.device_put(v, sh) for k, v in batch.items()}
