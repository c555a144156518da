"""Optimizers (self-contained — no optax dependency).

* ``lars``  — Layer-wise Adaptive Rate Scaling [arXiv:1708.03888], the
  optimizer used by the paper (and Barlow Twins / VICReg).  Bias/norm
  parameters (ndim < 2) are excluded from adaptation + weight decay, as in
  the reference implementations.
* ``adamw`` — decoupled weight decay Adam; moment dtype configurable
  (bf16 moments halve optimizer HBM for the 100B+ archs — DESIGN.md §7).
* ``sgd_momentum``.

Interface: ``opt.init(params) -> state``; ``opt.update(grads, state, params,
lr) -> (new_params, new_state)``.  All pure pytree maps — shard-agnostic
(optimizer state inherits parameter sharding under pjit).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.obs import profiling

Array = jax.Array
PyTree = Any


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[PyTree], PyTree]
    update: Callable[[PyTree, PyTree, PyTree, Array], tuple[PyTree, PyTree]]
    name: str = "optimizer"

    def __post_init__(self):
        # every train step's update runs under the profiler's optimizer scope
        update = self.update

        def scoped(*args, **kwargs):
            with jax.named_scope(profiling.OPTIMIZER):
                return update(*args, **kwargs)

        object.__setattr__(self, "update", scoped)


def _tree_zeros_like(params, dtype=None):
    return jax.tree.map(lambda p: jnp.zeros_like(p, dtype=dtype or p.dtype), params)


def _is_adaptive(p: Array) -> bool:
    """LARS adaptation / weight decay applies to matrices, not bias/norm."""
    return p.ndim >= 2


# ---------------------------------------------------------------------------
# LARS (the paper's optimizer)
# ---------------------------------------------------------------------------


def lars(
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    trust_coefficient: float = 0.001,
    eps: float = 1e-8,
) -> Optimizer:
    def init(params):
        return {"mu": _tree_zeros_like(params, jnp.float32)}

    def update(grads, state, params, lr):
        def one(g, mu, p):
            g = g.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            if _is_adaptive(p):
                g = g + weight_decay * p32
                w_norm = jnp.linalg.norm(p32)
                g_norm = jnp.linalg.norm(g)
                trust = jnp.where(
                    (w_norm > 0) & (g_norm > 0),
                    trust_coefficient * w_norm / (g_norm + eps),
                    1.0,
                )
            else:
                trust = 1.0
            mu = momentum * mu + trust * g
            new_p = p32 - lr * mu
            return new_p.astype(p.dtype), mu

        flat = jax.tree.map(one, grads, state["mu"], params)
        new_params = jax.tree.map(lambda t: t[0], flat, is_leaf=lambda x: isinstance(x, tuple))
        new_mu = jax.tree.map(lambda t: t[1], flat, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"mu": new_mu}

    return Optimizer(init=init, update=update, name="lars")


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------


def adamw(
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.1,
    moment_dtype=jnp.float32,
) -> Optimizer:
    def init(params):
        return {
            "m": _tree_zeros_like(params, moment_dtype),
            "v": _tree_zeros_like(params, moment_dtype),
            "count": jnp.zeros((), jnp.int32),
        }

    def update(grads, state, params, lr):
        count = state["count"] + 1
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)

        def one(g, m, v, p):
            g32 = g.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * g32 * g32
            mh = m32 / c1
            vh = v32 / c2
            upd = mh / (jnp.sqrt(vh) + eps)
            if _is_adaptive(p):
                upd = upd + weight_decay * p.astype(jnp.float32)
            new_p = p.astype(jnp.float32) - lr * upd
            return new_p.astype(p.dtype), m32.astype(moment_dtype), v32.astype(moment_dtype)

        flat = jax.tree.map(one, grads, state["m"], state["v"], params)
        pick = lambda i: jax.tree.map(lambda t: t[i], flat, is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), {"m": pick(1), "v": pick(2), "count": count}

    return Optimizer(init=init, update=update, name="adamw")


def sgd_momentum(momentum: float = 0.9, weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        return {"mu": _tree_zeros_like(params, jnp.float32)}

    def update(grads, state, params, lr):
        def one(g, mu, p):
            g32 = g.astype(jnp.float32) + weight_decay * p.astype(jnp.float32)
            mu = momentum * mu + g32
            return (p.astype(jnp.float32) - lr * mu).astype(p.dtype), mu

        flat = jax.tree.map(one, grads, state["mu"], params)
        pick = lambda i: jax.tree.map(lambda t: t[i], flat, is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), {"mu": pick(1)}

    return Optimizer(init=init, update=update, name="sgd_momentum")


# ---------------------------------------------------------------------------
# Gradient utilities
# ---------------------------------------------------------------------------


def global_norm(tree) -> Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), norm


def warmup_cosine(lr: float, warmup_steps: int, total_steps: int, min_ratio: float = 0.01):
    """Linear warmup + cosine decay — the paper's schedule."""

    def schedule(step):
        step = jnp.asarray(step, jnp.float32)
        warm = lr * step / max(warmup_steps, 1)
        prog = jnp.clip(
            (step - warmup_steps) / max(total_steps - warmup_steps, 1), 0.0, 1.0
        )
        cos = lr * (min_ratio + (1 - min_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warmup_steps, warm, cos)

    return schedule
