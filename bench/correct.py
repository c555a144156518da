"""The comparison that decides ``correct``: the program's first steps against
the plain reference.

Four numbers, each with a limit of its own (``bench/limits/<cell>.json``):

- ``loss_gap``: the largest ``|L - L_ref| / |L_ref|`` over the first steps;
- ``grad_gap``: the worst leaf's ``| |g| - |g_ref| |`` over ``max(|g_ref|, median
  leaf |g_ref|)``, where g is the first gradient as the optimizer keeps it
  after one step (the program's ``first_gradient``; LARS's momentum);
- ``change_gap``: the same measure on the parameters' change over the first
  steps, ``p_3 - p_0``;
- ``direction_gap``: the worst leaf's ``1 - cos`` between the program's change
  ``p_3 - p_0`` and the reference's.  LARS sets a weight matrix's step to
  ``0.001 |w|`` whatever its gradient, so the norms above see a matrix's
  gradient only through how its steps add up; the direction sees the gradient
  itself.

Leaves whose first raw gradient in the reference is under ``ZERO_GRAD`` of the
median leaf's are left out of both leaf measures: they move by round-off alone
(the projector's output bias, which standardization cancels).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ZERO_GRAD = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "change_gap", "direction_gap")


@jax.jit
def leaf_norms(tree):
    """Per-leaf float32 L2 norms, in ``jax.tree.leaves`` order."""
    return [jnp.linalg.norm(x.astype(jnp.float32)) for x in jax.tree.leaves(tree)]


@jax.jit
def change(new, old):
    """Per-leaf float32 ``new - old``, in ``jax.tree.leaves`` order."""
    return [a.astype(jnp.float32) - b.astype(jnp.float32)
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]


@jax.jit
def cosines(a: list, b: list) -> list:
    """Per-leaf cosine between two lists of float32 arrays, each product and
    sum in float32 (no matrix unit, so no reduced-precision pass)."""
    return [jnp.sum(x * y) / (jnp.sqrt(jnp.sum(x * x)) * jnp.sqrt(jnp.sum(y * y))) for x, y in zip(a, b)]


def _worst(gaps) -> float:
    """The largest gap; NaN, from a non-finite reading, counts as infinite."""
    return max(g if g == g else math.inf for g in gaps)


def _leaf_gap(prog: list, ref: list, keep: list) -> float:
    floor = float(np.median([r for r, k in zip(ref, keep) if k]))
    return _worst(abs(p - r) / max(abs(r), floor) for p, r, k in zip(prog, ref, keep) if k)


def numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``loss``, ``grad``, ``change`` (norms) and
    ``delta`` (the change's leaves as float32 arrays); ``ref`` also
    ``grad_raw``.  A non-finite reading gives an infinite gap."""
    raw = np.asarray(ref["grad_raw"])
    keep = list(raw >= ZERO_GRAD * float(np.median(raw)))
    loss_gap = _worst(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    cos = [float(c) for c in cosines(prog["delta"], ref["delta"])]
    return {
        "loss_gap": loss_gap if len(prog["loss"]) == len(ref["loss"]) else math.inf,
        "grad_gap": _leaf_gap(prog["grad"], ref["grad"], keep),
        "change_gap": _leaf_gap(prog["change"], ref["change"], keep),
        "direction_gap": _worst(1.0 - c for c, k in zip(cos, keep) if k),
    }


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, per number, its value beside its limit."""
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in NUMBERS if k in limits}
    ok = len(checks) == len(NUMBERS) and all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
