"""Feature permutation (paper §4.3).

Random feature permutation applied identically to both views every training
step.  Rationale (paper): minimizing R_sum with fixed feature order solves an
under-determined homogeneous system (d-1 equations, d(d-1) unknowns); each
fresh permutation contributes a new set of equations, eventually ruling out
the non-trivial (badly-correlated) solutions.

SPMD notes (beyond the paper, which ran DDP with per-process host RNG):
  * The permutation MUST be identical across data shards when the ``global``
    distributed mode is used — otherwise the psum'd frequency accumulator
    mixes incompatible orderings.  We therefore derive the permutation from a
    step-keyed PRNG (`jax.random.fold_in(seed_key, step)`) that every shard
    computes identically; no communication needed.
  * The permutation is sampled *inside* jit — `jax.random.permutation` on an
    iota is a lowered sort, O(d log d), negligible next to the loss.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def permutation_for_step(key: Array, step: Array | int, d: int) -> Array:
    """Deterministic permutation of [0, d) for a given (key, step)."""
    k = jax.random.fold_in(key, jnp.asarray(step, dtype=jnp.uint32))
    return jax.random.permutation(k, d)


def permute_features(z: Array, perm: Array) -> Array:
    """Apply a feature permutation along the last axis."""
    return jnp.take(z, perm, axis=-1)


def permute_views(
    key: Optional[Array], z1: Array, z2: Optional[Array] = None
) -> Tuple[Array, Optional[Array]]:
    """Sample one permutation and apply it to both views (paper Listing 1).

    ``key=None`` disables permutation (ablation arm).
    """
    if key is None:
        return z1, z2
    d = z1.shape[-1]
    perm = jax.random.permutation(key, d)
    z1p = permute_features(z1, perm)
    if z2 is z1:  # one view (VICReg's R of one view) stays one array
        return z1p, z1p
    z2p = permute_features(z2, perm) if z2 is not None else None
    return z1p, z2p
