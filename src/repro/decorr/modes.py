"""Distributed decorrelation primitives (DESIGN.md §4).

Three modes for computing the decorrelation statistics under SPMD:

``local``  (paper-faithful): every data shard computes the loss on its local
    batch slice; cross-device traffic is only the usual gradient all-reduce.
    This reproduces the paper's DDP implementation, which states "we do not
    conduct collective operations" in the loss.

``global`` (beyond-paper): the frequency accumulator
    ``G = sum_k conj(F a_k) o F b_k`` is an *additive* statistic of the batch,
    so a single psum of d/2+1 complex numbers (~4d bytes at fp32) turns the
    local regularizer into the exact global-batch regularizer.  The same
    trick applies to the per-feature moments used for standardization and to
    the diagonal statistics — everything the loss needs is O(d) additive.

``tp``     (feature-sharded): when the projector output dimension d itself is
    tensor-parallel over the ``model`` axis, the FFT spans shards.  We
    transpose batch<->feature with one all_to_all (each of the P model shards
    ends up with n/P full-length feature vectors), run shard-local FFTs, and
    psum the accumulator.  Communication: n*d/P elements per shard instead of
    an all-gather's n*d.

All functions here are meant to be called inside ``shard_map``.  The mode
*routing* (which of these a given ``DecorrConfig`` hits, plus normalization,
permutation and scale bookkeeping) lives in ``repro.decorr.engine``; this
module only owns the collective algebra.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import sumvec as sv

Array = jax.Array


# ---------------------------------------------------------------------------
# Small collective helpers
# ---------------------------------------------------------------------------


def _axis_size(axis_name) -> float:
    # psum of a Python int literal is constant-folded to the static axis
    # size under shard_map — no runtime collective is emitted.
    return float(jax.lax.psum(1, axis_name))


def psum_if(x: Array, axis_name: Optional[str]) -> Array:
    """psum over ``axis_name`` when given, identity otherwise."""
    if axis_name is None:
        return x
    return jax.lax.psum(x, axis_name)


def effective_batch(n_local: int, axis_name: Optional[str]) -> float:
    """Global batch size as a STATIC float (n_local when no axis)."""
    if axis_name is None:
        return float(n_local)
    return float(n_local) * _axis_size(axis_name)


def all_to_all_features(z: Array, model_axis) -> Array:
    """(n, d_local) -> (n/P, d): split batch, exchange, concat features.

    Requires features laid out contiguously by shard index along
    ``model_axis`` (the natural layout of a TP projector output).
    """
    return jax.lax.all_to_all(z, model_axis, split_axis=0, concat_axis=1, tiled=True)


# ---------------------------------------------------------------------------
# R_sum from (already reduced + normalized) frequency accumulators
# ---------------------------------------------------------------------------


def reg_from_freq(g: Array, d: int, q: int) -> Array:
    """R_sum from an (already normalized) frequency accumulator."""
    if q == 2:
        sq, s0 = sv.sq_sum_and_zeroth_from_freq(g, d)
        return sq - s0**2
    svec = jnp.fft.irfft(g, n=d, axis=-1)
    return jnp.sum(jnp.abs(svec[..., 1:]))


def grouped_reg_from_freq(g: Array, b: int, q: int) -> Array:
    nb = g.shape[0]
    eye = jnp.eye(nb, dtype=jnp.float32)
    if q == 2:
        sq, s0 = sv.sq_sum_and_zeroth_from_freq(g, b)
        return jnp.sum(sq) - jnp.sum(eye * s0**2)
    svec = jnp.fft.irfft(g, n=b, axis=-1)
    full = jnp.sum(jnp.abs(svec), axis=-1)
    return jnp.sum(full) - jnp.sum(eye * jnp.abs(svec[..., 0]))


def frequency_accumulator(
    z1: Array, z2: Array, block_size: Optional[int], *, impl: Optional[str] = None
) -> Array:
    """The additive statistic every distributed mode psums.

    Ungrouped (block covers d): jnp rfft accumulator, (d//2+1,) complex —
    the four-step Pallas pipeline is a *time-domain* algorithm and cannot
    expose a mid-pipeline frequency accumulator, so the distributed modes
    always take the jnp FFT here (O(n d log d); the psum'd statistic is
    identical).  Grouped: routes jnp vs the Pallas block-DFT pipeline via
    ``repro.tune.best_impl`` (shard-local shapes — exactly what each shard
    sees inside shard_map).
    """
    d = z1.shape[-1]
    if block_size is None or block_size >= d:
        return sv.frequency_accumulator(z1, z2)
    b = int(block_size)
    if impl is None:
        from repro.tune import dispatch as tune_dispatch

        impl = tune_dispatch.best_impl("r_sum_grouped")
    from repro.kernels.grouped_sumvec import ops as gops

    if impl == "pallas" and b <= d and gops.fits(d, b):
        g_r, g_i = gops.grouped_frequency_accumulator_kernel(z1, z2, b)
        # kernel layout (nf, nb, nb) -> core layout (nb, nb, nf)
        return jnp.transpose(jax.lax.complex(g_r, g_i), (1, 2, 0))
    return sv.grouped_frequency_accumulator(z1, z2, b)


# ---------------------------------------------------------------------------
# Mode primitives (compat surface of the old core/distributed.py)
# ---------------------------------------------------------------------------


def r_sum_global(
    z1: Array,
    z2: Array,
    *,
    axis_name,
    q: int = 2,
    block_size: Optional[int] = None,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
) -> Array:
    """Exact global-batch R_sum with one psum of the frequency accumulator.

    ``z1, z2``: the *local* (n_local, d) shard of the standardized/centered
    views.  ``scale``: the *local* normalizer (n_local or n_local - 1); it is
    multiplied by the axis size so the result matches a single-device run on
    the concatenated batch.  (The engine passes exact global scales instead —
    see ``engine._distributed_regularizer``.)
    """
    p = _axis_size(axis_name)
    s = (1.0 if scale is None else scale) * p
    return r_sum_from_psummed(z1, z2, axis_name, q=q, block_size=block_size, total_scale=s, impl=impl)


def r_sum_from_psummed(
    z1: Array,
    z2: Array,
    axis_name,
    *,
    q: int,
    block_size: Optional[int],
    total_scale,
    impl: Optional[str] = None,
) -> Array:
    """R_sum of the psum'd accumulator with an explicit TOTAL normalizer."""
    d = z1.shape[-1]
    g = frequency_accumulator(z1, z2, block_size, impl=impl)
    g = psum_if(g, axis_name) / jnp.asarray(total_scale, jnp.float32).astype(g.dtype)
    if block_size is None or block_size >= d:
        return reg_from_freq(g, d, q)
    return grouped_reg_from_freq(g, int(block_size), q)


def r_sum_tp(
    z1: Array,
    z2: Array,
    *,
    model_axis,
    batch_axis=None,
    q: int = 2,
    block_size: Optional[int] = None,
    scale: Optional[float] = None,
    perm_key: Optional[Array] = None,
    impl: Optional[str] = None,
) -> Array:
    """R_sum when the feature dim is sharded over ``model_axis``.

    Inside shard_map each shard holds (n, d_local) with d = P * d_local and
    features laid out contiguously by shard index.  One tiled all_to_all
    converts to (n / P, d) full-feature rows, then the computation proceeds
    as in ``global`` mode with the accumulator psum'd over the model axis
    (batch chunks) and, if given, the batch axis (data parallel shards).

    ``perm_key``: optional feature permutation applied to the full-feature
    rows after the transpose — the same key on every shard yields the exact
    permutation a single-device run would apply to the unsharded d.
    """
    from repro.core import permutation as perm_lib

    same = z1 is z2
    z1f = all_to_all_features(z1.astype(jnp.float32), model_axis)
    z2f = z1f if same else all_to_all_features(z2.astype(jnp.float32), model_axis)
    if perm_key is not None:
        z1f, z2f = perm_lib.permute_views(perm_key, z1f, z2f)
    d = z1f.shape[-1]

    g = frequency_accumulator(z1f, z2f, block_size, impl=impl)
    g = jax.lax.psum(g, model_axis)
    s = jnp.asarray(1.0 if scale is None else scale, jnp.float32)
    if batch_axis is not None:
        g = jax.lax.psum(g, batch_axis)
        s = s * _axis_size(batch_axis)
    g = g / s.astype(g.dtype)

    if block_size is None or block_size >= d:
        return reg_from_freq(g, d, q)
    return grouped_reg_from_freq(g, int(block_size), q)


def r_off_global(
    z1: Array,
    z2: Array,
    *,
    axis_name,
    total_scale,
) -> Array:
    """Exact global-batch R_off via one psum of the d x d accumulator.

    This is O(d^2) traffic — the baseline's irreducible cost, kept for
    apples-to-apples comparisons; the R_sum modes above are the O(d) path.
    """
    from repro.core import regularizers as regs

    c = z1.astype(jnp.float32).T @ z2.astype(jnp.float32)
    c = psum_if(c, axis_name) / jnp.asarray(total_scale, jnp.float32)
    return regs.r_off(c)


# ---------------------------------------------------------------------------
# Reference: what a single device computes on the concatenated global batch.
# Used by tests to check the distributed modes bit-for-bit (up to fp assoc).
# ---------------------------------------------------------------------------


def r_sum_single_device(z1, z2, *, q=2, block_size=None, scale=None):
    from repro.core import regularizers as regs

    return regs.r_sum_auto(z1, z2, q=q, block_size=block_size, scale=scale)
