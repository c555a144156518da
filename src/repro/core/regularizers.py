"""Decorrelating regularizers.

Baselines (paper §3):
  * ``r_off``  — Barlow Twins / VICReg off-diagonal penalty, Eq. (2).  O(n d^2).
  * ``r_var``  — VICReg variance hinge, Eq. (4).  O(n d).

Proposed (paper §4):
  * ``r_sum``          — Eq. (6), FFT path, O(n d log d).
  * ``r_sum_grouped``  — Eq. (13), block size b, O((n d^2 / b) log b).

Both proposed regularizers take the *embeddings* (already standardized or
centered by the caller), never a materialized correlation matrix.  For q=2
the sums of squares are evaluated directly in the frequency domain via
Parseval (beyond-paper; skips the inverse FFT — see DESIGN.md §3.3); for q=1
the inverse transform is required because the l1 norm is not a frequency-
domain quantity.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import sumvec as sv
from repro.tune import dispatch as tune_dispatch

Array = jax.Array


# ---------------------------------------------------------------------------
# Baseline regularizers (matrix route)
# ---------------------------------------------------------------------------


def r_off(m: Array) -> Array:
    """Eq. (2): sum of squared off-diagonal elements."""
    total = jnp.sum(m.astype(jnp.float32) ** 2)
    diag = jnp.sum(jnp.diagonal(m).astype(jnp.float32) ** 2)
    return total - diag


def r_var(m: Array, gamma: float = 1.0, eps: float = 1e-4) -> Array:
    """Eq. (4): hinge on per-feature standard deviation (diagonal of K)."""
    std = jnp.sqrt(jnp.clip(jnp.diagonal(m).astype(jnp.float32), 0.0) + eps)
    return jnp.sum(jnp.maximum(0.0, gamma - std))


def r_var_from_embeddings(z: Array, gamma: float = 1.0, eps: float = 1e-4) -> Array:
    """Variance hinge straight from (n, d) embeddings — O(n d)."""
    var = jnp.var(z.astype(jnp.float32), axis=0, ddof=1)
    std = jnp.sqrt(var + eps)
    return jnp.sum(jnp.maximum(0.0, gamma - std))


def cross_correlation_matrix(z1: Array, z2: Array, scale: Optional[float] = None) -> Array:
    """C = (1/scale) Z1^T Z2 — caller standardizes/centers first. O(n d^2)."""
    n = z1.shape[0]
    c = z1.astype(jnp.float32).T @ z2.astype(jnp.float32)
    return c / (n if scale is None else scale)


# ---------------------------------------------------------------------------
# Proposed regularizers (paper Eq. 6 / Eq. 13)
# ---------------------------------------------------------------------------


def _resolve_impl(op: str, q: int, impl: Optional[str]) -> str:
    """Shared q/impl validation + backend routing for r_sum / r_sum_grouped."""
    if q not in (1, 2):
        raise ValueError(f"q must be 1 or 2, got {q!r}")
    if impl is None:
        impl = tune_dispatch.best_impl(op)
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"impl must be 'jnp' or 'pallas', got {impl!r}")
    return impl


def r_sum_from_sumvec(svec: Array, q: int) -> Array:
    """Eq. (6) given a precomputed summary vector (drops component 0)."""
    tail = svec[..., 1:]
    if q == 1:
        return jnp.sum(jnp.abs(tail))
    return jnp.sum(tail**2)


def r_sum(
    z1: Array,
    z2: Array,
    *,
    q: int = 2,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
) -> Array:
    """Eq. (6) computed via FFT directly from embeddings.

    ``z1, z2`` : (n, d) standardized (BT-style) or centered (VICReg-style,
    with z1 is z2) views. ``scale``: normalizer s of C (n or n-1).
    ``impl``: None consults ``repro.tune`` (jnp FFT off-TPU, Pallas four-step
    on TPU); "jnp" / "pallas" pin the route.
    """
    d = z1.shape[-1]
    s = 1.0 if scale is None else float(scale)
    impl = _resolve_impl("r_sum", q, impl)
    if impl == "pallas":
        from repro.kernels.sumvec_fft import ops as fops

        return fops.r_sum_fourstep(z1, z2, q=q, scale=s)
    if q == 2:
        # Parseval path — no inverse FFT (beyond-paper optimization).
        g = sv.frequency_accumulator(z1, z2) / s
        sq, s0 = sv.sq_sum_and_zeroth_from_freq(g, d)
        return sq - s0**2
    svec = sv.sumvec_fft(z1, z2, scale=s)
    return r_sum_from_sumvec(svec, q)


def r_sum_grouped(
    z1: Array,
    z2: Array,
    block_size: int,
    *,
    q: int = 2,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
) -> Array:
    """Eq. (13): grouped summary regularizer with block size b.

    Diagonal blocks drop their component 0 (the trace entries of C);
    off-diagonal blocks keep all b components (they contain only
    off-diagonal elements of C).  ``impl`` as in :func:`r_sum`.
    """
    b = int(block_size)
    s = 1.0 if scale is None else float(scale)
    impl = _resolve_impl("r_sum_grouped", q, impl)
    # b > d means "pad d up to b" here (matching the matrix oracle), but the
    # Pallas kernel clamps b to d — route the degenerate case through jnp on
    # every backend so the loss value never depends on hardware.  So do
    # shapes whose batch tile the kernels cannot hold in VMEM.
    from repro.kernels.grouped_sumvec import ops as gops

    d = z1.shape[-1]
    if impl == "pallas" and b <= d and gops.fits(d, b):
        # one view twice (VICReg): the kernel transforms it once
        return gops.r_sum_kernel(z1, None if z2 is z1 else z2, block_size=b, q=q, scale=s)
    g = sv.grouped_frequency_accumulator(z1, z2, b) / s  # (nb, nb, nf)
    nb = g.shape[0]
    eye = jnp.eye(nb, dtype=jnp.float32)
    if q == 2:
        sq, s0 = sv.sq_sum_and_zeroth_from_freq(g, b)  # (nb, nb) each
        # all blocks: full Parseval energy; diagonal blocks: subtract s0^2.
        return jnp.sum(sq) - jnp.sum(eye * s0**2)
    svec = jnp.fft.irfft(g, n=b, axis=-1)  # (nb, nb, b)
    full = jnp.sum(jnp.abs(svec), axis=-1)  # includes component 0
    zeroth = jnp.abs(svec[..., 0])
    return jnp.sum(full) - jnp.sum(eye * zeroth)


def r_sum_auto(
    z1: Array,
    z2: Array,
    *,
    q: int = 2,
    block_size: Optional[int] = None,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
) -> Array:
    """Dispatch between grouped / ungrouped forms (b = None or b >= d ==> Eq. 6).

    ``impl`` forwards to :func:`r_sum` / :func:`r_sum_grouped` (None consults
    ``repro.tune``); the degenerate b <= 1 matrix route ignores it.
    """
    d = z1.shape[-1]
    if block_size is None or block_size >= d:
        return r_sum(z1, z2, q=q, scale=scale, impl=impl)
    if block_size <= 1:
        # R_sum^(1) with q=2 is exactly R_off (paper §4.4); compute the
        # matrix route for fidelity at this degenerate setting.
        c = cross_correlation_matrix(z1, z2, scale=scale)
        if q == 2:
            return r_off(c)
        off = jnp.sum(jnp.abs(c)) - jnp.sum(jnp.abs(jnp.diagonal(c)))
        return off
    return r_sum_grouped(z1, z2, block_size, q=q, scale=scale, impl=impl)


# ---------------------------------------------------------------------------
# Oracle forms (used by tests/benchmarks only)
# ---------------------------------------------------------------------------


def r_sum_from_matrix(c: Array, q: int = 2) -> Array:
    """Eq. (6) by explicitly building sumvec(C) from the matrix."""
    return r_sum_from_sumvec(sv.sumvec_from_matrix(c), q)


def r_sum_grouped_from_matrix(c: Array, block_size: int, q: int = 2) -> Array:
    """Eq. (13) from an explicit matrix (oracle)."""
    blocks = sv.grouped_sumvec_from_matrix(c, block_size)  # (nb, nb, b)
    nb = blocks.shape[0]
    if q == 1:
        vals = jnp.abs(blocks)
    else:
        vals = blocks**2
    full = jnp.sum(vals, axis=-1)
    zeroth = vals[..., 0]
    eye = jnp.eye(nb, dtype=vals.dtype)
    return jnp.sum(full) - jnp.sum(eye * zeroth)
