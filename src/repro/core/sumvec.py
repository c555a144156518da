"""Summary-vector (``sumvec``) primitives — the paper's Eq. (5)–(12).

The summary vector of a square matrix ``C`` collects its "wrapped diagonals"::

    [sumvec(C)]_i = sum_j C[j, (i + j) mod d]          (Eq. 5)

Component 0 is the trace; components 1..d-1 partition the off-diagonal
elements (every element of C appears in exactly one component).

The key identity (Eq. 10/12): when ``C = (1/s) * sum_k a_k b_k^T`` the summary
vector equals an average of circular correlations, computable **without
materializing C** via the convolution theorem::

    sumvec(C) = (1/s) * F^-1( sum_k conj(F(a_k)) o F(b_k) )

which is O(n d log d) time and O(n d) space, versus O(n d^2) / O(n d + d^2)
for the matrix route.

All functions are pure-jnp and jit/vjp friendly.  FFT work is done in float32
regardless of input dtype (correlation statistics are long reductions and
bf16 accumulation destroys them); see DESIGN.md §6.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# ---------------------------------------------------------------------------
# Basic building blocks (Eq. 5, Eq. 7, involution)
# ---------------------------------------------------------------------------


def involution(x: Array) -> Array:
    """inv(x): reverse components 1..d-1, keep component 0 (paper §4.2).

    ``[inv(x)]_i = [x]_{(d - i) mod d}``. Works on the last axis.
    """
    d = x.shape[-1]
    idx = (-jnp.arange(d)) % d
    return x[..., idx]


def circular_convolve(x: Array, y: Array) -> Array:
    """Circular convolution x * y along the last axis (Eq. 7). O(d^2) naive."""
    d = x.shape[-1]
    i = jnp.arange(d)[:, None]
    j = jnp.arange(d)[None, :]
    # [x * y]_i = sum_j x_j y_{(i-j) mod d}
    gather = (i - j) % d
    return jnp.einsum("...j,...ij->...i", x, y[..., gather])


def circular_correlate_naive(x: Array, y: Array) -> Array:
    """inv(x) * y along last axis via the direct O(d^2) sum (Appendix A).

    ``[inv(x) * y]_i = sum_j x_j y_{(i+j) mod d}``.
    """
    d = x.shape[-1]
    i = jnp.arange(d)[:, None]
    j = jnp.arange(d)[None, :]
    gather = (i + j) % d
    return jnp.einsum("...j,...ij->...i", x, y[..., gather])


def sumvec_from_matrix(c: Array) -> Array:
    """Eq. (5): summary vector of a square matrix. O(d^2); reference path."""
    d = c.shape[-1]
    i = jnp.arange(d)[:, None]  # output component
    j = jnp.arange(d)[None, :]  # row index
    cols = (i + j) % d  # shape (d, d): column gathered for (i, j)
    # sumvec[i] = sum_j C[j, cols[i, j]]
    return jnp.sum(c[..., j, cols], axis=-1)


# ---------------------------------------------------------------------------
# FFT path (Eq. 12) — the paper's contribution
# ---------------------------------------------------------------------------


@jax.custom_vjp
def rfft(x: Array) -> Array:
    """``jnp.fft.rfft`` along the last axis, differentiable inside ``shard_map``.

    JAX's own rfft transpose re-traces a naive rfft on a dummy primal that
    drops the varying-manual-axes type, so its VJP fails under ``shard_map``
    for any shard-varying input.  The transpose is written out instead: with
    ``A[f, t] = exp(-2 pi i f t / d)``, ``x_bar = Re(A^T t_bar)``, which is
    ``d * irfft(conj(t_bar) * m)`` where ``m`` halves the bins that the full
    spectrum holds twice (all but DC and, for even d, Nyquist).
    """
    return jnp.fft.rfft(x, axis=-1)


def _rfft_fwd(x):
    return rfft(x), x.shape[-1]


def _rfft_bwd(d, t):
    m = 1.0 / rfft_parseval_weights(d)
    return (d * jnp.fft.irfft(jnp.conj(t) * m, n=d, axis=-1),)


rfft.defvjp(_rfft_fwd, _rfft_bwd)


def frequency_accumulator(z1: Array, z2: Array, *, precision_dtype=jnp.float32) -> Array:
    """``G = sum_k conj(F(z1_k)) o F(z2_k)`` — rfft bins, complex64.

    ``z1, z2``: (n, d). Returns (d//2 + 1,) complex. This is the only
    batch-dependent work in the FFT path; everything downstream is O(d).
    In the distributed ``global`` mode this accumulator is what gets psum'd
    (see core/distributed.py).
    """
    z1 = z1.astype(precision_dtype)
    z2 = z2.astype(precision_dtype)
    f1 = rfft(z1)
    f2 = rfft(z2)
    return jnp.sum(jnp.conj(f1) * f2, axis=0)


def sumvec_fft(z1: Array, z2: Array, *, scale: Optional[float] = None) -> Array:
    """Eq. (12): sumvec of the (scaled) sum of outer products, via FFT.

    ``z1, z2``: (n, d) — row k holds a^(k) resp. b^(k).
    ``scale``: divisor ``s`` in ``C = (1/s) sum_k a_k b_k^T``; defaults to 1
    (caller applies its own normalization, e.g. n for BT, n-1 for VICReg).
    Returns the d-vector sumvec(C) in float32.
    """
    d = z1.shape[-1]
    g = frequency_accumulator(z1, z2)
    sv = jnp.fft.irfft(g, n=d, axis=-1)
    if scale is not None:
        sv = sv / scale
    return sv


def sumvec_direct(z1: Array, z2: Array, *, scale: Optional[float] = None) -> Array:
    """Eq. (10): sumvec via per-sample circular correlation. O(n d^2) oracle."""
    cc = circular_correlate_naive(z1.astype(jnp.float32), z2.astype(jnp.float32))
    sv = jnp.sum(cc, axis=0)
    if scale is not None:
        sv = sv / scale
    return sv


# ---------------------------------------------------------------------------
# Grouped (block) path — paper §4.4
# ---------------------------------------------------------------------------


def pad_to_blocks(z: Array, block_size: int) -> Array:
    """Pad trailing feature dim with zeros to a multiple of ``block_size``.

    Paper §4.4 footnote: "pad dummy features that are constantly 0 in the
    last group".  Padding is applied AFTER standardization/centering so the
    dummy features contribute exactly zero to every correlation.
    """
    d = z.shape[-1]
    rem = (-d) % block_size
    if rem == 0:
        return z
    pad = [(0, 0)] * (z.ndim - 1) + [(0, rem)]
    return jnp.pad(z, pad)


def blockify(z: Array, block_size: int) -> Array:
    """(n, d) -> (n, d/b, b) after zero padding."""
    z = pad_to_blocks(z, block_size)
    n = z.shape[0]
    return z.reshape(n, -1, block_size)


def grouped_frequency_accumulator(
    z1: Array, z2: Array, block_size: int, *, precision_dtype=jnp.float32
) -> Array:
    """``G[i, j, f] = sum_k conj(F(a_k,i))[f] * F(b_k,j)[f]`` for all block
    pairs (i, j).

    ``z1, z2``: (n, d). Returns (nb, nb, b//2+1) complex64 where
    nb = ceil(d / b).  Cost: O(n d log b) for the FFTs + O(n (d/b)^2 b) for
    the pairwise products — the paper's O((n d^2 / b) log b) with the log
    factor moved into an MXU-friendly batched contraction over n (this einsum
    is a batch of (nb x n) @ (n x nb) complex matmuls, one per frequency bin;
    the Pallas kernel in kernels/grouped_sumvec tiles exactly this).
    """
    b1 = blockify(z1.astype(precision_dtype), block_size)
    b2 = blockify(z2.astype(precision_dtype), block_size)
    f1 = rfft(b1)  # (n, nb, nf)
    f2 = rfft(b2)
    return jnp.einsum("kif,kjf->ijf", jnp.conj(f1), f2)


def grouped_sumvec_fft(
    z1: Array, z2: Array, block_size: int, *, scale: Optional[float] = None
) -> Array:
    """sumvec(C_ij) for every b x b block of C. Returns (nb, nb, b)."""
    g = grouped_frequency_accumulator(z1, z2, block_size)
    sv = jnp.fft.irfft(g, n=block_size, axis=-1)
    if scale is not None:
        sv = sv / scale
    return sv


def grouped_sumvec_from_matrix(c: Array, block_size: int) -> Array:
    """Oracle: blockify a full matrix C and sumvec each block. (nb, nb, b)."""
    d = c.shape[-1]
    rem = (-d) % block_size
    if rem:
        c = jnp.pad(c, ((0, rem), (0, rem)))
    nb = c.shape[-1] // block_size
    blocks = c.reshape(nb, block_size, nb, block_size).transpose(0, 2, 1, 3)
    return jax.vmap(jax.vmap(sumvec_from_matrix))(blocks)


# ---------------------------------------------------------------------------
# Parseval shortcuts (beyond-paper; DESIGN.md §3.3)
# ---------------------------------------------------------------------------


def rfft_parseval_weights(d: int) -> np.ndarray:
    """w_f such that sum_t s[t]^2 = (1/d) sum_f w_f |S_rfft[f]|^2.

    A NumPy constant: it carries no sharding, so it mixes with arrays on any
    mesh (Explicit axes included) without a device assignment of its own.
    """
    w = np.full((d // 2 + 1,), 2.0, dtype=np.float32)
    w[0] = 1.0
    if d % 2 == 0:
        w[-1] = 1.0
    return w


def sq_sum_and_zeroth_from_freq(g: Array, d: int) -> tuple[Array, Array]:
    """Given rfft-domain G (last axis = bins) of a real signal s of length d,
    return (sum_t s[t]^2, s[0]) computed WITHOUT an inverse transform.

    sum_t s[t]^2 = (1/d) sum_f w_f |G_f|^2           (Parseval)
    s[0]         = (1/d) sum_f w_f Re(G_f)           (DC synthesis)
    """
    w = rfft_parseval_weights(d)
    sq = jnp.sum(w * (g.real**2 + g.imag**2), axis=-1) / d
    s0 = jnp.sum(w * g.real, axis=-1) / d
    return sq, s0
