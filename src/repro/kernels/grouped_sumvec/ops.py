"""jit'd wrappers for the grouped-sumvec Pallas kernels.

Pipeline (all MXU work, fully differentiable — every Pallas primitive carries
a custom_vjp whose backward is the same kernels):

  Z (n, d) --blockify--> (n, nb, b)
    --pmatmul with [Cr | Ci] (block DFT)--> F_r, F_i (n, nb, nf)
    --transpose--> (nf, n, nb)
    --freq_outer x2--> G_r, G_i (nf, nb, nb)      # "compressed outer product"
    --q=2: Parseval in jnp (O(nb^2 nf));  q=1: pmatmul with synthesis basis

Complexity: O(n d b) for the DFT + O(n (d/b)^2 b) for the pairwise stage
— the paper's O((n d^2 / b) log b) with log b traded for an MXU-resident b.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sumvec import rfft_parseval_weights
from repro.kernels.grouped_sumvec import kernel as K
from repro.kernels.pallas_utils import dft_matrices, irfft_basis
from repro.tune import space as tune_space

Array = jax.Array


def auto_block_size(d: int, prefer: int = 128) -> int:
    """A tuned default block size b for width d: the largest legal candidate
    <= ``prefer``.

    The paper (Fig. 3) finds b = 128 is the accuracy sweet spot — also
    exactly one MXU tile; widths below ``prefer`` get b = d (ungrouped,
    Eq. 6).  Note b is part of the LOSS definition — this helper is for
    call sites choosing a b (the CLI pre-tuner, configs), never silently
    applied inside ``r_sum_kernel``.
    """
    legal = tune_space.grouped_block_size_candidates(d)
    return max(b for b in legal if b <= prefer)


def _blockify(z: Array, b: int) -> Array:
    n, d = z.shape
    rem = (-d) % b
    if rem:
        z = jnp.pad(z, ((0, 0), (0, rem)))
    return z.reshape(n, -1, b)


def block_dft(z: Array, b: int) -> tuple[Array, Array]:
    """Per-block rfft of (n, d) via one MXU matmul. Returns (nf, n, nb) x2."""
    zb = _blockify(z.astype(jnp.float32), b)
    n, nb, _ = zb.shape
    nf = b // 2 + 1
    cr, ci = dft_matrices(b)
    basis = jnp.concatenate([cr, ci], axis=1)  # (b, 2 nf)
    f = K.pmatmul(zb.reshape(n * nb, b), basis)  # (n*nb, 2 nf)
    f = f.reshape(n, nb, 2 * nf)
    fr = jnp.transpose(f[..., :nf], (2, 0, 1))  # (nf, n, nb)
    fi = jnp.transpose(f[..., nf:], (2, 0, 1))
    return fr, fi


def grouped_frequency_accumulator_kernel(
    z1: Array, z2: Array, block_size: int
) -> tuple[Array, Array]:
    """G[i,j,f] = sum_k conj(F1[k,i,f]) F2[k,j,f], returned as (nf, nb, nb)
    real/imag pair.  Matches core.sumvec.grouped_frequency_accumulator
    (transposed to frequency-major layout)."""
    b = int(block_size)
    f1r, f1i = block_dft(z1, b)
    f2r, f2i = block_dft(z2, b)
    # G_r = F1r^T F2r + F1i^T F2i ; G_i = F1r^T F2i - F1i^T F2r  (per f)
    a_r = jnp.concatenate([f1r, f1i], axis=1)
    b_r = jnp.concatenate([f2r, f2i], axis=1)
    g_r = K.freq_outer(a_r, b_r)
    a_i = jnp.concatenate([f1r, -f1i], axis=1)
    b_i = jnp.concatenate([f2i, f2r], axis=1)
    g_i = K.freq_outer(a_i, b_i)
    return g_r, g_i


@functools.partial(jax.jit, static_argnames=("block_size", "q", "scale"))
def r_sum_kernel(
    z1: Array,
    z2: Array,
    *,
    block_size: Optional[int],
    q: int = 2,
    scale: Optional[float] = None,
) -> Array:
    """Eq. (13) (or Eq. 6 when block covers d) through the Pallas pipeline."""
    d = z1.shape[-1]
    b = int(block_size) if block_size is not None else d
    b = min(b, d)
    s = 1.0 if scale is None else float(scale)
    g_r, g_i = grouped_frequency_accumulator_kernel(z1, z2, b)
    g_r = g_r / s
    g_i = g_i / s
    nf, nb, _ = g_r.shape
    w = rfft_parseval_weights(b)[:, None, None]
    eye = jnp.eye(nb, dtype=jnp.float32)
    if q == 2:
        sq = jnp.sum(w * (g_r**2 + g_i**2), axis=0) / b  # (nb, nb)
        s0 = jnp.sum(w * g_r, axis=0) / b
        return jnp.sum(sq) - jnp.sum(eye * s0**2)
    # q = 1: synthesize the time-domain summary vectors with one more matmul.
    br, bi = irfft_basis(b)  # (nf, b) each
    gr_flat = jnp.transpose(g_r, (1, 2, 0)).reshape(nb * nb, nf)
    gi_flat = jnp.transpose(g_i, (1, 2, 0)).reshape(nb * nb, nf)
    sv = K.pmatmul(gr_flat, br) + K.pmatmul(gi_flat, bi)  # (nb*nb, b)
    sv = sv.reshape(nb, nb, b)
    full = jnp.sum(jnp.abs(sv), axis=-1)
    return jnp.sum(full) - jnp.sum(eye * jnp.abs(sv[..., 0]))
