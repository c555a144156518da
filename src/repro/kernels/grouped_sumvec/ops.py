"""jit'd wrappers for the grouped-sumvec Pallas kernels.

Pipeline (``kernel.py`` has the kernels and their VMEM layout):

  Z1, Z2 (n, d) --spectral_gram--> K (nf, 2 nbp, 2 nbp), spectra S1, S2
      per batch tile, in VMEM: block rfft of each view (MXU, chunk by chunk)
      -> per frequency the Gram of the stacked spectra [Re; Im], summed over
      the batch; K_f = [[G_r, G_i], [-G_i, G_r]], G the paper's "compressed
      outer product" of every block pair; S (batch on lanes) is kept for
      the backward
    q=2: Parseval on K in jnp (O(nb^2 nf)); the backward is one
         spectral_gram_vjp that forms the loss's cotangent from K itself
    q=1: G_r, G_i sliced from K, summary vectors synthesized with pmatmul

  backward: (S1, S2, K or the cotangent of G) --spectral_gram_vjp--> dZ1, dZ2
      per batch tile: P_bar X per frequency, inverse DFT

Between the calls only Z, dZ, S and the (nf, 2 nbp, 2 nbp) Gram cross HBM.
One view passed twice (VICReg's R of one view) is transformed once.

Complexity: O(n d b) for the DFTs + O(n (d/b)^2 b) for the pairwise stage
— the paper's O((n d^2 / b) log b) with log b traded for an MXU-resident b.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sumvec import rfft_parseval_weights
from repro.kernels.grouped_sumvec import kernel as K
from repro.kernels.pallas_utils import LANE, irfft_basis
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


def fits(d: int, b: int) -> bool:
    """Whether both kernels hold a 128-row batch tile of width d, block size b,
    within ``tune.space``'s VMEM budget.  Wider rows, or a chunk basis too
    large for VMEM, take the jnp FFT route (``core.regularizers``,
    ``decorr.modes``)."""
    shape = (LANE, d, b)
    return all(tune_space.candidates(k, shape) for k in ("spectral_gram", "spectral_gram_vjp"))


def _split(k: Array, nb: int) -> tuple[Array, Array]:
    """(G_r, G_i), each (nf, nb, nb), from K = [[G_r, G_i], [-G_i, G_r]]."""
    nbp = k.shape[-1] // 2
    return k[:, :nb, :nb], k[:, :nb, nbp : nbp + nb]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _accumulate(views, b: int) -> tuple[Array, Array]:
    return _split(K.spectral_gram(views, b)[0], K.layout(views[0].shape[1], b).nb)


def _accumulate_fwd(views, b):
    k, spectra = K.spectral_gram(views, b)
    return _split(k, K.layout(views[0].shape[1], b).nb), (views, spectra)


def _accumulate_bwd(b, res, ct):
    # the real form of conj(G_bar), laid out like K
    views, spectra = res
    lay = K.layout(views[0].shape[1], b)
    pad = lambda x: jnp.pad(x, ((0, 0), (0, lay.nbp - lay.nb), (0, lay.nbp - lay.nb)))
    gr, gi = (pad(x.astype(jnp.float32)) for x in ct)
    h = jnp.concatenate(
        [jnp.concatenate([gr, gi], axis=2), jnp.concatenate([-gi, gr], axis=2)], axis=1
    )
    ones = jnp.ones((lay.nf,), jnp.float32)
    zeros = jnp.zeros((2 * lay.nbp,), jnp.float32)
    return (K.spectral_gram_vjp(views, spectra, h, ones, zeros, b),)


_accumulate.defvjp(_accumulate_fwd, _accumulate_bwd)


def grouped_frequency_accumulator_kernel(
    z1: Array, z2: Array, block_size: int
) -> tuple[Array, Array]:
    """G[i,j,f] = sum_k conj(F1[k,i,f]) F2[k,j,f], returned as (nf, nb, nb)
    real/imag pair.  Matches core.sumvec.grouped_frequency_accumulator
    (transposed to frequency-major layout)."""
    views = (z1,) if z1 is z2 else (z1, z2)
    return _accumulate(views, int(block_size))


def _q2(k: Array, b: int, s: float, nb: int) -> tuple[Array, Array]:
    """Eq. (13) at q=2 by Parseval from K, and the per-block DC sums u.

    Every block pair's sum of squared lags is sum_f w_f |G_f|^2 / b (K holds
    each of G_r, G_i twice); a diagonal block drops lag 0, whose value is
    u_i / s with u_i = sum_f w_f G_r[f, i, i] / b.
    """
    w = jnp.asarray(rfft_parseval_weights(b) / b)[:, None, None]
    energy = 0.5 * jnp.sum(w * k * k)
    # a masked sum, not jnp.diagonal: the gather would relayout all of K
    u = jnp.sum(w * k * jnp.eye(k.shape[-1], dtype=k.dtype), axis=(0, 2))[:nb]
    return (energy - jnp.sum(u * u)) / (s * s), u


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _r_sum_q2(views, b: int, s: float) -> Array:
    k = K.spectral_gram(views, b)[0]
    return _q2(k, b, s, K.layout(views[0].shape[1], b).nb)[0]


def _r_sum_q2_fwd(views, b, s):
    k, spectra = K.spectral_gram(views, b)
    loss, u = _q2(k, b, s, K.layout(views[0].shape[1], b).nb)
    return loss, (views, spectra, k, u)


def _r_sum_q2_bwd(b, s, res, ct):
    # dL/dG = c_f (G - diag(u)), c_f = 2 w_f / (b s^2): the kernel forms it
    # from K in VMEM, so no G-sized cotangent is written
    views, spectra, k, u = res
    lay = K.layout(views[0].shape[1], b)
    c = ct * jnp.asarray(2.0 * rfft_parseval_weights(b) / (b * s * s))
    u = jnp.pad(u, (0, lay.nbp - lay.nb))
    return (K.spectral_gram_vjp(views, spectra, k, c, jnp.concatenate([u, u]), b),)


_r_sum_q2.defvjp(_r_sum_q2_fwd, _r_sum_q2_bwd)


@functools.partial(jax.jit, static_argnames=("block_size", "q", "scale"))
def r_sum_kernel(
    z1: Array,
    z2: Optional[Array],
    *,
    block_size: Optional[int],
    q: int = 2,
    scale: Optional[float] = None,
) -> Array:
    """Eq. (13) (or Eq. 6 when block covers d) through the Pallas pipeline.

    ``z2=None`` means both views are ``z1`` (VICReg's R of one view): its
    spectra are computed once.
    """
    d = z1.shape[-1]
    b = int(block_size) if block_size is not None else d
    b = min(b, d)
    s = 1.0 if scale is None else float(scale)
    views = (z1,) if z2 is None else (z1, z2)
    if q == 2:
        return _r_sum_q2(views, b, s)
    # q = 1: synthesize the time-domain summary vectors with one more matmul.
    g_r, g_i = _accumulate(views, b)
    nf, nb, _ = g_r.shape
    br, bi = irfft_basis(b)  # (nf, b) each
    gr_flat = jnp.transpose(g_r / s, (1, 2, 0)).reshape(nb * nb, nf)
    gi_flat = jnp.transpose(g_i / s, (1, 2, 0)).reshape(nb * nb, nf)
    sv = K.pmatmul(gr_flat, br) + K.pmatmul(gi_flat, bi)  # (nb*nb, b)
    sv = sv.reshape(nb, nb, b)
    full = jnp.sum(jnp.abs(sv), axis=-1)
    eye = jnp.eye(nb, dtype=jnp.float32)
    return jnp.sum(full) - jnp.sum(eye * jnp.abs(sv[..., 0]))
