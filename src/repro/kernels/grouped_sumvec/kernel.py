"""Pallas TPU kernels for the grouped FFT decorrelation regularizer.

Two kernels carry the grouped R_sum, forward and backward.  Between them only
Z, its cotangent, one spectrum per view and the (nf, 2 nbp, 2 nbp) Gram cross
HBM, each with the batch or a multiple of 128 on lanes:

  * ``spectral_gram(views, b)`` — for each batch tile (the grid's reduction
    axis) the rfft of every b-wide block of each view, computed on the MXU
    into the tile's spectra block, then per frequency the Gram of the stacked
    spectra X = [Re F; Im F] (2 nbp rows, the tile on lanes), accumulated
    over the batch.  After the last tile each frequency's Gram P is folded
    into K = [[G_r, G_i], [-G_i, G_r]], the real form of conj(G), where
    G[i, j] = sum_k conj(F1[k, i]) F2[k, j] is the paper's "compressed outer
    product" for every block pair.  Returns K (nf, 2 nbp, 2 nbp) and each
    view's spectra (the backward's residual).
  * ``spectral_gram_vjp(views, spectra, h, c, u2, b)`` — for each batch tile
    (independent): per frequency P_bar = c_f (h_f - diag(u2)) and the
    spectral cotangents dX1 = P_bar X2, dX2 = P_bar^T X1, then the inverse
    block DFT straight into dZ1, dZ2 (n, d).  ``h = K`` with the Parseval
    weights in ``c`` is the q=2 loss's cotangent (the loss of
    ``ops.r_sum_kernel``); any cotangent G_bar of G enters as
    ``h = [[G_bar_r, G_bar_i], [-G_bar_i, G_bar_r]]``, ``c = 1``, ``u2 = 0``.
  * ``pmatmul(a, b)`` — tiled (M,K)@(K,N) matmul; q=1 synthesizes the
    time-domain summary vectors from G with it.

Layout (``Layout``): a view's features are cut into chunks of ``width``
lanes (a multiple of 128 that holds ``per`` whole blocks); each chunk's DFT
is one NT matmul of the chunk's block-diagonal basis (2 per rh, width) with
a 128-row slice of the tile.  The spectra are (rows_of_batch / 128, rows,
128), part-major: real rows [0, nbp rh), imaginary rows [nbp rh, 2 nbp rh),
block i's frequency f at row i rh + f of its part, the batch on lanes.  So a
frequency's stacked spectra are one strided load (start f, stride rh), and
no array crosses HBM with a 64-, 65- or 130-wide minor axis.  Features past
d (the zero padding of paper section 4.4) and batch rows past n are masked
to zero in VMEM.  In-kernel contractions are ``dot_f32`` (full f32).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_utils import (
    LANE,
    SUBLANE,
    cotangent_like,
    dft_matrices,
    dot_f32,
    next_multiple,
    pad_axis,
    pallas_call,
)
from repro.tune.dispatch import best_config
from repro.tune.space import VMEM_BYTES, vmem_bytes

# ---------------------------------------------------------------------------
# pmatmul: tiled matmul
# ---------------------------------------------------------------------------


def _mm_kernel(a_ref, b_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += dot_f32(a_ref[...], b_ref[...])


def _pmatmul_raw(a, b, tm=None, tn=None, tk=None):
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if tm is None or tn is None or tk is None:
        cfg = best_config("pmatmul", (m, k, n), a.dtype)
        tm = cfg["tm"] if tm is None else tm
        tn = cfg["tn"] if tn is None else tn
        tk = cfg["tk"] if tk is None else tk
    tm = min(tm, next_multiple(m, SUBLANE))
    tn = min(tn, next_multiple(n, LANE))
    tk = min(tk, next_multiple(k, LANE))
    mp, kp, np_ = next_multiple(m, tm), next_multiple(k, tk), next_multiple(n, tn)
    a = pad_axis(pad_axis(a, 0, mp), 1, kp)
    b = pad_axis(pad_axis(b, 0, kp), 1, np_)
    grid = (mp // tm, np_ // tn, kp // tk)
    out = pallas_call(
        _mm_kernel,
        a.astype(jnp.float32), b.astype(jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((tk, tn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
    )
    return out[:m, :n]


@jax.custom_vjp
def pmatmul(a, b):
    return _pmatmul_raw(a, b)


def _pmatmul_fwd(a, b):
    return _pmatmul_raw(a, b), (a, b)


def _pmatmul_bwd(res, g):
    a, b = res
    da = _pmatmul_raw(g, b.T)
    db = _pmatmul_raw(a.T, g)
    return cotangent_like(da.astype(a.dtype), a), cotangent_like(db.astype(b.dtype), b)


pmatmul.defvjp(_pmatmul_fwd, _pmatmul_bwd)


# ---------------------------------------------------------------------------
# Spectral Gram of the block DFTs, and its cotangent
# ---------------------------------------------------------------------------


class Layout(NamedTuple):
    """Where a (n, d) view's block spectra sit in VMEM, for block size b."""

    nb: int  # blocks: ceil(d / b), the last one zero-padded
    nf: int  # rfft bins per block: b // 2 + 1
    rh: int  # spectra rows per block and part: nf up to a sublane tile
    width: int  # lanes of one chunk: a multiple of 128 holding whole blocks
    per: int  # blocks per chunk
    chunks: int

    @property
    def nbp(self) -> int:
        """Blocks the chunks hold (>= nb; the extra ones are all zero)."""
        return self.chunks * self.per

    @property
    def dp(self) -> int:
        return self.chunks * self.width

    @property
    def rows(self) -> int:
        """Spectra rows per view: real parts, then imaginary parts."""
        return 2 * self.nbp * self.rh


def layout(d: int, b: int) -> Layout:
    nb = -(-d // b)
    nf = b // 2 + 1
    width = b * LANE // math.gcd(b, LANE)
    if nb * b <= width:  # one chunk holds every block
        width, per = next_multiple(nb * b, LANE), nb
    else:
        per = width // b
    return Layout(nb, nf, next_multiple(nf, SUBLANE), width, per, -(-nb // per))


def chunk_basis(b: int, lay: Layout) -> np.ndarray:
    """(2 per rh, width): a chunk's block-diagonal rfft basis (``dft_matrices``
    transposed), real rows then imaginary rows."""
    out = np.zeros((2, lay.per, lay.rh, lay.width), np.float32)
    for part, basis in enumerate(dft_matrices(b)):
        for m in range(lay.per):
            out[part, m, : lay.nf, m * b : (m + 1) * b] = basis.T
    return out.reshape(2 * lay.per * lay.rh, lay.width)


def _batch_tile(kernel: str, n: int, d: int, b: int, tk=None) -> int:
    """Batch rows per grid step: a multiple of 128, the spectra's lanes (a
    strided load reads whole 128-lane rows); rows past n are masked."""
    tk = best_config(kernel, (n, d, b))["tk"] if tk is None else tk
    return min(next_multiple(tk, LANE), next_multiple(n, LANE))


def _compiler_params(kernel, n, d, b, tk, semantics):
    need = vmem_bytes(kernel, (n, d, b), {"tk": tk})
    return pltpu.CompilerParams(
        dimension_semantics=(semantics,),
        vmem_limit_bytes=int(min(max(need + need // 4, 32 * 2**20), VMEM_BYTES)),
    )


def _spectra(z_ref, s_ref, basis, lay: Layout, n: int, d: int):
    """Block rfft of the (tk, dp) tile in ``z_ref`` into ``s_ref`` (tk/128,
    rows, 128): each 128-row slice of the tile fills one lane tile."""
    half = lay.per * lay.rh
    tiles = s_ref.shape[0]
    rows_left = n - pl.program_id(0) * tiles * LANE if n % (tiles * LANE) else None

    def chunk(c, carry):
        off = pl.multiple_of(c * lay.width, LANE)
        r0 = pl.multiple_of(c * half, SUBLANE)
        for j in range(tiles):
            z = z_ref[pl.ds(j * LANE, LANE), pl.ds(off, lay.width)].astype(jnp.float32)
            if rows_left is not None or lay.dp > d:
                keep = jnp.ones(z.shape, jnp.bool_)
                if rows_left is not None:
                    row = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0) + j * LANE
                    keep &= row < rows_left
                if lay.dp > d:
                    keep &= jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) + off < d
                z = jnp.where(keep, z, 0.0)
            spec = dot_f32(basis, z, ((1,), (1,)))  # (2 half, 128)
            s_ref[j, pl.ds(r0, half), :] = spec[:half]
            s_ref[j, pl.ds(lay.nbp * lay.rh + r0, half), :] = spec[half:]
        return carry

    jax.lax.fori_loop(0, lay.chunks, chunk, 0)


def _synthesize(s_ref, dz_ref, basis, lay: Layout):
    """Inverse of ``_spectra``: dZ tile = spectra^T @ basis, chunk by chunk."""
    half = lay.per * lay.rh

    def chunk(c, carry):
        off = pl.multiple_of(c * lay.width, LANE)
        r0 = pl.multiple_of(c * half, SUBLANE)
        for j in range(s_ref.shape[0]):
            spec = jnp.concatenate(
                [s_ref[j, pl.ds(r0, half), :], s_ref[j, pl.ds(lay.nbp * lay.rh + r0, half), :]],
                axis=0,
            )
            dz = dot_f32(spec, basis, ((0,), (0,)))  # (128, width)
            dz_ref[pl.ds(j * LANE, LANE), pl.ds(off, lay.width)] = dz.astype(dz_ref.dtype)
        return carry

    jax.lax.fori_loop(0, lay.chunks, chunk, 0)


def _rows(f, lay: Layout):
    """Frequency f of every block, stacked [Re; Im]: 2 nbp rows, stride rh."""
    return pl.ds(f, 2 * lay.nbp, stride=lay.rh)


def _gram_kernel(*refs, lay: Layout, n: int, d: int, nviews: int):
    z_refs, (basis_ref, k_ref), s_refs = refs[:nviews], refs[nviews : nviews + 2], refs[nviews + 2 :]
    kt = pl.program_id(0)

    @pl.when(kt == 0)
    def _init():
        k_ref[...] = jnp.zeros_like(k_ref)

    basis = basis_ref[...]
    for z_ref, s_ref in zip(z_refs, s_refs):
        _spectra(z_ref, s_ref, basis, lay, n, d)

    def gram(f, carry):
        p = k_ref[f]
        for j in range(s_refs[0].shape[0]):
            x1 = s_refs[0][j, _rows(f, lay), :]
            x2 = s_refs[-1][j, _rows(f, lay), :] if nviews == 2 else x1
            p += dot_f32(x1, x2, ((1,), (1,)))
        k_ref[f] = p
        return carry

    jax.lax.fori_loop(0, lay.nf, gram, 0)

    @pl.when(kt == pl.num_programs(0) - 1)
    def _fold():
        # P = [[rr, ri], [ir, ii]] -> K = P + sign * (E P E), E swapping the
        # halves: [[rr + ii, ri - ir], [ir - ri, rr + ii]].  E is a 0/1
        # matrix, so the products are exact.
        m = 2 * lay.nbp
        row = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
        swap = (col == (row + lay.nbp) % m).astype(jnp.float32)
        sign = jnp.where((row < lay.nbp) == (col < lay.nbp), 1.0, -1.0)

        def fold(f, carry):
            p = k_ref[f]
            k_ref[f] = p + sign * dot_f32(dot_f32(swap, p), swap)
            return carry

        jax.lax.fori_loop(0, lay.nf, fold, 0)


def _spectra_spec(tk: int, lay: Layout) -> pl.BlockSpec:
    return pl.BlockSpec((tk // LANE, lay.rows, LANE), lambda kt: (kt, 0, 0))


def spectral_gram(views, b: int, tk=None):
    """K (nf, 2 nbp, 2 nbp) of one view (z, z) or two, each view (n, d), and
    each view's spectra (ceil(n / tk) tk / 128, rows, 128).  ``tk`` (batch
    rows per tile) defaults to the tuned one."""
    n, d = views[0].shape
    lay = layout(d, b)
    tk = _batch_tile("spectral_gram", n, d, b, tk)
    nt = pl.cdiv(n, tk)
    basis = jnp.asarray(chunk_basis(b, lay))
    m = 2 * lay.nbp
    kernel = functools.partial(_gram_kernel, lay=lay, n=n, d=d, nviews=len(views))
    k, *spectra = pallas_call(
        kernel,
        *views, basis,
        grid=(nt,),
        in_specs=[pl.BlockSpec((tk, lay.dp), lambda kt: (kt, 0)) for _ in views]
        + [pl.BlockSpec(basis.shape, lambda kt: (0, 0))],
        out_specs=[pl.BlockSpec((lay.nf, m, m), lambda kt: (0, 0, 0))]
        + [_spectra_spec(tk, lay) for _ in views],
        out_shape=[jax.ShapeDtypeStruct((lay.nf, m, m), jnp.float32)]
        + [jax.ShapeDtypeStruct((nt * tk // LANE, lay.rows, LANE), jnp.float32) for _ in views],
        compiler_params=_compiler_params("spectral_gram", n, d, b, tk, "arbitrary"),
    )
    return k, tuple(spectra)


def _gram_vjp_kernel(*refs, lay: Layout, nviews: int):
    x_refs = refs[:nviews]
    basis_ref, h_ref, c_ref, u_ref = refs[nviews : nviews + 4]
    dz_refs, s_refs = refs[nviews + 4 : 2 * nviews + 4], refs[2 * nviews + 4 :]
    m = 2 * lay.nbp
    diag = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0) == jax.lax.broadcasted_iota(
        jnp.int32, (m, m), 1
    )
    shift = jnp.where(diag, u_ref[...], 0.0)  # diag(u2)
    # the rows past nf of each block meet zero basis rows in the synthesis,
    # but scratch starts undefined (NaN * 0 is NaN): clear them
    for s_ref in s_refs:
        for j in range(s_ref.shape[0]):
            for r in range(lay.nf, lay.rh):
                s_ref[j, _rows(r, lay), :] = jnp.zeros((m, LANE), jnp.float32)

    def cotangent(f, carry):
        pbar = c_ref[f] * (h_ref[f] - shift)
        rows = _rows(f, lay)
        for j in range(s_refs[0].shape[0]):
            x1 = x_refs[0][j, rows, :]
            if nviews == 1:
                s_refs[0][j, rows, :] = dot_f32(pbar, x1) + dot_f32(pbar, x1, ((0,), (0,)))
            else:
                s_refs[0][j, rows, :] = dot_f32(pbar, x_refs[1][j, rows, :])
                s_refs[1][j, rows, :] = dot_f32(pbar, x1, ((0,), (0,)))
        return carry

    jax.lax.fori_loop(0, lay.nf, cotangent, 0)
    basis = basis_ref[...]
    for dz_ref, s_ref in zip(dz_refs, s_refs):
        _synthesize(s_ref, dz_ref, basis, lay)


def spectral_gram_vjp(views, spectra, h, c, u2, b: int, tk=None):
    """(dZ per view) for P_bar_f = c_f (h_f - diag(u2)), from the spectra that
    ``spectral_gram`` returned for ``views``: see the module doc."""
    n, d = views[0].shape
    lay = layout(d, b)
    tk = _batch_tile("spectral_gram_vjp", n, d, b, tk)
    basis = jnp.asarray(chunk_basis(b, lay))
    m = 2 * lay.nbp
    kernel = functools.partial(_gram_vjp_kernel, lay=lay, nviews=len(views))
    tile = pl.BlockSpec((tk, lay.dp), lambda kt: (kt, 0))
    dz = pallas_call(
        kernel,
        *spectra, basis, h.astype(jnp.float32), c.astype(jnp.float32),
        u2.astype(jnp.float32).reshape(1, m),
        grid=(pl.cdiv(n, tk),),
        in_specs=[_spectra_spec(tk, lay) for _ in views]
        + [
            pl.BlockSpec(basis.shape, lambda kt: (0, 0)),
            pl.BlockSpec((lay.nf, m, m), lambda kt: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, m), lambda kt: (0, 0)),
        ],
        out_specs=[tile for _ in views],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype) for v in views],
        scratch_shapes=[pltpu.VMEM((tk // LANE, lay.rows, LANE), jnp.float32) for _ in views],
        compiler_params=_compiler_params("spectral_gram_vjp", n, d, b, tk, "parallel"),
    )
    return tuple(cotangent_like(g, v) for g, v in zip(dz, views))
