"""Pallas TPU kernels for the ungrouped sumvec via a four-step FFT.

For the ungrouped regularizer the DFT length is the full projector width d
(up to 16384 in the paper).  A direct DFT-matmul would need a d x d basis
(1 GiB at d = 16384) — instead we use the classic Bailey four-step
factorization d = d1 * d2 (DESIGN.md §3.2):

    t = t1*d2 + t2,  f = k1 + d1*k2
    step 1: DFT_{d1} along t1      (batched d1 x d1 complex matmul)
    step 2: twiddle by W_d^{t2 k1} (elementwise complex multiply)
    step 3: DFT_{d2} along t2      (batched d2 x d2 complex matmul)

Both matmul steps run on the MXU with ~sqrt(d)-sized bases that live in
VMEM; total O(n d (d1 + d2)) FLOPs instead of O(n d^2).

Kernels here:
  * ``cmatmul``  — fused complex matmul (4 real dots, 2 outputs) with a
                   custom_vjp expressed as two more cmatmuls (conjugate
                   transpose identities).
  * ``ctwiddle`` — elementwise complex multiply by a constant plane; vjp is
                   a ctwiddle by the conjugate plane.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pallas_utils import (
    LANE,
    SUBLANE,
    cotangent_like,
    dot_f32,
    next_multiple,
    pad_axis,
    pallas_call,
)
from repro.tune.dispatch import best_config


# ---------------------------------------------------------------------------
# cmatmul: (Ar + i Ai) @ (Br + i Bi) fused
# ---------------------------------------------------------------------------


def _cmm_kernel(ar_ref, ai_ref, br_ref, bi_ref, cr_ref, ci_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        cr_ref[...] = jnp.zeros_like(cr_ref)
        ci_ref[...] = jnp.zeros_like(ci_ref)

    ar, ai = ar_ref[...], ai_ref[...]
    br, bi = br_ref[...], bi_ref[...]
    cr_ref[...] += dot_f32(ar, br) - dot_f32(ai, bi)
    ci_ref[...] += dot_f32(ar, bi) + dot_f32(ai, br)


def _cmatmul_raw(ar, ai, br, bi, tm=None, tn=None, tk=None):
    m, kdim = ar.shape
    _, n = br.shape
    if tm is None or tn is None or tk is None:
        cfg = best_config("cmatmul", (m, kdim, n), ar.dtype)
        tm = cfg["tm"] if tm is None else tm
        tn = cfg["tn"] if tn is None else tn
        tk = cfg["tk"] if tk is None else tk
    tm = min(tm, next_multiple(m, SUBLANE))
    tn = min(tn, next_multiple(n, LANE))
    tk = min(tk, next_multiple(kdim, LANE))
    mp, kp, np_ = next_multiple(m, tm), next_multiple(kdim, tk), next_multiple(n, tn)
    pad = lambda x, s0, s1: pad_axis(pad_axis(x, 0, s0), 1, s1)
    ar, ai = pad(ar, mp, kp), pad(ai, mp, kp)
    br, bi = pad(br, kp, np_), pad(bi, kp, np_)
    grid = (mp // tm, np_ // tn, kp // tk)
    a_spec = pl.BlockSpec((tm, tk), lambda i, j, kk: (i, kk))
    b_spec = pl.BlockSpec((tk, tn), lambda i, j, kk: (kk, j))
    o_spec = pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j))
    cr, ci = pallas_call(
        _cmm_kernel,
        ar.astype(jnp.float32), ai.astype(jnp.float32), br.astype(jnp.float32), bi.astype(jnp.float32),
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[jax.ShapeDtypeStruct((mp, np_), jnp.float32)] * 2,
    )
    return cr[:m, :n], ci[:m, :n]


@jax.custom_vjp
def cmatmul(ar, ai, br, bi):
    """Complex matmul on real/imag planes: C = A @ B."""
    return _cmatmul_raw(ar, ai, br, bi)


def _cmm_fwd(ar, ai, br, bi):
    return _cmatmul_raw(ar, ai, br, bi), (ar, ai, br, bi)


def _cmm_bwd(res, g):
    ar, ai, br, bi = res
    gr, gi = g
    # dA = g @ B^H ;  dB = A^H @ g   (conjugate transposes)
    dar, dai = _cmatmul_raw(gr, gi, br.T, -bi.T)
    dbr, dbi = _cmatmul_raw(ar.T, -ai.T, gr, gi)
    return tuple(cotangent_like(c, x) for c, x in zip((dar, dai, dbr, dbi), res))


cmatmul.defvjp(_cmm_fwd, _cmm_bwd)


def rmatmul_complex_basis(x, br, bi):
    """Real input times complex basis — cmatmul with Ai = 0 folded out."""
    return cmatmul(x, jnp.zeros_like(x), br, bi)


# ---------------------------------------------------------------------------
# ctwiddle: elementwise complex multiply by a constant plane
# ---------------------------------------------------------------------------


def _ctw_kernel(xr_ref, xi_ref, wr_ref, wi_ref, yr_ref, yi_ref):
    xr, xi = xr_ref[...], xi_ref[...]
    wr, wi = wr_ref[...], wi_ref[...]
    yr_ref[...] = xr * wr - xi * wi
    yi_ref[...] = xr * wi + xi * wr


def _ctwiddle_raw(xr, xi, wr, wi, tn=None):
    n, d = xr.shape
    assert wr.shape == (d,), (xr.shape, wr.shape)
    if tn is None:
        tn = best_config("ctwiddle", (n, d), xr.dtype)["tn"]
    tn = min(tn, next_multiple(n, SUBLANE))
    dp = next_multiple(d, LANE)
    np_ = next_multiple(n, tn)
    xr = pad_axis(pad_axis(xr, 0, np_), 1, dp)
    xi = pad_axis(pad_axis(xi, 0, np_), 1, dp)
    wr2 = pad_axis(wr, 0, dp).reshape(1, dp)
    wi2 = pad_axis(wi, 0, dp).reshape(1, dp)
    grid = (np_ // tn,)
    yr, yi = pallas_call(
        _ctw_kernel,
        xr.astype(jnp.float32), xi.astype(jnp.float32), wr2, wi2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, dp), lambda i: (i, 0)),
            pl.BlockSpec((tn, dp), lambda i: (i, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tn, dp), lambda i: (i, 0)),
            pl.BlockSpec((tn, dp), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((np_, dp), jnp.float32)] * 2,
    )
    return yr[:n, :d], yi[:n, :d]


@jax.custom_vjp
def ctwiddle(xr, xi, wr, wi):
    """y = x o w (x: (n, d) complex pair, w: (d,) complex pair constant)."""
    return _ctwiddle_raw(xr, xi, wr, wi)


def _ctw_fwd(xr, xi, wr, wi):
    return _ctwiddle_raw(xr, xi, wr, wi), (xr, xi, wr, wi)


def _ctw_bwd(res, g):
    xr, xi, wr, wi = res
    gr, gi = g
    # dx = g o conj(w)
    dxr, dxi = _ctwiddle_raw(gr, gi, wr, -wi)
    # dw = sum_k conj(x_k) o g_k   (w is a constant basis; grads rarely used)
    dwr = jnp.sum(xr * gr + xi * gi, axis=0)
    dwi = jnp.sum(xr * gi - xi * gr, axis=0)
    return tuple(cotangent_like(c, x) for c, x in zip((dxr, dxi, dwr, dwi), res))


ctwiddle.defvjp(_ctw_fwd, _ctw_bwd)
