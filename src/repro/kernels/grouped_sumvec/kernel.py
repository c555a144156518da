"""Pallas TPU kernels for the grouped FFT decorrelation regularizer.

Three primitives, each a ``pl.pallas_call`` with explicit VMEM ``BlockSpec``
tiling, each wrapped in ``jax.custom_vjp`` whose backward pass is expressed
with the *same* kernels (so fwd and bwd both run on the MXU):

  * ``pmatmul(a, b)``      — tiled (M,K)@(K,N) matmul; used for the block-DFT
                             (Z @ [Cr | Ci]) and its transpose in the vjp.
  * ``freq_outer(a, b)``   — per-frequency batched contraction over the batch:
                             G[f] = a[f]^T @ b[f], a,b: (F, K, N) -> (F, N, N).
                             This is the "compressed outer product" of the
                             paper, evaluated for all (d/b)^2 block pairs at
                             once as b//2+1 MXU matmuls.
  * ``freq_mat(a, m)``     — per-frequency right-multiplication
                             Y[f] = a[f] @ m[f]; the vjp partner of
                             freq_outer.

TPU adaptation (DESIGN.md §3): the per-block DFT is a b x b matmul (b = 128
is the paper's best block size — exactly one MXU tile), so the whole
regularizer is systolic-array work; no vector-unit FFT is involved.
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
# freq_outer: G[f] = a[f]^T @ b[f]   (F, K, N) x (F, K, N) -> (F, N, N)
# ---------------------------------------------------------------------------


def _fo_kernel(a_ref, b_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[0]  # (tk, N)
    b = b_ref[0]  # (tk, tn)
    o_ref[0] += dot_f32(a.T, b)


def _freq_outer_raw(a, b, tk=None, tn=None):
    f, k, n = a.shape
    fb, kb, nb = b.shape
    assert (f, k) == (fb, kb), (a.shape, b.shape)
    if tk is None or tn is None:
        cfg = best_config("freq_outer", (f, k, max(n, nb)), a.dtype)
        tk = cfg["tk"] if tk is None else tk
        tn = cfg["tn"] if tn is None else tn
    npad = next_multiple(max(n, nb), LANE)
    tn = min(tn, npad)
    tk = min(tk, next_multiple(k, SUBLANE))
    kp = next_multiple(k, tk)
    a = pad_axis(pad_axis(a, 1, kp), 2, npad)
    b = pad_axis(pad_axis(b, 1, kp), 2, npad)
    grid = (f, npad // tn, kp // tk)
    out = pallas_call(
        _fo_kernel,
        a.astype(jnp.float32), b.astype(jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tk, npad), lambda ff, j, kk: (ff, kk, 0)),
            pl.BlockSpec((1, tk, tn), lambda ff, j, kk: (ff, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, npad, tn), lambda ff, j, kk: (ff, 0, j)),
        out_shape=jax.ShapeDtypeStruct((f, npad, npad), jnp.float32),
    )
    return out[:, :n, :nb]


# ---------------------------------------------------------------------------
# freq_mat: Y[f] = a[f] @ m[f]   (F, K, N) x (F, N, N2) -> (F, K, N2)
# ---------------------------------------------------------------------------


def _fm_kernel(a_ref, m_ref, o_ref):
    o_ref[0] = dot_f32(a_ref[0], m_ref[0])


def _freq_mat_raw(a, m, tk=None):
    f, k, n = a.shape
    fm, nm, n2 = m.shape
    assert f == fm and n == nm, (a.shape, m.shape)
    if tk is None:
        tk = best_config("freq_mat", (f, k, n, n2), a.dtype)["tk"]
    npad = next_multiple(n, LANE)
    n2pad = next_multiple(n2, LANE)
    tk = min(tk, next_multiple(k, SUBLANE))
    kp = next_multiple(k, tk)
    a = pad_axis(pad_axis(a, 1, kp), 2, npad)
    m = pad_axis(pad_axis(m, 1, npad), 2, n2pad)
    grid = (f, kp // tk)
    out = pallas_call(
        _fm_kernel,
        a.astype(jnp.float32), m.astype(jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tk, npad), lambda ff, kk: (ff, kk, 0)),
            pl.BlockSpec((1, npad, n2pad), lambda ff, kk: (ff, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tk, n2pad), lambda ff, kk: (ff, kk, 0)),
        out_shape=jax.ShapeDtypeStruct((f, kp, n2pad), jnp.float32),
    )
    return out[:, :k, :n2]


@jax.custom_vjp
def freq_outer(a, b):
    """G[f] = a[f]^T @ b[f]."""
    return _freq_outer_raw(a, b)


def _fo_fwd(a, b):
    return _freq_outer_raw(a, b), (a, b)


def _fo_bwd(res, g):
    a, b = res
    # dA[f] = b[f] @ g[f]^T ; dB[f] = a[f] @ g[f]
    da = _freq_mat_raw(b, jnp.swapaxes(g, 1, 2))
    db = _freq_mat_raw(a, g)
    return cotangent_like(da.astype(a.dtype), a), cotangent_like(db.astype(b.dtype), b)


freq_outer.defvjp(_fo_fwd, _fo_bwd)


@jax.custom_vjp
def freq_mat(a, m):
    """Y[f] = a[f] @ m[f]."""
    return _freq_mat_raw(a, m)


def _fm_fwd(a, m):
    return _freq_mat_raw(a, m), (a, m)


def _fm_bwd(res, g):
    a, m = res
    da = _freq_mat_raw(g, jnp.swapaxes(m, 1, 2))
    dm = _freq_outer_raw(a, g)
    return cotangent_like(da.astype(a.dtype), a), cotangent_like(dm.astype(m.dtype), m)


freq_mat.defvjp(_fm_fwd, _fm_bwd)
