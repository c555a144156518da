"""Fused off-diagonal penalty kernel — the O(n d^2) baseline, done right.

Barlow Twins / VICReg compute ``R_off = sum_{i != j} C_ij^2`` by materializing
the d x d matrix C = (1/s) Z1^T Z2 in HBM (1 GiB fp32 at d = 16384).  This
kernel streams C tile-by-tile through VMEM: each (ti, tj) tile is accumulated
over the batch contraction in a VMEM scratch buffer, squared, diagonal-masked
and folded into partial sums — C never exists in HBM.

Grid: (I, J, K) with K (the batch contraction) innermost.  Each (i, j) tile
writes its squared off-diagonal entries folded to one lane-aligned (8, td)
block of partial sums; the wrapper sums those partials.  (A (1, 1) scalar
accumulator block does not lower on TPU: Mosaic cannot store scalars to VMEM.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_utils import LANE, SUBLANE, dot_f32, next_multiple, pad_axis, pallas_call
from repro.tune.dispatch import best_config


def _xcorr_kernel(z1_ref, z2_ref, out_ref, acc_ref):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += dot_f32(z1_ref[...].T, z2_ref[...])

    @pl.when(k == nk - 1)
    def _fold():
        c = acc_ref[...]
        ti, tj = c.shape
        row = jax.lax.broadcasted_iota(jnp.int32, (ti, tj), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (ti, tj), 1)
        # global diagonal: tile (i, j) covers rows i*ti + row, cols j*tj + col
        is_diag = (i * ti + row) == (j * tj + col)
        sq = jnp.where(is_diag, 0.0, c * c)
        out_ref[...] = jnp.sum(sq.reshape(ti // SUBLANE, SUBLANE, tj), axis=0)


def off_diagonal_sq_sum_raw(z1, z2, tile_d=None, tile_n=None):
    """sum_{i != j} (Z1^T Z2)_{ij}^2 without materializing the d x d matrix.

    Tiling comes from ``repro.tune`` unless pinned explicitly via the
    ``tile_d`` / ``tile_n`` arguments (tests, benchmarks, the tuner itself).
    """
    n, d = z1.shape
    if tile_d is None or tile_n is None:
        cfg = best_config("xcorr_offdiag", (n, d), z1.dtype)
        tile_d = cfg["tile_d"] if tile_d is None else tile_d
        tile_n = cfg["tile_n"] if tile_n is None else tile_n
    td = min(tile_d, next_multiple(d, LANE))
    tn = min(tile_n, next_multiple(n, SUBLANE))
    dp = next_multiple(d, td)
    np_ = next_multiple(n, tn)
    z1 = pad_axis(pad_axis(z1, 0, np_), 1, dp).astype(jnp.float32)
    z2 = pad_axis(pad_axis(z2, 0, np_), 1, dp).astype(jnp.float32)
    nd = dp // td
    partials = pallas_call(
        _xcorr_kernel,
        z1, z2,
        grid=(nd, nd, np_ // tn),
        in_specs=[
            pl.BlockSpec((tn, td), lambda i, j, k: (k, i)),
            pl.BlockSpec((tn, td), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((SUBLANE, td), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nd * SUBLANE, nd * td), jnp.float32),
        scratch_shapes=[pltpu.VMEM((td, td), jnp.float32)],
    )
    return jnp.sum(partials)
