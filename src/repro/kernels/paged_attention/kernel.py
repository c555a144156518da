"""Pallas TPU kernel for paged (block-table) decode attention.

One grid step = (slot b, logical block j).  The block table and the per-slot
context lengths ride in as *scalar prefetch* operands, so the k/v ``BlockSpec``
index maps can pick the PHYSICAL page ``bt[b, j]`` for each grid step — the
kernel never sees a gathered dense cache, only one page of it at a time.
Per-slot online-softmax state (running max / normalizer / value accumulator)
lives in VMEM scratch, re-initialized at j == 0 and folded across the slot's
pages exactly like the chunked-prefill scan in ``models/attention.py``; the
output block for slot b is revisited every j and the final page's write wins.

Pages whose first token is already past the slot's valid length are skipped
with ``pl.when`` (free-slot lanes decode a single masked row, same as the
dense path — the engine discards their output).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_utils import LANE, SUBLANE, next_multiple, pad_axis, pallas_call

NEG_INF = -1e30


def _paged_decode_kernel(
    bt_ref,  # (B, NB) int32 scalar prefetch: block table
    len_ref,  # (B,) int32 scalar prefetch: valid context tokens per slot
    q_ref,  # (1, KVp, Rp, HDp): queries grouped by shared kv head
    k_ref,  # (1, page, KVp, HDp): the physical page bt[b, j]
    v_ref,  # (1, page, KVp, HDp)
    o_ref,  # (1, KVp, Rp, HDp)
    acc_ref,  # (KVp, Rp, HDp) f32 scratch: value accumulator
    m_ref,  # (KVp, Rp, LANE) f32 scratch: running max (broadcast over lanes)
    l_ref,  # (KVp, Rp, LANE) f32 scratch: running normalizer
    *,
    page: int,
    scale: float,
    softcap: float,
    window: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    @pl.when(j * page < length)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)  # (KVp, Rp, HDp)
        k = k_ref[0].astype(jnp.float32)  # (page, KVp, HDp)
        v = v_ref[0].astype(jnp.float32)
        # GQA without expansion: batch over kv heads, each serving its Rp
        # query heads — (KVp, Rp, HDp) x (page, KVp, HDp) -> (KVp, Rp, page)
        s = jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )
        s = s * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = pos < length
        if window:
            mask &= pos >= length - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :, :1]  # (KVp, Rp, 1)
        l_prev = l_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # masked lanes: exp(NEG_INF - m) == 0 exactly
        l_new = l_prev * corr + jnp.sum(p, axis=2, keepdims=True)
        # (KVp, Rp, page) x (page, KVp, HDp) -> (KVp, Rp, HDp)
        pv = jax.lax.dot_general(
            p,
            v,
            dimension_numbers=(((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[:, :, :1], 1e-30)


def paged_decode_kernel_call(q, k_pages, v_pages, block_tables, lens, *, scale, softcap, window):
    """Pad to tile boundaries and launch the kernel.

    ``q``: (B, H, hd) with H == n_rep * KV; pages stay in their native
    (P, page, KV, hd) layout and dtype — GQA is handled by batching the dots
    over kv heads inside the kernel and the f32 cast happens per block, so
    the only whole-pool materialization is the zero-pad of kv/hd up to tile
    boundaries (a no-op at real model shapes like kv=8, hd=128/256).
    """
    b, h, hd = q.shape
    p_total, page, kv, hdk = k_pages.shape
    assert hdk == hd and h % kv == 0, (q.shape, k_pages.shape)
    assert page % SUBLANE == 0, f"page size {page} must be a sublane multiple"
    n_rep = h // kv
    nb = block_tables.shape[1]
    kvp = next_multiple(kv, SUBLANE)
    rp = next_multiple(n_rep, SUBLANE)
    hdp = next_multiple(hd, LANE)
    q = q.reshape(b, kv, n_rep, hd)
    q = pad_axis(pad_axis(pad_axis(q, 1, kvp), 2, rp), 3, hdp)
    k_pages = pad_axis(pad_axis(k_pages, 2, kvp), 3, hdp)
    v_pages = pad_axis(pad_axis(v_pages, 2, kvp), 3, hdp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, kvp, rp, hdp), lambda bb, jj, bt, ln: (bb, 0, 0, 0)),
            pl.BlockSpec((1, page, kvp, hdp), lambda bb, jj, bt, ln: (bt[bb, jj], 0, 0, 0)),
            pl.BlockSpec((1, page, kvp, hdp), lambda bb, jj, bt, ln: (bt[bb, jj], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kvp, rp, hdp), lambda bb, jj, bt, ln: (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvp, rp, hdp), jnp.float32),
            pltpu.VMEM((kvp, rp, LANE), jnp.float32),
            pltpu.VMEM((kvp, rp, LANE), jnp.float32),
        ],
    )
    out = pallas_call(
        functools.partial(
            _paged_decode_kernel,
            page=page,
            scale=float(scale),
            softcap=float(softcap or 0.0),
            window=int(window or 0),
        ),
        block_tables.astype(jnp.int32), lens.astype(jnp.int32), q, k_pages, v_pages,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvp, rp, hdp), jnp.float32),
    )
    return out[:, :kv, :n_rep, :hd].reshape(b, h, hd)
