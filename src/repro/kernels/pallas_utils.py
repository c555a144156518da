"""Shared Pallas/TPU helpers: padding, tiling, the one ``pallas_call`` site.

TPU tiling rules baked in here:
  * lane (last) dim of every VMEM block is a multiple of 128,
  * sublane (second-to-last) a multiple of 8 for f32.
Inputs are zero-padded up to tile multiples in the op wrappers — all our
contractions are linear, so zero padding never changes results, and outputs
are sliced back.

``interpret()`` is True on the CPU backend only: kernels then execute their
Python bodies (the Pallas interpreter), which validates the kernel logic
without a TPU; on a TPU the same code lowers to Mosaic.  It is read when a
kernel is traced, never at import, so importing the kernels initialises no
backend.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

LANE = 128
SUBLANE = 8


def dot_f32(a, b, contract=((1,), (0,))):
    """In-kernel f32 matmul at full f32 precision.

    ``contract`` names the contracted axis of each 2-D operand: the default is
    ``a @ b``; ``((1,), (1,))`` is ``a @ b.T`` and ``((0,), (0,))`` is
    ``a.T @ b``.

    Mosaic's default contracts f32 operands at reduced precision; through the
    DFT bases that biased the grouped R_sum on a v5e by +1.1e-3 relative to
    the exact matrix form (the jnp FFT route: 2e-6).
    """
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST,
    )


def interpret() -> bool:
    """Whether ``pallas_call`` runs in the interpreter: on the CPU backend only."""
    return jax.default_backend() == "cpu"


def pallas_call(kernel, *operands, out_shape, **kwargs):
    """``pl.pallas_call(kernel, out_shape=..., **kwargs)(*operands)``.

    Interpret mode is decided here, when the kernel is traced.  The call is
    typed for ``shard_map`` with its varying-manual-axes checks on: operands
    that vary over fewer mesh axes than the others (a replicated DFT basis
    beside a batch shard) are cast to vary over all of them, since the kernel
    body mixes them, and the outputs vary over the same axes.  Outside
    ``shard_map`` every set is empty and nothing is cast.
    """
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    operands = [_vary(x, vma) for x in operands]
    typed = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma)
    if isinstance(out_shape, (list, tuple)):
        out_shape = [typed(s) for s in out_shape]
    else:
        out_shape = typed(out_shape)
    return pl.pallas_call(kernel, out_shape=out_shape, interpret=interpret(), **kwargs)(*operands)


def _vary(x, vma):
    missing = tuple(vma - jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def cotangent_like(ct, x):
    """Cotangent ``ct`` typed like the primal ``x``.

    A kernel operand that varies over fewer mesh axes than the output (a
    replicated DFT basis times a batch shard) was implicitly broadcast; the
    transpose of that broadcast sums the shards' contributions.
    """
    extra = tuple(jax.typeof(ct).vma - jax.typeof(x).vma)
    return jax.lax.psum(ct, extra) if extra else ct


def next_multiple(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_axis(x, axis: int, target: int):
    """Zero-pad ``axis`` of x up to length ``target``."""
    cur = x.shape[axis]
    if cur == target:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - cur)
    return jnp.pad(x, pad)


def dft_matrices(d: int):
    """Real/imag rfft basis: F[f] = sum_t z[t] * (Cr[t,f] + i Ci[t,f]).

    Cr[t, f] = cos(2 pi t f / d);  Ci[t, f] = -sin(2 pi t f / d).
    Shapes (d, d//2 + 1), NumPy float32: kernels lay them out at trace time.
    """
    nf = d // 2 + 1
    t = np.arange(d)[:, None]
    f = np.arange(nf)[None, :]
    ang = 2.0 * np.pi * t * f / d
    return np.cos(ang).astype(np.float32), (-np.sin(ang)).astype(np.float32)


def full_dft_matrices(d: int, sign: int = -1, dtype=jnp.float32):
    """Full complex DFT basis W[t, f] = exp(sign * 2 pi i t f / d) as (re, im)."""
    t = np.arange(d)[:, None]
    f = np.arange(d)[None, :]
    ang = 2.0 * np.pi * t * f / d * sign
    return jnp.asarray(np.cos(ang), dtype), jnp.asarray(np.sin(ang), dtype)


def irfft_basis(d: int, dtype=jnp.float32):
    """Synthesis basis: s[t] = sum_f  Br[f, t] * Gr[f] + Bi[f, t] * Gi[f].

    Derived from s = irfft(G):  s[t] = (1/d) sum_f w_f (Gr cos(2pi ft/d)
    - Gi sin(2pi ft/d)), w_f the rfft duplication weights.
    Shapes (d//2+1, d).
    """
    nf = d // 2 + 1
    w = np.full((nf,), 2.0)
    w[0] = 1.0
    if d % 2 == 0:
        w[-1] = 1.0
    f = np.arange(nf)[:, None]
    t = np.arange(d)[None, :]
    ang = 2.0 * np.pi * f * t / d
    br = (w[:, None] * np.cos(ang)) / d
    bi = (-w[:, None] * np.sin(ang)) / d
    return jnp.asarray(br, dtype), jnp.asarray(bi, dtype)
