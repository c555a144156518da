"""The tuner: search a kernel's config space and persist the winner.

Modes:
  * ``analytic`` — rank by the closed-form model only.  Instant.
  * ``dry``      — compile each candidate (top-K by analytic pre-rank) and
                   rank by trip-exact HLO FLOPs, then HBM bytes.  No kernel
                   is executed, so this is deterministic on CPU/interpret
                   and on real hardware alike.
  * ``measure``  — additionally run each compiled candidate and rank by
                   best-of-N wall time (compiled FLOPs as tiebreak).

In ``dry``/``measure`` mode the legacy default config is always evaluated,
and ``guard_default=True`` (the default) only accepts a winner that is no
worse than the default on BOTH compiled FLOPs and bytes — the tuner can
refuse to move, it can never regress the baseline.

Trace-time caveat: kernel wrappers resolve configs when jit TRACES them, so
a wrapper already traced in this process keeps its old config until its jit
cache entry is evicted (e.g. new shape) or the process restarts.  Pre-tune
before the first training step — the ``repro.tune.cli`` workflow — or tune
in a separate process and let the JSON cache carry the result.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.tune import cache as _cache
from repro.tune import cost as _cost
from repro.tune import dispatch as _dispatch
from repro.tune import space as _space

Config = Dict[str, int]


@dataclasses.dataclass
class Candidate:
    config: Config
    cost: Dict[str, float]
    time_us: Optional[float] = None


@dataclasses.dataclass
class TuneResult:
    kernel: str
    shape: Tuple[int, ...]
    dtype: str
    backend: str
    mode: str
    best: Config
    default: Config
    candidates: List[Candidate]

    def candidate_for(self, config: Config) -> Candidate:
        for c in self.candidates:
            if c.config == config:
                return c
        raise KeyError(config)


# ---------------------------------------------------------------------------
# Builders: (shape, config) -> (fn, concrete example args) for compile/run.
# Kernel modules are imported lazily to keep tune importable from them.
# ---------------------------------------------------------------------------


def _ones(*shapes):
    return [jnp.ones(s, jnp.float32) for s in shapes]


def _build(kernel: str, shape: Tuple[int, ...], cfg: Config) -> Tuple[Callable, list]:
    if kernel == "xcorr_offdiag":
        from repro.kernels.xcorr_offdiag.kernel import off_diagonal_sq_sum_raw

        n, d = shape
        fn = lambda a, b: off_diagonal_sq_sum_raw(
            a, b, tile_d=cfg["tile_d"], tile_n=cfg["tile_n"]
        )
        return fn, _ones((n, d), (n, d))
    if kernel == "cmatmul":
        from repro.kernels.sumvec_fft.kernel import _cmatmul_raw

        m, k, n = shape
        fn = lambda ar, ai, br, bi: _cmatmul_raw(
            ar, ai, br, bi, tm=cfg["tm"], tn=cfg["tn"], tk=cfg["tk"]
        )
        return fn, _ones((m, k), (m, k), (k, n), (k, n))
    if kernel == "ctwiddle":
        from repro.kernels.sumvec_fft.kernel import _ctwiddle_raw

        n, d = shape
        fn = lambda xr, xi, wr, wi: _ctwiddle_raw(xr, xi, wr, wi, tn=cfg["tn"])
        return fn, _ones((n, d), (n, d), (d,), (d,))
    if kernel == "pmatmul":
        from repro.kernels.grouped_sumvec.kernel import _pmatmul_raw

        m, k, n = shape
        fn = lambda a, b: _pmatmul_raw(a, b, tm=cfg["tm"], tn=cfg["tn"], tk=cfg["tk"])
        return fn, _ones((m, k), (k, n))
    if kernel in ("spectral_gram", "spectral_gram_vjp"):
        from repro.kernels.grouped_sumvec import kernel as gk

        n, d, b = shape
        if kernel == "spectral_gram":
            fn = lambda z1, z2: gk.spectral_gram((z1, z2), b, tk=cfg["tk"])
            return fn, _ones((n, d), (n, d))
        lay = gk.layout(d, b)
        m, spec = 2 * lay.nbp, (-(-n // gk.LANE), lay.rows, gk.LANE)
        fn = lambda z1, z2, s1, s2, h, c, u2: gk.spectral_gram_vjp(
            (z1, z2), (s1, s2), h, c, u2, b, tk=cfg["tk"]
        )
        return fn, _ones((n, d), (n, d), spec, spec, (lay.nf, m, m), (lay.nf,), (m,))
    if kernel == "paged_attention":
        from repro.kernels.paged_attention.ops import paged_decode_attention_raw

        b, s, h, hd = shape
        page = cfg["page"]
        nb = -(-s // page)
        bt = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
        lens = jnp.full((b,), s, jnp.int32)
        fn = lambda q, kp, vp: paged_decode_attention_raw(
            q, kp, vp, bt, lens, scale=1.0 / max(hd, 1) ** 0.5
        )
        return fn, _ones((b, h, hd), (b * nb, page, h, hd), (b * nb, page, h, hd))
    if kernel == "grouped_block_plan":
        from repro.kernels.grouped_sumvec import ops as gops

        n, d = shape
        fn = lambda a, b_: gops.r_sum_kernel(a, b_, block_size=cfg["b"], q=2)
        return fn, _ones((n, d), (n, d))
    if kernel == "sumvec_fft_plan":
        from repro.kernels.sumvec_fft import ops as fops

        (d,) = shape
        plan = fops.FFTPlan(d=d, dp=cfg["dp"], d1=cfg["d1"], d2=cfg["d2"])
        # evaluate at a realistic batch: the inverse stage runs once on the
        # batch-reduced accumulator, so a tiny n would overweight it
        n = _cost.NOMINAL_BATCH
        fn = lambda a, b: fops._r_sum_impl(a, b, q=2, s=1.0, plan=plan)
        return fn, _ones((n, d), (n, d))
    raise KeyError(kernel)


def _compiled_key(cost: Dict[str, float]) -> Tuple[float, float]:
    return (cost["flops"], cost["hbm_bytes"])


def tune(
    kernel: str,
    shape,
    dtype=jnp.float32,
    *,
    mode: str = "dry",
    max_candidates: int = 6,
    guard_default: bool = True,
    persist: bool = True,
    repeats: int = 3,
    backend: Optional[str] = None,
) -> TuneResult:
    """Search ``kernel``'s config space at ``shape``; install + persist the best."""
    assert mode in ("analytic", "dry", "measure"), mode
    backend = backend or jax.default_backend()
    canon = _dispatch.canonical_shape(kernel, shape)
    dtype_s = jnp.dtype(dtype).name
    default = _space.default_config(kernel, canon)

    cands = _space.candidates(kernel, canon)
    cands.sort(key=lambda c: _cost.rank_key(_cost.analytic_cost(kernel, canon, c), kernel))
    if max_candidates and len(cands) > max_candidates:
        cands = cands[:max_candidates]
    if default not in cands:
        cands.append(default)

    evaluated: List[Candidate] = []
    if mode == "analytic":
        for cfg in cands:
            evaluated.append(Candidate(cfg, _cost.analytic_cost(kernel, canon, cfg)))
        best = min(evaluated, key=lambda c: _cost.rank_key(c.cost, kernel)).config
    else:
        for cfg in cands:
            fn, args = _build(kernel, canon, cfg)
            compiled, c = _cost.compiled_with_cost(fn, *args)
            t = (
                _cost.measured_time_us(compiled, *args, repeats=repeats)
                if mode == "measure"
                else None
            )
            evaluated.append(Candidate(cfg, c, t))
        default_cand = next(c for c in evaluated if c.config == default)
        pool = evaluated
        if guard_default:
            pool = [
                c
                for c in evaluated
                if c.cost["flops"] <= default_cand.cost["flops"]
                and c.cost["hbm_bytes"] <= default_cand.cost["hbm_bytes"]
            ] or [default_cand]
        if mode == "measure":
            best = min(pool, key=lambda c: (c.time_us, *_compiled_key(c.cost))).config
        else:
            best = min(pool, key=lambda c: _compiled_key(c.cost)).config

    _dispatch.record(kernel, canon, best, dtype, backend=backend)
    if persist:
        best_cand = next(c for c in evaluated if c.config == best)
        cost_rec = dict(best_cand.cost)
        if best_cand.time_us is not None:
            cost_rec["time_us"] = best_cand.time_us
        _cache.store(kernel, canon, dtype_s, backend, best, source=mode, cost=cost_rec)
    return TuneResult(
        kernel=kernel,
        shape=canon,
        dtype=dtype_s,
        backend=backend,
        mode=mode,
        best=dict(best),
        default=default,
        candidates=evaluated,
    )
