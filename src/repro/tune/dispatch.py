"""Kernel-config dispatch: the one place call sites get their tiling from.

``best_config(kernel, shape)`` resolves, in precedence order:

  1. an explicit override installed with ``override(...)`` / ``set_override``
     (tests and benchmarks pin configs without touching the cache),
  2. the in-process memo (one search per (kernel, shape, dtype, backend)
     per process — a cache hit never re-searches),
  3. the persistent JSON cache (written by the CLI pre-tuner or by
     ``tuner.tune(persist=True)``),
  4. a deterministic analytic search over ``space.candidates`` ranked by
     ``cost.analytic_cost`` (instant; memoized but not persisted, so the
     on-disk cache only ever contains deliberately tuned entries).

All resolution happens at trace time with concrete Python ints, so jitted
wrappers pay nothing at execution time.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.pallas_utils import LANE, SUBLANE, next_multiple
from repro.tune import cache as _cache
from repro.tune import cost as _cost
from repro.tune import space as _space

Config = Dict[str, int]

_lock = threading.Lock()
_MEMO: Dict[Tuple, Config] = {}
_OVERRIDES: Dict[str, list] = {}

# shape-canonicalization units per axis, by kernel (None = semantic, no pad)
_CANON_UNITS = {
    "xcorr_offdiag": (SUBLANE, LANE),
    "cmatmul": (SUBLANE, LANE, LANE),
    "pmatmul": (SUBLANE, LANE, LANE),
    "ctwiddle": (SUBLANE, LANE),
    "spectral_gram": (LANE, None, None),
    "spectral_gram_vjp": (LANE, None, None),
    "sumvec_fft_plan": (None,),
    "grouped_block_plan": (None, None),
    "paged_attention": (None, SUBLANE, SUBLANE, LANE),
}


def canonical_shape(kernel: str, shape) -> Tuple[int, ...]:
    """The padded shape used as cache key (all configs clamp identically on
    it, so logically-distinct shapes that tile the same share one entry)."""
    units = _CANON_UNITS[kernel]
    assert len(units) == len(shape), (kernel, shape)
    return tuple(
        int(s) if u is None else next_multiple(int(s), u) for s, u in zip(shape, units)
    )


def _dtype_str(dtype) -> str:
    return jnp.dtype(dtype).name


def _analytic_search(kernel: str, shape: Tuple[int, ...]) -> Config:
    cands = _space.candidates(kernel, shape)
    if not cands:
        # Some shapes have a config-independent VMEM term that alone busts
        # the budget (e.g. spectral_gram's spectra of every block), so
        # no candidate is "legal".  These shapes always ran with the clamped
        # hardwired tiles before tuning existed — keep running them.
        return _space.default_config(kernel, shape)
    return min(
        cands, key=lambda c: _cost.rank_key(_cost.analytic_cost(kernel, shape, c), kernel)
    )


def best_config(
    kernel: str,
    shape,
    dtype=jnp.float32,
    *,
    backend: Optional[str] = None,
) -> Config:
    """The config every kernel wrapper consults when given no explicit tiles."""
    with _lock:
        stack = _OVERRIDES.get(kernel)
        params = dict(stack[-1]) if stack else None
    canon = canonical_shape(kernel, shape)
    if params is not None:
        merged = {**_space.default_config(kernel, canon), **params}
        if kernel == "sumvec_fft_plan":
            # plan keys are jointly constrained (dp == d1 * d2, dp == d or
            # dp >= 2d - 1); complete a partial override instead of handing
            # back an inconsistent merge, and reject the unsatisfiable ones
            # here with a message rather than deep in FFTPlan.
            has_d1, has_d2 = "d1" in params, "d2" in params
            if has_d1 and has_d2:
                if "dp" in params and params["dp"] != params["d1"] * params["d2"]:
                    raise ValueError(
                        f"sumvec_fft_plan override {params}: dp != d1 * d2"
                    )
                merged["dp"] = merged["d1"] * merged["d2"]
            elif has_d1 or has_d2:
                # one factor pinned: complete against the (possibly also
                # pinned) dp, never silently drop the pinned factor
                given = params["d1"] if has_d1 else params["d2"]
                if given <= 0 or merged["dp"] % given:
                    raise ValueError(
                        f"sumvec_fft_plan override {params} does not divide dp={merged['dp']}"
                    )
                other = merged["dp"] // given
                merged["d1"], merged["d2"] = (given, other) if has_d1 else (other, given)
            elif "dp" in params:
                merged["d1"], merged["d2"] = _space.balanced_factors(merged["dp"])
            if not _space.is_legal(kernel, canon, merged):
                raise ValueError(
                    f"sumvec_fft_plan override {params} is inconsistent at d={canon[0]}: {merged}"
                )
        return merged
    backend = backend or jax.default_backend()
    key = (kernel, canon, _dtype_str(dtype), backend)
    with _lock:
        hit = _MEMO.get(key)
    if hit is not None:
        return dict(hit)
    entry = _cache.lookup(kernel, canon, _dtype_str(dtype), backend)
    try:
        legal = entry is not None and _space.is_legal(kernel, canon, entry["config"])
    except (KeyError, TypeError):
        legal = False  # config with missing/renamed keys == cache miss
    if legal:
        cfg = entry["config"]
    else:
        cfg = _analytic_search(kernel, canon)
    with _lock:
        _MEMO[key] = dict(cfg)
    return dict(cfg)


def best_impl(op: str, *, backend: Optional[str] = None) -> str:
    """Implementation choice for ops with a jnp and a Pallas route.

    The Pallas kernels target the TPU MXU; under the CPU interpreter (and on
    backends Mosaic does not serve) the pure-jnp FFT route wins, so that is
    the deterministic analytic answer.  Overridable like any kernel via
    ``override(op, impl=...)``.

    Known limit: routing keys on the PROCESS default backend, not the device
    a particular computation is placed on — a CPU-placed loss inside a TPU
    process still routes to Pallas.  Pass ``impl="jnp"`` explicitly (or use
    ``override``) for cross-device debug/validation passes.
    """
    with _lock:
        stack = _OVERRIDES.get(op)
        pinned = stack[-1].get("impl") if stack else None
    if pinned is not None:
        return str(pinned)
    backend = backend or jax.default_backend()
    return "pallas" if backend == "tpu" else "jnp"


# ---------------------------------------------------------------------------
# Overrides + cache control
# ---------------------------------------------------------------------------


def set_override(kernel: str, **params) -> None:
    with _lock:
        _OVERRIDES.setdefault(kernel, []).append(dict(params))


def clear_override(kernel: str) -> None:
    with _lock:
        stack = _OVERRIDES.get(kernel)
        if stack:
            stack.pop()
        if not stack:
            _OVERRIDES.pop(kernel, None)


@contextlib.contextmanager
def override(kernel: str, **params):
    """Pin (part of) a kernel's config; beats every cache tier while active.

    Note: jitted wrappers resolve configs at trace time — an override only
    affects computations traced while it is active.
    """
    set_override(kernel, **params)
    try:
        yield
    finally:
        clear_override(kernel)


def clear_memory_cache() -> None:
    with _lock:
        _MEMO.clear()


def record(kernel: str, shape, config: Config, dtype=jnp.float32, *, backend: Optional[str] = None) -> None:
    """Install a searched config into the in-process memo (tuner hook)."""
    backend = backend or jax.default_backend()
    key = (kernel, canonical_shape(kernel, shape), _dtype_str(dtype), backend)
    with _lock:
        _MEMO[key] = dict(config)
