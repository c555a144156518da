"""Cost models for ranking kernel configs.

Three fidelity tiers, all deterministic on CPU/interpret:

  * ``analytic_cost``   — closed-form FLOPs / HBM-traffic / VMEM estimates
                          derived from the kernels' grid + BlockSpec algebra.
                          Instant; used by the implicit dispatch fallback.
  * ``compiled_cost``   — lower + compile the real kernel for the candidate
                          and read trip-exact FLOPs/bytes off the optimized
                          HLO via ``launch.hlo_cost.analyze_hlo`` ("dry"
                          mode: no execution, deterministic everywhere).
  * ``measured_time_us``— best-of-N wall clock of the jitted candidate
                          (optional refinement; non-deterministic, never the
                          primary key in dry mode).

Analytic ranking is a roofline scalar, not flops-lexicographic:
``max(flops / peak_flops, hbm_bytes / hbm_bw) + grid_steps * step_overhead``
(flops and vmem as deterministic tiebreaks).  Padding FLOPs on the MXU are
nearly free while re-reads and per-grid-step dispatch are not — a
flops-first ordering would pick degenerate minimum-sublane tiles (tm = 8)
for any m a larger tile would pad, which is exactly backwards on hardware.
Peaks are those of ``launch.hlo_cost.TARGET_DEVICE_KIND`` in its one
``PEAKS`` table.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Tuple

import jax

from repro.kernels.pallas_utils import LANE, SUBLANE, next_multiple
from repro.launch.hlo_cost import PEAKS, TARGET_DEVICE_KIND
from repro.tune.space import Config, Shape, vmem_bytes

F32 = 4
_PEAK = PEAKS[TARGET_DEVICE_KIND]
# charged per grid step: DMA descriptor / pipeline dispatch latency
GRID_STEP_OVERHEAD_S = 1e-6
# batch the plan cost model amortizes batch-independent stages over (the
# paper's SSL batch); plans are cached per d, so one representative n is used
NOMINAL_BATCH = 256


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def analytic_cost(kernel: str, shape: Shape, cfg: Config) -> Dict[str, float]:
    """Closed-form {flops, hbm_bytes, grid_steps, vmem_bytes} for a config."""
    if kernel == "xcorr_offdiag":
        n, d = shape
        tn, td = cfg["tile_n"], cfg["tile_d"]
        dp, npd = next_multiple(d, td), next_multiple(n, tn)
        grid = (dp // td) ** 2 * (npd // tn)
        flops = 2.0 * dp * dp * npd
        hbm = F32 * (2.0 * dp * dp * npd / td)  # both inputs, re-read per j/i
    elif kernel == "cmatmul":
        m, k, n = shape
        tm, tn, tk = cfg["tm"], cfg["tn"], cfg["tk"]
        mp, kp, npd = next_multiple(m, tm), next_multiple(k, tk), next_multiple(n, tn)
        grid = (mp // tm) * (npd // tn) * (kp // tk)
        flops = 8.0 * mp * npd * kp  # 4 real dots
        hbm = F32 * (2.0 * mp * kp * (npd / tn) + 2.0 * kp * npd * (mp / tm) + 2.0 * mp * npd)
    elif kernel == "pmatmul":
        m, k, n = shape
        tm, tn, tk = cfg["tm"], cfg["tn"], cfg["tk"]
        mp, kp, npd = next_multiple(m, tm), next_multiple(k, tk), next_multiple(n, tn)
        grid = (mp // tm) * (npd // tn) * (kp // tk)
        flops = 2.0 * mp * npd * kp
        hbm = F32 * (mp * kp * (npd / tn) + kp * npd * (mp / tm) + mp * npd)
    elif kernel == "ctwiddle":
        n, d = shape
        tn = cfg["tn"]
        dp, npd = next_multiple(d, LANE), next_multiple(n, tn)
        grid = npd // tn
        flops = 6.0 * npd * dp
        hbm = F32 * (4.0 * npd * dp + 2.0 * dp * grid)
    elif kernel in ("spectral_gram", "spectral_gram_vjp"):
        n, d, b = shape
        tk = cfg["tk"]
        flops, hbm, grid = _spectral_gram_cost(n, d, b, tk, vjp=kernel == "spectral_gram_vjp")
    elif kernel == "paged_attention":
        b, s, h, hd = shape
        page = cfg["page"]
        hp = next_multiple(h, SUBLANE)
        hdp = next_multiple(hd, LANE)
        nb = _cdiv(s, page)
        sp = nb * page
        grid = b * nb
        flops = 4.0 * b * sp * hp * hdp  # qk + pv per context token
        # k/v pages stream once; q and the revisited output block re-read per page
        hbm = F32 * b * (2.0 * sp * hp * hdp + 2.0 * nb * hp * hdp)
    elif kernel == "grouped_block_plan":
        n, d = shape
        # the forward pass at the default batch tile: block DFTs of both
        # views plus the per-frequency Gram of the (2 nb)-row stacked spectra,
        # which the MXU pads to full 128-row tiles — tiny nb pays that
        # padding, which is what makes very small b lose despite its lower
        # DFT flops
        flops, hbm, grid = _spectral_gram_cost(n, d, cfg["b"], min(128, next_multiple(n, LANE)))
    elif kernel == "sumvec_fft_plan":
        (d,) = shape
        dp, d1, d2 = cfg["dp"], cfg["d1"], cfg["d2"]
        padded = dp > d
        # forward runs per batch row (both views: two cmatmul stages + one
        # twiddle); the inverse runs ONCE on the batch-reduced accumulator,
        # so it is amortized over the batch — charge it against a nominal
        # training batch, not per row, or padded plans look ~n times worse
        # than they are.
        fwd = 16.0 * dp * (d1 + d2) + 12.0 * dp
        inv = 8.0 * dp * (d1 + d2) + 6.0 * dp
        flops = NOMINAL_BATCH * fwd + (inv if padded else 0.0)
        # basis materialization + one streaming pass per stage
        hbm = F32 * (6.0 * dp * NOMINAL_BATCH + 2.0 * (d1 * d1 + d2 * d2))
        grid = _cdiv(dp, LANE)
    else:
        raise KeyError(kernel)
    return {
        "flops": float(flops),
        "hbm_bytes": float(hbm),
        "grid_steps": float(grid),
        "vmem_bytes": float(vmem_bytes(kernel, shape, cfg)),
    }


def _spectral_gram_cost(n: int, d: int, b: int, tk: int, vjp: bool = False):
    """(flops, hbm_bytes, grid_steps) of ``spectral_gram`` (or its vjp) for
    two views, from the kernel's layout.  Forward, per batch tile: the block
    DFTs of both views and one (2 nbp)-square Gram per frequency; it reads Z
    and writes the spectra.  The vjp reads the spectra: two cotangent
    products per frequency and the inverse DFTs, writing dZ."""
    from repro.kernels.grouped_sumvec.kernel import layout

    lay = layout(d, b)
    tiles = _cdiv(n, tk)
    npd = tiles * tk
    m = next_multiple(2 * lay.nbp, LANE)
    dft = 2.0 * 2.0 * npd * lay.chunks * (2 * lay.per * lay.rh) * lay.width
    gram = 2.0 * lay.nf * m * m * npd
    hbm = F32 * (2.0 * n * d + 2.0 * npd * lay.rows + lay.nf * m * m)
    if vjp:
        return dft + 2.0 * gram, hbm, tiles
    return dft + gram + 2.0 * 2.0 * lay.nf * m**3, hbm, tiles


def rank_key(cost: Dict[str, float], kernel: str = "") -> Tuple[float, float, float]:
    if kernel in ("sumvec_fft_plan", "grouped_block_plan"):
        # plans trade padding against factor balance (or DFT work against
        # pairwise-stage padding) — arithmetic IS the tradeoff, and per-row
        # costs are too small for the roofline's grid term to mean anything.
        # Rank flops-first.
        return (cost["flops"], cost["hbm_bytes"], cost.get("vmem_bytes", 0.0))
    roofline_s = (
        max(cost["flops"] / _PEAK["flops"], cost["hbm_bytes"] / _PEAK["hbm_bw"])
        + cost.get("grid_steps", 0.0) * GRID_STEP_OVERHEAD_S
    )
    return (roofline_s, cost["flops"], cost.get("vmem_bytes", 0.0))


# ---------------------------------------------------------------------------
# Compiled ("dry") and measured tiers
# ---------------------------------------------------------------------------


def compiled_with_cost(fn: Callable, *shape_args):
    """(compiled executable, trip-exact cost dict) — one compilation serves
    both the dry ranking and measure-mode timing."""
    # imported here, not at module top: the analytic tier (what kernels use
    # implicitly) must not drag repro.launch into the hot dispatch path.
    from repro.launch.hlo_cost import analyze_hlo

    compiled = jax.jit(fn).lower(*shape_args).compile()
    a = analyze_hlo(compiled.as_text())
    cost = {"flops": a.flops, "hbm_bytes": a.hbm_bytes, "grid_steps": 0.0, "vmem_bytes": 0.0}
    return compiled, cost


def compiled_cost(fn: Callable, *shape_args) -> Dict[str, float]:
    """Trip-exact FLOPs/bytes of the compiled single-device graph (no run)."""
    return compiled_with_cost(fn, *shape_args)[1]


def measured_time_us(fn: Callable, *args, repeats: int = 3, warmup: int = 1) -> float:
    """Best-of-N wall time in microseconds (blocks on results).

    ``fn`` must already be jitted or AOT-compiled — this times exactly the
    callable it is given, so the tuner can reuse the executable it already
    compiled for the dry ranking instead of compiling twice.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6
