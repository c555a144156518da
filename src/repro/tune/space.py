"""Candidate enumeration for every tunable Pallas kernel in the repo.

Each kernel family exposes a *config space*: the set of legal tiling /
factorization choices for a given (logical) input shape.  Legality encodes
the TPU constraints that used to be implicit in hand-picked constants:

  * lane (last) block dim a multiple of LANE (128),
  * sublane (second-to-last) a multiple of SUBLANE (8, f32),
  * the working set of all VMEM-resident blocks — double-buffered inputs/
    outputs plus scratch — under ``VMEM_BUDGET_BYTES`` (a conservative
    slice of the ~16 MiB/core VMEM so the pipeline can overlap DMA).

Configs are plain ``{str: int}`` dicts so they round-trip through the JSON
cache unchanged.  ``default_config`` reproduces the repo's legacy hardwired
constants (clamped to the shape exactly the way the kernels used to), so the
tuner always has the historical baseline in its candidate set.

Kernel names and their shape/config conventions:

  kernel             shape                 config keys
  -----------------  --------------------  -------------------------
  xcorr_offdiag      (n, d)                tile_n, tile_d
  cmatmul            (m, k, n)             tm, tn, tk
  ctwiddle           (n, d)                tn
  pmatmul            (m, k, n)             tm, tn, tk
  spectral_gram      (n, d, b)             tk           (batch rows per tile)
  spectral_gram_vjp  (n, d, b)             tk
  sumvec_fft_plan    (d,)                  dp, d1, d2   (dp > d => padded)
  grouped_block_plan (n, d)                b            (block DFT group size)
  paged_attention    (b, s, kv, hd)        page         (KV tokens per block)

``grouped_block_plan`` is a *plan* kernel like ``sumvec_fft_plan``: its
config is the grouped regularizer's block size b itself (searched over
``grouped_block_size_candidates`` instead of fixed by the caller), and the
pipeline it selects delegates all tiling to spectral_gram/spectral_gram_vjp.
NOTE: b is part of the LOSS definition — plan-tuning it is for perf studies
and serve probes where any legal b computes a valid health signal; training
configs that pin b for accuracy reasons must keep passing it explicitly.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

from repro.kernels.pallas_utils import LANE, SUBLANE, next_multiple

Config = Dict[str, int]
Shape = Tuple[int, ...]

# VMEM of one core of the target chip (launch.hlo_cost.TARGET_DEVICE_KIND,
# TPU v5e); a kernel may raise its scoped limit up to this.
VMEM_BYTES = 128 * 2**20
# Working-set ceiling for one kernel instance (inputs/outputs double-buffered
# + scratch) under the compiler's default scoped limit, 16 MiB: 3/4 of it
# leaves room for compiler spills and semaphores.
VMEM_BUDGET_BYTES = 12 * 2**20
# The grouped R_sum kernels hold whole feature rows of a batch tile and their
# spectra in VMEM: they raise their scoped limit (``vmem_limit_bytes``, the
# need plus a quarter) and are held to this ceiling instead, which leaves the
# limit under VMEM_BYTES.
GROUPED_VMEM_BUDGET_BYTES = 80 * 2**20

F32 = 4  # bytes; all kernels accumulate in f32

_SUBLANE_TILES = (8, 16, 32, 64, 128, 256, 512)
_LANE_TILES = (128, 256, 512, 1024)

KERNELS = (
    "xcorr_offdiag",
    "cmatmul",
    "ctwiddle",
    "pmatmul",
    "spectral_gram",
    "spectral_gram_vjp",
    "sumvec_fft_plan",
    "grouped_block_plan",
    "paged_attention",
)


def _tile_options(dim: int, unit: int, grid) -> List[int]:
    """Tile sizes from ``grid`` clamped to the padded extent of ``dim``."""
    cap = next_multiple(dim, unit)
    opts = sorted({min(t, cap) for t in grid})
    return [t for t in opts if t % unit == 0]


# ---------------------------------------------------------------------------
# Per-kernel VMEM working sets (bytes).  Factor 2 = double buffering.
# ---------------------------------------------------------------------------


def vmem_bytes(kernel: str, shape: Shape, cfg: Config) -> int:
    if kernel == "xcorr_offdiag":
        tn, td = cfg["tile_n"], cfg["tile_d"]
        return 2 * (2 * tn * td + SUBLANE * td) * F32 + td * td * F32
    if kernel == "cmatmul":
        tm, tn, tk = cfg["tm"], cfg["tn"], cfg["tk"]
        return 2 * (2 * tm * tk + 2 * tk * tn + 2 * tm * tn) * F32
    if kernel == "pmatmul":
        tm, tn, tk = cfg["tm"], cfg["tn"], cfg["tk"]
        return 2 * (tm * tk + tk * tn + tm * tn) * F32
    if kernel == "ctwiddle":
        tn = cfg["tn"]
        dp = next_multiple(shape[1], LANE)
        return 2 * (4 * tn * dp + 2 * dp) * F32
    if kernel in ("spectral_gram", "spectral_gram_vjp"):
        from repro.kernels.grouped_sumvec.kernel import layout

        _, d, b = shape
        tk, lay = cfg["tk"], layout(d, b)
        m = 2 * lay.nbp
        # both views, double-buffered: Z tiles (dZ in the vjp) and spectra
        tiles = 2 * 2 * tk * (lay.dp + lay.rows)
        gram = 2 * lay.nf * next_multiple(m, SUBLANE) * next_multiple(m, LANE)
        # the chunk basis: its double-buffered block and the value loaded from it
        basis = 3 * 2 * lay.per * lay.rh * lay.width
        # the vjp's cotangent spectra
        scratch = 2 * lay.rows * tk if kernel == "spectral_gram_vjp" else 0
        return (tiles + gram + basis + scratch) * F32
    if kernel in ("sumvec_fft_plan", "grouped_block_plan"):
        # plans delegate all blocking to the matmul/twiddle kernels they
        # select; their own VMEM footprint is whatever those choose.
        return 0
    if kernel == "paged_attention":
        page = cfg["page"]
        kvp = next_multiple(shape[2], SUBLANE)
        hdp = next_multiple(shape[3], LANE)
        # q + out blocks are (kv, n_rep, hd); n_rep is not part of the cache
        # key, so charge one sublane tile of query heads per kv head.  One k
        # + one v page per grid step (all double-buffered), plus the
        # online-softmax scratch (acc, m, l).
        qo = SUBLANE * kvp * hdp
        return 2 * (2 * qo + 2 * page * kvp * hdp) * F32 + (qo + 2 * SUBLANE * kvp * LANE) * F32
    raise KeyError(kernel)


def is_legal(kernel: str, shape: Shape, cfg: Config) -> bool:
    """Lane/sublane alignment + VMEM budget for one candidate."""
    if kernel == "sumvec_fft_plan":
        (d,) = shape
        dp, d1, d2 = cfg["dp"], cfg["d1"], cfg["d2"]
        # enumeration canonicalizes to d1 <= d2, but any ordering is valid
        if d1 * d2 != dp or d1 < 1 or d2 < 1:
            return False
        # padded plans must be linear-correlation safe (no wraparound):
        return dp == d or dp >= 2 * d - 1
    if kernel == "grouped_block_plan":
        n, d = shape
        return 2 <= cfg["b"] <= d
    lane_keys = {
        "xcorr_offdiag": ("tile_d",),
        "cmatmul": ("tn", "tk"),
        "pmatmul": ("tn", "tk"),
        "ctwiddle": (),
        "spectral_gram": ("tk",),
        "spectral_gram_vjp": ("tk",),
        "paged_attention": (),
    }[kernel]
    sub_keys = {
        "xcorr_offdiag": ("tile_n",),
        "cmatmul": ("tm",),
        "pmatmul": ("tm",),
        "ctwiddle": ("tn",),
        "spectral_gram": (),
        "spectral_gram_vjp": (),
        "paged_attention": ("page",),
    }[kernel]
    for k in lane_keys:
        if cfg[k] <= 0 or cfg[k] % LANE:
            return False
    for k in sub_keys:
        if cfg[k] <= 0 or cfg[k] % SUBLANE:
            return False
    budget = GROUPED_VMEM_BUDGET_BYTES if kernel.startswith("spectral_gram") else VMEM_BUDGET_BYTES
    return vmem_bytes(kernel, shape, cfg) <= budget


# ---------------------------------------------------------------------------
# Factorization helpers (sumvec_fft four-step plans)
# ---------------------------------------------------------------------------


def balanced_factors(x: int) -> Tuple[int, int]:
    """(d1, d2), d1 <= d2, d1 * d2 == x, d1 as large as possible.

    The single source of factorization policy: ``sumvec_fft.ops
    .choose_factors`` delegates here, as do plan defaults and candidates.
    """
    for d1 in range(int(math.isqrt(x)), 0, -1):
        if x % d1 == 0:
            return d1, x // d1
    return 1, x


def _divisor_factorizations(x: int, limit: int = 8) -> List[Tuple[int, int]]:
    out = []
    for d1 in range(int(math.isqrt(x)), 0, -1):
        if x % d1 == 0:
            out.append((d1, x // d1))
        if len(out) >= limit:
            break
    return out


def padded_plan_candidates(d: int, scan: int = 256, keep: int = 4) -> List[Config]:
    """Tile-friendly padded DFT lengths dp >= 2d - 1 with balanced factors.

    Zero-padding the feature axis to dp and folding the linear correlation
    back to d circular lags is exact (see sumvec_fft.ops), so any dp here is
    semantics-preserving; we scan a bounded window above 2d - 1 for highly
    composite lengths and keep the cheapest few by the four-step FLOP proxy
    dp * (d1 + d2).
    """
    lo = max(2 * d - 1, 2)
    scored = []
    for dp in range(lo, lo + scan):
        d1, d2 = balanced_factors(dp)
        if d1 < max(2, math.isqrt(dp) // 4):
            continue  # too lopsided to beat the direct DFT reliably
        scored.append((dp * (d1 + d2), {"dp": dp, "d1": d1, "d2": d2}))
    scored.sort(key=lambda t: (t[0], t[1]["dp"]))
    return [cfg for _, cfg in scored[:keep]]


# ---------------------------------------------------------------------------
# Candidate enumeration + defaults
# ---------------------------------------------------------------------------


def candidates(kernel: str, shape: Shape) -> List[Config]:
    """All legal configs for ``kernel`` at ``shape`` (default always included)."""
    out: List[Config] = []
    if kernel == "xcorr_offdiag":
        n, d = shape
        for td in _tile_options(d, LANE, _LANE_TILES):
            for tn in _tile_options(n, SUBLANE, _SUBLANE_TILES):
                out.append({"tile_n": tn, "tile_d": td})
    elif kernel in ("cmatmul", "pmatmul"):
        m, k, n = shape
        for tm in _tile_options(m, SUBLANE, _SUBLANE_TILES):
            for tn in _tile_options(n, LANE, _LANE_TILES):
                for tk in _tile_options(k, LANE, _LANE_TILES):
                    out.append({"tm": tm, "tn": tn, "tk": tk})
    elif kernel == "ctwiddle":
        n, d = shape
        for tn in _tile_options(n, SUBLANE, _SUBLANE_TILES):
            out.append({"tn": tn})
    elif kernel in ("spectral_gram", "spectral_gram_vjp"):
        n, d, b = shape
        for tk in _tile_options(n, LANE, _LANE_TILES):
            out.append({"tk": tk})
    elif kernel == "sumvec_fft_plan":
        (d,) = shape
        for d1, d2 in _divisor_factorizations(d):
            out.append({"dp": d, "d1": d1, "d2": d2})
        out.extend(padded_plan_candidates(d))
    elif kernel == "grouped_block_plan":
        n, d = shape
        out.extend({"b": b} for b in grouped_block_size_candidates(d))
    elif kernel == "paged_attention":
        b, s, kv, hd = shape
        for page in _tile_options(s, SUBLANE, _SUBLANE_TILES):
            out.append({"page": page})
    else:
        raise KeyError(kernel)
    default = default_config(kernel, shape)
    if default not in out:
        out.append(default)
    return [cfg for cfg in out if is_legal(kernel, shape, cfg)]


def default_config(kernel: str, shape: Shape) -> Config:
    """The repo's historical hardwired choice, clamped the way the kernels
    used to clamp it (``min(CONST, next_multiple(dim, unit))``)."""
    if kernel == "xcorr_offdiag":
        n, d = shape
        return {
            "tile_n": min(128, next_multiple(n, SUBLANE)),
            "tile_d": min(256, next_multiple(d, LANE)),
        }
    if kernel in ("cmatmul", "pmatmul"):
        m, k, n = shape
        return {
            "tm": min(128, next_multiple(m, SUBLANE)),
            "tn": min(128, next_multiple(n, LANE)),
            "tk": min(128, next_multiple(k, LANE)),
        }
    if kernel == "ctwiddle":
        n, d = shape
        return {"tn": min(128, next_multiple(n, SUBLANE))}
    if kernel in ("spectral_gram", "spectral_gram_vjp"):
        n, d, b = shape
        return {"tk": min(128, next_multiple(n, LANE))}
    if kernel == "sumvec_fft_plan":
        (d,) = shape
        d1, d2 = balanced_factors(d)
        return {"dp": d, "d1": d1, "d2": d2}
    if kernel == "grouped_block_plan":
        n, d = shape
        # the paper's Fig. 3 sweet spot: largest legal b <= 128 (one MXU
        # tile); mirrors grouped_sumvec.ops.auto_block_size, inlined to keep
        # space importable from the kernel modules
        return {"b": max(b for b in grouped_block_size_candidates(d) if b <= 128)}
    if kernel == "paged_attention":
        b, s, kv, hd = shape
        # vLLM's classic 16-token block, clamped to short contexts
        return {"page": min(16, next_multiple(s, SUBLANE))}
    raise KeyError(kernel)


def grouped_block_size_candidates(d: int) -> List[int]:
    """Legal grouped-regularizer block sizes b for width d: powers of two
    from 2 up to d, plus d itself (== ungrouped Eq. 6).  Consumed by
    benchmarks/bench_blocksize.py, the CLI pre-tuner, and the
    ``grouped_block_plan`` candidate space."""
    out = []
    b = 2
    while b < d:
        out.append(b)
        b *= 2
    out.append(d)
    return out
