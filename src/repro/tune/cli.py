"""Offline pre-tuner: ``python -m repro.tune.cli --dry --arch ssl-paper``.

Derives the hot kernel shapes of an architecture config (batch x projector
widths, the four-step inner matmuls from the tuned FFT plan, the grouped
pipeline at the paper's best block size), tunes each, and persists the
winners to the JSON cache so training jobs start with a warm cache.

    python -m repro.tune.cli --dry --arch ssl-paper        # HLO-ranked, deterministic
    python -m repro.tune.cli --measure --arch ssl-paper    # wall-time ranked
    python -m repro.tune.cli --analytic --shape 256x2048   # instant, model-only
    python -m repro.tune.cli --dry --serve --shape 64x2048 # serve bucket ladder,
                                                           # forward-only shapes
"""

from __future__ import annotations

import argparse
import math
import os
import sys
from typing import List, Tuple

ARCHS = {
    "ssl-paper": "repro.configs.ssl_paper",
}

Job = Tuple[str, Tuple[int, ...]]


def arch_shapes(name: str) -> List[Tuple[int, int]]:
    """(batch, width) pairs for a registered architecture config."""
    import importlib

    mod = importlib.import_module(ARCHS[name])
    cfg = mod.config()
    n = int(cfg.batch_size)
    widths = sorted({int(w) for w in cfg.projector_widths})
    return [(n, d) for d in widths]


def jobs_for(n: int, d: int, block_size=None, forward_only=False, **tune_kw):
    """All tunable kernel shapes reached from one (n, d) regularizer call,
    forward AND backward pass (training dispatches the vjp shapes too).

    ``block_size``: the grouped-regularizer b the training config will use —
    pass the real one, or the grouped shapes warmed here won't match runtime
    dispatch.  With ``block_size=None`` the pre-tuner SEARCHES b itself: the
    ``grouped_block_plan`` space enumerates every legal candidate
    (``grouped_block_size_candidates``) and the winner — not a fixed paper
    constant — drives the derived grouped shapes.  b is part of the loss
    definition, so accuracy-pinned training configs should keep passing it.
    ``forward_only``: drop the vjp shapes — the serve path (inference probes)
    never differentiates, so pre-tuning them would warm dead entries.

    The four-step inner matmul shapes depend on the FFT plan (and the grouped
    shapes on b), so both plans are tuned here first and the derived shapes
    read off the winners.  Returns ([plan TuneResults], remaining jobs).
    """
    from repro import tune

    plans = [tune.tune("sumvec_fft_plan", (d,), **tune_kw)]
    dp, d1, d2 = (plans[0].best[k] for k in ("dp", "d1", "d2"))
    if block_size:
        b = min(int(block_size), d)
    else:
        plans.append(tune.tune("grouped_block_plan", (n, d), **tune_kw))
        b = int(plans[-1].best["b"])
    nb = math.ceil(d / b)
    nf = b // 2 + 1
    jobs = [
        ("xcorr_offdiag", (n, d)),
        # four-step forward: step-1/step-3 complex matmuls + twiddle
        ("cmatmul", (n * d2, d1, d1)),
        ("cmatmul", (n * d1, d2, d2)),
        ("ctwiddle", (n, dp)),
        # inverse four-step (padded plans and q = 1): batch-1 accumulator
        ("cmatmul", (d1, d2, d2)),
        ("cmatmul", (d2, d1, d1)),
        ("ctwiddle", (1, dp)),
        # grouped pipeline: block DFTs + per-frequency Gram, then q = 1's
        # synthesis of the summary vectors
        ("spectral_gram", (n, d, b)),
        ("pmatmul", (nb * nb, nf, b)),
    ]
    if not forward_only:
        jobs += [
            # four-step vjp: dB = A^H @ g shapes from _cmm_bwd
            ("cmatmul", (d1, n * d2, d1)),
            ("cmatmul", (d2, n * d1, d2)),
            ("spectral_gram_vjp", (n, d, b)),
        ]
    # distinct canonical shapes only (small d collapses several of these)
    seen, uniq = set(), []
    for kernel, shape in jobs:
        key = (kernel, tune.canonical_shape(kernel, shape))
        if key not in seen:
            seen.add(key)
            uniq.append((kernel, shape))
    return plans, uniq


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="repro.tune.cli", description=__doc__)
    p.add_argument("--arch", choices=sorted(ARCHS), help="architecture config to pre-tune")
    p.add_argument(
        "--shape",
        action="append",
        default=[],
        metavar="NxD",
        help="explicit (batch x width) shape, repeatable (e.g. 256x2048)",
    )
    mode = p.add_mutually_exclusive_group()
    mode.add_argument("--dry", action="store_true", help="rank by compiled HLO cost (default)")
    mode.add_argument("--measure", action="store_true", help="rank by measured wall time")
    mode.add_argument("--analytic", action="store_true", help="rank by the closed-form model only")
    p.add_argument("--max-candidates", type=int, default=6, help="compile/run at most K candidates")
    p.add_argument(
        "--block-size",
        type=int,
        help="grouped-regularizer b your training config uses (default: "
        "search the grouped_block_plan candidate space for it)",
    )
    p.add_argument(
        "--serve",
        action="store_true",
        help="pre-tune the SERVE bucket shapes instead: expand each (n, d) "
        "into the micro-batcher's bucket ladder (align .. n rows, width d) "
        "and tune forward-only (the inference probes never differentiate)",
    )
    p.add_argument(
        "--serve-align",
        type=int,
        default=None,
        help="bucket granularity for --serve (default: the f32 sublane tile)",
    )
    p.add_argument(
        "--data-parallel",
        type=int,
        default=1,
        help="batch-shard count: tune the SHARD-LOCAL rows (n / data_parallel) "
        "the decorr engine dispatches inside shard_map",
    )
    p.add_argument(
        "--model-parallel",
        type=int,
        default=1,
        help="feature-shard count for the engine's tp mode: the regularizer "
        "runs on the all_to_all-transposed (n / (dp * mp), d) rows",
    )
    p.add_argument(
        "--distributed",
        default=None,
        choices=["local", "global", "tp"],
        help="engine mode the shard-local shapes are for (default: tp when "
        "--model-parallel > 1, else global — only tp divides rows by mp)",
    )
    p.add_argument("--cache-dir", help="override the JSON cache directory (REPRO_TUNE_CACHE)")
    p.add_argument("--no-persist", action="store_true", help="search but do not write the cache")
    p.add_argument("-v", "--verbose", action="store_true")
    args = p.parse_args(argv)

    if args.cache_dir:
        os.environ["REPRO_TUNE_CACHE"] = args.cache_dir
    mode_s = "measure" if args.measure else "analytic" if args.analytic else "dry"

    shapes: List[Tuple[int, int]] = []
    for spec in args.shape:
        n_s, _, d_s = spec.lower().partition("x")
        try:
            shapes.append((int(n_s), int(d_s)))
        except ValueError:
            p.error(f"--shape wants NxD (e.g. 256x2048), got {spec!r}")
    if args.arch:
        shapes.extend(arch_shapes(args.arch))
    if not shapes:
        p.error("nothing to tune: pass --arch and/or --shape NxD")
    if args.serve:
        # one job per (bucket, width): every compiled variant the serving
        # engine's bucket ladder can dispatch, mirroring ServeEngine.warmup.
        from repro.serve.buckets import BucketPolicy, bucket_shapes

        expanded = []
        for n, d in shapes:
            policy = BucketPolicy(
                max_batch=n, align=args.serve_align or BucketPolicy().align
            )
            expanded.extend(bucket_shapes(policy, d))
        shapes = sorted(set(expanded))
    if args.data_parallel > 1 or args.model_parallel > 1:
        # mirror repro.decorr.warmup.shard_local_shape: model_parallel only
        # shrinks the rows the kernels see in the engine's tp mode.
        from repro.decorr import shard_local_shape
        from repro.decorr.config import DecorrConfig

        dist = args.distributed or ("tp" if args.model_parallel > 1 else "global")
        cfg = DecorrConfig(distributed=dist)
        shapes = [
            shard_local_shape(
                n, d, cfg,
                data_parallel=args.data_parallel,
                model_parallel=args.model_parallel,
            )
            for n, d in shapes
        ]

    from repro import tune
    from repro.tune import cache as tcache

    tune_kw = dict(
        mode=mode_s, max_candidates=args.max_candidates, persist=not args.no_persist
    )
    def report(res):
        moved = "tuned" if res.best != res.default else "kept default"
        line = f"{res.kernel:>16} {'x'.join(map(str, res.shape)):>18}  {moved}: {res.best}"
        if args.verbose:
            for c in sorted(res.candidates, key=lambda c: c.cost["flops"]):
                line += f"\n{'':>38}{c.config}  flops={c.cost['flops']:.3e} bytes={c.cost['hbm_bytes']:.3e}"
        print(line, flush=True)

    n_jobs = 0
    for n, d in shapes:
        plans, jobs = jobs_for(
            n, d, block_size=args.block_size, forward_only=args.serve, **tune_kw
        )
        for plan_result in plans:
            report(plan_result)
            n_jobs += 1
        for kernel, shape in jobs:
            res = tune.tune(kernel, shape, **tune_kw)
            n_jobs += 1
            report(res)
    where = tcache.cache_dir() if not args.no_persist else "(not persisted)"
    print(f"# tuned {n_jobs} kernel shapes in {mode_s} mode -> {where}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
