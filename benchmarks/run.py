"""Benchmark harness entry point — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run complexity # one suite
"""

from __future__ import annotations

import sys
import time
import traceback

SUITES = {
    "complexity": "benchmarks.bench_complexity",       # Fig. 2 / Table 7
    "blocksize": "benchmarks.bench_blocksize",         # Fig. 3
    "permutation": "benchmarks.bench_permutation",     # Tables 5 & 6
    "q": "benchmarks.bench_q",                         # Table 11
    "training_time": "benchmarks.bench_training_time", # Table 4 / 12 / 13
    "equivalence": "benchmarks.bench_loss_equivalence",# kernel agreement
    "distributed": "benchmarks.bench_distributed",     # DESIGN §4 modes
    "roofline": "benchmarks.roofline",                 # §Roofline (from dryrun)
    "tune": "benchmarks.bench_tune",                   # default-vs-tuned -> BENCH_tune.json
    "serve": "benchmarks.bench_serve",                 # serving policies -> BENCH_serve.json
}


def main() -> int:
    """Run the named suites (all by default); 1 if any of them raised."""
    import importlib

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    wanted = sys.argv[1:] or list(SUITES)
    failed = []
    print("name,us_per_call,derived")
    for key in wanted:
        mod = importlib.import_module(SUITES[key])
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # run the other suites, then fail the harness
            traceback.print_exc()
            failed.append(key)
            rows = [f"{key}/ERROR,0,{type(e).__name__}: {e}"]
        for row in rows:
            print(row, flush=True)
        print(f"# suite {key} took {time.time()-t0:.1f}s", flush=True)
    if failed:
        print(f"# FAILED suites: {','.join(failed)}", file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
