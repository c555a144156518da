"""Run one cell of the benchmark once, on the chip this process finds.

    python3 bench/run.py --workload bt-sum-d8192.dev-b256 --seed 7 --seconds 20 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted`` (train steps in the window), ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number that ``correct`` compared, beside its limit.  The same
checks are the last lines of standard error.  Without a TPU, or with fewer
chips than the cell asks for, it prints no result and exits non-zero.
"""

from __future__ import annotations

import os
import time

T0 = time.perf_counter()

# The host-fed traffic renders in NumPy on the host; one BLAS thread keeps that
# load to one core beside the device runtime's threads.  Read when NumPy loads.
for _var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_var, "1")

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _finite(x):
    """JSON has no infinity: a gap that overflowed is written as 1e300."""
    if isinstance(x, float) and not math.isfinite(x):
        return 1e300
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench import cells

    cell = cells.load_cell(args.workload)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench import harness

    result = _finite(harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T0))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
