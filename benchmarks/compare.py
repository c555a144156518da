"""Bench-regression gate: current BENCH_*.json vs committed baselines.

CI runs the bench suites, then::

    PYTHONPATH=src python -m benchmarks.compare serve tune

Two kinds of checks per suite:

  * **hard gates** — absolute invariants that must hold on any machine
    (micro-batching beats naive, continuous batching beats whole-request
    with zero token mismatches, probes agree with the training-path oracle,
    tuned kernel costs <= default);
  * **baseline regression** — RATIO metrics (speedups, tuned/default cost
    ratios) compared against ``benchmarks/baselines/BENCH_*.json``.  Ratios
    are machine-portable where absolute throughput is not; a ratio more than
    ``REL_TOL`` (20%) worse than the committed baseline fails the gate.

``--write-baseline`` snapshots the current reports into the baselines dir
(run locally, commit the result) after an intentional perf change.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Tuple

REL_TOL = 0.20
BASELINE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "baselines")


def _lookup(report: dict, path: str):
    cur = report
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def _tune_ratio_metrics(report: dict) -> Dict[str, float]:
    out = {}
    for k in report.get("kernels", []):
        name = f"{k['kernel']}{tuple(k['shape'])}"
        out[f"{name}.flops_ratio"] = float(k["flops_ratio"])
        out[f"{name}.bytes_ratio"] = float(k["bytes_ratio"])
    return out


# (path, predicate, description) hard gates per suite
HARD_GATES = {
    "serve": [
        ("gate.microbatch_beats_naive", lambda v: bool(v), "micro-batched throughput >= naive"),
        ("probe.oracle_rel_err", lambda v: v < 1e-3, "embedding probe matches training oracle"),
        ("lm.gate.continuous_beats_whole_request", lambda v: bool(v),
         "continuous-batching tok/s >= whole-request generate"),
        ("lm.gate.token_mismatches", lambda v: v == 0,
         "slot interleaving changes no request's tokens"),
        ("lm.gate.probe_oracle_rel_err", lambda v: v < 1e-3,
         "in-flight probe matches training oracle under interleaving"),
        ("paged.gate.token_mismatches", lambda v: v == 0,
         "paged KV cache changes no request's greedy tokens"),
        ("paged.gate.paged_peak_lt_dense", lambda v: bool(v),
         "paged peak cache bytes < dense pool at the skewed length mix"),
        ("prefix.gate.token_mismatches", lambda v: v == 0,
         "prefix sharing changes no request's greedy tokens"),
        ("prefix.gate.warm_ttft_lt_unshared", lambda v: bool(v),
         "warm-prefix TTFT strictly below the unshared paged run"),
        ("prefix.gate.peak_pages_lt_unshared", lambda v: bool(v),
         "prefix sharing's peak pool pages strictly below unshared"),
        ("prefix.gate.prefix_hit_rate", lambda v: v > 0,
         "the radix cache actually served hits on the fan-out workload"),
        ("prefix.gate.probe_oracle_rel_err", lambda v: v < 1e-3,
         "in-flight probe matches training oracle under page sharing"),
        ("spec.gate.token_mismatches", lambda v: v == 0,
         "speculative decode changes no request's greedy tokens"),
        ("spec.gate.spec_beats_plain", lambda v: bool(v),
         "speculative tok/s >= plain paged decode on the decode-heavy mix"),
        ("spec.gate.accepted_tokens_per_step", lambda v: v > 1,
         "each verify step emits more than one token on average"),
        ("obs.gate.overhead_ok", lambda v: bool(v),
         "always-on telemetry keeps >= 95% of telemetry-off tok/s"),
        ("perf.gate.has_required", lambda v: bool(v),
         "attribution covers embed buckets, prefill buckets, decode tick, "
         "chunked prefill and the probe update"),
        ("perf.gate.nonzero_samples", lambda v: bool(v),
         "every attributed executable has nonzero wall-time samples"),
        ("perf.gate.utilization_ok", lambda v: bool(v),
         "every attributed executable's roofline utilization is in (0, 1]"),
        ("fabric.gate.token_mismatches", lambda v: v == 0,
         "replica routing changes no request's greedy tokens"),
        ("fabric.gate.requeue_token_mismatches", lambda v: v == 0,
         "failover requeue re-derives every killed replica's tokens bit-exactly"),
        ("fabric.gate.requeued", lambda v: v > 0,
         "the kill-one-replica leg actually stranded and requeued requests"),
        ("fabric.gate.scaling_ok", lambda v: bool(v),
         "N-replica aggregate tok/s meets the hardware-aware scaling target"),
        ("fabric.gate.tp", lambda v: v >= 2,
         "the tp oracle sharded the forward over at least two devices"),
        ("fabric.gate.tp_rel_err", lambda v: v < 1e-5,
         "feature-sharded tp forward matches the single-device oracle"),
        ("fabric.gate.embed_max_abs_err", lambda v: v < 1e-5,
         "embedding results are route-independent across replicas"),
    ],
    "tune": [],  # per-kernel gates generated below
}

# suite -> (bench file, {metric name: (direction, dotted path)})
#   direction: +1 higher is better (speedups), -1 lower is better (costs)
RATIO_METRICS = {
    "serve": {
        "microbatch_speedup": (+1, "gate.speedup"),
        "continuous_speedup": (+1, "lm.gate.speedup"),
        "slot_occupancy": (+1, "lm.service_metrics.slots_occupancy"),
        # peak_cache_bytes_ratio is deterministic (same workload, same
        # allocator) — gate it; tok_per_s_ratio is reported in the JSON but
        # too load-sensitive on CPU CI to gate against a snapshot baseline
        "paged_peak_bytes_ratio": (-1, "paged.gate.peak_cache_bytes_ratio"),
        # deterministic for the same reason: page arithmetic, not wall clock
        "prefix_peak_pages_ratio": (-1, "prefix.gate.peak_pages_ratio"),
    },
    "tune": {},  # per-kernel ratios generated from the report
}

FILES = {"serve": "BENCH_serve.json", "tune": "BENCH_tune.json"}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def check_suite(
    suite: str, current: dict, baseline: dict | None
) -> Tuple[List[str], List[str]]:
    """Returns (failures, notes)."""
    failures, notes = [], []

    # hard gates
    if suite == "tune":
        for k in current.get("kernels", []):
            name = f"{k['kernel']}{tuple(k['shape'])}"
            for key in ("flops_ratio", "bytes_ratio"):
                if float(k[key]) > 1.0:
                    failures.append(f"[{suite}] tuned worse than default: {name}.{key}={k[key]:.3f}")
    for path, pred, desc in HARD_GATES.get(suite, []):
        v = _lookup(current, path)
        if v is None:
            failures.append(f"[{suite}] missing gate metric {path} ({desc})")
        elif not pred(v):
            failures.append(f"[{suite}] HARD gate failed: {desc} ({path}={v})")
        else:
            notes.append(f"[{suite}] ok: {desc} ({path}={v})")

    # baseline ratio regression
    if baseline is None:
        notes.append(f"[{suite}] no baseline committed — regression check skipped")
        return failures, notes
    if suite == "tune":
        cur_m = _tune_ratio_metrics(current)
        base_m = _tune_ratio_metrics(baseline)
        pairs = {name: (-1, cur_m[name], base_m.get(name)) for name in cur_m}
    else:
        pairs = {
            name: (direction, _lookup(current, path), _lookup(baseline, path))
            for name, (direction, path) in RATIO_METRICS[suite].items()
        }
    for name, (direction, cur, base) in pairs.items():
        if cur is None or base is None or base == 0:
            notes.append(f"[{suite}] {name}: not comparable (cur={cur}, base={base})")
            continue
        if direction > 0:
            ok = cur >= base * (1.0 - REL_TOL)
        else:
            ok = cur <= base * (1.0 + REL_TOL)
        line = f"{name}: current={cur:.3f} baseline={base:.3f} (tol {REL_TOL:.0%})"
        if ok:
            notes.append(f"[{suite}] ok: {line}")
        else:
            failures.append(f"[{suite}] REGRESSION >{REL_TOL:.0%}: {line}")
    return failures, notes


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmarks.compare", description=__doc__)
    p.add_argument("suites", nargs="*", default=None,
                   help="which suites to gate (default: all with a bench file present)")
    p.add_argument("--current-dir", default=".",
                   help="where the freshly produced BENCH_*.json live")
    p.add_argument("--baseline-dir", default=BASELINE_DIR)
    p.add_argument("--write-baseline", action="store_true",
                   help="snapshot current reports as the new committed baselines")
    args = p.parse_args(argv)

    suites = args.suites or [s for s in FILES
                             if os.path.exists(os.path.join(args.current_dir, FILES[s]))]
    if not suites:
        print("benchmarks.compare: no BENCH_*.json found; run `python -m benchmarks.run` first")
        return 2

    all_failures: List[str] = []
    for suite in suites:
        cur_path = os.path.join(args.current_dir, FILES[suite])
        if not os.path.exists(cur_path):
            all_failures.append(f"[{suite}] missing {cur_path}")
            continue
        current = _load(cur_path)
        if args.write_baseline:
            os.makedirs(args.baseline_dir, exist_ok=True)
            dst = os.path.join(args.baseline_dir, FILES[suite])
            with open(dst, "w") as f:
                json.dump(current, f, indent=2, sort_keys=True)
            print(f"[{suite}] baseline written: {dst}")
            continue
        base_path = os.path.join(args.baseline_dir, FILES[suite])
        baseline = _load(base_path) if os.path.exists(base_path) else None
        failures, notes = check_suite(suite, current, baseline)
        for n in notes:
            print(n)
        for fail in failures:
            print(fail, file=sys.stderr)
        all_failures.extend(failures)

    if all_failures:
        print(f"\nbench gate FAILED ({len(all_failures)} violations)", file=sys.stderr)
        return 1
    if not args.write_baseline:
        print("\nbench gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
