"""Serve CLI: load-generate against the embedding service and print the
scrape metrics.

    # reduced end-to-end smoke (CI): naive vs micro-batched + probes
    PYTHONPATH=src python -m repro.serve.cli --smoke

    # bigger sweep, explicit knobs
    PYTHONPATH=src python -m repro.serve.cli --requests 1024 --d 2048 \
        --max-batch 64 --max-wait-ms 2

    # token-model serving demo (prefill/decode path, shared helpers)
    PYTHONPATH=src python -m repro.serve.cli --lm-arch rwkv6-3b

    # continuous batching vs whole-request generate + probe oracle gate (CI)
    PYTHONPATH=src python -m repro.serve.cli --smoke --lm-arch gemma2-2b --continuous

    # paged KV cache + chunked prefill + sampled decoding smoke (CI)
    PYTHONPATH=src python -m repro.serve.cli --smoke --lm-arch gemma2-2b \
        --continuous --paged --block-size 16 --prefill-chunk 16 \
        --temperature 0.8 --top-k 8

    # + the prefix-sharing radix cache gate (warm TTFT / peak pages / tokens)
    PYTHONPATH=src python -m repro.serve.cli --smoke --lm-arch gemma2-2b \
        --continuous --paged --block-size 16 --prefill-chunk 16 --prefix-cache

    # + the speculative-decoding gate (bit-identical tokens, accepted/step)
    PYTHONPATH=src python -m repro.serve.cli --smoke --lm-arch gemma2-2b \
        --continuous --paged --block-size 16 --speculative --draft-k 4

    # + the fabric failover gate (kill one of N replicas mid-decode; the
    # requeued requests must stay bit-identical to a 1-replica run)
    PYTHONPATH=src python -m repro.serve.cli --smoke --lm-arch gemma2-2b \
        --continuous --fabric --replicas 2

``--pretune`` warms the repro.tune cache for the serve bucket shapes first —
the same job list ``python -m repro.tune.cli --serve`` persists offline.
"""

from __future__ import annotations

import argparse
import json
import sys

from repro.serve.buckets import BucketPolicy, bucket_sizes


def _build_obs(args):
    """The CLI's telemetry bundle: default serve alert rules unless
    ``--alerts`` points at a JSON rule list (file path or inline)."""
    from repro.obs import AlertManager, Obs, default_serve_rules

    alerts = (
        AlertManager.from_config(args.alerts)
        if args.alerts
        else AlertManager(default_serve_rules())
    )
    return Obs(alerts=alerts)


def _finish_obs(args, obs, report_metrics) -> bool:
    """Post-run telemetry outputs: self-scrape the HTTP endpoint
    (``--metrics-port``; asserts every legacy ``metrics()`` key survived into
    the exposition), dump the Chrome trace (``--trace-out``), the metrics
    exposition text (``--metrics-out``) and the flight recorder
    (``--flight-out``)."""
    from repro.obs.registry import sanitize_name

    ok = True
    exposition = None
    if args.metrics_port is not None:
        import urllib.request

        server = obs.start_server(port=args.metrics_port)
        text = urllib.request.urlopen(f"{server.url}/metrics", timeout=10).read().decode()
        exposition = text
        exposed = {
            line.split("{")[0].split(" ")[0]
            for line in text.splitlines()
            if line and not line.startswith("#")
        }
        missing = []
        for k in report_metrics:
            s = sanitize_name(k)
            if s in exposed:
                continue
            # per-name heartbeat ages are claimed by the labelled family
            # (heartbeat_age_s{name=...}); the legacy name-suffixed keys only
            # live in the metrics() dict view
            if s.startswith("heartbeat_age_s_") and "heartbeat_age_s" in exposed:
                continue
            missing.append(k)
        print(
            f"[obs] scrape {server.url}/metrics: {len(text.splitlines())} lines, "
            f"{len(exposed)} series, active_alerts={obs.alerts.active()}"
        )
        if missing:
            print(f"[obs] MISSING from exposition: {missing[:8]}")
            ok = False
        server.stop()
    top = obs.perf.snapshot(top_k=3)
    if top:
        slowest = ", ".join(
            f"{r['executable']} ({r['calls']}x, {r['total_s']:.3f}s"
            + (f", util={r['roofline_utilization']:.3g}" if "roofline_utilization" in r else "")
            + ")"
            for r in top
        )
        print(f"[obs] slowest executables: {slowest}")
    if args.trace_out:
        obs.tracer.write(args.trace_out)
        print(f"[obs] trace: {len(obs.tracer)} events -> {args.trace_out}")
    if getattr(args, "metrics_out", None):
        if exposition is None:
            exposition = obs.scrape()
        with open(args.metrics_out, "w") as f:
            f.write(exposition)
        print(f"[obs] exposition -> {args.metrics_out}")
    if getattr(args, "flight_out", None):
        obs.recorder.dump_json(args.flight_out)
        print(f"[obs] flight recorder: {len(obs.recorder)} events -> {args.flight_out}")
    return ok


def _build_engine(args):
    import jax

    from repro.decorr.config import DecorrConfig
    from repro.serve.engine import ServeEngine
    from repro.serve.probes import DecorrProbe
    from repro.train.ssl import SSLModelConfig, init_ssl_params

    model = SSLModelConfig(
        input_dim=args.input_dim,
        backbone_widths=(args.backbone,),
        projector_widths=(args.d, args.d),
    )
    policy = BucketPolicy(
        max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        max_queue=args.max_queue,
    )

    def engine_fn():
        if args.ckpt_dir:
            return ServeEngine.from_checkpoint(args.ckpt_dir, model, policy=policy)
        params = init_ssl_params(jax.random.PRNGKey(args.seed), model)
        return ServeEngine(model, params, policy=policy)

    probe_cfg = DecorrConfig(
        style=args.probe_style, reg="sum", q=2, block_size=args.probe_block
    )
    return model, policy, engine_fn, lambda: DecorrProbe(probe_cfg)


def _run_embedding(args) -> int:
    from repro.serve.loadgen import LoadConfig, compare_policies

    model, policy, engine_fn, probe_fn = _build_engine(args)

    if args.pretune != "off":
        from repro import tune
        from repro.tune.cli import jobs_for

        n_jobs = 0
        for b in bucket_sizes(policy):
            _, jobs = jobs_for(
                b, args.d, block_size=args.probe_block, forward_only=True,
                mode=args.pretune, persist=False,
            )
            n_jobs += 1 + len(jobs)
            for kernel, shape in jobs:
                tune.tune(kernel, shape, mode=args.pretune, persist=False)
        print(f"[serve] pre-tuned {n_jobs} forward bucket shapes ({args.pretune})")

    load = LoadConfig(
        n_requests=args.requests,
        input_dim=args.input_dim,
        arrival_rps=args.arrival_rps,
        seed=args.seed,
    )
    print(
        f"[serve] d={args.d} requests={load.n_requests} "
        f"buckets={list(bucket_sizes(policy))} max_wait={policy.max_wait_ms}ms"
    )
    obs = _build_obs(args)
    if args.profile_dir:
        obs.profiler.start(args.profile_dir)
    report = compare_policies(engine_fn, load, policy, probe_fn=probe_fn, obs=obs)
    if args.profile_dir and obs.profiler.stop():
        print(f"[obs] profiler trace -> {args.profile_dir}")
    for name in ("naive", "microbatch"):
        r = report[name]
        print(
            f"[serve] {name:>10}: p50={r['p50_ms']:.2f}ms p99={r['p99_ms']:.2f}ms "
            f"throughput={r['throughput_rps']:.0f} req/s"
        )
    g = report["gate"]
    print(f"[serve] micro-batching speedup: {g['speedup']:.2f}x "
          f"(beats naive: {g['microbatch_beats_naive']})")
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True, default=float))
    else:
        m = report["service_metrics"]
        probes = {k: round(v, 6) for k, v in m.items() if k.startswith("decorr_")}
        print(f"[serve] probe metrics: {probes}")
        print(f"[serve] heartbeat stale={m['heartbeat_stale']:.0f} "
              f"missed={m['heartbeat_missed_events']:.0f}")
    obs_ok = _finish_obs(args, obs, report["service_metrics"])
    ok = g["microbatch_beats_naive"] and obs_ok
    return 0 if ok or not args.gate else 1


def _run_lm(args) -> int:
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.common import make_prompt, timed_generate
    from repro.serve.engine import LMServeEngine

    cfg = get_config(args.lm_arch).reduced()
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.continuous:
        return _run_lm_continuous(args, cfg, params)
    engine = LMServeEngine(cfg)
    prompt = make_prompt(cfg, jax.random.PRNGKey(args.seed + 1), args.max_batch, args.prompt_len)
    out, stats = timed_generate(
        params, cfg, prompt, args.new_tokens, steps=engine.steps
    )
    print(
        f"[serve] lm arch={cfg.name} (reduced): batch={prompt.shape[0]} "
        f"prompt={args.prompt_len} -> {args.new_tokens} tokens in "
        f"{stats['seconds']:.2f}s ({stats['tok_per_s']:.1f} tok/s)"
    )
    print("sample:", out[0].tolist()[:8])
    return 0


def _run_lm_continuous(args, cfg, params) -> int:
    """Continuous batching vs whole-request generate on a mixed-length
    workload, with the in-flight decorrelation probe replayed against the
    offline oracle.  ``--paged`` routes the slot pool through the block-table
    KV cache (page size from ``--block-size`` or the repro.tune winner) and
    additionally gates paged-vs-dense peak cache bytes; ``--temperature`` /
    ``--top-k`` run a sampled demo batch after the greedy gates."""
    from repro.decorr.config import DecorrConfig
    from repro.serve.loadgen import LMLoadConfig, compare_lm_policies
    from repro.serve.probes import DecorrProbe

    engine_kw = {}
    if args.paged:
        # no prefill_chunk here: this comparison hard-gates BIT-identical
        # tokens vs the whole-request oracle, and chunked prefill is only
        # argmax-stable (different prefill einsum shapes) — chunking is
        # exercised in _gate_paged's report-only pass instead
        engine_kw = dict(paged=True, page_size=args.block_size)
    load = LMLoadConfig(n_requests=args.requests, seed=args.seed)
    probe_cfg = DecorrConfig(style=args.probe_style, reg="sum", q=2, block_size=args.probe_block)
    obs = _build_obs(args)
    if args.profile_dir:
        obs.profiler.start(args.profile_dir)
    report = compare_lm_policies(
        cfg,
        params,
        load,
        n_slots=args.slots,
        probe_fn=lambda: DecorrProbe(probe_cfg),
        record_probe_rows=True,
        engine_kw=engine_kw,
        obs=obs,
    )
    if args.profile_dir and obs.profiler.stop():
        print(f"[obs] profiler trace -> {args.profile_dir}")
    for name in ("whole_request", "continuous"):
        r = report[name]
        print(
            f"[serve] {name:>14}: p50={r['p50_ms']:.2f}ms p99={r['p99_ms']:.2f}ms "
            f"{r['tok_per_s']:.0f} tok/s ({r['requests']:.0f} requests)"
        )
    g = report["gate"]
    m = report["service_metrics"]
    print(
        f"[serve] continuous-batching speedup: {g['speedup']:.2f}x "
        f"(beats whole-request: {g['continuous_beats_whole_request']}, "
        f"token mismatches: {g['token_mismatches']:.0f})"
    )
    print(
        f"[serve] occupancy={m['slots_occupancy']:.2f} "
        f"ttft_p50={m['ttft_p50_ms']:.2f}ms probe_steps={m.get('decorr_probe_steps', 0):.0f} "
        f"probe_oracle_rel_err={g.get('probe_oracle_rel_err', float('nan')):.2e}"
    )
    paged_ok = True
    if args.paged:
        paged_ok = _gate_paged(args, cfg, params, load)
    prefix_ok = True
    if args.prefix_cache:
        prefix_ok = _gate_prefix(args, cfg, params)
    spec_ok = True
    if args.speculative:
        spec_ok = _gate_speculative(args, cfg, params)
    fabric_ok = True
    if args.fabric:
        fabric_ok = _gate_fabric(args, cfg, params)
    if args.temperature or args.top_k:
        _demo_sampling(args, cfg, params)
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True, default=float))
    obs_ok = _finish_obs(args, obs, report["service_metrics"])
    # fail-closed like benchmarks/compare.py: a probe that never fired a
    # full window means the oracle check did NOT run — that fails the gate
    probe_err = g.get("probe_oracle_rel_err")
    ok = (
        g["continuous_beats_whole_request"]
        and g["token_mismatches"] == 0
        and probe_err is not None
        and probe_err < 1e-3
        and paged_ok
        and prefix_ok
        and spec_ok
        and fabric_ok
        and obs_ok
    )
    return 0 if ok or not args.gate else 1


def _gate_paged(args, cfg, params, load) -> bool:
    """Dense vs paged at the same workload: identical tokens + peak cache
    bytes strictly below the dense pool's permanent reservation."""
    from repro.serve.loadgen import compare_paged_dense

    rep = compare_paged_dense(
        cfg, params, load,
        n_slots=args.slots,
        page_size=args.block_size or 16,
        prefill_chunk=args.prefill_chunk,
    )
    g = rep["gate"]
    print(
        f"[serve] paged vs dense: peak_cache_bytes_ratio={g['peak_cache_bytes_ratio']:.3f} "
        f"(paged<dense: {g['paged_peak_lt_dense']}, "
        f"token mismatches: {g['token_mismatches']:.0f}, "
        f"tok/s ratio {g['tok_per_s_ratio']:.2f})"
    )
    return bool(g["paged_peak_lt_dense"]) and g["token_mismatches"] == 0


def _gate_prefix(args, cfg, params) -> bool:
    """Prefix sharing on vs off over the same paged chunk-all engine on a
    shared-prefix fan-out workload: bit-identical tokens, warm-phase TTFT and
    peak pool pages both strictly below the unshared run.  The engine shape
    is pinned (4 slots, page 16, chunk 8) to match the workload defaults —
    this is a regression gate over a known-stressing shape (the chunk must
    halve the page or copy-on-write never triggers), not a knob explorer."""
    from repro.serve.loadgen import SharedPrefixLoadConfig, compare_prefix_sharing

    load = SharedPrefixLoadConfig(seed=args.seed)
    rep = compare_prefix_sharing(
        cfg, params, load, n_slots=4, page_size=16, prefill_chunk=8,
    )
    g = rep["gate"]
    print(
        f"[serve] prefix cache: hit_rate={g['prefix_hit_rate']:.2f} "
        f"warm_ttft_ratio={g['warm_ttft_ratio']:.3f} "
        f"peak_pages_ratio={g['peak_pages_ratio']:.3f} "
        f"(token mismatches: {g['token_mismatches']:.0f})"
    )
    return (
        g["token_mismatches"] == 0
        and bool(g["warm_ttft_lt_unshared"])
        and bool(g["peak_pages_lt_unshared"])
        and g["prefix_hit_rate"] > 0
    )


def _gate_speculative(args, cfg, params) -> bool:
    """Plain paged vs self-drafting speculative decode on a decode-heavy
    workload: bit-identical greedy tokens (the hard gate — a smoke-sized run
    is too short to gate CPU wall clock) and more than one token emitted per
    verify slot-lane, i.e. the drafter is actually accepting tokens."""
    from repro.serve.loadgen import LMLoadConfig, compare_speculative

    load = LMLoadConfig(
        n_requests=min(args.requests, 16),
        prompt_lens=(4, 6, 8), new_tokens=(24, 32), seed=args.seed,
    )
    rep = compare_speculative(
        cfg, params, load,
        n_slots=args.slots,
        page_size=args.block_size or 16,
        draft_k=args.draft_k,
    )
    g = rep["gate"]
    print(
        f"[serve] speculative: accepted/step={g['accepted_tokens_per_step']:.2f} "
        f"tokens/lane={g['tokens_per_lane']:.2f} "
        f"hit_rate={g['draft_hit_rate']:.2f} "
        f"tok/s ratio {g['tok_per_s_ratio']:.2f} "
        f"(token mismatches: {g['token_mismatches']:.0f})"
    )
    return g["token_mismatches"] == 0 and g["tokens_per_lane"] > 1


def _gate_fabric(args, cfg, params) -> bool:
    """Kill-one-replica failover on a synchronous N-replica fabric (fake
    clock: the smoke never sleeps).  The hard gate is LOSSLESS determinism:
    every request — including the ones stranded on the killed replica and
    requeued — must emit the exact greedy token stream of a 1-replica run,
    and the kill must actually strand work (``requeued > 0``).  On failure
    every replica's flight recorder is dumped to
    ``flightrec_replica_<name>.json`` (CI uploads ``flightrec_*.json``)."""
    import numpy as np

    from repro.obs import Obs
    from repro.serve.fabric import FabricConfig
    from repro.serve.loadgen import FabricLoadConfig, LMLoadConfig, make_lm_fabric

    load = FabricLoadConfig(
        lm=LMLoadConfig(
            n_requests=min(args.requests, 12),
            prompt_lens=(4, 8, 14),
            new_tokens=(8, 16),
            seed=args.seed,
        )
    )
    kw = dict(n_slots=args.slots, page_size=args.block_size or 16)

    def submit_all(fab):
        stream = load.lm.request_stream(cfg.vocab_size)
        return [fab.submit_lm(tok, mn) for tok, mn in stream]

    oracle_fab, _ = make_lm_fabric(
        cfg, params, FabricConfig(replicas=1, heartbeat_timeout_s=5.0), load, **kw
    )
    ofuts = submit_all(oracle_fab)
    oracle_fab.drain()
    oracle = [f.result(timeout=60) for f in ofuts]

    t = {"now": 0.0}
    fab_obs = Obs()
    fab, _ = make_lm_fabric(
        cfg, params,
        FabricConfig(replicas=args.replicas, heartbeat_timeout_s=5.0),
        load, obs=fab_obs, clock=lambda: t["now"], **kw,
    )
    futs = submit_all(fab)
    for _ in range(3):  # let every replica admit + decode a few ticks
        fab.step()
    fab.kill("r0")
    t["now"] += 10.0  # heartbeat goes stale; the next step drains r0
    fab.drain()
    outs = [f.result(timeout=60) for f in futs]
    mismatches = sum(
        1 for a, b in zip(oracle, outs) if not np.array_equal(a, b)
    )
    counts = fab_obs.recorder.counts()
    print(
        f"[serve] fabric: replicas={args.replicas} "
        f"requeued={fab.requeued_total} dead={fab.dead_total} "
        f"routes={counts.get('route', 0)} "
        f"(requeue token mismatches: {mismatches})"
    )
    ok = mismatches == 0 and fab.requeued_total > 0 and fab.dead_total == 1
    if not ok:
        fab_obs.recorder.dump_json("flightrec_fabric.json")
        for r in fab.replicas:
            if r.lm is not None:
                r.lm.obs.recorder.dump_json(f"flightrec_replica_{r.name}.json")
        print("[serve] fabric gate FAILED; flight dumps -> flightrec_fabric.json, "
              "flightrec_replica_*.json")
    return ok


def _demo_sampling(args, cfg, params):
    """A short sampled batch through the paged/dense pool: per-request
    temperature/top-k/seed, reproducibility printed for two replays."""
    from repro.serve.engine import ContinuousLMEngine
    from repro.serve.service import LMService

    import numpy as np

    def run():
        eng = ContinuousLMEngine(
            cfg, params, n_slots=args.slots, max_len=64, max_prompt_len=24,
            paged=args.paged, page_size=args.block_size if args.paged else None,
            sampling=True,
        )
        svc = LMService(eng)
        svc.warmup()
        rng = np.random.default_rng(args.seed)
        futs = [
            svc.submit(
                rng.integers(0, cfg.vocab_size, 8).astype(np.int32), 8,
                temperature=args.temperature or 0.0, top_k=args.top_k, seed=i,
            )
            for i in range(4)
        ]
        svc.drain()
        return [f.result(timeout=30).tolist() for f in futs]

    a, b = run(), run()
    print(
        f"[serve] sampled decode (T={args.temperature}, top_k={args.top_k}): "
        f"sample={a[0][:8]} reproducible={a == b}"
    )


def main(argv=None) -> int:
    """Argparse entry point (see the module docstring for usage)."""
    p = argparse.ArgumentParser(prog="repro.serve.cli", description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="reduced config + few requests (CI smoke; implies --gate)")
    p.add_argument("--requests", type=int, default=512)
    p.add_argument("--input-dim", type=int, default=128)
    p.add_argument("--backbone", type=int, default=256)
    p.add_argument("--d", type=int, default=512, help="projector/embedding width")
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--max-wait-ms", type=float, default=2.0)
    p.add_argument("--max-queue", type=int, default=4096)
    p.add_argument("--arrival-rps", type=float, default=None,
                   help="open-loop arrival rate (default: closed-loop burst)")
    p.add_argument("--ckpt-dir", default=None,
                   help="serve params from a repro.checkpoint directory")
    p.add_argument("--probe-style", default="vic", choices=["bt", "vic"])
    p.add_argument("--probe-block", type=int, default=None)
    p.add_argument("--pretune", default="off",
                   choices=["off", "analytic", "dry", "measure"],
                   help="warm the repro.tune cache for the serve buckets first")
    p.add_argument("--gate", action="store_true",
                   help="exit 1 unless micro-batched throughput beats naive")
    p.add_argument("--json", action="store_true", help="dump the full report as JSON")
    p.add_argument("--seed", type=int, default=0)
    # token-model demo path
    p.add_argument("--lm-arch", default=None,
                   help="serve a token model instead (e.g. rwkv6-3b, gemma2-2b)")
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--new-tokens", type=int, default=8)
    p.add_argument("--continuous", action="store_true",
                   help="with --lm-arch: continuous batching vs whole-request "
                        "generate on a mixed-length workload")
    p.add_argument("--slots", type=int, default=8,
                   help="continuous-batching decode slot pool size")
    p.add_argument("--paged", action="store_true",
                   help="with --continuous: paged (block-table) KV cache; also "
                        "gates paged peak cache bytes < dense")
    p.add_argument("--block-size", type=int, default=None,
                   help="KV page size in tokens (default: the repro.tune winner "
                        "for the pool shape, fragmentation-capped)")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="with --paged: prefill long prompts N tokens per decode "
                        "tick instead of stalling the pool")
    p.add_argument("--speculative", action="store_true",
                   help="with --paged: also gate self-drafting speculative "
                        "decoding (bit-identical greedy tokens, more than one "
                        "token emitted per verify slot-lane)")
    p.add_argument("--draft-k", type=int, default=4,
                   help="speculative draft tokens proposed per verify tick")
    p.add_argument("--fabric", action="store_true",
                   help="with --continuous: also gate the replica-router "
                        "failover path (kill one replica mid-decode on a fake "
                        "clock; requeued requests must emit bit-identical "
                        "tokens to a 1-replica run)")
    p.add_argument("--replicas", type=int, default=2,
                   help="fabric size for --fabric")
    p.add_argument("--prefix-cache", action="store_true",
                   help="with --paged: also gate the prefix-sharing radix "
                        "cache (bit-identical tokens + warm TTFT and peak "
                        "pages strictly below the unshared paged run)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="run a sampled demo batch after the greedy gates "
                        "(0 = greedy only)")
    p.add_argument("--top-k", type=int, default=None,
                   help="restrict sampled decoding to the k highest logits")
    # telemetry (repro.obs)
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve /metrics over HTTP after the run and self-scrape "
                        "it (0 = ephemeral port); the gate fails if any legacy "
                        "metrics() key is missing from the exposition")
    p.add_argument("--trace-out", default=None,
                   help="write the Chrome trace_event JSON of the run here")
    p.add_argument("--metrics-out", default=None,
                   help="write the final Prometheus exposition text here "
                        "(CI failure artifact)")
    p.add_argument("--flight-out", default=None,
                   help="write the flight recorder's event ring as JSON here")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of the run into this dir")
    p.add_argument("--alerts", default=None,
                   help="alert rules as a JSON file path or inline JSON list "
                        "(default: the built-in serve rules)")
    args = p.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.fabric and not (args.lm_arch and args.continuous):
        p.error("--fabric routes continuous LM replicas; it requires "
                "--lm-arch and --continuous")
    if args.prefix_cache and not args.paged:
        p.error("--prefix-cache shares KV pages; it requires --paged")
    if args.speculative and not args.paged:
        p.error("--speculative verifies through scratch pages; it requires --paged")

    if args.smoke:
        args.requests = min(args.requests, 192)
        args.input_dim, args.backbone, args.d = 32, 64, 256
        args.max_batch = min(args.max_batch, 32)
        args.gate = True
        if args.lm_arch and args.continuous:
            args.requests = min(args.requests, 24)

    if args.lm_arch:
        return _run_lm(args)
    return _run_embedding(args)


if __name__ == "__main__":
    sys.exit(main())
