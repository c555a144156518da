"""Serving policies: (embedding path) naive per-request vs dynamic
micro-batching on the ssl-paper reduced config, (LM path) whole-request
``greedy_generate`` vs continuous batching on a mixed-length workload, and
(paged path) dense vs paged KV cache on a length-SKEWED workload — many
short requests sharing a pool sized for the rare long one, the fragmentation
case block tables exist for — (prefix path) the prefix-sharing radix
cache on a shared-prefix fan-out workload: warm requests resume chunked
prefill past the cached pages — and (spec path) self-drafting speculative
decode vs plain paged decode on a decode-heavy workload.  Emits
``BENCH_serve.json`` (p50/p99 latency + throughput per policy, probe health,
probe-vs-oracle agreement, paged peak cache bytes vs the dense pool,
warm-vs-cold prefix TTFT + peak pages, speculative acceptance stats); CI
gates (``benchmarks/compare.py``) that micro-batched >= naive, continuous >=
whole-request (identical tokens), paged == dense tokens with strictly
smaller peak cache bytes, prefix sharing == unshared tokens with strictly
lower warm TTFT and peak pages, speculative == plain tokens at >= plain
tok/s with more than one accepted token per verify step, probes match the
training-path oracle, and no gated ratio regresses >20% against the
committed baseline.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import fmt_row

# ssl-paper reduced: paper-shaped siamese MLP, sized for a CPU bench run
REDUCED = dict(input_dim=64, backbone=128, d=512)
POLICY = dict(max_batch=64, max_wait_ms=2.0)
N_REQUESTS = 512
# LM continuous batching: small attention arch, mixed-length closed loop
LM = dict(arch="gemma2-2b", n_requests=32, slots=8)
# paged KV: skewed length mix (mostly short prompts, a rare long one dictates
# the dense pool's max_len), page size pinned for a reproducible layout
PAGED = dict(
    n_requests=24,
    prompt_lens=(4, 6, 8, 40),
    new_tokens=(4, 12, 20),
    slots=8,
    page_size=16,
    prefill_chunk=16,
)
# prefix sharing: 2 long prefixes fanned out to 7 requests each; page 16 with
# chunk 8 and a 92-token prefix puts warm hits mid-page (h=88), so the
# copy-on-write path runs, not just whole-page binding
PREFIX = dict(
    n_prefixes=2,
    fan_out=7,
    prefix_len=92,
    tail_lens=(3, 5, 9),
    new_tokens=(32, 48),
    slots=4,
    page_size=16,
    prefill_chunk=8,
)
# speculative decoding: decode-heavy mix (short prompts, long generations) so
# verify steps dominate and the n-gram drafter has context to look up; greedy
# from a random-init net falls into repetitive cycles the drafter catches
SPECDEC = dict(
    n_requests=32,
    prompt_lens=(4, 6, 8),
    new_tokens=(24, 32),
    slots=8,
    page_size=16,
    draft_k=4,
    ngram_max=3,
    ngram_min=1,
)
# serving fabric: N threaded replicas behind the router (XLA releases the GIL
# during device execution, so scaling needs real cores — the scaling target is
# hardware-aware), plus the synchronous kill-one-replica failover leg and the
# feature-sharded tp forward vs its single-device oracle
FABRIC = dict(
    replicas=2,
    n_requests=16,
    prompt_lens=(4, 8, 14),
    new_tokens=(8, 16),
    n_embed=4,
    slots=4,
    page_size=16,
    tp=2,
)


def run():
    from repro.decorr import probe_metrics
    from repro.decorr.config import DecorrConfig
    from repro.serve import BucketPolicy, DecorrProbe, LoadConfig, ServeEngine, bucket_sizes
    from repro.serve.loadgen import compare_policies
    from repro.train.ssl import SSLModelConfig, init_ssl_params

    model = SSLModelConfig(
        input_dim=REDUCED["input_dim"],
        backbone_widths=(REDUCED["backbone"],),
        projector_widths=(REDUCED["d"], REDUCED["d"]),
    )
    params = init_ssl_params(jax.random.PRNGKey(0), model)
    policy = BucketPolicy(**POLICY)
    probe_cfg = DecorrConfig(style="vic", reg="sum", q=2)

    load = LoadConfig(n_requests=N_REQUESTS, input_dim=REDUCED["input_dim"])
    report = compare_policies(
        lambda: ServeEngine(model, params, policy=policy),
        load,
        policy,
        probe_fn=lambda: DecorrProbe(probe_cfg),
    )

    # probe-vs-oracle agreement on one served batch (acceptance criterion:
    # the online probe equals the training-path computation to tolerance)
    n = bucket_sizes(policy)[-1]
    x = np.random.default_rng(1).standard_normal((n, REDUCED["input_dim"])).astype(np.float32)
    engine = ServeEngine(model, params, policy=policy)
    z = engine.encode(x)
    key = jax.random.fold_in(jax.random.PRNGKey(0), jnp.uint32(0))
    served = DecorrProbe(probe_cfg, sample_rows=n)
    served.observe(np.asarray(z))
    oracle = {k: float(v) for k, v in probe_metrics(z, cfg=probe_cfg, perm_key=key).items()}
    probe_err = max(
        abs(served.metrics()[f"decorr_{k}"] - v) / max(abs(v), 1e-6)
        for k, v in oracle.items()
    )

    lm_report = _run_lm_continuous()
    paged_report = _run_paged()
    prefix_report = _run_prefix()
    spec_report = _run_spec()
    obs_report = _run_obs_overhead()
    perf_report = _run_perf()
    fabric_report = _run_fabric()

    out = {
        "config": {
            **REDUCED,
            **POLICY,
            "n_requests": N_REQUESTS,
            "buckets": list(bucket_sizes(policy)),
            "lm": LM,
            "paged": PAGED,
            "prefix": PREFIX,
            "spec": SPECDEC,
            "fabric": FABRIC,
        },
        "naive": report["naive"],
        "microbatch": report["microbatch"],
        "probe": {
            "oracle_rel_err": probe_err,
            **{k: v for k, v in report["service_metrics"].items() if k.startswith("decorr_")},
        },
        "gate": report["gate"],
        "lm": lm_report,
        "paged": paged_report,
        "prefix": prefix_report,
        "spec": spec_report,
        "obs": obs_report,
        "perf": perf_report,
        "fabric": fabric_report,
    }
    with open(os.path.join(os.getcwd(), "BENCH_serve.json"), "w") as f:
        json.dump(out, f, indent=2, sort_keys=True, default=float)

    rows = []
    for name in ("naive", "microbatch"):
        r = report[name]
        rows.append(fmt_row(
            f"serve/{name}", r["p50_ms"] * 1e3,
            f"p99_ms={r['p99_ms']:.2f};throughput_rps={r['throughput_rps']:.0f}",
        ))
    rows.append(fmt_row(
        "serve/gate_microbatch_beats_naive", 0.0,
        f"speedup={report['gate']['speedup']:.2f}x;"
        f"ok={report['gate']['microbatch_beats_naive']};"
        f"probe_oracle_rel_err={probe_err:.2e}",
    ))
    for name in ("whole_request", "continuous"):
        r = lm_report[name]
        rows.append(fmt_row(
            f"serve/lm_{name}", r["p50_ms"] * 1e3,
            f"p99_ms={r['p99_ms']:.2f};tok_per_s={r['tok_per_s']:.0f}",
        ))
    g = lm_report["gate"]
    rows.append(fmt_row(
        "serve/gate_continuous_beats_whole_request", 0.0,
        f"speedup={g['speedup']:.2f}x;ok={g['continuous_beats_whole_request']};"
        f"token_mismatches={g['token_mismatches']:.0f};"
        f"probe_oracle_rel_err={g.get('probe_oracle_rel_err', float('nan')):.2e};"
        f"occupancy={lm_report['service_metrics']['slots_occupancy']:.2f}",
    ))
    for name in ("dense", "paged"):
        r = paged_report[name]
        cache = r.get("cache_bytes", r.get("paged_peak_cache_bytes", 0.0))
        rows.append(fmt_row(
            f"serve/paged_{name}", r["p50_ms"] * 1e3,
            f"tok_per_s={r['tok_per_s']:.0f};cache_bytes={cache:.0f}",
        ))
    pg = paged_report["gate"]
    rows.append(fmt_row(
        "serve/gate_paged_peak_lt_dense", 0.0,
        f"ok={pg['paged_peak_lt_dense']};bytes_ratio={pg['peak_cache_bytes_ratio']:.3f};"
        f"token_mismatches={pg['token_mismatches']:.0f};"
        f"tok_per_s_ratio={pg['tok_per_s_ratio']:.2f}",
    ))
    for name in ("unshared", "shared"):
        r = prefix_report[name]
        rows.append(fmt_row(
            f"serve/prefix_{name}", r["warm_ttft_p50_ms"] * 1e3,
            f"tok_per_s={r['tok_per_s']:.0f};peak_pages={r['peak_pages']:.0f}",
        ))
    xg = prefix_report["gate"]
    rows.append(fmt_row(
        "serve/gate_prefix_sharing", 0.0,
        f"ok={xg['warm_ttft_lt_unshared'] and xg['peak_pages_lt_unshared']};"
        f"hit_rate={xg['prefix_hit_rate']:.2f};"
        f"warm_ttft_ratio={xg['warm_ttft_ratio']:.3f};"
        f"peak_pages_ratio={xg['peak_pages_ratio']:.3f};"
        f"token_mismatches={xg['token_mismatches']:.0f};"
        f"probe_oracle_rel_err={xg.get('probe_oracle_rel_err', float('nan')):.2e}",
    ))
    for name in ("plain", "speculative"):
        r = spec_report[name]
        rows.append(fmt_row(
            f"serve/spec_{name}", r["p50_ms"] * 1e3,
            f"p99_ms={r['p99_ms']:.2f};tok_per_s={r['tok_per_s']:.0f}",
        ))
    sg = spec_report["gate"]
    rows.append(fmt_row(
        "serve/gate_speculative", 0.0,
        f"ok={sg['spec_beats_plain'] and sg['accepted_tokens_per_step'] > 1};"
        f"tok_per_s_ratio={sg['tok_per_s_ratio']:.2f};"
        f"accepted_per_step={sg['accepted_tokens_per_step']:.2f};"
        f"tokens_per_lane={sg['tokens_per_lane']:.2f};"
        f"hit_rate={sg['draft_hit_rate']:.2f};"
        f"token_mismatches={sg['token_mismatches']:.0f}",
    ))
    for name in ("off", "on"):
        r = obs_report[name]
        rows.append(fmt_row(
            f"serve/obs_{name}", r["p50_ms"] * 1e3,
            f"tok_per_s={r['tok_per_s']:.0f}",
        ))
    og = obs_report["gate"]
    rows.append(fmt_row(
        "serve/gate_obs_overhead", 0.0,
        f"ok={og['overhead_ok']};tok_per_s_ratio={og['tok_per_s_ratio']:.3f}",
    ))
    pf = perf_report["gate"]
    rows.append(fmt_row(
        "serve/gate_perf_attribution", 0.0,
        f"ok={pf['has_required'] and pf['nonzero_samples'] and pf['utilization_ok']};"
        f"executables={pf['n_executables']};"
        f"max_disagreement={pf['max_disagreement']:.1f}",
    ))
    for name in ("single", "multi"):
        r = fabric_report[name]
        rows.append(fmt_row(
            f"serve/fabric_{name}", r["p50_ms"] * 1e3,
            f"p99_ms={r['p99_ms']:.2f};tok_per_s={r['tok_per_s']:.0f}",
        ))
    fg = fabric_report["gate"]
    rows.append(fmt_row(
        "serve/gate_fabric", 0.0,
        f"ok={fg['scaling_ok'] and fg['token_mismatches'] == 0 and fg['requeue_token_mismatches'] == 0};"
        f"scaling_x={fg['scaling_x']:.2f};target={fg['scaling_target']:.2f};"
        f"cores={fg['cores']:.0f};"
        f"requeued={fg['requeued']:.0f};"
        f"token_mismatches={fg['token_mismatches']:.0f};"
        f"requeue_token_mismatches={fg['requeue_token_mismatches']:.0f};"
        f"tp_rel_err={fg['tp_rel_err']:.2e}",
    ))
    return rows


def _run_lm_continuous():
    """Whole-request vs continuous batching on a mixed-length LM workload
    (the acceptance gate: interleaving must win throughput without changing
    a single emitted token, with the in-flight probe oracle-exact)."""
    from repro.configs import get_config
    from repro.decorr.config import DecorrConfig
    from repro.models import init_params
    from repro.serve.loadgen import LMLoadConfig, compare_lm_policies

    from repro.serve import DecorrProbe

    cfg = get_config(LM["arch"]).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    load = LMLoadConfig(n_requests=LM["n_requests"])
    report = compare_lm_policies(
        cfg,
        params,
        load,
        n_slots=LM["slots"],
        probe_fn=lambda: DecorrProbe(DecorrConfig(style="vic", reg="sum", q=2)),
        record_probe_rows=True,
    )
    keep = ("whole_request", "continuous", "gate")
    out = {k: report[k] for k in keep}
    out["service_metrics"] = {
        k: v
        for k, v in report["service_metrics"].items()
        if k.startswith(("slots_", "ttft_", "decorr_")) or k in ("tok_per_s", "tokens_total")
    }
    return out


def _run_paged():
    """Dense vs paged continuous batching at a skewed length mix (the
    acceptance gate: identical greedy tokens, strictly lower peak cache
    bytes than the dense pool's permanent reservation; a chunked-prefill
    paged run reports its tokens + TTFT alongside)."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.loadgen import LMLoadConfig, compare_paged_dense

    cfg = get_config(LM["arch"]).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    load = LMLoadConfig(
        n_requests=PAGED["n_requests"],
        prompt_lens=PAGED["prompt_lens"],
        new_tokens=PAGED["new_tokens"],
    )
    return compare_paged_dense(
        cfg,
        params,
        load,
        n_slots=PAGED["slots"],
        page_size=PAGED["page_size"],
        prefill_chunk=PAGED["prefill_chunk"],
    )


def _run_prefix():
    """Prefix sharing on vs off over the same paged chunk-all engine on a
    shared-prefix fan-out workload (the acceptance gate: bit-identical
    tokens, warm-phase TTFT and peak pool pages both strictly below the
    unshared run, with the in-flight probe still oracle-exact under page
    sharing)."""
    from repro.configs import get_config
    from repro.decorr.config import DecorrConfig
    from repro.models import init_params
    from repro.serve import DecorrProbe
    from repro.serve.loadgen import SharedPrefixLoadConfig, compare_prefix_sharing

    cfg = get_config(LM["arch"]).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    load = SharedPrefixLoadConfig(
        n_prefixes=PREFIX["n_prefixes"],
        fan_out=PREFIX["fan_out"],
        prefix_len=PREFIX["prefix_len"],
        tail_lens=PREFIX["tail_lens"],
        new_tokens=PREFIX["new_tokens"],
    )
    return compare_prefix_sharing(
        cfg,
        params,
        load,
        n_slots=PREFIX["slots"],
        page_size=PREFIX["page_size"],
        prefill_chunk=PREFIX["prefill_chunk"],
        probe_fn=lambda: DecorrProbe(DecorrConfig(style="vic", reg="sum", q=2)),
        record_probe_rows=True,
    )


def _run_spec():
    """Plain paged vs self-drafting speculative decode on a decode-heavy
    workload (the acceptance gate: bit-identical greedy tokens, tok/s at
    least the plain paged run's, and more than one token emitted per verify
    step — the speculation actually pays for its lane-batched forward)."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.loadgen import LMLoadConfig, compare_speculative

    cfg = get_config(LM["arch"]).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    load = LMLoadConfig(
        n_requests=SPECDEC["n_requests"],
        prompt_lens=SPECDEC["prompt_lens"],
        new_tokens=SPECDEC["new_tokens"],
    )
    return compare_speculative(
        cfg,
        params,
        load,
        n_slots=SPECDEC["slots"],
        page_size=SPECDEC["page_size"],
        draft_k=SPECDEC["draft_k"],
        spec_ngram_max=SPECDEC["ngram_max"],
        spec_ngram_min=SPECDEC["ngram_min"],
    )


def _run_obs_overhead():
    """Telemetry on vs off on the same continuous-batching workload (the
    acceptance gate: the always-on tracer/recorder/registry path must keep
    >= 95% of the telemetry-off tok/s — observability that taxes the decode
    loop does not ship)."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.obs import Obs
    from repro.serve import ContinuousLMEngine, LMService
    from repro.serve.loadgen import LMLoadConfig, run_continuous

    cfg = get_config(LM["arch"]).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    load = LMLoadConfig(n_requests=LM["n_requests"])

    def measure(obs):
        engine = ContinuousLMEngine(
            cfg, params, n_slots=LM["slots"],
            max_len=max(load.max_request_len + 8, 32),
            max_prompt_len=max(load.prompt_lens),
        )
        svc = LMService(engine, obs=obs)
        best = None
        for _ in range(2):  # first pass pays compile; keep the best of two
            summary, _ = run_continuous(svc, load)
            if best is None or summary["tok_per_s"] > best["tok_per_s"]:
                best = summary
        return best

    off = measure(Obs.disabled())
    on = measure(Obs())
    ratio = on["tok_per_s"] / max(off["tok_per_s"], 1e-9)
    return {
        "on": on,
        "off": off,
        "gate": {"tok_per_s_ratio": ratio, "overhead_ok": ratio >= 0.95},
    }


def _run_perf():
    """Per-executable attribution over both serving paths (the acceptance
    gate: every compiled executable the workload exercises shows nonzero
    wall-time samples AND, on a device in the peak table, a roofline-
    utilization value in (0, 1] from the measured-time x analytic-HLO-cost
    join; on any other device no utilization at all).  One shared ``Obs`` so the
    embedding buckets, the LM prefill/decode/chunk executables and the
    probe land in one attribution table — what the ``/perf`` endpoint and
    the analytic-vs-measured disagreement metric read."""
    from repro.configs import get_config
    from repro.decorr.config import DecorrConfig
    from repro.models import init_params
    from repro.obs import Obs
    from repro.serve import (
        BucketPolicy,
        ContinuousLMEngine,
        DecorrProbe,
        LMService,
        ServeEngine,
    )
    from repro.serve.service import EmbeddingService
    from repro.serve.loadgen import LMLoadConfig, run_continuous
    from repro.train.ssl import SSLModelConfig, init_ssl_params

    obs = Obs()
    probe_cfg = DecorrConfig(style="vic", reg="sum", q=2)

    # embedding leg: warm the bucket ladder, then serve a closed-loop burst
    model = SSLModelConfig(
        input_dim=REDUCED["input_dim"],
        backbone_widths=(REDUCED["backbone"],),
        projector_widths=(REDUCED["d"], REDUCED["d"]),
    )
    ssl_params = init_ssl_params(jax.random.PRNGKey(0), model)
    policy = BucketPolicy(**POLICY)
    svc = EmbeddingService(
        ServeEngine(model, ssl_params, policy=policy),
        probe=DecorrProbe(probe_cfg),
        obs=obs,
    ).warmup()
    rng = np.random.default_rng(2)
    futs = [
        svc.submit(rng.standard_normal(REDUCED["input_dim"]).astype(np.float32))
        for _ in range(32)
    ]
    while svc.run_pending():
        pass
    for f in futs:
        f.result(timeout=30)

    # LM leg: paged + chunked prefill so the skewed mix exercises the
    # per-bucket prefills, the chunk step AND the batched decode tick
    cfg = get_config(LM["arch"]).reduced()
    lm_params = init_params(jax.random.PRNGKey(0), cfg)
    load = LMLoadConfig(
        n_requests=PAGED["n_requests"],
        prompt_lens=PAGED["prompt_lens"],
        new_tokens=PAGED["new_tokens"],
    )
    engine = ContinuousLMEngine(
        cfg, lm_params, n_slots=PAGED["slots"],
        max_len=max(load.max_request_len + 8, 32),
        max_prompt_len=max(load.prompt_lens),
        paged=True, page_size=PAGED["page_size"],
        prefill_chunk=PAGED["prefill_chunk"],
    )
    lm_svc = LMService(engine, probe=DecorrProbe(probe_cfg), obs=obs)
    summary, _ = run_continuous(lm_svc, load)

    from repro.launch.hlo_cost import PEAKS

    has_peaks = jax.devices()[0].device_kind in PEAKS
    rows = obs.perf.snapshot()
    names = {r["executable"] for r in rows}
    utils = {r["executable"]: r.get("roofline_utilization") for r in rows}
    disagreements = [r["disagreement"] for r in rows if r.get("disagreement")]
    gate = {
        "n_executables": len(rows),
        "has_required": (
            {"decode_step", "chunk_prefill", "probe_update"} <= names
            and any(n.startswith("prefill_b") for n in names)
            and any(n.startswith("embed_b") for n in names)
        ),
        "nonzero_samples": bool(rows) and all(
            r["calls"] > 0 and r["total_s"] > 0 for r in rows
        ),
        "utilization_ok": bool(rows) and all(
            (u is not None and 0.0 < u <= 1.0) if has_peaks else u is None
            for u in utils.values()
        ),
        "max_disagreement": max(disagreements, default=0.0),
    }
    return {
        "executables": {r["executable"]: r for r in rows},
        "lm_tok_per_s": summary["tok_per_s"],
        "gate": gate,
    }


def _tp_oracle(tp: int) -> float:
    """Max relative error of the tp-forward oracle over ``tp`` devices.

    Where this process sees ``tp`` devices it runs here, over them (a child
    could not reach chips this process holds).  Otherwise a child forces
    ``tp`` virtual CPU devices (``JAX_PLATFORMS=cpu``, so it never competes
    for a chip) and reports the error back (the test-suite pattern, see
    test_serve_fabric).  It never runs at fewer than ``tp`` devices, where the
    sharded forward would equal the oracle by construction."""
    if len(jax.devices()) >= tp:
        from repro.serve.loadgen import tp_oracle_err
        from repro.train.ssl import SSLModelConfig, init_ssl_params

        model = SSLModelConfig(input_dim=24, backbone_widths=(32,), projector_widths=(48, 48))
        params = init_ssl_params(jax.random.PRNGKey(0), model)
        return tp_oracle_err(model, params, tp=tp)
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={tp}"
        import jax
        from repro.serve.loadgen import tp_oracle_err
        from repro.train.ssl import SSLModelConfig, init_ssl_params

        model = SSLModelConfig(input_dim=24, backbone_widths=(32,),
                               projector_widths=(48, 48))
        params = init_ssl_params(jax.random.PRNGKey(0), model)
        print(tp_oracle_err(model, params, tp={tp}))
        """
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=420,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"tp oracle subprocess failed:\n{proc.stderr[-3000:]}")
    return float(proc.stdout.strip().splitlines()[-1])


def _run_fabric():
    """N threaded replicas behind the router vs one, the kill-one-replica
    failover leg, and the tp-forward oracle (the acceptance gates:
    route-independent AND requeue-surviving token identity — both must be
    bit-exact — plus aggregate tok/s scaling against a hardware-aware
    target: replica threads only overlap on real cores, so a 1-core runner
    gates at ~parity while multi-core runners must show the scaling win)."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.loadgen import FabricLoadConfig, LMLoadConfig, compare_fabric
    from repro.train.ssl import SSLModelConfig, init_ssl_params

    cfg = get_config(LM["arch"]).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    embed_model = SSLModelConfig(
        input_dim=24, backbone_widths=(32,), projector_widths=(48, 48)
    )
    embed_params = init_ssl_params(jax.random.PRNGKey(1), embed_model)
    load = FabricLoadConfig(
        lm=LMLoadConfig(
            n_requests=FABRIC["n_requests"],
            prompt_lens=FABRIC["prompt_lens"],
            new_tokens=FABRIC["new_tokens"],
        ),
        n_embed=FABRIC["n_embed"],
        input_dim=24,
    )
    report = compare_fabric(
        cfg, params, load,
        replicas=FABRIC["replicas"],
        n_slots=FABRIC["slots"],
        page_size=FABRIC["page_size"],
        embed_cfg=embed_model,
        embed_params=embed_params,
    )
    cores = float(os.cpu_count() or 1)
    scaling_target = 1.6 if cores >= 2 else 1.05
    tp_err = _tp_oracle(FABRIC["tp"])
    report["gate"].update(
        cores=cores,
        scaling_target=scaling_target,
        scaling_ok=report["gate"]["scaling_x"] >= scaling_target,
        tp_rel_err=tp_err,
        tp=float(FABRIC["tp"]),
    )
    # the labelled per-replica gauges don't serialize as flat floats; keep
    # the flat subset in the JSON report
    report["fabric_metrics"] = {
        k: v for k, v in report["fabric_metrics"].items()
        if isinstance(v, (int, float))
    }
    return report


if __name__ == "__main__":
    print("\n".join(run()))
