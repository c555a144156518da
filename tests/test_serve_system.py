"""repro.serve subsystem: buckets, micro-batcher, engine (checkpoint
round-trip, compile cache, sharded execution), online decorrelation probes
(training-oracle agreement, local AND sharded), and the end-to-end service.

Multi-device cases run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (same pattern as
test_decorr_engine) so the main pytest process keeps one CPU device."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import save_checkpoint
from repro.decorr import probe_metrics
from repro.decorr.config import DecorrConfig
from repro.serve import (
    Backpressure,
    BucketPolicy,
    DecorrProbe,
    EmbeddingService,
    LMServeEngine,
    MicroBatcher,
    ServeEngine,
    bucket_for,
    bucket_sizes,
)
from repro.train.ssl import SSLModelConfig, embed, init_ssl_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODEL = SSLModelConfig(input_dim=24, backbone_widths=(32,), projector_widths=(48, 48))


def _params(seed=0):
    return init_ssl_params(jax.random.PRNGKey(seed), MODEL)


# ---------------------------------------------------------------------------
# Buckets
# ---------------------------------------------------------------------------


class TestBuckets:
    def test_ladder_is_geometric_and_aligned(self):
        p = BucketPolicy(max_batch=64, align=8)
        assert bucket_sizes(p) == (8, 16, 32, 64)
        for b in bucket_sizes(p):
            assert b % p.align == 0

    def test_non_power_of_two_max_batch_rounds_up(self):
        p = BucketPolicy(max_batch=50, align=8)
        assert bucket_sizes(p)[-1] == 56
        assert bucket_for(50, p) == 56

    def test_bucket_for_is_smallest_cover(self):
        p = BucketPolicy(max_batch=64, align=8)
        assert bucket_for(1, p) == 8
        assert bucket_for(8, p) == 8
        assert bucket_for(9, p) == 16
        assert bucket_for(64, p) == 64
        assert bucket_for(1000, p) == 64  # clamped to the top bucket


# ---------------------------------------------------------------------------
# Micro-batcher
# ---------------------------------------------------------------------------


class TestMicroBatcher:
    def test_coalesces_fifo_up_to_max_batch(self):
        mb = MicroBatcher(BucketPolicy(max_batch=4, max_wait_ms=0.0))
        futs = [mb.submit(np.full((3,), i, np.float32)) for i in range(6)]
        first = mb.next_batch(timeout=0.0)
        assert [int(r.x[0]) for r in first] == [0, 1, 2, 3]
        second = mb.next_batch(timeout=0.0)
        assert [int(r.x[0]) for r in second] == [4, 5]
        assert mb.next_batch(timeout=0.0) == []
        assert all(not f.done() for f in futs)

    def test_backpressure_raises_when_full(self):
        mb = MicroBatcher(BucketPolicy(max_queue=2, max_wait_ms=0.0))
        mb.submit(np.zeros(3))
        mb.submit(np.zeros(3))
        with pytest.raises(Backpressure):
            mb.submit(np.zeros(3))

    def test_shutdown_flushes_then_signals(self):
        mb = MicroBatcher(BucketPolicy(max_batch=8, max_wait_ms=0.0))
        mb.submit(np.zeros(3))
        mb.shutdown()
        batch = mb.next_batch(timeout=0.0)
        assert len(batch) == 1
        assert mb.next_batch(timeout=0.0) is None

    def test_shutdown_with_full_queue_never_blocks_and_drains(self):
        """Regression: shutdown used to enqueue a sentinel with a blocking
        put — on a full queue that deadlocked the dispatch loop."""
        mb = MicroBatcher(BucketPolicy(max_batch=2, max_queue=2, max_wait_ms=0.0))
        mb.submit(np.zeros(3))
        mb.submit(np.zeros(3))
        mb.shutdown()  # queue full: must return immediately, not block
        with pytest.raises(Backpressure):
            mb.submit(np.zeros(3))  # no admissions after shutdown
        assert len(mb.next_batch(timeout=0.0)) == 2  # queued work still flushes
        assert mb.next_batch(timeout=0.0) is None

    def test_multi_row_requests_counted_by_rows(self):
        mb = MicroBatcher(BucketPolicy(max_batch=4, max_wait_ms=0.0))
        mb.submit(np.zeros((3, 2), np.float32))
        mb.submit(np.zeros((3, 2), np.float32))
        mb.submit(np.zeros((3, 2), np.float32))
        batch = mb.next_batch(timeout=0.0)
        # 3 + 3 >= max_batch: the second request is admitted, the third waits
        assert len(batch) == 2
        assert len(mb.next_batch(timeout=0.0)) == 1


# ---------------------------------------------------------------------------
# Engine: compile cache, padding correctness, checkpoint round-trip
# ---------------------------------------------------------------------------


class TestServeEngine:
    def test_padded_encode_matches_direct_forward(self):
        params = _params()
        eng = ServeEngine(MODEL, params, policy=BucketPolicy(max_batch=16, align=8))
        x = jax.random.normal(jax.random.PRNGKey(1), (5, MODEL.input_dim))
        np.testing.assert_allclose(
            np.asarray(eng.encode(x)), np.asarray(embed(params, x)), rtol=2e-5, atol=2e-6
        )

    def test_compile_cache_bounded_by_ladder(self):
        params = _params()
        eng = ServeEngine(MODEL, params, policy=BucketPolicy(max_batch=16, align=8))
        for n in (1, 3, 8, 9, 11, 16):
            eng.encode(jnp.zeros((n, MODEL.input_dim)))
        assert set(eng.compiled_buckets()) <= set(bucket_sizes(eng.policy))

    def test_oversize_batch_chunks_through_top_bucket(self):
        params = _params()
        eng = ServeEngine(MODEL, params, policy=BucketPolicy(max_batch=8, align=8))
        x = jax.random.normal(jax.random.PRNGKey(2), (19, MODEL.input_dim))
        np.testing.assert_allclose(
            np.asarray(eng.encode(x)), np.asarray(embed(params, x)), rtol=2e-5, atol=2e-6
        )

    def test_warmup_precompiles_every_bucket(self):
        eng = ServeEngine(MODEL, _params(), policy=BucketPolicy(max_batch=16, align=8))
        assert eng.compiled_buckets() == ()
        eng.warmup()
        assert eng.compiled_buckets() == bucket_sizes(eng.policy)

    def test_checkpoint_roundtrip_params_tree(self, tmp_path):
        params = _params(3)
        save_checkpoint(str(tmp_path), 7, params)
        eng = ServeEngine.from_checkpoint(str(tmp_path), MODEL)
        x = jax.random.normal(jax.random.PRNGKey(4), (6, MODEL.input_dim))
        np.testing.assert_allclose(
            np.asarray(eng.encode(x)), np.asarray(embed(params, x)), rtol=2e-5, atol=2e-6
        )

    def test_checkpoint_roundtrip_train_state(self, tmp_path):
        """The train loop's own checkpoint layout serves directly."""
        from repro.optim import adamw
        from repro.train import create_train_state

        params = _params(5)
        state = create_train_state(params, adamw())
        save_checkpoint(str(tmp_path), 42, state)
        eng = ServeEngine.from_checkpoint(str(tmp_path), MODEL)
        x = jax.random.normal(jax.random.PRNGKey(6), (4, MODEL.input_dim))
        np.testing.assert_allclose(
            np.asarray(eng.encode(x)), np.asarray(embed(params, x)), rtol=2e-5, atol=2e-6
        )

    def test_missing_checkpoint_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            ServeEngine.from_checkpoint(str(tmp_path), MODEL)


# ---------------------------------------------------------------------------
# Probes: training-oracle agreement + streaming bookkeeping
# ---------------------------------------------------------------------------


class TestProbes:
    @pytest.mark.parametrize("style,q,block", [("bt", 2, None), ("vic", 1, 16), ("vic", 2, None)])
    def test_probe_matches_training_path_oracle(self, style, q, block):
        """probe r_off/r_sum == the repro.decorr engine computation with the
        training normalization + permutation semantics."""
        from repro.core import permutation as perm_lib
        from repro.core import regularizers as regs
        from repro.decorr import engine as dengine

        cfg = DecorrConfig(style=style, reg="sum", q=q, block_size=block)
        key = jax.random.PRNGKey(9)
        z1 = jax.random.normal(jax.random.PRNGKey(10), (32, 48))
        z2 = jax.random.normal(jax.random.PRNGKey(11), (32, 48))
        same = style == "vic"
        m = probe_metrics(z1, None if same else z2, cfg, key)

        n = z1.shape[0]
        if style == "bt":
            a, b = dengine.standardize(z1, cfg), dengine.standardize(z2, cfg)
            scale = float(n)
        else:
            a = dengine.center(z1, cfg)
            b = a
            scale = float(n - 1)
        ap, bp = perm_lib.permute_views(key, a, b)
        want_sum = regs.r_sum_auto(ap, bp, q=q, block_size=block, scale=scale)
        want_off = regs.r_off(regs.cross_correlation_matrix(a, b, scale=scale))
        np.testing.assert_allclose(float(m["r_sum"]), float(want_sum), rtol=1e-5)
        np.testing.assert_allclose(float(m["r_off"]), float(want_off), rtol=1e-5)

    def test_probe_streaming_window_and_ema(self):
        probe = DecorrProbe(DecorrConfig(style="vic", reg="sum", q=2), sample_rows=16, ema=0.5)
        rng = np.random.default_rng(0)
        # 3 batches of 8 rows -> one 16-row probe fires, 8 rows remain buffered
        fired = [probe.observe(rng.standard_normal((8, 48)).astype(np.float32)) for _ in range(3)]
        assert sum(fired) == 1 and probe.steps == 1
        m = probe.metrics()
        assert m["decorr_probe_steps"] == 1.0
        assert "decorr_r_sum" in m and "decorr_r_sum_ema" in m
        mean, var = probe.feature_moments()
        assert mean.shape == (48,) and var.shape == (48,)

    def test_probe_permutation_reproducible(self):
        """Step t of the stream equals an offline probe with the same folded key."""
        cfg = DecorrConfig(style="vic", reg="sum", q=2)
        z = np.asarray(jax.random.normal(jax.random.PRNGKey(12), (16, 48)), np.float32)
        probe = DecorrProbe(cfg, sample_rows=16, perm_seed=3)
        batch = probe.update(z)
        key = jax.random.fold_in(jax.random.PRNGKey(3), jnp.uint32(0))
        want = probe_metrics(jnp.asarray(z), cfg=cfg, perm_key=key)
        np.testing.assert_allclose(batch["r_sum"], float(want["r_sum"]), rtol=1e-5)


# ---------------------------------------------------------------------------
# Service end to end
# ---------------------------------------------------------------------------


class TestEmbeddingService:
    def _service(self, **kw):
        eng = ServeEngine(MODEL, _params(), policy=BucketPolicy(max_batch=8, align=8, max_wait_ms=0.0))
        return EmbeddingService(eng, probe=DecorrProbe(DecorrConfig(style="vic")), **kw)

    def test_synchronous_roundtrip_and_metrics(self):
        svc = self._service()
        svc.warmup()
        xs = np.asarray(jax.random.normal(jax.random.PRNGKey(13), (11, MODEL.input_dim)))
        futs = [svc.submit(x) for x in xs]
        while any(not f.done() for f in futs):
            assert svc.run_pending(timeout=0.0) > 0
        got = np.stack([f.result() for f in futs])
        np.testing.assert_allclose(got, np.asarray(embed(svc.engine.params, xs)), rtol=2e-5, atol=2e-6)
        m = svc.metrics()
        assert m["served_total"] == 11.0
        assert m["batches_total"] == 2.0  # 8 + 3
        assert m["queue_depth"] == 0.0
        assert m["heartbeat_stale"] == 0.0
        assert m["latency_p99_ms"] >= m["latency_p50_ms"] >= 0.0

    def test_threaded_service(self):
        svc = self._service().start()
        try:
            xs = np.asarray(jax.random.normal(jax.random.PRNGKey(14), (20, MODEL.input_dim)))
            futs = [svc.submit(x) for x in xs]
            got = np.stack([f.result(timeout=30.0) for f in futs])
        finally:
            svc.stop()
        np.testing.assert_allclose(got, np.asarray(embed(svc.engine.params, xs)), rtol=2e-5, atol=2e-6)
        # probe saw full sample windows of served embeddings
        assert svc.probe.steps >= 1

    def test_service_feeds_heartbeat(self):
        t = {"now": 0.0}
        from repro.ft.watchdog import HeartbeatMonitor

        hb = HeartbeatMonitor(clock=lambda: t["now"])
        svc = self._service(heartbeat=hb, heartbeat_timeout_s=5.0)
        svc.submit(np.zeros(MODEL.input_dim, np.float32))
        t["now"] = 10.0
        assert "serve.dispatch" in hb.stale()
        svc.run_pending(timeout=0.0)  # dispatch beats
        assert hb.stale() == {}


# ---------------------------------------------------------------------------
# Sharded execution: encode + global-mode probe vs single-device oracle
# ---------------------------------------------------------------------------


def test_sharded_serve_matches_local_oracle():
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, json
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.decorr import probe_metrics
        from repro.decorr.config import DecorrConfig
        from repro.serve import ServeEngine, BucketPolicy
        from repro.train.ssl import SSLModelConfig, init_ssl_params

        mesh = jax.make_mesh((8,), ("data",))
        model = SSLModelConfig(input_dim=24, backbone_widths=(32,), projector_widths=(48, 48))
        params = init_ssl_params(jax.random.PRNGKey(0), model)
        pol = BucketPolicy(max_batch=32, align=8)
        local = ServeEngine(model, params, policy=pol)
        sharded = ServeEngine(model, params, policy=pol, mesh=mesh)
        x = np.random.default_rng(0).standard_normal((20, 24)).astype(np.float32)
        enc_err = float(jnp.max(jnp.abs(local.encode(x) - sharded.encode(x))))

        out = {"enc_err": enc_err}
        key = jax.random.PRNGKey(5)
        z = jax.random.normal(jax.random.PRNGKey(7), (64, 48))
        for style, q, block in (("bt", 2, 16), ("vic", 2, None)):
            cfg_l = DecorrConfig(style=style, reg="sum", q=q, block_size=block)
            cfg_g = dataclasses.replace(cfg_l, distributed="global", axis_name="data")
            oracle = probe_metrics(z, cfg=cfg_l, perm_key=key)
            f = shard_map(lambda zz, k: probe_metrics(zz, cfg=cfg_g, perm_key=k),
                          mesh=mesh, in_specs=(P("data"), P()), out_specs=P())
            got = jax.jit(f)(z, key)
            out[style] = max(
                abs(float(oracle[k]) - float(got[k])) / max(abs(float(oracle[k])), 1e-6)
                for k in oracle
            )
        print(json.dumps(out))
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=420
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["enc_err"] < 1e-5, res
    assert res["bt"] < 1e-4, res
    assert res["vic"] < 1e-4, res


# ---------------------------------------------------------------------------
# LM serving engine (prefill/decode factories shared with train.serve)
# ---------------------------------------------------------------------------


def test_lm_serve_engine_matches_greedy_generate():
    from repro.configs import get_config
    from repro.models import init_params
    from repro.train.serve import greedy_generate

    cfg = get_config("rwkv6-3b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    eng = LMServeEngine(cfg)
    a = eng.generate(params, prompt, 5)
    b = greedy_generate(params, cfg, prompt, 5)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # second call reuses the cached jitted steps
    np.testing.assert_array_equal(np.asarray(eng.generate(params, prompt, 5)), np.asarray(a))
