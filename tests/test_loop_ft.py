"""Train-loop fault tolerance: retry, preemption, deterministic resume."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import LMDataConfig, lm_batch
from repro.ft import PreemptionSignal, StragglerWatchdog, with_retries
from repro.models import init_params
from repro.optim import adamw, warmup_cosine
from repro.train import LoopConfig, create_train_state, make_train_step, run_training


def _setup():
    cfg = get_config("rwkv6-3b").reduced(n_layers=2)
    opt = adamw()
    step_fn = jax.jit(make_train_step(cfg, opt, warmup_cosine(1e-3, 2, 50)))
    dcfg = LMDataConfig(vocab_size=cfg.vocab_size, batch=2, seq_len=8)

    def batch_fn(step):
        return {k: jnp.asarray(v) for k, v in lm_batch(dcfg, step).items()}

    def fresh_state():
        return create_train_state(init_params(jax.random.PRNGKey(0), cfg), opt)

    return step_fn, batch_fn, fresh_state


def test_transient_fault_retried(tmp_path):
    step_fn, batch_fn, fresh = _setup()
    calls = {"faults": 0}

    def fault_hook(step):
        if step == 3 and calls["faults"] < 2:
            calls["faults"] += 1
            raise RuntimeError("flaky device")

    cfg = LoopConfig(total_steps=5, ckpt_dir=str(tmp_path), ckpt_interval=100, max_step_retries=3)
    state = run_training(fresh(), step_fn, batch_fn, cfg, fault_hook=fault_hook)
    assert int(state.step) == 5
    assert calls["faults"] == 2


def test_unrecoverable_fault_raises(tmp_path):
    step_fn, batch_fn, fresh = _setup()

    def fault_hook(step):
        if step == 2:
            raise RuntimeError("dead host")

    cfg = LoopConfig(total_steps=5, ckpt_dir=str(tmp_path), max_step_retries=1)
    try:
        run_training(fresh(), step_fn, batch_fn, cfg, fault_hook=fault_hook)
        assert False, "should raise"
    except RuntimeError:
        pass


def test_resume_trajectory_identical(tmp_path):
    """Crash-restart must produce the same final params as an uninterrupted
    run (deterministic data keyed by step + checkpointed RNG)."""
    step_fn, batch_fn, fresh = _setup()

    # uninterrupted 8 steps
    ref = run_training(
        fresh(), step_fn, batch_fn, LoopConfig(total_steps=8, ckpt_dir=None)
    )

    # run to 4 with checkpointing, then "crash" and resume to 8
    d1 = str(tmp_path / "ckpt")
    run_training(fresh(), step_fn, batch_fn, LoopConfig(total_steps=4, ckpt_dir=d1, ckpt_interval=2))
    resumed = run_training(fresh(), step_fn, batch_fn, LoopConfig(total_steps=8, ckpt_dir=d1, ckpt_interval=2))

    for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(resumed.params)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-6
        )


def test_preemption_checkpoints_and_exits(tmp_path):
    step_fn, batch_fn, fresh = _setup()
    flag = str(tmp_path / "PREEMPT")
    PreemptionSignal(flag).set()
    cfg = LoopConfig(total_steps=100, ckpt_dir=str(tmp_path), ckpt_interval=1000, preempt_flag=flag)
    state = run_training(fresh(), step_fn, batch_fn, cfg)
    assert int(state.step) == 1  # exited after first step
    from repro.checkpoint import latest_step

    assert latest_step(str(tmp_path)) == 1


def test_straggler_watchdog_flags_outliers():
    """Rolling-median outlier detection with an injected clock (no sleeping)."""
    t = {"now": 0.0}
    wd = StragglerWatchdog(window=16, factor=3.0, min_samples=4, clock=lambda: t["now"])

    def step(seconds):
        wd.step_start()
        t["now"] += seconds
        return wd.step_end()

    assert not any(step(0.002) for _ in range(6))
    assert step(0.005) is False  # 2.5x the median: not an outlier
    assert step(0.05) is True
    assert wd.straggler_events == 1
    assert wd.median == pytest.approx(0.002)


def test_with_retries_backoff():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("boom")
        return 42

    assert with_retries(flaky, max_retries=5, backoff_s=0.001)() == 42
    assert calls["n"] == 3


def test_heartbeat_missed_detection():
    """Missed-heartbeat detection with an injected clock (no sleeping)."""
    from repro.ft import HeartbeatMonitor

    t = {"now": 100.0}
    hb = HeartbeatMonitor(default_timeout_s=2.0, clock=lambda: t["now"])
    hb.register("serve.dispatch")
    hb.register("ckpt.writer", timeout_s=10.0)

    assert hb.stale() == {}
    t["now"] = 103.0  # dispatch overdue (3s > 2s), writer fine (3s < 10s)
    overdue = hb.stale()
    assert list(overdue) == ["serve.dispatch"]
    assert overdue["serve.dispatch"] == 3.0
    assert hb.missed_events == 1
    # still stale on re-check: edge-triggered counter does not double-count
    hb.stale()
    assert hb.missed_events == 1

    hb.beat("serve.dispatch")
    assert hb.stale() == {}
    t["now"] = 106.5  # second miss -> second event
    assert "serve.dispatch" in hb.stale()
    assert hb.missed_events == 2

    m = hb.metrics()
    assert m["heartbeat_components"] == 2.0
    assert m["heartbeat_stale"] == 1.0
    assert m["heartbeat_missed_events"] == 2.0
    # per-name ages are exposition-safe (dots sanitized) so alert rules can
    # target them directly
    assert m["heartbeat_age_s_serve_dispatch"] == 3.5


def test_heartbeat_auto_registers_on_beat():
    from repro.ft import HeartbeatMonitor

    t = {"now": 0.0}
    hb = HeartbeatMonitor(default_timeout_s=1.0, clock=lambda: t["now"])
    hb.beat("adhoc")
    t["now"] = 0.5
    assert hb.stale() == {}
    t["now"] = 2.0
    assert "adhoc" in hb.stale()
