"""Production train loop: checkpoint/restart, preemption, stragglers, retry.

The loop is deliberately host-side-thin: all math lives in the jitted step.
What it adds is the operational envelope a 1000-node run needs:
  * auto-resume from the newest committed checkpoint,
  * interval + final + preemption-triggered checkpoints (async, atomic),
  * straggler watchdog (rolling-median outlier detection),
  * bounded retry of transient step failures (fault injection in tests),
  * deterministic data (batches keyed by step — a restart replays nothing
    and skips nothing).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.ft.watchdog import PreemptionSignal, StragglerWatchdog, with_retries
from repro.obs import profiling
from repro.train.train_state import TrainState


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_interval: int = 50
    ckpt_keep: int = 3
    log_interval: int = 10
    preempt_flag: Optional[str] = None
    max_step_retries: int = 2


def run_training(
    state: TrainState,
    train_step: Callable,
    batch_fn: Callable[[int], Any],
    cfg: LoopConfig,
    log_fn: Callable[[int, Dict], None] = None,
    fault_hook: Optional[Callable[[int], None]] = None,
    registry=None,
    monitor=None,
) -> TrainState:
    """batch_fn(step) -> device-ready batch (deterministic per step).
    fault_hook(step) may raise RuntimeError to simulate transient faults.
    ``registry`` (an ``repro.obs.MetricsRegistry``) gets per-phase host-time
    histograms (loop iteration / batch fetch / log-interval publish) + a step
    counter every step, and ``train_``-prefixed gauges of the training
    metrics plus a global param-norm gauge at each log interval (where they
    are already host-synced — never on the hot path).
    ``monitor`` (an ``repro.obs.DecorrHealthMonitor``) probes the current
    params against the step's batch at each log interval, publishing the
    ``train_decorr_*`` health gauges its alert rules read.

    Each iteration is a ``StepTraceAnnotation`` and each phase a profiler
    span (``repro.obs.profiling``: batch, dispatch, sync, publish, ckpt);
    the step's device time per part comes from a capture through the
    program's named scopes.  The step is asynchronous: only the log
    interval's sync waits for the device."""
    mgr = (
        CheckpointManager(cfg.ckpt_dir, interval=cfg.ckpt_interval, keep=cfg.ckpt_keep)
        if cfg.ckpt_dir
        else None
    )
    preempt = PreemptionSignal(cfg.preempt_flag) if cfg.preempt_flag else None
    watchdog = StragglerWatchdog()
    span = jax.profiler.TraceAnnotation
    h_step = c_steps = h_batch = h_publish = None
    if registry is not None:
        h_step = registry.histogram(
            "train_step_seconds", "host time per loop iteration: batch fetch and enqueue"
        )
        c_steps = registry.counter("train_steps_total", "train steps run")
        h_batch = registry.histogram("train_batch_seconds", "batch fetch wall time")
        h_publish = registry.histogram(
            "train_publish_seconds", "log-interval publish + health-probe wall time"
        )

    # auto-resume
    start_step = int(state.step)
    if mgr is not None:
        restored, step = mgr.restore_latest(state)
        if restored is not None:
            state = restored
            start_step = step

    # the batch fetch's time lands in a cell so one_step keeps the
    # (state, metrics) return contract with_retries wraps
    phase = {"batch_s": 0.0}

    def one_step(step: int, state: TrainState):
        if fault_hook is not None:
            fault_hook(step)
        t0 = time.perf_counter()
        with span(profiling.SPAN_BATCH):
            batch = batch_fn(step)
        phase["batch_s"] = time.perf_counter() - t0
        with span(profiling.SPAN_DISPATCH):
            return train_step(state, batch)

    step_with_retry = with_retries(one_step, max_retries=cfg.max_step_retries)

    def save(state: TrainState, force: bool = False):
        with span(profiling.SPAN_CKPT):
            mgr.save(int(state.step), state, force=force)

    metrics: Dict = {}
    for step in range(start_step, cfg.total_steps):
        with jax.profiler.StepTraceAnnotation(profiling.STEP_NAME, step_num=step):
            watchdog.step_start()
            state, metrics = step_with_retry(step, state)
            watchdog.step_end()
            if registry is not None:
                h_step.observe(watchdog.durations[-1])
                h_batch.observe(phase["batch_s"])
                c_steps.inc()

            at_log = (step + 1) % cfg.log_interval == 0
            if at_log and (log_fn is not None or registry is not None or monitor is not None):
                t_pub = time.perf_counter()
                with span(profiling.SPAN_SYNC):
                    host_metrics = {k: float(v) for k, v in metrics.items()}
                with span(profiling.SPAN_PUBLISH):
                    host_metrics["stragglers"] = watchdog.straggler_events
                    if registry is not None:
                        registry.publish(
                            {f"train_{k}": v for k, v in host_metrics.items()}
                        )
                        registry.gauge("train_step_seconds_median").set(watchdog.median)
                        _publish_param_norm(registry, state)
                    if monitor is not None:
                        monitor.update(state, batch_fn(step), step=step + 1, registry=registry)
                    if log_fn is not None:
                        log_fn(step + 1, host_metrics)
                    if h_publish is not None:
                        h_publish.observe(time.perf_counter() - t_pub)

            if mgr is not None:
                save(state)

        if preempt is not None and preempt.raised():
            if mgr is not None:
                save(state, force=True)
                mgr.wait()
            break

    if mgr is not None:
        save(state, force=True)
        mgr.wait()
    return state


def _publish_param_norm(registry, state):
    """Global L2 norm of the params as a gauge.  Tolerant of duck-typed
    states (tests pass step-only stand-ins) — publishes nothing then."""
    params = getattr(state, "params", None)
    if params is None:
        return
    try:
        import jax
        import jax.numpy as jnp

        leaves = jax.tree_util.tree_leaves(params)
        if not leaves:
            return
        sq = sum(float(jnp.vdot(x, x).real) for x in leaves)
        registry.gauge("train_param_norm", "global L2 norm of the params").set(sq ** 0.5)
    except Exception:
        return
