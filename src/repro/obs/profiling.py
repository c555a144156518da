"""Opt-in profiling hooks: ``jax.profiler`` trace capture behind a tiny
start/stop API.

Profiling is the one telemetry layer that is NOT always-on — a profiler
trace costs real overhead and disk, so capture is explicit: the service API
(``LMService.start_profiling``), the CLI (``--profile-dir``), or a direct
``Profiler`` call.  Everything degrades to a no-op when ``jax.profiler`` is
unavailable or the capture fails (CI containers without libtpu, double
starts) — profiling must never take the serving path down.

The cheap always-on counterpart — per-executable step-time histograms for
prefill / chunked-prefill / decode — lives in the metrics registry
(``serve_*_seconds``), fed by the service tick; this module only owns the
heavyweight trace capture.

Names a capture can attribute time by.  They cost nothing when no trace is
running: device scopes only change HLO metadata, and an inactive host span is
a no-op of about a microsecond.

* Device scopes (``jax.named_scope``): each compiled instruction's
  ``op_name`` metadata, and the ``tf_op`` of its event in a TPU trace, carries
  the path of scopes it was traced under; backward ops carry them inside
  ``transpose(jvp(...))``.

  ======================  ==================================================
  ``ENCODER``             ``train/ssl.embed``: backbone and projector
  ``LOSS``                ``decorr/engine.apply``: normalization,
                          permutation, invariance and the regularizer
  ``REGULARIZER``         every route of ``decorr/engine``'s R(C): jnp and
                          Pallas R_off, FFT and grouped R_sum, the
                          ``local``/``global``/``tp`` modes
  ``OPTIMIZER``           ``Optimizer.update``, in every train step
  ======================  ==================================================

* Host spans (``jax.profiler.TraceAnnotation``) of ``train/loop.run_training``,
  inside one ``jax.profiler.StepTraceAnnotation("train", step_num=step)`` per
  iteration:

  ======================  ==================================================
  ``SPAN_BATCH``          ``batch_fn(step)``
  ``SPAN_DISPATCH``       the ``train_step`` call (enqueue; blocks only when
                          the device is behind)
  ``SPAN_SYNC``           the log interval's device-to-host read of the
                          step's metrics: waits until the queued steps drain
  ``SPAN_PUBLISH``        ``log_fn``, registry and health-monitor work at the
                          log interval
  ``SPAN_CKPT``           ``CheckpointManager.save``
  ======================  ==================================================
"""

from __future__ import annotations

import logging
from typing import Dict, Optional

log = logging.getLogger("repro.obs.profiling")

ENCODER = "encoder"
LOSS = "loss"
REGULARIZER = "regularizer"
OPTIMIZER = "optimizer"

STEP_NAME = "train"
SPAN_BATCH = "train.batch"
SPAN_DISPATCH = "train.dispatch"
SPAN_SYNC = "train.sync"
SPAN_PUBLISH = "train.publish"
SPAN_CKPT = "train.ckpt"


class Profiler:
    """Start/stop ``jax.profiler`` traces into a directory."""

    def __init__(self, trace_dir: Optional[str] = None):
        self.trace_dir = trace_dir
        self.active = False
        self.sessions = 0
        self.errors = 0

    def start(self, trace_dir: Optional[str] = None) -> bool:
        """Begin a capture; returns False (and stays inert) when profiling
        cannot start — no directory configured, already active, or the
        backend refuses."""
        trace_dir = trace_dir or self.trace_dir
        if trace_dir is None or self.active:
            return False
        try:
            import jax.profiler

            jax.profiler.start_trace(trace_dir)
        except Exception as e:  # pragma: no cover - backend-dependent
            self.errors += 1
            log.warning("jax.profiler trace did not start: %s", e)
            return False
        self.trace_dir = trace_dir
        self.active = True
        return True

    def stop(self) -> Optional[str]:
        """End the capture; returns the trace directory, or None if no
        capture was running."""
        if not self.active:
            return None
        self.active = False
        try:
            import jax.profiler

            jax.profiler.stop_trace()
        except Exception as e:  # pragma: no cover - backend-dependent
            self.errors += 1
            log.warning("jax.profiler trace did not stop cleanly: %s", e)
            return None
        self.sessions += 1
        return self.trace_dir

    def metrics(self, prefix: str = "profiler_") -> Dict[str, float]:
        return {
            f"{prefix}active": 1.0 if self.active else 0.0,
            f"{prefix}sessions_total": float(self.sessions),
            f"{prefix}errors_total": float(self.errors),
        }
