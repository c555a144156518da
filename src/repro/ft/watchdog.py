"""Fault-tolerance runtime: straggler detection, preemption handling,
transient-failure retry.

On real pods the heartbeat store is a distributed KV (or jax coordination
service); here it is process-local but the policy logic — rolling-median
step-time outlier detection, preemption-flag draining, bounded retry with
backoff — is exactly what the loop would run at scale.
"""

from __future__ import annotations

import os
import time
from collections import deque
from typing import Callable, Dict, Optional


class StragglerWatchdog:
    """Flags steps slower than ``factor`` x rolling median (straggler
    mitigation hook: at scale the action is to re-shard around the slow
    host / trigger elastic re-mesh; here we count and expose the signal).

    ``clock`` is injectable (seconds) so tests never sleep."""

    def __init__(
        self,
        window: int = 32,
        factor: float = 3.0,
        min_samples: int = 8,
        clock: Callable[[], float] = time.perf_counter,
    ):
        self.durations: deque = deque(maxlen=window)
        self.factor = factor
        self.min_samples = min_samples
        self.straggler_events = 0
        self._clock = clock
        self._t0: Optional[float] = None

    def step_start(self):
        self._t0 = self._clock()

    def step_end(self) -> bool:
        """Returns True if this step was a straggler."""
        dt = self._clock() - self._t0
        is_straggler = False
        if len(self.durations) >= self.min_samples:
            med = sorted(self.durations)[len(self.durations) // 2]
            if dt > self.factor * med:
                self.straggler_events += 1
                is_straggler = True
        self.durations.append(dt)
        return is_straggler

    @property
    def median(self) -> float:
        if not self.durations:
            return 0.0
        return sorted(self.durations)[len(self.durations) // 2]


class HeartbeatMonitor:
    """Liveness tracking for long-running components (serve dispatch loop,
    train loop, checkpoint writer).  Components ``register`` with a timeout
    and ``beat`` on every unit of progress; ``stale()`` reports the ones
    whose last beat is older than their timeout.  Transitions fresh->stale
    are counted once each (``missed_events``), so a flapping component shows
    up as many events rather than one long one.

    ``clock`` is injectable (monotonic seconds) so tests — and deterministic
    replay of an incident — never sleep.
    """

    def __init__(self, default_timeout_s: float = 10.0, clock: Callable[[], float] = time.monotonic):
        self.default_timeout_s = default_timeout_s
        self._clock = clock
        self._last: Dict[str, float] = {}
        self._timeout: Dict[str, float] = {}
        self._was_stale: Dict[str, bool] = {}
        self.missed_events = 0

    def register(self, name: str, timeout_s: Optional[float] = None):
        self._timeout[name] = self.default_timeout_s if timeout_s is None else float(timeout_s)
        self._last[name] = self._clock()
        self._was_stale[name] = False

    def beat(self, name: str):
        if name not in self._last:
            self.register(name)
        self._last[name] = self._clock()
        self._was_stale[name] = False

    def stale(self) -> Dict[str, float]:
        """{name: seconds since last beat} for every overdue component.
        Fresh->stale transitions increment ``missed_events``."""
        now = self._clock()
        out: Dict[str, float] = {}
        for name, last in self._last.items():
            age = now - last
            if age > self._timeout[name]:
                out[name] = age
                if not self._was_stale[name]:
                    self._was_stale[name] = True
                    self.missed_events += 1
        return out

    def age(self, name: str) -> float:
        return self._clock() - self._last[name]

    def metrics(self, prefix: str = "heartbeat_") -> Dict[str, float]:
        """Flat gauge dict for scraping alongside the serve metrics.  Per-name
        age gauges use exposition-safe names (``heartbeat_age_s_serve_dispatch``)
        so alert rules can target them directly."""
        from repro.obs.registry import sanitize_name

        overdue = self.stale()
        out = {
            f"{prefix}components": float(len(self._last)),
            f"{prefix}stale": float(len(overdue)),
            f"{prefix}missed_events": float(self.missed_events),
        }
        for name in self._last:
            out[sanitize_name(f"{prefix}age_s_{name}")] = self.age(name)
        return out

    def publish_metrics(self, registry, prefix: str = "heartbeat_") -> set:
        """Registry view of the scrape surface: ONE labelled age gauge
        (``heartbeat_age_s{name="serve.dispatch"}``) instead of a metric
        family per component — N fabric replicas add N label children, not N
        families — plus the flat aggregate gauges.  Returns the legacy
        name-suffixed keys this publish *claims*: they stay in the
        ``metrics()`` dict view for existing callers, but the caller
        (``serve.collect_metrics``) must not ALSO publish them flat, or the
        family namespace would grow per component again."""
        from repro.obs.registry import sanitize_name

        m = self.metrics(prefix)
        gauge = registry.gauge(
            f"{prefix}age_s",
            "seconds since a component's last heartbeat",
            labelnames=("name",),
        )
        claimed = set()
        for name in self._last:
            gauge.labels(name=name).set(self.age(name))
            claimed.add(sanitize_name(f"{prefix}age_s_{name}"))
        registry.publish({k: v for k, v in m.items() if k not in claimed})
        return claimed


class PreemptionSignal:
    """File-flag preemption notice (SIGTERM handler writes it; tests touch
    it).  The train loop checks every step and exits through a final
    checkpoint when raised."""

    def __init__(self, flag_path: str):
        self.flag_path = flag_path

    def raised(self) -> bool:
        return os.path.exists(self.flag_path)

    def set(self):
        with open(self.flag_path, "w") as f:
            f.write("preempt")

    def clear(self):
        if os.path.exists(self.flag_path):
            os.remove(self.flag_path)


def with_retries(
    fn: Callable,
    max_retries: int = 3,
    backoff_s: float = 0.05,
    retryable=(RuntimeError,),
    on_retry: Optional[Callable[[int, BaseException], None]] = None,
):
    """Bounded-retry wrapper for transient device/step failures."""

    def wrapped(*args, **kwargs):
        attempt = 0
        while True:
            try:
                return fn(*args, **kwargs)
            except retryable as e:
                attempt += 1
                if attempt > max_retries:
                    raise
                if on_retry:
                    on_retry(attempt, e)
                time.sleep(backoff_s * (2 ** (attempt - 1)))

    return wrapped
