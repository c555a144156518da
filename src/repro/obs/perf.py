"""Per-executable device-time attribution with a roofline join.

The serve stack compiles a handful of executables (per-bucket embedding
forwards, per-bucket prefills, the batched decode tick, the chunked-prefill
step, the probe update), each blocked on before it is timed, and until now the
telemetry only gated the AGGREGATE — tok/s — so a regression in one
executable hid behind the others.  ``ExecTimer`` is the attribution layer:

  * **wall time** — a labelled ``exec_seconds{executable=...}`` histogram
    plus host-side calls/total/best stats per executable (the ``/perf``
    endpoint and the bench ``perf`` section read these);
  * **compile time** — ``exec_compile_seconds{executable=...}`` gauges set
    when an executable is AOT lowered+compiled at warmup;
  * **compile-cache traffic** — ``exec_cache_{hits,misses}_total`` counters
    from the engines' bucket caches;
  * **the roofline join** — ``attach_compiled``/``attach_jit`` parse the
    optimized HLO through ``repro.launch.hlo_cost`` (trip-exact FLOPs/bytes,
    the same analyzer the tune dry tier uses) and every snapshot derives
    achieved GFLOP/s, achieved GB/s, a roofline-utilization gauge
    ``min(1, analytic_bound_s / best_measured_s)`` and the analytic-vs-
    measured disagreement ratio ``best_measured_s / analytic_bound_s`` —
    directly feeding the ROADMAP debt "analytic tier favors large pages —
    validate against wall time".  The join needs the measuring device's
    peaks (``launch.hlo_cost.PEAKS``, keyed by ``device_kind``): on a device
    that is not in that table nothing is attached and no roofline gauge is
    published, so a host-CPU timing is never divided by a chip's peaks.

Everything is lazy and failure-tolerant: the HLO analyzer import happens
only when something attaches (the analytic tier never pays it), a backend
without ``as_text()`` simply yields no join, and a disabled timer
(``Obs.disabled()``) costs one attribute read per hot-path check because the
engines hold ``perf = None`` instead of a disabled object.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Any, Dict, List, Optional

from repro.obs.registry import DEFAULT_BUCKETS, MetricsRegistry

# executable steps on a warm pool run well under the latency ladder's 100us
# floor on real accelerators — extend the default buckets downward
EXEC_BUCKETS = (1e-5, 2.5e-5, 5e-5) + DEFAULT_BUCKETS


class _ExecStat:
    __slots__ = ("calls", "total_s", "best_s")

    def __init__(self):
        self.calls = 0
        self.total_s = 0.0
        self.best_s = math.inf


class ExecTimer:
    """Labelled wall-time attribution + analytic-cost join per executable."""

    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        *,
        enabled: bool = True,
        clock=time.perf_counter,
    ):
        self.enabled = bool(enabled)
        self._device_kind: Optional[str] = None  # read at the first roofline join
        self.registry = registry if registry is not None else MetricsRegistry()
        self._clock = clock
        self._lock = threading.Lock()
        self._stats: Dict[str, _ExecStat] = {}
        self._analysis: Dict[str, Dict[str, Any]] = {}
        self._compile_s: Dict[str, float] = {}
        self.observed_total = 0
        r = self.registry
        self._h_exec = r.histogram(
            "exec_seconds", "per-executable wall time",
            labelnames=("executable",), buckets=EXEC_BUCKETS,
        )
        self._g_compile = r.gauge(
            "exec_compile_seconds", "AOT lower+compile wall time",
            labelnames=("executable",),
        )
        self._c_hits = r.counter(
            "exec_cache_hits_total", "compile-cache hits",
            labelnames=("executable",),
        )
        self._c_misses = r.counter(
            "exec_cache_misses_total", "compile-cache misses",
            labelnames=("executable",),
        )

    # -- hot path -------------------------------------------------------------
    # engines guard every call with `if self.perf is not None`, so a disabled
    # bundle never reaches these; the methods themselves still honor
    # `enabled` so a shared timer can be switched off without re-wiring.

    def start(self) -> float:
        return self._clock()

    def elapsed(self, t0: float) -> float:
        return self._clock() - t0

    def observe(self, name: str, seconds: float):
        """Fold one executable invocation's wall time into the stream."""
        if not self.enabled:
            return
        s = float(seconds)
        with self._lock:
            st = self._stats.get(name)
            if st is None:
                st = self._stats[name] = _ExecStat()
            st.calls += 1
            st.total_s += s
            if s < st.best_s:
                st.best_s = s
            self.observed_total += 1
        self._h_exec.labels(executable=name).observe(s)

    def cache_hit(self, name: str):
        if self.enabled:
            self._c_hits.labels(executable=name).inc()

    def cache_miss(self, name: str):
        if self.enabled:
            self._c_misses.labels(executable=name).inc()

    # -- the analytic join ----------------------------------------------------

    def _peaks(self) -> Optional[Dict[str, float]]:
        """The measuring device's peaks, or None when it has no table entry."""
        from repro.launch.hlo_cost import PEAKS

        if self._device_kind is None:
            import jax

            self._device_kind = jax.devices()[0].device_kind
        return PEAKS.get(self._device_kind)

    def record_compile(self, name: str, seconds: float):
        if not self.enabled:
            return
        with self._lock:
            self._compile_s[name] = float(seconds)
        self._g_compile.labels(executable=name).set(float(seconds))

    def attach_analysis(
        self,
        name: str,
        *,
        flops: float,
        hbm_bytes: float,
        collective_bytes: float = 0.0,
        bound_s: Optional[float] = None,
        dominant: Optional[str] = None,
        compile_s: Optional[float] = None,
    ):
        """Attach analytic costs directly (tests; callers with their own
        cost model).  ``bound_s`` defaults to the roofline bound on the
        measuring device's peaks; a device without peaks attaches nothing."""
        if not self.enabled:
            return
        if compile_s is not None:
            self.record_compile(name, compile_s)
        peak = self._peaks()
        if peak is None:
            return
        if bound_s is None:
            terms = {
                "compute": flops / peak["flops"],
                "memory": hbm_bytes / peak["hbm_bw"],
                "collective": collective_bytes / peak["ici_bw"],
            }
            dominant = dominant or max(terms, key=terms.get)
            bound_s = max(terms.values())
        with self._lock:
            self._analysis[name] = {
                "flops": float(flops),
                "hbm_bytes": float(hbm_bytes),
                "collective_bytes": float(collective_bytes),
                "bound_s": float(bound_s),
                "dominant": dominant,
            }

    def attach_compiled(self, name: str, compiled) -> bool:
        """Join one AOT-compiled executable: parse its optimized HLO for
        trip-exact FLOPs/bytes and store the roofline terms.  Idempotent per
        name; returns False (and attaches nothing) when the device has no
        peaks, the backend exposes no HLO text or the analyzer cannot parse
        it."""
        if not self.enabled or self._peaks() is None:
            return False
        with self._lock:
            if name in self._analysis:
                return True
        try:
            hlo = compiled.as_text()
            from repro.launch.hlo_cost import analyze_hlo, roofline_terms

            a = analyze_hlo(hlo)
            terms = roofline_terms(a, self._device_kind)
        except Exception:
            return False
        self.attach_analysis(
            name,
            flops=a.flops,
            hbm_bytes=a.hbm_bytes,
            collective_bytes=a.total_collective_bytes,
            bound_s=terms["bound_s"],
            dominant=terms["dominant"],
        )
        return True

    def attach_jit(self, name: str, fn, *args, **kw) -> bool:
        """AOT lower+compile a jitted callable purely for attribution (the
        caller keeps executing its own jit cache) and join the result.
        Records the lower+compile wall time as the compile gauge on any
        device; the roofline join only where ``attach_compiled`` makes it."""
        if not self.enabled:
            return False
        with self._lock:
            if name in self._analysis:
                return True
        t0 = self._clock()
        try:
            compiled = fn.lower(*args, **kw).compile()
        except Exception:
            return False
        self.record_compile(name, self._clock() - t0)
        return self.attach_compiled(name, compiled)

    @property
    def analyzed(self) -> int:
        with self._lock:
            return len(self._analysis)

    # -- read side ------------------------------------------------------------

    def snapshot(self, top_k: Optional[int] = None) -> List[Dict[str, Any]]:
        """Per-executable rows, slowest total first: measured stats joined
        with the analytic roofline (achieved GFLOP/s and GB/s from the BEST
        measured time — the least-noisy invocation; utilization clamped into
        (0, 1]; ``disagreement`` = measured/analytic, >= 1 by construction,
        the validate-against-wall-time ratio)."""
        with self._lock:
            stats = {n: (s.calls, s.total_s, s.best_s) for n, s in self._stats.items()}
            analysis = dict(self._analysis)
            compile_s = dict(self._compile_s)
        rows: List[Dict[str, Any]] = []
        for name, (calls, total_s, best_s) in stats.items():
            row: Dict[str, Any] = {
                "executable": name,
                "calls": calls,
                "total_s": total_s,
                "best_s": best_s,
                "mean_s": total_s / max(calls, 1),
            }
            if name in compile_s:
                row["compile_s"] = compile_s[name]
            a = analysis.get(name)
            if a is not None:
                best = max(best_s, 1e-9)
                bound = a["bound_s"]
                row.update(
                    flops=a["flops"],
                    hbm_bytes=a["hbm_bytes"],
                    bound_s=bound,
                    dominant=a["dominant"],
                    achieved_gflops=a["flops"] / best / 1e9,
                    achieved_gbps=a["hbm_bytes"] / best / 1e9,
                    roofline_utilization=min(1.0, bound / best) if bound > 0 else 0.0,
                    disagreement=(best / bound) if bound > 0 else None,
                )
            rows.append(row)
        rows.sort(key=lambda r: r["total_s"], reverse=True)
        return rows[:top_k] if top_k else rows

    def report(self, top_k: int = 10) -> Dict[str, Any]:
        """The ``/perf`` endpoint payload: top-k slowest executables with
        their utilization, plus the aggregate counts."""
        return {
            "executables": len(self._stats),
            "analyzed": self.analyzed,
            "observed_total": self.observed_total,
            "top": self.snapshot(top_k),
        }

    def publish(self, registry: Optional[MetricsRegistry] = None):
        """Mirror the derived roofline values as labelled gauges (scrape
        path: called by ``Obs.scrape`` each cycle, like quantile gauges)."""
        if not self.enabled:
            return
        r = registry if registry is not None else self.registry
        g_total = r.gauge("exec_wall_seconds_total", "summed executable wall time",
                          labelnames=("executable",))
        g_calls = r.gauge("exec_calls_total", "executable invocations",
                          labelnames=("executable",))
        g_util = r.gauge("exec_roofline_utilization",
                         "analytic roofline bound / best measured time, clamped to 1",
                         labelnames=("executable",))
        g_gflops = r.gauge("exec_achieved_gflops", "FLOPs / best measured second / 1e9",
                           labelnames=("executable",))
        g_gbps = r.gauge("exec_achieved_gbps", "HBM bytes / best measured second / 1e9",
                         labelnames=("executable",))
        g_dis = r.gauge("exec_analytic_disagreement",
                        "best measured time / analytic roofline bound",
                        labelnames=("executable",))
        for row in self.snapshot():
            lbl = {"executable": row["executable"]}
            g_total.labels(**lbl).set(row["total_s"])
            g_calls.labels(**lbl).set(float(row["calls"]))
            if "roofline_utilization" in row:
                g_util.labels(**lbl).set(row["roofline_utilization"])
                g_gflops.labels(**lbl).set(row["achieved_gflops"])
                g_gbps.labels(**lbl).set(row["achieved_gbps"])
                if row["disagreement"] is not None:
                    g_dis.labels(**lbl).set(row["disagreement"])

    def metrics(self, prefix: str = "perf_") -> Dict[str, float]:
        with self._lock:
            return {
                f"{prefix}executables": float(len(self._stats)),
                f"{prefix}analyzed": float(len(self._analysis)),
                f"{prefix}observed_total": float(self.observed_total),
            }
