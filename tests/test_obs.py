"""repro.obs — the unified telemetry stack.

Primitive-level contracts first (registry types, exposition grammar, alert
edge-triggering, flight-recorder wraparound, tracer export), then the
integration the subsystem exists for: a mixed LM workload whose legacy
``metrics()`` dict, Prometheus scrape, Chrome trace and flight-recorder dump
all tell the same story — and a synthetic probe-drift crossing that fires
its alert exactly once and clears on recovery."""

import json
import math
import urllib.request

import numpy as np
import pytest

from repro.obs import (
    AlertManager,
    AlertRule,
    ExecTimer,
    FlightRecorder,
    MetricsRegistry,
    Obs,
    Profiler,
    Tracer,
    default_serve_rules,
    default_train_rules,
    quantile_from_buckets,
    reconstruct_request,
    sanitize_name,
)

@pytest.fixture
def chip_peaks(monkeypatch):
    """Give the test process's device the v5e peaks, so the roofline join runs
    on the CPU; a device missing from the table gets no roofline at all."""
    import jax

    from repro.launch import hlo_cost

    monkeypatch.setitem(
        hlo_cost.PEAKS, jax.devices()[0].device_kind, hlo_cost.PEAKS["TPU v5 lite"]
    )


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_monotone(self):
        reg = MetricsRegistry()
        c = reg.counter("requests_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        g = MetricsRegistry().gauge("depth")
        g.set(4)
        g.inc()
        g.dec(2)
        assert g.value == 3.0

    def test_histogram_bucket_boundaries(self):
        h = MetricsRegistry().histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.1, 0.05, 0.5, 5.0, 50.0):  # 0.1 lands IN le=0.1 (<=)
            h.observe(v)
        cum = h._default_child().bucket_counts()
        assert [(le, c) for le, c in cum] == [
            (0.1, 2), (1.0, 3), (10.0, 4), (math.inf, 5)
        ]
        assert h.count == 5 and h.sum == pytest.approx(55.65)

    def test_histogram_rejects_bad_buckets(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.histogram("h1", buckets=())
        with pytest.raises(ValueError):
            reg.histogram("h2", buckets=(1.0, 1.0))

    def test_label_cardinality_guard(self):
        reg = MetricsRegistry(max_label_sets=3)
        c = reg.counter("hits", labelnames=("path",))
        for i in range(3):
            c.labels(path=f"/p{i}").inc()
        c.labels(path="/p0").inc()  # existing set: fine
        with pytest.raises(ValueError, match="cardinality"):
            c.labels(path="/p3")

    def test_type_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")
        with pytest.raises(ValueError, match="labelnames"):
            reg.counter("x", labelnames=("a",))

    def test_sanitize_name(self):
        assert sanitize_name("heartbeat_age_s:serve.dispatch") == \
            "heartbeat_age_s_serve_dispatch"
        assert sanitize_name("9lives") == "_9lives"

    def test_publish_and_value(self):
        reg = MetricsRegistry()
        reg.publish({"tok_per_s": 12.5, "decorr.r_off": 0.1})
        assert reg.value("tok_per_s") == 12.5
        assert reg.value("decorr_r_off") == 0.1
        assert reg.value("missing") is None

    def test_exposition_round_trip(self):
        reg = MetricsRegistry()
        reg.counter("served_total", "requests served").inc(7)
        reg.gauge("queue_depth").set(3)
        reg.histogram("step_s", buckets=(0.5,)).observe(0.2)
        g = reg.gauge("err", labelnames=("kind",))
        g.labels(kind='dev"ice\n').set(1)
        text = reg.exposition()
        assert "# HELP served_total requests served" in text
        assert "# TYPE served_total counter" in text
        assert "served_total 7" in text.splitlines()
        assert 'step_s_bucket{le="0.5"} 1' in text
        assert 'step_s_bucket{le="+Inf"} 1' in text
        assert "step_s_count 1" in text.splitlines()
        assert 'err{kind="dev\\"ice\\n"} 1' in text.splitlines()
        # every sample line parses as <name>[{labels}] <float>
        for line in text.splitlines():
            if line.startswith("#") or not line:
                continue
            name, value = line.rsplit(" ", 1)
            float(value.replace("+Inf", "inf"))
            assert sanitize_name(name.split("{")[0]) == name.split("{")[0]

    def test_as_dict_matches_values(self):
        reg = MetricsRegistry()
        reg.publish({"a": 1.0, "b": 2.0})
        reg.histogram("h").observe(0.3)
        d = reg.as_dict()
        assert d["a"] == 1.0 and d["b"] == 2.0
        assert d["h_count"] == 1.0 and "h_bucket" not in str(sorted(d))

    def test_quantile_from_buckets_interpolates(self):
        bounds = (1.0, 2.0, 4.0)
        # 2 obs in (0,1], 2 in (1,2], none in (2,4], 0 in +Inf
        counts = (2, 2, 0, 0)
        # rank q*total from 0 at the holding bucket's LOWER bound
        assert quantile_from_buckets(bounds, counts, 0.5) == pytest.approx(1.0)
        assert quantile_from_buckets(bounds, counts, 0.25) == pytest.approx(0.5)
        assert quantile_from_buckets(bounds, counts, 0.75) == pytest.approx(1.5)
        assert quantile_from_buckets(bounds, counts, 1.0) == pytest.approx(2.0)

    def test_quantile_from_buckets_edges(self):
        assert quantile_from_buckets((1.0, 2.0), (0, 0, 0), 0.99) == 0.0  # empty
        # everything in the +Inf bucket clamps to the top finite bound
        assert quantile_from_buckets((1.0, 2.0), (0, 0, 5), 0.99) == 2.0
        with pytest.raises(ValueError, match="quantile"):
            quantile_from_buckets((1.0,), (1, 0), 1.5)
        with pytest.raises(ValueError, match="quantile"):
            quantile_from_buckets((1.0,), (1, 0), -0.1)

    def test_quantile_from_buckets_single_bucket(self):
        # one finite bucket holding all the mass: every quantile interpolates
        # within (0, bound]
        assert quantile_from_buckets((2.0,), (4, 0), 0.0) == pytest.approx(0.0)
        assert quantile_from_buckets((2.0,), (4, 0), 0.5) == pytest.approx(1.0)
        assert quantile_from_buckets((2.0,), (4, 0), 1.0) == pytest.approx(2.0)
        # a single observation degenerates to the bucket's upper bound at q=1
        assert quantile_from_buckets((2.0,), (1, 0), 1.0) == pytest.approx(2.0)

    def test_label_cardinality_overflow_keeps_existing_children(self):
        reg = MetricsRegistry(max_label_sets=2)
        c = reg.counter("hits", labelnames=("path",))
        c.labels(path="/a").inc()
        c.labels(path="/b").inc(2)
        with pytest.raises(ValueError, match="cardinality"):
            c.labels(path="/c")
        # the overflow attempt must not corrupt or evict live children
        c.labels(path="/a").inc()
        assert reg.value("hits", {"path": "/a"}) == 2.0
        assert reg.value("hits", {"path": "/b"}) == 2.0
        text = reg.exposition()
        assert 'hits{path="/a"} 2' in text and 'hits{path="/c"}' not in text
        # and a second overflow attempt still raises (no partial registration)
        with pytest.raises(ValueError, match="cardinality"):
            c.labels(path="/c")

    def test_histogram_quantile_and_derived_gauges(self):
        reg = MetricsRegistry()
        h = reg.histogram("step_s", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.2, 0.4, 0.9, 20.0):
            h.observe(v)
        assert 0.1 < h.quantile(0.5) < 1.0
        derived = reg.quantile_gauges()
        assert derived["step_s_p50"] == pytest.approx(h.quantile(0.5))
        assert derived["step_s_p99"] == 10.0  # +Inf rank clamps to top bound
        # labelled histograms are skipped (cross-series aggregation is out of
        # scope), unlabelled non-histograms contribute nothing
        lab = reg.histogram("lat_s", labelnames=("path",))
        lab.labels(path="/a").observe(0.3)
        reg.gauge("depth").set(2)
        assert set(reg.quantile_gauges()) == {"step_s_p50", "step_s_p99"}

    def test_scrape_derives_quantiles_and_fires_ttft_alert(self):
        obs = Obs(alerts=AlertManager(default_serve_rules()))
        h = obs.registry.histogram("serve_ttft_seconds", "ttft")
        for _ in range(4):
            h.observe(30.0)  # p99 lands far above the 5s threshold
        rule = next(r for r in default_serve_rules() if r.name == "ttft_p99_high")
        for _ in range(rule.window):
            obs.scrape()
        assert "ttft_p99_high" in obs.alerts.active()
        assert obs.registry.value("serve_ttft_seconds_p99") > 5.0


# ---------------------------------------------------------------------------
# Alerts: edge-triggered threshold rules
# ---------------------------------------------------------------------------


class TestAlerts:
    def test_fire_once_per_crossing_and_clear(self):
        events = []
        am = AlertManager(
            [AlertRule("drift", "m", ">", 1.0)], sink=events.append
        )
        for v in (2.0, 3.0, 4.0):  # one crossing, three breaching scrapes
            am.evaluate({"m": v})
        assert [e["type"] for e in events] == ["fire"]
        am.evaluate({"m": 0.5})  # recovery: single clear
        am.evaluate({"m": 0.5})
        assert [e["type"] for e in events] == ["fire", "clear"]
        am.evaluate({"m": 9.0})  # re-crossing fires again
        assert [e["type"] for e in events] == ["fire", "clear", "fire"]
        st = am.state("drift")
        assert st.fired == 2 and st.cleared == 1

    def test_window_needs_consecutive_breaches(self):
        events = []
        am = AlertManager(
            [AlertRule("w", "m", ">", 1.0, window=3)], sink=events.append
        )
        am.evaluate({"m": 2.0})
        am.evaluate({"m": 2.0})
        am.evaluate({"m": 0.0})  # streak broken before the window filled
        am.evaluate({"m": 2.0})
        am.evaluate({"m": 2.0})
        assert events == []
        am.evaluate({"m": 2.0})  # third consecutive breach
        assert [e["type"] for e in events] == ["fire"]

    def test_missing_metric_leaves_rule_untouched(self):
        events = []
        am = AlertManager([AlertRule("a", "m", ">", 1.0)], sink=events.append)
        am.evaluate({"m": 5.0})
        am.evaluate({"other": 0.0})  # m absent: no false clear
        assert [e["type"] for e in events] == ["fire"]
        assert am.active() == ["a"]

    def test_from_config_and_validation(self, tmp_path):
        rules = [{"name": "r1", "metric": "m", "op": "<", "threshold": 0.1,
                  "window": 2, "severity": "critical"}]
        am = AlertManager.from_config(json.dumps(rules))
        assert am.rules[0].severity == "critical"
        path = tmp_path / "alerts.json"
        path.write_text(json.dumps(rules))
        assert AlertManager.from_config(str(path)).rules[0].window == 2
        with pytest.raises(ValueError, match="comparator"):
            AlertRule("bad", "m", "~", 1.0).validate()
        with pytest.raises(ValueError, match="duplicate"):
            AlertManager([AlertRule("x", "m", ">", 1), AlertRule("x", "m", ">", 2)])

    def test_publish_labelled_gauges(self):
        reg = MetricsRegistry()
        am = AlertManager([AlertRule("drift", "m", ">", 1.0)])
        am.evaluate({"m": 2.0})
        am.publish(reg)
        assert reg.value("alert_active", {"alert": "drift"}) == 1.0
        assert reg.value("alert_fired_total", {"alert": "drift"}) == 1.0
        assert reg.value("alerts_active") == 1.0

    def test_fired_counter_survives_clears_between_scrapes(self):
        reg = MetricsRegistry()
        am = AlertManager([AlertRule("flap", "m", ">", 1.0)])
        am.publish(reg)  # zero-valued series exists before any firing
        assert reg.value("obs_alerts_fired_total", {"rule": "flap"}) == 0.0
        for _ in range(3):  # three full fire/clear flaps
            am.evaluate({"m": 5.0})
            am.evaluate({"m": 0.0})
        am.publish(reg)
        # the gauge view says "not active" but the counter keeps the history
        assert reg.value("alert_active", {"alert": "flap"}) == 0.0
        assert reg.value("obs_alerts_fired_total", {"rule": "flap"}) == 3.0
        am.publish(reg)  # republish without new firings must not double-count
        assert reg.value("obs_alerts_fired_total", {"rule": "flap"}) == 3.0

    def test_default_train_rules_target_health_gauges(self):
        rules = {r.name: r for r in default_train_rules()}
        assert rules["train_variance_collapse"].metric == "train_decorr_feat_var_ema"
        assert rules["train_variance_collapse"].severity == "critical"
        assert (rules["train_relaxation_gap_blowup"].metric
                == "train_decorr_relaxation_gap_ema")
        for r in rules.values():
            r.validate()

    def test_default_serve_rules_target_live_gauges(self):
        names = {r.metric for r in default_serve_rules()}
        assert "decorr_r_sum_norm_ema" in names
        assert "heartbeat_stale" in names
        # TTFT alerts read the scrape-derived histogram quantile gauge, not
        # the service's parallel rolling-window percentile
        assert "serve_ttft_seconds_p99" in names
        assert "paged_pages_utilization" in names


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_wraparound_keeps_newest(self):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record("tick", i=i)
        assert len(rec) == 4 and rec.recorded_total == 10 and rec.dropped == 6
        evs = rec.events()
        assert [e["i"] for e in evs] == [6, 7, 8, 9]
        assert [e["seq"] for e in evs] == [6, 7, 8, 9]  # seq survives the wrap

    def test_disabled_recorder_is_noop(self):
        rec = FlightRecorder(capacity=0)
        rec.record("tick")
        assert len(rec) == 0 and rec.events() == [] and not rec.enabled

    def test_filter_counts_dump(self, tmp_path):
        rec = FlightRecorder(capacity=16)
        rec.record("admit", slot=0)
        rec.record("retire", slot=0)
        rec.record("admit", slot=1)
        assert rec.counts() == {"admit": 2, "retire": 1}
        assert [e["slot"] for e in rec.events("admit")] == [0, 1]
        path = rec.dump_json(str(tmp_path / "fr.json"))
        dump = json.loads(open(path).read())
        assert dump["recorded_total"] == 3 and len(dump["events"]) == 3


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_request_lifecycle_spans(self):
        t = Tracer()
        rt = t.start_request("lm", prompt_len=8)
        rt.mark_admit(slot=0)
        rt.mark_first()
        rt.tick(); rt.tick(); rt.tick()
        rt.mark_done()
        rec = reconstruct_request(t.to_chrome(), rt.rid)
        assert rec["phases"] == ["queue", "prefill", "decode"]
        assert rec["ticks"] == 3 and rec["retired"] and rec["status"] == "ok"
        assert rt.latency_s >= rt.ttft_s >= rt.queue_s >= 0

    def test_reconstruct_missing_request_raises(self):
        t = Tracer()
        with pytest.raises(KeyError):
            reconstruct_request(t.to_chrome(), 99)

    def test_disabled_tracer_marks_still_time(self):
        t = Tracer(enabled=False)
        rt = t.start_request("lm")
        rt.mark_admit(); rt.mark_first(); rt.mark_done()
        assert rt.latency_s is not None  # marks are the timing source
        assert len(t) == 0  # but no events buffered

    def test_write_chrome_json(self, tmp_path):
        t = Tracer()
        with t.span("decode_step", lanes=4):
            pass
        t.instant("retire", request_id=0)
        path = t.write(str(tmp_path / "trace.json"))
        dump = json.loads(open(path).read())
        names = [e["name"] for e in dump["traceEvents"]]
        assert names == ["decode_step", "retire"]
        assert dump["traceEvents"][0]["ph"] == "X"

    def test_bounded_buffer_drops_oldest(self):
        t = Tracer(capacity=2)
        for i in range(5):
            t.instant("e", i=i)
        assert len(t) == 2 and t.dropped_events == 3


# ---------------------------------------------------------------------------
# Obs bundle + HTTP endpoint + profiler
# ---------------------------------------------------------------------------


class TestObsBundle:
    def test_scrape_evaluates_rules_and_dumps_recorder(self, tmp_path):
        obs = Obs(alerts=AlertManager(default_serve_rules()),
                  dump_dir=str(tmp_path))
        obs.recorder.record("tick", i=1)
        bad = {"decorr_r_sum_norm_ema": 0.9}
        for _ in range(3):  # window=3 on the drift rule
            text = obs.scrape(lambda: bad)
        assert obs.alerts.active() == ["probe_r_sum_drift"]
        dumps = list(tmp_path.glob("flightrec_probe_r_sum_drift_*.json"))
        assert len(dumps) == 1  # edge-triggered: one fire, one dump
        assert json.loads(dumps[0].read_text())["events"][0]["kind"] == "tick"
        assert 'alert_active{alert="probe_r_sum_drift"} 1' in text
        obs.scrape(lambda: {"decorr_r_sum_norm_ema": 0.0})
        assert obs.alerts.active() == []

    def test_disabled_obs_turns_hot_paths_off(self):
        obs = Obs.disabled()
        assert not obs.tracer.enabled and not obs.recorder.enabled
        rt = obs.tracer.start_request("lm")
        rt.mark_done()
        assert rt.latency_s is not None and len(obs.tracer) == 0
        assert obs.metrics()["obs_enabled"] == 0.0

    def test_http_endpoint(self):
        obs = Obs(alerts=AlertManager([AlertRule("a", "m", ">", 1.0)]))
        server = obs.start_server(port=0, metrics_fn=lambda: {"m": 5.0})
        try:
            base = server.url
            text = urllib.request.urlopen(base + "/metrics", timeout=10).read().decode()
            assert "m 5" in text and "alerts_fired_total 1" in text
            alerts = json.loads(
                urllib.request.urlopen(base + "/alerts", timeout=10).read()
            )
            assert alerts[0]["alert"] == "a" and alerts[0]["active"]
            assert urllib.request.urlopen(base + "/healthz", timeout=10).read() == b"ok\n"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + "/nope", timeout=10)
        finally:
            server.stop()

    def test_profiler_noop_without_dir(self):
        p = Profiler()
        assert p.start() is False and p.stop() is None
        assert p.metrics()["profiler_active"] == 0.0

    def test_perf_and_flight_endpoints(self, chip_peaks):
        obs = Obs()
        obs.perf.attach_analysis("decode", flops=2e9, hbm_bytes=1e8)
        obs.perf.observe("decode", 0.004)
        obs.perf.observe("decode", 0.002)
        obs.recorder.record("admit", slot=1)
        server = obs.start_server(port=0)
        try:
            base = server.url
            perf = json.loads(urllib.request.urlopen(base + "/perf", timeout=10).read())
            assert perf["executables"] == 1 and perf["observed_total"] == 2
            row = perf["top"][0]
            assert row["executable"] == "decode" and row["calls"] == 2
            assert 0.0 < row["roofline_utilization"] <= 1.0
            assert row["best_s"] == pytest.approx(0.002)
            flight = json.loads(urllib.request.urlopen(base + "/flight", timeout=10).read())
            assert flight["recorded_total"] == 1
            assert flight["events"][0]["kind"] == "admit"
            # the scrape path mirrors the roofline join as labelled gauges
            urllib.request.urlopen(base + "/metrics", timeout=10).read()
            assert obs.registry.value(
                "exec_roofline_utilization", {"executable": "decode"}
            ) == pytest.approx(row["roofline_utilization"])
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# ExecTimer: per-executable attribution + the analytic roofline join
# ---------------------------------------------------------------------------


class TestExecTimer:
    def test_observe_tracks_calls_total_best(self):
        t = ExecTimer()
        for s in (0.03, 0.01, 0.02):
            t.observe("step", s)
        (row,) = t.snapshot()
        assert row["calls"] == 3
        assert row["total_s"] == pytest.approx(0.06)
        assert row["best_s"] == pytest.approx(0.01)
        assert row["mean_s"] == pytest.approx(0.02)
        assert "roofline_utilization" not in row  # no analysis attached yet
        assert t.registry.get("exec_seconds").labels(executable="step").count == 3

    def test_analysis_join_derives_roofline_fields(self, chip_peaks):
        t = ExecTimer()
        t.attach_analysis("step", flops=1e9, hbm_bytes=4e6, compile_s=0.5)
        t.observe("step", 1e-3)
        (row,) = t.snapshot()
        # achieved rates come from the BEST measured time
        assert row["achieved_gflops"] == pytest.approx(1e9 / 1e-3 / 1e9)
        assert row["achieved_gbps"] == pytest.approx(4e6 / 1e-3 / 1e9)
        assert 0.0 < row["roofline_utilization"] <= 1.0
        # measured/analytic disagreement: CPU-measured vs TPU-analytic >> 1
        assert row["disagreement"] == pytest.approx(
            1e-3 / row["bound_s"]
        )
        assert row["compile_s"] == 0.5
        assert row["dominant"] in ("compute", "memory", "collective")

    def test_utilization_clamps_to_one(self, chip_peaks):
        t = ExecTimer()
        # analytic bound far ABOVE the measured time (pessimistic model):
        # the gauge clamps at 1.0 instead of reporting >100% of roofline
        t.attach_analysis("fast", flops=0.0, hbm_bytes=0.0, bound_s=10.0)
        t.observe("fast", 1e-3)
        (row,) = t.snapshot()
        assert row["roofline_utilization"] == 1.0

    def test_snapshot_sorts_by_total_and_top_k(self):
        t = ExecTimer()
        t.observe("minor", 0.001)
        for _ in range(5):
            t.observe("major", 0.1)
        rows = t.snapshot()
        assert [r["executable"] for r in rows] == ["major", "minor"]
        assert [r["executable"] for r in t.snapshot(top_k=1)] == ["major"]
        rep = t.report(top_k=1)
        assert rep["executables"] == 2 and len(rep["top"]) == 1

    def test_publish_emits_labelled_gauges(self, chip_peaks):
        reg = MetricsRegistry()
        t = ExecTimer(reg)
        t.attach_analysis("step", flops=1e9, hbm_bytes=1e6)
        t.observe("step", 0.01)
        t.publish()
        lbl = {"executable": "step"}
        assert reg.value("exec_wall_seconds_total", lbl) == pytest.approx(0.01)
        assert reg.value("exec_calls_total", lbl) == 1.0
        assert 0.0 < reg.value("exec_roofline_utilization", lbl) <= 1.0
        assert reg.value("exec_analytic_disagreement", lbl) > 1.0

    def test_cache_hit_miss_counters(self):
        t = ExecTimer()
        t.cache_miss("embed_b32")
        t.cache_hit("embed_b32")
        t.cache_hit("embed_b32")
        assert t.registry.value(
            "exec_cache_hits_total", {"executable": "embed_b32"}) == 2.0
        assert t.registry.value(
            "exec_cache_misses_total", {"executable": "embed_b32"}) == 1.0

    def test_disabled_timer_is_inert(self):
        t = ExecTimer(enabled=False)
        t.observe("x", 1.0)
        t.cache_hit("x")
        t.attach_analysis("x", flops=1.0, hbm_bytes=1.0)
        assert t.snapshot() == [] and t.analyzed == 0
        assert t.metrics()["perf_observed_total"] == 0.0

    def test_attach_jit_parses_real_hlo(self, chip_peaks):
        import jax
        import jax.numpy as jnp

        fn = jax.jit(lambda a, b: a @ b)
        x = jnp.ones((32, 32), jnp.float32)
        t = ExecTimer()
        assert t.attach_jit("matmul", fn, x, x)
        t.observe("matmul", 1e-3)
        (row,) = t.snapshot()
        assert row["flops"] > 0 and row["bound_s"] > 0
        assert 0.0 < row["roofline_utilization"] <= 1.0
        assert row["compile_s"] > 0  # the AOT lower+compile was timed
        # idempotent: re-attaching the same name is a no-op that reports True
        assert t.attach_jit("matmul", fn, x, x)

    def test_attach_compiled_tolerates_bad_backends(self, chip_peaks):
        class NoText:
            def as_text(self):
                raise RuntimeError("no HLO here")

        t = ExecTimer()
        assert t.attach_compiled("weird", NoText()) is False
        assert t.analyzed == 0

    def test_no_roofline_for_a_device_without_peaks(self):
        import jax
        import jax.numpy as jnp

        from repro.launch.hlo_cost import PEAKS

        assert jax.devices()[0].device_kind not in PEAKS
        reg = MetricsRegistry()
        t = ExecTimer(reg)
        t.attach_analysis("step", flops=1e9, hbm_bytes=1e6, compile_s=0.5)
        fn = jax.jit(lambda a, b: a @ b)
        x = jnp.ones((32, 32), jnp.float32)
        assert t.attach_jit("matmul", fn, x, x) is False
        t.observe("step", 0.01)
        t.observe("matmul", 0.01)
        t.publish()
        assert t.analyzed == 0
        rows = {r["executable"]: r for r in t.snapshot()}
        for name in ("step", "matmul"):
            assert "roofline_utilization" not in rows[name]
            assert rows[name]["compile_s"] > 0  # host-side compile time still lands
        text = reg.exposition()
        assert 'exec_wall_seconds_total{executable="step"}' in text
        assert "exec_roofline_utilization{" not in text
        assert "exec_analytic_disagreement{" not in text


# ---------------------------------------------------------------------------
# DecorrHealthMonitor: the train-side collapse watchdog
# ---------------------------------------------------------------------------


class TestDecorrHealthMonitor:
    def _monitor(self, **kw):
        from repro.obs import DecorrHealthMonitor

        # ema=0 -> every indicator tracks the latest batch exactly, so a
        # synthetic collapse registers on the first observation
        kw.setdefault("ema", 0.0)
        return DecorrHealthMonitor(**kw)

    def test_healthy_stream_reports_unit_variance(self):
        mon = self._monitor()
        rng = np.random.default_rng(0)
        m = mon.observe(rng.standard_normal((64, 16)).astype(np.float32))
        assert m["train_decorr_feat_var_ema"] > 0.5
        assert m["train_decorr_collapsed_frac"] == 0.0
        assert "train_decorr_relaxation_gap" in m  # d=16 affords exact R_off
        assert m["train_decorr_updates"] == 1.0

    def test_collapse_indicators_and_histogram(self):
        reg = MetricsRegistry()
        mon = self._monitor()
        z = np.ones((32, 16), np.float32)  # zero-variance features: collapse
        m = mon.observe(z, registry=reg)
        assert m["train_decorr_feat_var_ema"] < 1e-6
        assert m["train_decorr_collapsed_frac"] == 1.0
        assert m["train_decorr_feat_var_min_ema"] < 1e-6
        h = reg.get("train_feat_var")
        assert h.count == 16  # one sample per feature
        assert reg.value("train_decorr_feat_var_ema") == pytest.approx(
            m["train_decorr_feat_var_ema"], abs=1e-9
        )

    def test_update_embeds_with_params(self):
        mon = self._monitor(embed_fn=lambda params, batch: batch * params)

        class State:
            params = 2.0

        rng = np.random.default_rng(1)
        m = mon.update(State(), rng.standard_normal((16, 8)).astype(np.float32), step=5)
        assert m["train_decorr_step"] == 5.0 and mon.updates == 1
        with pytest.raises(ValueError, match="embed_fn"):
            self._monitor().update(State(), np.ones((4, 4), np.float32))

    def test_variance_collapse_alert_fires_once_and_clears(self):
        """The acceptance scenario: a synthetic variance-collapse training
        stream fires train_variance_collapse exactly once (edge-triggered,
        window=3) and clears on recovery."""
        obs = Obs(alerts=AlertManager(default_train_rules()))
        mon = self._monitor()
        fired = []
        obs.alerts.sink = fired.append
        rule = next(r for r in default_train_rules()
                    if r.name == "train_variance_collapse")
        # constant features: zero variance (collapse) but modest mean, so the
        # mean-drift rule stays quiet and exactly one rule breaches
        collapsed = np.full((32, 16), 0.25, np.float32)
        for _ in range(rule.window + 1):  # extra scrape must NOT refire
            mon.observe(collapsed, registry=obs.registry)
            obs.scrape()
        assert [e["type"] for e in fired] == ["fire"]
        assert fired[0]["alert"] == "train_variance_collapse"
        assert fired[0]["severity"] == "critical"
        assert obs.registry.value(
            "obs_alerts_fired_total", {"rule": "train_variance_collapse"}) == 1.0
        # recovery: healthy unit-variance embeddings clear the alert
        rng = np.random.default_rng(2)
        mon.observe(rng.standard_normal((32, 16)).astype(np.float32),
                    registry=obs.registry)
        obs.scrape()
        assert [e["type"] for e in fired] == ["fire", "clear"]
        assert obs.alerts.active() == []
        # the firing history survives the clear
        assert obs.registry.value(
            "obs_alerts_fired_total", {"rule": "train_variance_collapse"}) == 1.0


# ---------------------------------------------------------------------------
# Train-loop registry integration (no model needed: duck-typed state)
# ---------------------------------------------------------------------------


def test_train_loop_publishes_registry():
    from repro.train.loop import LoopConfig, run_training

    class State:
        step = 0

    def train_step(state, batch):
        state.step += 1
        return state, {"loss": 0.25}

    reg = MetricsRegistry()
    run_training(State(), train_step, lambda step: None,
                 LoopConfig(total_steps=7, log_interval=2), registry=reg)
    assert reg.value("train_steps_total") == 7.0
    assert reg.get("train_step_seconds").count == 7
    assert reg.value("train_loss") == 0.25
    assert reg.value("train_stragglers") == 0.0
    assert reg.value("train_step_seconds_median") > 0.0


def test_train_loop_phase_timing_perf_and_monitor():
    from repro.obs import DecorrHealthMonitor
    from repro.train.loop import LoopConfig, run_training

    class State:
        step = 0

    def train_step(state, batch):
        state.step += 1
        return state, {"loss": 0.5}

    rng = np.random.default_rng(0)

    def batch_fn(step):
        return rng.standard_normal((16, 8)).astype(np.float32)

    reg = MetricsRegistry()
    # embed_fn ignores the duck-typed state and probes the batch directly
    monitor = DecorrHealthMonitor(lambda params, batch: batch, ema=0.0)
    run_training(State(), train_step, batch_fn,
                 LoopConfig(total_steps=6, log_interval=2),
                 registry=reg, monitor=monitor)
    # every step lands in the phase histograms: the loop iteration holds the
    # batch fetch, and the train step is not attributed on its own
    assert reg.get("train_batch_seconds").count == 6
    assert reg.get("train_step_seconds").count == 6
    assert reg.get("train_step_seconds").sum >= reg.get("train_batch_seconds").sum > 0
    assert reg.get("train_publish_seconds").count == 3  # log steps 2, 4, 6
    assert not any(m.name.startswith("exec_") for m in reg.metrics())
    # the health monitor probed at each log interval and published its gauges
    assert monitor.updates == 3
    assert reg.value("train_decorr_updates") == 3.0
    assert reg.value("train_decorr_step") == 6.0
    assert reg.value("train_decorr_feat_var_ema") > 0.5
    assert reg.get("train_feat_var").count == 8 * 3  # d observations per probe


# ---------------------------------------------------------------------------
# Serve integration: one workload, four consistent telemetry views
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gemma():
    import jax

    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config("gemma2-2b").reduced()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


class TestLMServiceObs:
    def _service(self, gemma, obs, **kw):
        from repro.serve import ContinuousLMEngine, LMService

        cfg, params = gemma
        eng = ContinuousLMEngine(
            cfg, params, n_slots=4, max_len=64, max_prompt_len=24,
            paged=True, page_size=16, **kw,
        )
        return LMService(eng, obs=obs)

    def _run(self, svc, cfg, n=6, new_tokens=4, seed=0):
        rng = np.random.default_rng(seed)
        futs = [
            svc.submit(rng.integers(0, cfg.vocab_size, 8).astype(np.int32), new_tokens)
            for _ in range(n)
        ]
        svc.drain()
        for f in futs:
            f.result(timeout=60)
        return futs

    def test_legacy_dict_equals_registry_view(self, gemma):
        obs = Obs()
        svc = self._service(gemma, obs)
        self._run(svc, gemma[0])
        m = svc.metrics()
        # every legacy gauge the PR-5 scrape exported is still present...
        for k in ("queue_depth", "dispatch_errors", "tokens_total", "tok_per_s",
                  "ttft_p50_ms", "ttft_p99_ms", "slots_total", "slots_occupancy",
                  "slots_admitted_total", "slots_retired_total", "latency_p50_ms",
                  "latency_p99_ms", "served_total", "throughput_rps",
                  "heartbeat_stale", "admission_deferred", "paged_pages_in_use",
                  "paged_pages_utilization"):
            assert k in m, f"legacy key {k} vanished from metrics()"
        # ...and the registry mirrors the flat dict, key for key — except the
        # per-name heartbeat ages, which the registry carries as label
        # children of ONE family (heartbeat_age_s{name=}) instead of a
        # family per component
        for k, v in m.items():
            if k.startswith("heartbeat_age_s_"):
                continue
            assert obs.registry.value(k) == pytest.approx(v), k
        assert obs.registry.value("heartbeat_age_s_serve_lm_decode") is None
        hb = svc.heartbeat
        for name in hb._last:
            assert obs.registry.value("heartbeat_age_s", {"name": name}) is not None

    def test_scrape_and_trace_tell_one_story(self, gemma, tmp_path):
        obs = Obs(alerts=AlertManager(default_serve_rules()))
        svc = self._service(gemma, obs)
        futs = self._run(svc, gemma[0])
        text = svc.scrape()
        assert "# TYPE tok_per_s gauge" in text
        assert 'heartbeat_age_s{name="serve.lm_decode"}' in text
        assert "serve_decode_step_seconds_bucket" in text  # step-time histogram
        # the trace reconstructs a full lifecycle: queue -> prefill ->
        # >=1 decode tick -> retire
        path = obs.tracer.write(str(tmp_path / "trace.json"))
        trace = json.loads(open(path).read())
        rec = reconstruct_request(trace, futs[0].trace.rid)
        assert rec["phases"] == ["queue", "prefill", "decode"]
        assert rec["ticks"] >= 1 and rec["retired"]
        # timing unification: the service TTFT gauges come from the same
        # marks the futures carry
        ttfts = sorted(f.trace.ttft_s for f in futs)
        m = svc.metrics()
        assert m["ttft_p50_ms"] == pytest.approx(
            float(np.percentile(np.asarray(ttfts), 50) * 1e3), rel=1e-6
        )
        # flight recorder saw the whole schedule, page churn included
        counts = obs.recorder.counts()
        assert counts["admit"] == len(futs) and counts["retire"] == len(futs)
        assert counts["page_alloc"] >= 1 and counts["page_free"] >= 1

    def test_probe_drift_alert_fires_once_and_clears(self, gemma):
        obs = Obs(alerts=AlertManager(default_serve_rules()))
        svc = self._service(gemma, obs)
        self._run(svc, gemma[0])
        fired = []
        obs.alerts.sink = fired.append
        base = svc.metrics()
        drifted = dict(base, decorr_r_sum_norm_ema=0.9)  # synthetic crossing
        for _ in range(4):  # rule window = 3; extra scrape must NOT refire
            obs.check_alerts(drifted)
        assert [e["type"] for e in fired] == ["fire"]
        assert fired[0]["alert"] == "probe_r_sum_drift"
        obs.check_alerts(dict(base, decorr_r_sum_norm_ema=0.0))
        assert [e["type"] for e in fired] == ["fire", "clear"]
        assert obs.alerts.active() == []

    def test_perf_attribution_joins_serve_executables(self, gemma, chip_peaks):
        obs = Obs()
        svc = self._service(gemma, obs)
        assert svc.engine.perf is obs.perf  # service wires the shared timer
        svc.warmup()
        self._run(svc, gemma[0])
        rows = {r["executable"]: r for r in obs.perf.snapshot()}
        for name in ("decode_step", "prefill_b8"):
            assert rows[name]["calls"] >= 1, name
            assert rows[name]["total_s"] > 0, name
            assert 0.0 < rows[name]["roofline_utilization"] <= 1.0, name
        # warmup's AOT lower+compile was timed, and the 8-token prompts all
        # hit the pre-warmed prefill bucket
        assert rows["prefill_b8"]["compile_s"] > 0
        assert obs.registry.value(
            "exec_cache_hits_total", {"executable": "prefill_b8"}) >= 1.0
        # the scrape path mirrors the same derived values as labelled gauges
        svc.scrape()
        assert obs.registry.value(
            "exec_roofline_utilization", {"executable": "decode_step"}
        ) == pytest.approx(rows["decode_step"]["roofline_utilization"])
        assert obs.registry.value(
            "exec_calls_total", {"executable": "decode_step"}
        ) == float(rows["decode_step"]["calls"])

    def test_disabled_obs_serves_identically(self, gemma):
        on = self._run(self._service(gemma, Obs()), gemma[0], seed=3)
        obs = Obs.disabled()
        svc = self._service(gemma, obs)
        assert svc.engine.perf is None  # hot path keeps its sync profile
        off = self._run(svc, gemma[0], seed=3)
        for a, b in zip(on, off):
            assert np.array_equal(a.result(timeout=5), b.result(timeout=5))
        assert len(obs.tracer) == 0 and len(obs.recorder) == 0
        m = svc.metrics()  # the scrape contract holds with telemetry off
        assert "tok_per_s" in m and m["obs_enabled"] == 0.0


class TestEmbeddingServiceObs:
    def test_metrics_registry_and_trace(self):
        import jax

        from repro.serve import EmbeddingService, ServeEngine
        from repro.train.ssl import SSLModelConfig, init_ssl_params

        model = SSLModelConfig(input_dim=8, backbone_widths=(16,),
                               projector_widths=(16, 16))
        params = init_ssl_params(jax.random.PRNGKey(0), model)
        obs = Obs()
        svc = EmbeddingService(ServeEngine(model, params), obs=obs)
        futs = [svc.submit(np.ones(8, np.float32)) for _ in range(3)]
        while svc.run_pending():
            pass
        for f in futs:
            f.result(timeout=10)
        m = svc.metrics()
        for k in ("queue_depth", "compiled_buckets", "latency_p50_ms",
                  "served_total", "heartbeat_stale"):
            assert k in m and obs.registry.value(k) == pytest.approx(m[k]), k
        rec = reconstruct_request(obs.tracer.to_chrome(), futs[0].trace.rid)
        assert rec["phases"] == ["queue", "dispatch"] and rec["retired"]
        assert obs.recorder.counts()["dispatch"] >= 1
