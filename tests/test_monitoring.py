"""Tier-1 (CPU) tests for the unified telemetry subsystem (monitoring/).

Covers: registry thread-safety, Prometheus text exposition, span
nesting/exception paths, the jit-recompile watcher across a forced
retrace, the /metrics route on UIServer, flat phase sequences, and the
no-new-retraces guard for the instrumented fit path.
"""

import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.monitoring import runtime, tracing
from deeplearning4j_tpu.monitoring.exporters import (
    JsonlSink, metrics_snapshot, render_prometheus)
from deeplearning4j_tpu.monitoring.listener import MetricsListener
from deeplearning4j_tpu.monitoring.metrics import MetricsRegistry
from deeplearning4j_tpu.monitoring.tracing import span, span_histogram


def make_net(seed=1):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.Builder().seed(seed).list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss="categorical_crossentropy"))
            .build())
    return MultiLayerNetwork(conf).init()


def make_data(n=64):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


def compile_total():
    monitoring.ensure_started()
    c = monitoring.global_registry().get(runtime.COMPILE_COUNTER)
    return 0.0 if c is None else c.total()


class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        r = MetricsRegistry()
        c = r.counter("c_total", "help", ("k",))
        c.inc(k="a")
        c.inc(2.5, k="b")
        assert c.value(k="a") == 1.0
        assert c.value(k="b") == 2.5
        assert c.total() == 3.5
        with pytest.raises(ValueError):
            c.inc(-1, k="a")
        g = r.gauge("g")
        g.set(4.0)
        g.inc()
        assert g.value() == 5.0
        g.set_function(lambda: 42.0)
        assert g.value() == 42.0
        h = r.histogram("h", buckets=(1.0, 10.0))
        h.observe(0.5)
        h.observe(5.0)
        h.observe(50.0)
        assert h.count() == 3
        assert h.sum() == 55.5

    def test_a_counter_can_read_its_owners_total_at_scrape_time(self):
        """One store: the owner keeps the count, the registry reads it
        when collected (value, total, snapshot, exposition)."""
        r = MetricsRegistry()
        owned = {"n": 7}
        c = r.counter("owned_total", "help", ("k",))
        c.set_function(lambda: owned["n"], k="a")
        c.inc(2, k="b")
        assert c.value(k="a") == 7.0 and c.total() == 9.0
        owned["n"] = 11
        assert c.value(k="a") == 11.0
        with pytest.raises(ValueError):
            c.inc(k="a")                      # read-only
        samples = r.snapshot()["owned_total"]["samples"]
        assert {s["labels"]["k"]: s["value"] for s in samples} == \
            {"a": 11.0, "b": 2.0}
        assert 'owned_total{k="a"} 11' in render_prometheus(r)

    def test_get_or_create_is_idempotent_and_type_checked(self):
        r = MetricsRegistry()
        c1 = r.counter("x_total", "h", ("a",))
        assert r.counter("x_total", "h", ("a",)) is c1
        with pytest.raises(ValueError):
            r.gauge("x_total")
        with pytest.raises(ValueError):
            r.counter("x_total", "h", ("b",))
        with pytest.raises(ValueError):
            c1.inc(wrong="label")

    def test_thread_safety_under_concurrent_increments(self):
        r = MetricsRegistry()
        c = r.counter("n_total", "", ("t",))
        h = r.histogram("lat", buckets=(0.5,))
        n_threads, per_thread = 8, 2000

        def worker(i):
            for _ in range(per_thread):
                c.inc(t=str(i % 2))
                h.observe(0.25)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.total() == n_threads * per_thread
        assert h.count() == n_threads * per_thread

    def test_snapshot_compact(self):
        r = MetricsRegistry()
        r.counter("a_total", "", ("x",)).inc(3, x="v")
        r.histogram("h").observe(2.0)
        snap = r.snapshot_compact()
        assert snap["a_total{x=v}"] == 3.0
        assert snap["h"]["count"] == 1

    def test_histogram_bucket_mismatch_raises(self):
        r = MetricsRegistry()
        r.histogram("h", buckets=(0.1, 1.0))
        with pytest.raises(ValueError, match="buckets"):
            r.histogram("h", buckets=(0.5, 2.0))


_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? \S+$")


class TestPrometheusExposition:
    def test_format_and_cumulative_buckets(self):
        r = MetricsRegistry()
        r.counter("req_total", "requests", ("code",)).inc(5, code="200")
        h = r.histogram("lat_seconds", "latency", ("route",),
                        buckets=(0.1, 1.0))
        h.observe(0.05, route="/a")
        h.observe(0.5, route="/a")
        h.observe(5.0, route="/a")
        text = render_prometheus(r, refresh_runtime=False)
        lines = text.strip().splitlines()
        for ln in lines:
            if not ln.startswith("#"):
                assert _SAMPLE_RE.match(ln), ln
        assert "# TYPE req_total counter" in lines
        assert 'req_total{code="200"} 5.0' in lines
        assert '# TYPE lat_seconds histogram' in lines
        assert 'lat_seconds_bucket{route="/a",le="0.1"} 1' in lines
        assert 'lat_seconds_bucket{route="/a",le="1.0"} 2' in lines
        assert 'lat_seconds_bucket{route="/a",le="+Inf"} 3' in lines
        assert 'lat_seconds_count{route="/a"} 3' in lines

    def test_label_escaping(self):
        r = MetricsRegistry()
        r.counter("e_total", "", ("v",)).inc(v='say "hi"\nback\\slash')
        text = render_prometheus(r, refresh_runtime=False)
        assert r'v="say \"hi\"\nback\\slash"' in text

    def test_declared_but_unobserved_series_render(self):
        r = MetricsRegistry()
        r.histogram("empty_h", "", ("span",)).labels(span="forward")
        text = render_prometheus(r, refresh_runtime=False)
        assert 'empty_h_count{span="forward"} 0' in text


class TestSpans:
    def test_nesting_paths_and_recording(self):
        r = MetricsRegistry()
        with span("outer", registry=r):
            with span("inner", registry=r):
                assert tracing.current_path().endswith("outer/inner")
        h = r.get(tracing.SPAN_HISTOGRAM)
        assert h.count(span="outer") == 1
        assert h.count(span="inner") == 1

    def test_exception_path_records_and_pops(self):
        r = MetricsRegistry()
        depth_before = tracing.current_path()
        with pytest.raises(RuntimeError):
            with span("failing", registry=r):
                raise RuntimeError("boom")
        assert tracing.current_path() == depth_before  # stack popped
        assert r.get(tracing.SPAN_HISTOGRAM).count(span="failing") == 1
        assert r.get(tracing.SPAN_ERRORS).value(span="failing") == 1

    def test_disabled_spans_are_noops(self):
        r = MetricsRegistry()
        tracing.set_enabled(False)
        try:
            with span("off", registry=r):
                pass
        finally:
            tracing.set_enabled(True)
        assert r.get(tracing.SPAN_HISTOGRAM) is None

    def test_training_stats_flow_into_registry(self):
        from deeplearning4j_tpu.parallel.stats import TrainingStats
        r = MetricsRegistry()
        ts = TrainingStats(registry=r)
        with ts.time_phase("etl"):
            pass
        assert ts.summary()["etl"]["count"] == 1
        assert r.get(tracing.SPAN_HISTOGRAM).count(span="etl") == 1


class TestRecompileWatcher:
    def test_counts_forced_retrace_per_function_name(self):
        import jax
        import jax.numpy as jnp
        monitoring.ensure_started()

        def _monitoring_retrace_probe(a):
            return a * 2

        f = jax.jit(_monitoring_retrace_probe)
        c = monitoring.global_registry().get(runtime.COMPILE_COUNTER)
        before = c.value(fn="_monitoring_retrace_probe")
        f(jnp.ones(3))
        f(jnp.ones(5))   # forced retrace: new shape
        f(jnp.ones(3))   # cache hit: no compile
        after = c.value(fn="_monitoring_retrace_probe")
        assert after - before == 2

    def test_compile_durations_histogram_exists(self):
        monitoring.ensure_started()
        h = monitoring.global_registry().get(runtime.COMPILE_SECONDS)
        assert h is not None and h.kind == "histogram"


class TestFitTelemetry:
    def test_fit_populates_spans_score_and_throughput(self):
        net = make_net()
        x, y = make_data()
        h = span_histogram()
        etl0, step0 = h.count(span="etl"), h.count(span="step")
        net.fit(x, y, epochs=1, batch_size=16)
        assert h.count(span="etl") - etl0 == 4
        assert h.count(span="step") - step0 == 4
        r = monitoring.global_registry()
        assert r.get("dl4jtpu_score").value(
            model="MultiLayerNetwork") == pytest.approx(net.score_value)
        assert r.get("dl4jtpu_samples_per_sec").value(
            model="MultiLayerNetwork") > 0
        assert r.get("dl4jtpu_batches_per_sec").value(
            model="MultiLayerNetwork") > 0

    def test_metrics_listener_owns_publishing_no_double_count(self):
        reg = MetricsRegistry()
        net = make_net()
        net.set_listeners(MetricsListener(registry=reg))
        x, y = make_data()
        g_iter = monitoring.global_registry().get("dl4jtpu_iterations_total")
        before = g_iter.value(model="MultiLayerNetwork")
        net.fit(x, y, epochs=1, batch_size=16)
        # explicit listener → custom registry gets the 4 iterations,
        # the global auto-hook stands down
        assert reg.get("dl4jtpu_iterations_total").value(
            model="MultiLayerNetwork") == 4
        assert reg.get("dl4jtpu_examples_total").value(
            model="MultiLayerNetwork") == 64
        assert g_iter.value(model="MultiLayerNetwork") == before

    def test_computation_graph_fit_records_spans(self):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        conf = (NeuralNetConfiguration.Builder().seed(3).graph_builder()
                .add_inputs("in")
                .set_input_types(InputType.feed_forward(4))
                .add_layer("d", DenseLayer(n_in=4, n_out=8), "in")
                .add_layer("out", OutputLayer(
                    n_in=8, n_out=3, activation="softmax",
                    loss="categorical_crossentropy"), "d")
                .set_outputs("out").build())
        g = ComputationGraph(conf).init()
        x, y = make_data(32)
        h = span_histogram()
        step0 = h.count(span="step")
        g.fit(x, y, epochs=1, batch_size=16)
        assert h.count(span="step") - step0 == 2
        assert monitoring.global_registry().get("dl4jtpu_score").value(
            model="ComputationGraph") == pytest.approx(g.score_value)


class TestNoRetraceGuard:
    """Observability must not cost recompiles: the instrumented fit path
    (spans on, default) compiles exactly what the uninstrumented path
    (spans off) compiles, and steady-state iterations compile nothing."""

    def _fit_compiles(self, enabled):
        net = make_net()
        x, y = make_data()
        tracing.set_enabled(enabled)
        try:
            before = compile_total()
            net.fit(x, y, epochs=1, batch_size=16)
            mid = compile_total()
            net.fit(x, y, epochs=2, batch_size=16)
            after = compile_total()
        finally:
            tracing.set_enabled(True)
        return mid - before, after - mid

    def test_instrumented_fit_adds_no_retraces(self):
        first_on, steady_on = self._fit_compiles(True)
        first_off, steady_off = self._fit_compiles(False)
        assert steady_on == 0, "instrumented steady-state fit recompiled"
        assert steady_off == 0
        assert first_on == first_off, (
            f"span instrumentation changed compile count: "
            f"{first_on} vs {first_off}")


class TestMetricsRoute:
    def test_ui_server_serves_prometheus_exposition(self):
        from deeplearning4j_tpu.ui.server import UIServer
        net = make_net()
        x, y = make_data()
        net.fit(x, y, epochs=1, batch_size=16)
        server = UIServer(port=0)
        try:
            req = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics", timeout=10)
            assert req.status == 200
            assert req.headers["Content-Type"].startswith("text/plain")
            text = req.read().decode()
        finally:
            server.stop()
        # the fit loops' declared span histograms
        for phase in ("etl", "step", "listener"):
            assert f'dl4jtpu_span_seconds_bucket{{span="{phase}"' in text
        assert "dl4jtpu_score{" in text
        assert "dl4jtpu_samples_per_sec{" in text
        assert "dl4jtpu_host_rss_mb" in text
        assert "dl4jtpu_jit_compiles_total{" in text
        for ln in text.strip().splitlines():
            if not ln.startswith("#"):
                assert _SAMPLE_RE.match(ln), ln


class TestExporters:
    def test_jsonl_sink_appends_parseable_lines(self, tmp_path):
        r = MetricsRegistry()
        r.counter("j_total").inc(2)
        path = str(tmp_path / "metrics.jsonl")
        sink = JsonlSink(path, registry=r)
        sink.write_snapshot()
        sink.write_snapshot(extra={"round": 1})
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 2
        assert lines[0]["metrics"]["j_total"] == 2.0
        assert lines[1]["round"] == 1

    @pytest.mark.parametrize("refresh_timeout", [5.0, 0.5])
    def test_global_metrics_snapshot_is_json_serializable(
            self, refresh_timeout):
        monitoring.ensure_started()
        snap = metrics_snapshot(refresh_timeout=refresh_timeout)
        assert isinstance(snap, dict)
        json.dumps(snap)  # must round-trip into a run's record


class TestSatelliteListenerFixes:
    def test_time_iteration_listener_starts_lazily(self, monkeypatch):
        import time as time_mod
        from deeplearning4j_tpu.optimize.listeners import \
            TimeIterationListener
        now = [1000.0]
        monkeypatch.setattr(time_mod, "perf_counter", lambda: now[0])
        lst = TimeIterationListener(total_iterations=100)
        assert lst.start is None  # clock NOT started at construction
        now[0] += 3600.0          # setup delay that must not skew the ETA
        msgs = []
        monkeypatch.setattr(
            "deeplearning4j_tpu.optimize.listeners.log",
            type("L", (), {"info": lambda self, fmt, *a: msgs.append(
                fmt % a)})())
        lst.iteration_done(None, 0, 0.0)   # first call: starts the clock
        assert lst.start == now[0] and not msgs
        now[0] += 10.0
        lst.iteration_done(None, 10, 0.0)  # 10 iters in 10s -> 90s left
        assert msgs and "90.0s" in msgs[-1]

    def test_profiler_close_is_idempotent(self, tmp_path):
        from deeplearning4j_tpu.optimize.profiler import ProfilerListener
        p = ProfilerListener(str(tmp_path), start_iteration=0,
                             num_iterations=100)
        p.iteration_done(None, 0, 0.0)  # opens the trace
        assert p._active
        p.close()
        assert not p._active and p._done
        p.close()  # repeated close: no-op, no raise
        p.iteration_done(None, 1, 0.0)  # done: never reopens
        assert not p._active

    def test_fit_finally_closes_open_trace(self, tmp_path):
        from deeplearning4j_tpu.optimize.listeners import TrainingListener
        from deeplearning4j_tpu.optimize.profiler import ProfilerListener

        class Boom(TrainingListener):
            def iteration_done(self, model, iteration, score):
                raise RuntimeError("boom")

        net = make_net()
        prof = ProfilerListener(str(tmp_path), start_iteration=0,
                                num_iterations=100)
        net.set_listeners(prof, Boom())
        x, y = make_data(16)
        with pytest.raises(RuntimeError):
            net.fit(x, y, epochs=1, batch_size=16)
        # the fit loop's finally must have closed the leaked trace
        assert not prof._active and prof._done
