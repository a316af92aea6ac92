"""monitor/ — trace spans, metrics registry, step-time attribution.

Covers the ISSUE-5 acceptance criteria: a fused-window fit with tracing
enabled produces (a) a Perfetto-loadable chrome trace whose window spans
contain data-wait/dispatch/flush children, (b) {"type": "metrics"} and
{"type": "steptime"} records in StatsStorage, and (c) bit-identical
losses to the same fit with monitoring disabled; plus the tracer
overhead guard, the prometheus text parse check, and the report golden
render of the new sections.
"""
import json
import re
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.monitor import (MetricsRegistry, MonitorListener,
                                        RollingPercentiles,
                                        StragglerWatcher, TRACER,
                                        disable_tracing, enable_tracing,
                                        window_rows)
from deeplearning4j_tpu.ui.stats import StatsStorage


@pytest.fixture(autouse=True)
def _tracing_off_between_tests():
    """Each test opts in explicitly; nothing leaks across tests (the
    capacity reset matters: one test shrinks the shared ring)."""
    disable_tracing()
    TRACER.reset(capacity=65536)
    yield
    disable_tracing()
    TRACER.reset(capacity=65536)


def _build_mlp(fused_steps=4, seed=0):
    from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
    from deeplearning4j_tpu.learning.updaters import Adam

    rng = np.random.default_rng(seed)
    sd = SameDiff()
    x = sd.placeholder("x", shape=(-1, 16))
    w0 = sd.var("w0", value=rng.normal(0, .1, (16, 32)).astype(np.float32))
    h = sd.nn.relu(x.mmul(w0))
    w1 = sd.var("w1", value=rng.normal(0, .1, (32, 4)).astype(np.float32))
    logits = h.mmul(w1)
    labels = sd.placeholder("labels", shape=(-1, 4))
    sd.loss.softmax_cross_entropy(logits, labels, name="loss")
    sd.set_loss_variables(["loss"])
    sd.training_config = TrainingConfig(
        updater=Adam(1e-2), data_set_feature_mapping=["x"],
        data_set_label_mapping=["labels"], fused_steps=fused_steps)
    return sd


def _data(n=128, seed=1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 16)).astype(np.float32)
    Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, n)]
    return X, Y


class TestTracer:
    def test_nested_spans_and_parents(self):
        tr = enable_tracing(reset=True)
        with tr.span("outer", cat="t") as o:
            with tr.span("inner"):
                pass
        spans = tr.spans()
        assert [s.name for s in spans] == ["inner", "outer"]
        inner, outer = spans
        assert inner.parent == outer.sid
        assert outer.parent == 0
        assert inner.t0 >= outer.t0
        assert inner.dur <= outer.dur

    def test_disabled_records_nothing_and_null_span_api(self):
        TRACER.reset()
        assert not TRACER.enabled
        with TRACER.span("x", k=1) as sp:
            sp.set(a=2)
            sp.discard()
        assert TRACER.spans() == []
        assert TRACER.mark() == 0

    def test_discard(self):
        tr = enable_tracing(reset=True)
        with tr.span("kept"):
            pass
        with tr.span("dropped") as sp:
            sp.discard()
        assert [s.name for s in tr.spans()] == ["kept"]

    def test_exception_records_span_with_error(self):
        tr = enable_tracing(reset=True)
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        (sp,) = tr.spans()
        assert sp.args["error"] == "ValueError"

    def test_ring_eviction_and_drain_marks(self):
        tr = enable_tracing(reset=True)
        tr.reset(capacity=8)
        tr.enable()
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        spans, mark, dropped = tr.drain(0)
        assert [s.name for s in spans] == [f"s{i}" for i in range(5)]
        assert dropped == 0
        for i in range(5, 25):
            with tr.span(f"s{i}"):
                pass
        spans, mark2, dropped = tr.drain(mark)
        # 20 new spans, ring holds 8 — the drain reports the eviction
        assert dropped == 12
        assert [s.name for s in spans] == [f"s{i}" for i in range(17, 25)]
        assert tr.drain(mark2) == ([], mark2, 0)

    def test_thread_lanes_are_independent(self):
        tr = enable_tracing(reset=True)

        def worker():
            with tr.span("w_outer"):
                with tr.span("w_inner"):
                    time.sleep(0.002)

        with tr.span("main_outer"):
            t = threading.Thread(target=worker, name="lane2")
            t.start()
            t.join()
        by_name = {s.name: s for s in tr.spans()}
        # the worker's spans must NOT have picked up main_outer as
        # parent (per-thread stacks)
        assert by_name["w_outer"].parent == 0
        assert by_name["w_inner"].parent == by_name["w_outer"].sid
        assert by_name["w_outer"].tid != by_name["main_outer"].tid
        assert by_name["w_outer"].thread_name == "lane2"

    def test_traced_decorator(self):
        tr = enable_tracing(reset=True)

        @tr.traced(cat="test")
        def add(a, b):
            return a + b

        assert add(2, 3) == 5
        (sp,) = tr.spans()
        assert "add" in sp.name and sp.cat == "test"
        tr.disable()
        assert add(1, 1) == 2
        assert len(tr.spans()) == 1


class TestChromeTrace:
    def test_schema_and_monotonic_ts(self):
        tr = enable_tracing(reset=True)
        with tr.span("a", cat="x", k=3):
            with tr.span("b"):
                pass
        with tr.span("c"):
            pass
        doc = tr.to_chrome_trace()
        # must round-trip as plain JSON (Perfetto loads the file as-is)
        doc = json.loads(json.dumps(doc))
        assert "traceEvents" in doc
        events = doc["traceEvents"]
        xs = [e for e in events if e["ph"] == "X"]
        metas = [e for e in events if e["ph"] == "M"]
        assert metas and all(m["name"] == "thread_name" for m in metas)
        assert {e["name"] for e in xs} == {"a", "b", "c"}
        for e in xs:
            for key in ("name", "ph", "ts", "dur", "pid", "tid"):
                assert key in e, key
            assert e["dur"] >= 0 and e["ts"] >= 0
        assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
        a = next(e for e in xs if e["name"] == "a")
        assert a["args"]["k"] == 3 and a["cat"] == "x"

    def test_write_chrome_trace_file(self, tmp_path):
        tr = enable_tracing(reset=True)
        with tr.span("s"):
            pass
        p = tr.write_chrome_trace(str(tmp_path / "trace.json"))
        doc = json.load(open(p, encoding="utf-8"))
        assert any(e["name"] == "s" for e in doc["traceEvents"])


class TestFusedFitTracing:
    """The acceptance-criterion path: fused-window fit, tracing on."""

    def _run(self):
        from deeplearning4j_tpu.dataset.iterators import \
            ArrayDataSetIterator
        X, Y = _data()
        sd = _build_mlp(fused_steps=4)
        st = StatsStorage()
        mon = MonitorListener(st, frequency=10)
        hist = sd.fit(ArrayDataSetIterator(X, Y, batch_size=16),
                      epochs=2, listeners=[mon])
        return sd, st, hist

    def test_window_spans_have_stage_children(self):
        enable_tracing(reset=True)
        self._run()
        doc = TRACER.to_chrome_trace()
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        wins = [e for e in xs if e["name"] == "window"]
        assert len(wins) == 4           # 2 epochs x ceil(8 steps / K=4)

        def children(w):
            return {e["name"] for e in xs
                    if e["tid"] == w["tid"] and e["name"] != "window"
                    and e["ts"] >= w["ts"] - 1e-3
                    and e["ts"] + e["dur"] <= w["ts"] + w["dur"] + 1e-3}
        union = set()
        for w in wins:
            ch = children(w)
            assert {"data_wait", "dispatch"} <= ch, ch
            union |= ch
        # the epoch-crossing cadence flush lands inside its window
        assert "flush" in union
        # the stager's H2D lane exists and is OFF the training thread
        h2d = [e for e in xs if e["name"] == "h2d_stage"]
        assert h2d and all(e["tid"] != wins[0]["tid"] for e in h2d)

    def test_steptime_and_metrics_records(self):
        enable_tracing(reset=True)
        sd, st, _ = self._run()
        stp = [r for r in st.of_type("steptime")
               if r.get("event") != "straggler"]
        assert stp
        total_steps = sum(r["steps"] for r in stp)
        assert total_steps == 16         # 2 epochs x 8 steps, all seen
        for r in stp:
            for key in ("data_wait_s", "dispatch_s", "flush_s", "other_s",
                        "wall_s", "step_ms_p50", "step_ms_p95"):
                assert key in r
            assert r["wall_s"] > 0 and r["dispatch_s"] > 0
        # flush time is attributed (the device sync happens somewhere)
        assert sum(r["flush_s"] for r in stp) > 0
        mets = st.of_type("metrics")
        assert mets
        flat = mets[-1]["metrics"]
        assert flat['dl4j_fit_steps_per_epoch{tier="windowed"}'] == 8
        assert flat["dl4j_steptime_steps_total"] == 16
        # trace dump for the report swimlane
        (tr_rec,) = st.of_type("trace")
        assert tr_rec["spans"] and all(
            set(s) >= {"name", "ts", "dur", "tid", "sid", "parent"}
            for s in tr_rec["spans"])

    def test_losses_bit_identical_monitoring_on_vs_off(self):
        from deeplearning4j_tpu.autodiff import ScoreIterationListener
        from deeplearning4j_tpu.dataset.iterators import \
            ArrayDataSetIterator
        X, Y = _data()
        enable_tracing(reset=True)
        sd1, st1, h1 = self._run()
        disable_tracing()
        sd2 = _build_mlp(fused_steps=4)
        # same listener cadence, no monitoring, no tracing
        silent = ScoreIterationListener(print_every=10 ** 9,
                                        print_fn=lambda *a: None)
        silent.frequency = 10
        h2 = sd2.fit(ArrayDataSetIterator(X, Y, batch_size=16),
                     epochs=2, listeners=[silent])
        np.testing.assert_array_equal(
            np.asarray(h1.loss_curve.losses),
            np.asarray(h2.loss_curve.losses))
        for n in ("w0", "w1"):
            np.testing.assert_array_equal(
                np.asarray(sd1.get_variable(n).get_arr()),
                np.asarray(sd2.get_variable(n).get_arr()))

    def test_per_step_tier_also_attributed(self):
        from deeplearning4j_tpu.dataset.iterators import \
            ArrayDataSetIterator
        X, Y = _data(64)
        sd = _build_mlp(fused_steps=1)
        enable_tracing(reset=True)
        st = StatsStorage()
        sd.fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=1,
               listeners=[MonitorListener(st, frequency=2)])
        names = {s.name for s in TRACER.spans()}
        assert {"step", "data_wait", "dispatch", "flush"} <= names
        stp = [r for r in st.of_type("steptime")
               if r.get("event") != "straggler"]
        assert sum(r["steps"] for r in stp) == 4
        assert sum(r["flush_s"] for r in stp) > 0


class TestTracerOverhead:
    def test_disabled_span_cost_under_one_percent_of_step(self):
        """The always-on guard: the disabled tracer's per-span cost,
        times the spans-per-step the fused listener path emits, must be
        under 1% of the measured fused step time. Computed (not A/B
        timed) so the bound is deterministic on shared CI hardware."""
        from deeplearning4j_tpu.dataset.iterators import \
            ArrayDataSetIterator
        disable_tracing()
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            with TRACER.span("x", cat="c", k=8):
                pass
        per_span_s = (time.perf_counter() - t0) / n
        assert TRACER.spans() == []     # truly recorded nothing
        # fused K=8 listener path: window + data_wait + dispatch +
        # (flush + h2d_stage amortized) ≈ 5 spans per 8 steps
        spans_per_step = 5.0 / 8.0
        X, Y = _data()
        sd = _build_mlp(fused_steps=8)
        it = ArrayDataSetIterator(X, Y, batch_size=16)
        mon = MonitorListener(StatsStorage())
        sd.fit(it, epochs=1, listeners=[mon])          # compile
        t0 = time.perf_counter()
        sd.fit(it, epochs=2, listeners=[mon])
        step_s = (time.perf_counter() - t0) / 16
        overhead = per_span_s * spans_per_step / step_s
        assert overhead < 0.01, (
            f"disabled tracer {1e9 * per_span_s:.0f} ns/span = "
            f"{100 * overhead:.3f}% of a {1e3 * step_s:.3f} ms step")


class TestRegistry:
    def test_counter_gauge_histogram_and_labels(self):
        reg = MetricsRegistry()
        reg.inc("requests_total", 2, help="reqs", mode="batched")
        reg.inc("requests_total", 3, mode="batched")
        reg.inc("requests_total", 1, mode="inplace")
        reg.set_gauge("depth", 7.5)
        reg.observe("latency_seconds", 0.02)
        reg.observe("latency_seconds", 4.0)
        assert reg.get("requests_total", mode="batched") == 5
        assert reg.get("requests_total", mode="inplace") == 1
        assert reg.get("absent") is None
        flat = reg.collect()
        assert flat['dl4j_requests_total{mode="batched"}'] == 5
        assert flat["dl4j_depth"] == 7.5
        assert flat["dl4j_latency_seconds_count"] == 2
        assert flat["dl4j_latency_seconds_sum"] == pytest.approx(4.02)

    def test_counter_cannot_decrease_or_change_kind(self):
        reg = MetricsRegistry()
        reg.inc("a", 1)
        with pytest.raises(ValueError):
            reg.inc("a", -1)
        with pytest.raises(ValueError):
            reg.set_gauge("a", 2)

    def test_prometheus_text_parses(self):
        reg = MetricsRegistry()
        reg.inc("events_total", 3, help='has "quotes" and\nnewline',
                event="rollback")
        reg.set_gauge("up", 1)
        reg.observe("commit_seconds", 0.5, stage="commit")
        text = reg.to_prometheus_text()
        sample_re = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*'               # metric name
            r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
            r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
            r' -?[0-9.eE+-]+(\n|$)')
        seen_types = {}
        for line in text.strip().splitlines():
            if line.startswith("# TYPE"):
                _, _, name, kind = line.split()
                seen_types[name] = kind
                continue
            if line.startswith("# HELP"):
                assert "\n" not in line
                continue
            assert sample_re.match(line), line
        assert seen_types["dl4j_events_total"] == "counter"
        assert seen_types["dl4j_up"] == "gauge"
        assert seen_types["dl4j_commit_seconds"] == "histogram"
        # histogram exposes cumulative le buckets ending at +Inf
        bucket_lines = [l for l in text.splitlines()
                        if l.startswith("dl4j_commit_seconds_bucket")]
        assert bucket_lines and 'le="+Inf"' in bucket_lines[-1]
        counts = [int(l.rsplit(" ", 1)[1]) for l in bucket_lines]
        assert counts == sorted(counts)          # cumulative
        assert counts[-1] == 1

    def test_fold_serving_and_checkpoint_and_faults(self):
        from deeplearning4j_tpu.serving.metrics import ServingMetrics
        sm = ServingMetrics()
        sm.inc("requests_submitted", 4)
        sm.observe_batch(rows=6, padding=2, exec_ms=1.5)
        sm.observe_request(queue_wait_ms=0.3, e2e_ms=2.0)
        sm.record_failure(RuntimeError("x"))
        reg = MetricsRegistry()
        reg.fold_serving(sm)
        assert reg.get("serving_requests_submitted_total") == 4
        assert reg.get("serving_failures_by_cause_total",
                       cause="RuntimeError") == 1
        assert reg.get("serving_latency_ms", lane="e2e", stat="p50") > 0
        reg.fold_checkpoint({"type": "checkpoint", "step": 3, "bytes": 100,
                             "serialize_seconds": 0.1,
                             "commit_seconds": 0.2, "queue_seconds": 0.0})
        assert reg.get("checkpoint_commits_total") == 1
        assert reg.get("checkpoint_last_step") == 3
        reg.fold_faults([{"event": "rollback", "overhead_s": 0.4},
                         {"event": "retry"}])
        assert reg.get("faults_events_total", event="rollback") == 1
        text = reg.to_prometheus_text()
        assert "dl4j_serving_requests_submitted_total 4" in text

    def test_publish_record(self):
        st = StatsStorage()
        reg = MetricsRegistry()
        reg.inc("n", 1)
        rec = reg.publish(st)
        assert rec["type"] == "metrics"
        assert st.of_type("metrics")[0]["metrics"]["dl4j_n"] == 1

    def test_fold_storage_is_incremental_per_storage(self):
        """Review fix: re-folding a growing storage (the scrape-endpoint
        pattern) must not double-count counter-typed metrics."""
        st = StatsStorage()
        st.put({"type": "checkpoint", "step": 1, "bytes": 10,
                "commit_seconds": 0.1})
        st.put({"type": "faults", "event": "rollback", "overhead_s": 0.2})
        reg = MetricsRegistry()
        reg.fold_storage(st)
        reg.fold_storage(st)                     # same records again
        assert reg.get("checkpoint_commits_total") == 1
        assert reg.get("faults_events_total", event="rollback") == 1
        st.put({"type": "checkpoint", "step": 2, "bytes": 10,
                "commit_seconds": 0.1})
        reg.fold_storage(st)                     # only the new record
        assert reg.get("checkpoint_commits_total") == 2
        assert reg.get("checkpoint_last_step") == 2


class TestStepTime:
    def test_window_rows_groups_children(self):
        tr = enable_tracing(reset=True)
        with tr.span("window", k=4, iteration=0):
            with tr.span("data_wait"):
                pass
            with tr.span("dispatch"):
                pass
            with tr.span("flush"):
                pass
        with tr.span("window", k=2, iteration=4):
            with tr.span("dispatch"):
                pass
        rows = window_rows(tr.spans())
        assert [r["k"] for r in rows] == [4, 2]
        assert rows[0]["flush_s"] > 0 and rows[1]["flush_s"] == 0
        assert all(r["other_s"] >= 0 for r in rows)

    def test_rolling_percentiles(self):
        rp = RollingPercentiles(window=4)
        for v in (1.0, 2.0, 3.0, 4.0):
            rp.add(v)
        assert rp.percentile(0) == 1.0 and rp.percentile(100) == 4.0
        rp.add(100.0)                   # evicts 1.0
        assert rp.percentile(100) == 100.0
        assert rp.percentile(0) == 2.0
        assert len(rp) == 4

    def test_straggler_watcher_flags_spike_and_resets(self):
        st = StatsStorage()
        w = StragglerWatcher(threshold=3.0, alpha=0.5, warmup=3,
                             storage=st)
        for _ in range(6):
            assert w.observe(0.1) is None
        ev = w.observe(1.0, iteration=7, k=4)
        assert ev is not None and ev["ratio"] > 3
        assert st.of_type("steptime")[0]["event"] == "straggler"
        # the spike did not feed the EMA: a same-size spike still flags
        assert w.observe(1.0) is not None
        w.reset()
        assert w.observe(1.0) is None   # warmup restarts

    def test_straggler_threshold_validation(self):
        with pytest.raises(ValueError):
            StragglerWatcher(threshold=1.0)

    def test_flush_carrying_window_not_flagged_as_straggler(self):
        """Review fix: the flush child is a burst sync amortized over
        the whole cadence — the window that happens to carry it must
        not read as a step-time spike.

        Margins are sized for scheduler jitter on a loaded CI host
        (sleeps stretch): the base window sleeps 4 ms so a 1-2 ms
        hiccup stays well under the 6x threshold, while folding the
        80 ms flush in would read as ~5x the whole window — far past
        it — so the regression still trips the assert."""
        tr = enable_tracing(reset=True)
        st = StatsStorage()
        mon = MonitorListener(st, tracer=tr,
                              straggler=StragglerWatcher(
                                  threshold=6.0, warmup=2))
        mon.on_training_start(None)
        it = 0
        for burst in range(6):
            for w in range(4):
                with tr.span("window", k=4, iteration=it):
                    with tr.span("dispatch"):
                        time.sleep(0.004)
                    if w == 3:               # the cadence-crossing window
                        with tr.span("flush"):
                            time.sleep(0.08)  # 20x the dispatch time
                it += 4
            mon.iterations_done(None, 0, list(range(it - 16, it)), [0.0])
        assert mon.straggler.events == [], mon.straggler.events


class TestServingCheckpointSpans:
    def test_serving_lifecycle_spans(self):
        from deeplearning4j_tpu.learning.updaters import Adam
        from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           OutputLayer)
        from deeplearning4j_tpu.serving import (InferenceMode,
                                                ParallelInference)
        rng = np.random.default_rng(0)
        conf = (NeuralNetConfiguration.builder().seed(0)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=4, loss_function="MCXENT"))
                .set_input_type(InputType.feed_forward(8))
                .build())
        net = MultiLayerNetwork(conf).init()
        enable_tracing(reset=True)
        pi = ParallelInference(net, mode=InferenceMode.BATCHED,
                               max_batch_size=8, max_delay_ms=1.0)
        x = rng.normal(size=(4, 8)).astype(np.float32)
        y = pi.output(x)
        pi.shutdown()
        assert y.shape == (4, 4)
        names = {s.name for s in TRACER.spans()}
        assert {"serving.enqueue", "serving.batch", "serving.pad",
                "serving.exec", "serving.reply"} <= names
        # idle polls were discarded, not recorded
        batches = [s for s in TRACER.spans()
                   if s.name == "serving.batch"]
        assert all(s.args.get("requests") for s in batches)

    def test_checkpoint_commit_spans(self, tmp_path):
        from deeplearning4j_tpu.checkpoint import CheckpointManager
        sd = _build_mlp()
        enable_tracing(reset=True)
        with CheckpointManager(str(tmp_path), async_write=False) as mgr:
            mgr.save(0, model=sd, blocking=True)
        by_name = {}
        for s in TRACER.spans():
            by_name.setdefault(s.name, []).append(s)
        assert "checkpoint.capture" in by_name
        (commit,) = by_name["checkpoint.commit"]
        (serialize,) = by_name["checkpoint.serialize"]
        assert serialize.parent == commit.sid
        assert commit.args["step"] == 0
        assert commit.args["asynchronous"] is False


class TestReportRendering:
    def test_report_renders_observability_sections(self):
        """Golden render: timeline + breakdown + stragglers + metrics
        sections appear, and unknown record types land in the footer."""
        from deeplearning4j_tpu.ui.report import render_report
        from deeplearning4j_tpu.dataset.iterators import \
            ArrayDataSetIterator
        X, Y = _data()
        sd = _build_mlp(fused_steps=4)
        enable_tracing(reset=True)
        st = StatsStorage()
        sd.fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=2,
               listeners=[MonitorListener(st, frequency=10)])
        st.put({"type": "steptime", "event": "straggler", "iteration": 3,
                "step_s": 0.5, "ema_s": 0.1, "ratio": 5.0, "t": 0.0})
        st.put({"type": "from_the_future", "payload": 1})
        html = render_report(st, title="monitored run")
        assert "Step-time breakdown" in html
        assert "Span timeline" in html
        assert "Stragglers (1)" in html
        assert "Metrics (last snapshot" in html
        assert "unrendered record types: from_the_future (1)" in html
        # the stacked chart legend names every stage
        for stage in ("data wait", "dispatch", "flush", "other"):
            assert stage in html
        # known observability types are NOT in the footer
        assert "steptime (" not in html and "metrics (" not in html

    def test_report_without_observability_records_unchanged(self):
        from deeplearning4j_tpu.ui.report import render_report
        html = render_report(StatsStorage())
        assert "Step-time breakdown" not in html
        assert "unrendered record types" not in html


class TestProcessSelfTelemetry:
    def test_uptime_and_rss_in_exposition(self):
        reg = MetricsRegistry()
        text = reg.to_prometheus_text()
        m = re.search(r"^dl4j_process_uptime_seconds (\S+)$", text,
                      re.MULTILINE)
        assert m and float(m.group(1)) > 0
        assert "# TYPE dl4j_process_uptime_seconds gauge" in text
        # Linux exposes RSS via /proc; the series is optional elsewhere
        m = re.search(r"^dl4j_process_rss_bytes (\S+)$", text,
                      re.MULTILINE)
        if m is not None:
            assert float(m.group(1)) > 1 << 20
        # synthesized at scrape time, never stored as registry state
        assert reg.get("process_uptime_seconds") is None

    def test_uptime_monotonic_across_scrapes(self):
        reg = MetricsRegistry()

        def uptime():
            text = reg.to_prometheus_text()
            return float(re.search(
                r"^dl4j_process_uptime_seconds (\S+)$", text,
                re.MULTILINE).group(1))

        a = uptime()
        time.sleep(0.01)
        assert uptime() >= a


class TestHistogramInvariants:
    def test_inf_bucket_count_equals_count_for_every_histogram(self):
        """Satellite: for EVERY exported histogram the +Inf bucket's
        cumulative count equals its _count sample — the invariant
        Prometheus clients assume; a drift means observations leaked
        past the bucket ladder."""
        reg = MetricsRegistry()
        # several histogram families with different bucket ladders,
        # labels, and out-of-range observations
        for v in (1e-6, 0.02, 3.0, 500.0, 1e9):
            reg.observe("latency_seconds", v, lane="a")
            reg.observe("latency_seconds", v * 2, lane="b")
        reg.observe("ratio_dist", 1e-12, buckets=(0.1, 1.0))
        reg.observe("ratio_dist", 5.0, buckets=(0.1, 1.0))
        reg.inc("noise_total", 3)
        text = reg.to_prometheus_text()
        # parse every histogram series: {base{labels}: {le: cum}}
        bucket_re = re.compile(
            r'^(\w+)_bucket\{(.*?)le="([^"]+)"\} (\d+)$')
        count_re = re.compile(r"^(\w+)_count(\{.*\})? (\d+)$")
        buckets, counts = {}, {}
        for line in text.splitlines():
            mb = bucket_re.match(line)
            if mb:
                key = (mb.group(1), mb.group(2))
                buckets.setdefault(key, {})[mb.group(3)] = \
                    int(mb.group(4))
            mc = count_re.match(line)
            if mc:
                counts[(mc.group(1),
                        (mc.group(2) or "{}").strip("{}").rstrip(","))] \
                    = int(mc.group(3))
        assert buckets, "no histograms exported"
        for (name, labels), series in buckets.items():
            assert "+Inf" in series, (name, labels)
            ckey = (name, labels.rstrip(","))
            assert ckey in counts, (name, labels, sorted(counts))
            assert series["+Inf"] == counts[ckey], (name, labels)
            # cumulative le semantics: monotone nondecreasing
            ordered = [series[k] for k in series if k != "+Inf"]
            assert all(a <= b for a, b in zip(ordered, ordered[1:]))


# The PR-8 record-type lint moved to tests/test_static_lint.py (ISSUE
# 12 satellite), where it grew bare-except and traced-path-RNG lints
# alongside it.
