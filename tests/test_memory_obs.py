"""HBM memory observability (memory.py + monitor/memstats.py).

Covers: snapshot/watermark on the CPU live-array fallback, the
AllocationsTracker satellites (lock, clamp, counts, H2D/D2H wiring),
``{"type": "memory"}`` records at listener flush boundaries, compiled-
program memory plans (precompile + lazy promotion) and the live MFU
gauge, the /memory route, OOM forensics end-to-end via a chaos-injected
``RESOURCE_EXHAUSTED``, headroom-refused reload/warmup, and the
bit-identity of memory telemetry on vs off.
"""
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import memory
from deeplearning4j_tpu.autodiff import (SameDiff, ScoreIterationListener,
                                         TrainingConfig)
from deeplearning4j_tpu.checkpoint import CheckpointManager
from deeplearning4j_tpu.dataset.iterators import ArrayDataSetIterator
from deeplearning4j_tpu.faults import ChaosMonkey, FaultTolerantFit, \
    RetryPolicy
from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.memory import (AllocationsTracker,
                                       MemoryExhaustedError,
                                       MemoryHeadroomError)
from deeplearning4j_tpu.monitor import (MetricsRegistry, MonitorListener,
                                        memstats)
from deeplearning4j_tpu.monitor.server import health_snapshot
from deeplearning4j_tpu.ui.report import render_report
from deeplearning4j_tpu.ui.stats import StatsStorage


@pytest.fixture(autouse=True)
def _clean_memstats():
    """Plan capture and the tracker are process-global: every test
    starts from the off/empty state and leaves it that way."""
    memstats.disable_plan_capture()
    memstats.PLANS.reset()
    AllocationsTracker.get_instance().reset()
    yield
    memstats.disable_plan_capture()
    memstats.PLANS.reset()
    AllocationsTracker.get_instance().reset()


def _mlp(fused_steps=4, sentinel=False, seed=0):
    rng = np.random.default_rng(seed)
    sd = SameDiff()
    x = sd.placeholder("x", shape=(-1, 8))
    w0 = sd.var("w0", value=rng.normal(0, .1, (8, 16)).astype(np.float32))
    b0 = sd.var("b0", value=np.zeros(16, np.float32))
    h = sd.nn.relu(x.mmul(w0).add(b0))
    w1 = sd.var("w1", value=rng.normal(0, .1, (16, 2)).astype(np.float32))
    logits = h.mmul(w1)
    labels = sd.placeholder("labels", shape=(-1, 2))
    sd.loss.softmax_cross_entropy(logits, labels, name="loss")
    sd.set_loss_variables(["loss"])
    sd.training_config = TrainingConfig(
        updater=Adam(1e-2), data_set_feature_mapping=["x"],
        data_set_label_mapping=["labels"], fused_steps=fused_steps,
        sentinel=sentinel)
    return sd


def _it(batch=8, n=64, seed=1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    Y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
    return ArrayDataSetIterator(X, Y, batch_size=batch)


def _quiet():
    return ScoreIterationListener(print_every=10 ** 9,
                                  print_fn=lambda *a: None)


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode("utf-8")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8")


# ---------------------------------------------------------------------------
# snapshot / watermark / census (CPU fallback path)

class TestSnapshotWatermark:
    def test_snapshot_total(self):
        import jax.numpy as jnp
        big = jnp.ones((256, 1024), jnp.float32)  # 1 MiB resident
        big.block_until_ready()
        states = memory.snapshot()
        assert states and all(s.source in ("pjrt", "live_arrays")
                              for s in states)
        assert memory.total_bytes_in_use() >= big.nbytes
        del big

    def test_watermark_reports_per_device_peaks(self):
        import jax.numpy as jnp
        with memory.MemoryWatermark() as wm:
            a = jnp.ones((128, 1024), jnp.float32)
            a.block_until_ready()
        rep = wm.report()
        # one "peak ... delta" line per device, not just the max
        for s in wm.after:
            assert s.device in rep
        assert "peak" in rep and "delta" in rep
        assert wm.peak_bytes > 0
        del a

    def test_live_census_top_sorted(self):
        import jax.numpy as jnp
        a = jnp.ones((64, 1024), jnp.float32)
        a.block_until_ready()
        census = memory.live_census(top_n=5)
        assert census["arrays"] >= 1
        assert census["total_bytes"] >= a.nbytes
        tops = [r["nbytes"] for r in census["top"]]
        assert tops == sorted(tops, reverse=True)
        del a

    def test_fallback_counts_unsizable_arrays(self, monkeypatch):
        """Satellite: a deleted array and a donated array (shard read
        raises) are SKIPPED AND COUNTED — the fallback total can no
        longer silently undercount."""
        class _Deleted:
            def is_deleted(self):
                return True

        class _Donated:
            def is_deleted(self):
                return False

            @property
            def addressable_shards(self):
                raise RuntimeError("Array has been deleted.")

        class _Shard:
            def __init__(self):
                self.device = "FakeDevice(0)"

                class _D:
                    nbytes = 128
                self.data = _D()

        class _Live:
            def is_deleted(self):
                return False

            @property
            def addressable_shards(self):
                return [_Shard()]

        import jax
        monkeypatch.setattr(jax, "live_arrays",
                            lambda: [_Deleted(), _Donated(), _Live()])
        by_dev, skipped = memory._live_array_bytes_by_device()
        assert skipped == 2
        assert by_dev == {"FakeDevice(0)": 128}


# ---------------------------------------------------------------------------
# AllocationsTracker satellites

class TestAllocationsTracker:
    def test_release_clamps_at_zero(self):
        t = AllocationsTracker.get_instance()
        t.allocate("tag", 100)
        t.release("tag", 500)
        assert t.bytes_tracked("tag") == 0
        t.allocate("tag", 40)
        assert t.bytes_tracked("tag") == 40  # not 40 - 400

    def test_counts(self):
        t = AllocationsTracker.get_instance()
        t.allocate("a", 10)
        t.allocate("a", 10)
        t.allocate("b", 1)
        assert t.counts() == {"a": 2, "b": 1}

    def test_thread_safety(self):
        t = AllocationsTracker.get_instance()

        def hammer():
            for _ in range(1000):
                t.allocate("hot", 1)
                t.release("cold", 1)

        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert t.bytes_tracked("hot") == 8000
        assert t.counts()["hot"] == 8000
        assert t.bytes_tracked("cold") == 0

    def test_checkpoint_capture_tags_d2h_bytes(self):
        from deeplearning4j_tpu.checkpoint.state import \
            capture_training_state
        sd = _mlp()
        state = capture_training_state(sd)
        tracked = AllocationsTracker.get_instance().bytes_tracked(
            "checkpoint_d2h")
        assert tracked >= sum(a.nbytes for a in state.arrays.values())
        assert AllocationsTracker.get_instance().counts()[
            "checkpoint_d2h"] == 1

    def test_window_stager_tags_h2d_bytes(self):
        sd = _mlp(fused_steps=4)
        sd.fit(_it(), epochs=1, listeners=[_quiet()])
        t = AllocationsTracker.get_instance()
        # 64 rows x (8 feat + 2 label) x 4 bytes staged host-side
        assert t.bytes_tracked("h2d_stage") >= 64 * 10 * 4
        assert t.counts()["h2d_stage"] >= 1


# ---------------------------------------------------------------------------
# memory records at flush boundaries

class TestMemoryRecords:
    def test_records_at_flush_boundaries(self):
        sd = _mlp(fused_steps=4)
        storage = StatsStorage()
        mon = MonitorListener(storage, frequency=4)
        sd.fit(_it(), epochs=2, listeners=[mon])
        recs = storage.of_type("memory")
        # 64 rows / batch 8 = 8 steps/epoch, flush every 4 → ≥2/epoch
        assert len(recs) >= 4
        r = recs[-1]
        assert r["bytes_in_use"] >= 0 and "peak_bytes" in r
        assert r["devices"] and "device" in r["devices"][0]
        assert "iteration" in r
        assert "h2d_stage" in r["tracked"]

    def test_memory_off_publishes_nothing(self):
        sd = _mlp(fused_steps=4)
        storage = StatsStorage()
        sd.fit(_it(), epochs=1,
               listeners=[MonitorListener(storage, memory=False)])
        assert storage.of_type("memory") == []
        assert not memstats.plan_capture_enabled()

    def test_fold_memory_exports_hbm_gauges(self):
        reg = MetricsRegistry()
        reg.fold_memory({
            "type": "memory", "bytes_in_use": 100, "peak_bytes": 200,
            "bytes_limit": 1000, "headroom": 900,
            "devices": [{"device": "d0", "bytes_in_use": 100,
                         "peak_bytes": 200, "bytes_limit": 1000}],
            "tracked": {"h2d_stage": 42}})
        text = reg.to_prometheus_text()
        assert "dl4j_hbm_bytes_in_use 100" in text
        assert "dl4j_hbm_peak_bytes 200" in text
        assert "dl4j_hbm_bytes_limit 1000" in text
        assert "dl4j_hbm_headroom 900" in text
        assert 'dl4j_hbm_bytes_in_use{device="d0"} 100' in text
        assert 'dl4j_memory_tracked_bytes{tag="h2d_stage"} 42' in text

    def test_serving_batch_boundary_records(self):
        from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           OutputLayer)
        from deeplearning4j_tpu.serving import (InferenceMode,
                                                ParallelInference)
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=2, loss_function="MCXENT"))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf).init()
        st = StatsStorage()
        pi = ParallelInference(net, mode=InferenceMode.INPLACE,
                               stats_storage=st, memory_sample_every=2)
        try:
            x = np.ones((2, 4), np.float32)
            for _ in range(5):
                pi.output(x)
        finally:
            pi.shutdown()
        recs = st.of_type("memory")
        assert len(recs) >= 2
        assert all(r["source"] == "serving" for r in recs)


# ---------------------------------------------------------------------------
# memory plans + MFU

class TestMemoryPlans:
    def test_precompile_captures_window_plans(self):
        sd = _mlp(fused_steps=4)
        sd.precompile(batch_size=8)
        labels = {p.label for p in memstats.PLANS.plans()}
        assert {"window_k4", "window_k2", "window_k1"} <= labels
        plan = memstats.PLANS.find("window_k4")
        assert plan.steps == 4
        assert plan.argument_bytes is not None and plan.argument_bytes > 0
        assert plan.flops and plan.flops > 0
        assert plan.flops_per_step == plan.flops / 4
        assert plan.total_bytes > 0

    def test_lazy_promotion_captures_plan_and_is_bit_identical(self):
        X = np.random.default_rng(1).normal(size=(64, 8)) \
            .astype(np.float32)
        Y = np.eye(2, dtype=np.float32)[
            np.random.default_rng(2).integers(0, 2, 64)]

        def run(capture):
            memstats.PLANS.reset()
            if capture:
                memstats.enable_plan_capture()
            else:
                memstats.disable_plan_capture()
            sd = _mlp(fused_steps=4, seed=0)
            it = ArrayDataSetIterator(X, Y, batch_size=8)
            hist = sd.fit(it, epochs=2, listeners=[_quiet()])
            plans = {p.label for p in memstats.PLANS.plans()}
            return (hist.loss_curve.losses,
                    {n: np.asarray(a)
                     for n, a in sd.trainable_params().items()}, plans)

        losses_off, params_off, plans_off = run(False)
        losses_on, params_on, plans_on = run(True)
        assert plans_off == set()
        assert "window_k4" in plans_on       # lazy compile got a plan
        assert losses_on == losses_off       # bit-identical
        for n in params_off:
            np.testing.assert_array_equal(params_on[n], params_off[n])

    def test_serving_warmup_captures_bucket_plans(self):
        from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           OutputLayer)
        from deeplearning4j_tpu.serving import (InferenceMode,
                                                ParallelInference)
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=2, loss_function="MCXENT"))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf).init()
        pi = ParallelInference(net, mode=InferenceMode.BATCHED,
                               max_batch_size=8, warmup_buckets=True)
        try:
            labels = {p.label for p in memstats.PLANS.plans()}
            assert any(lb.startswith("output_b") for lb in labels)
            plan = next(p for p in memstats.PLANS.plans()
                        if p.label.startswith("output_b"))
            assert plan.output_bytes is not None
        finally:
            pi.shutdown()

    def test_mfu_gauge_mid_fit(self, monkeypatch):
        """Acceptance: /metrics exports dl4j_hbm_* gauges and a live
        MFU-estimate gauge MID-FIT (scraped from inside a listener
        flush while the fit is running)."""
        # the CPU is in no peak-rate table; give the gauge a denominator
        monkeypatch.setattr(memstats, "peak_flops", lambda: 1e12)
        sd = _mlp(fused_steps=4)
        sd.precompile(batch_size=8)          # plans → MFU numerator
        storage = StatsStorage()
        mon = MonitorListener(storage, frequency=4, serve_port=0)
        scraped = {}

        from deeplearning4j_tpu.autodiff.training import Listener

        class _Probe(Listener):
            frequency = 4
            calls = 0

            def iterations_done(self, _sd, epoch, iters, losses):
                _Probe.calls += 1
                if _Probe.calls == 3 and not scraped:
                    code, text = _get(mon.server.url + "/metrics")
                    scraped["code"] = code
                    scraped["text"] = text

        try:
            # listener order: mon flushes (and samples memory) first,
            # then the probe scrapes — a genuine mid-fit scrape
            sd.fit(_it(n=128), epochs=3, listeners=[mon, _Probe()])
            assert scraped, "probe never scraped mid-fit"
            assert scraped["code"] == 200
            assert "dl4j_hbm_bytes_in_use" in scraped["text"]
            assert "dl4j_mfu_estimate" in scraped["text"]
            assert "dl4j_plan_flops_per_step" in scraped["text"]
            mfu = [float(line.rsplit(" ", 1)[1])
                   for line in scraped["text"].splitlines()
                   if line.startswith("dl4j_mfu_estimate")]
            assert mfu and mfu[0] > 0
        finally:
            if mon.server is not None:
                mon.server.close()

    def test_plan_records_published_and_rendered(self):
        sd = _mlp(fused_steps=4)
        sd.precompile(batch_size=8)
        storage = StatsStorage()
        sd.fit(_it(), epochs=1, listeners=[MonitorListener(storage)])
        plan_recs = storage.of_type("memory_plan")
        assert {r["program"] for r in plan_recs} >= {"window_k4"}
        html = render_report(storage)
        assert "compiled-program memory plans" in html
        assert "window_k4" in html
        # the forward-compat footer must NOT list memory/memory_plan
        assert "unrendered record types" not in html


class TestPlanScoping:
    def test_second_models_listener_does_not_republish_first_models_plans(
            self):
        """Review regression: the plan registry is process-global, but
        a later model's MonitorListener must publish only ITS graph's
        plans — not the earlier model's — into its storage/report."""
        sd_a = _mlp(fused_steps=4, seed=0)
        sd_a.precompile(batch_size=8)
        st_a = StatsStorage()
        sd_a.fit(_it(), epochs=1, listeners=[MonitorListener(st_a)])
        assert {r["program"] for r in st_a.of_type("memory_plan")} \
            >= {"window_k4"}

        sd_b = _mlp(fused_steps=2, seed=1)
        sd_b.precompile(batch_size=8)
        st_b = StatsStorage()
        sd_b.fit(_it(), epochs=1, listeners=[MonitorListener(st_b)])
        progs_b = {r["program"] for r in st_b.of_type("memory_plan")}
        assert "window_k2" in progs_b
        assert "window_k4" not in progs_b, \
            "model B's storage republished model A's plans"


class TestAcceptanceReportPlans:
    def test_gpt_tiny_window_and_serving_bucket_plans_in_report(self):
        """Acceptance: /report shows the per-executable memory plan for
        at least the gpt_tiny fused window and one serving bucket."""
        from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           OutputLayer)
        from deeplearning4j_tpu.serving import (InferenceMode,
                                                ParallelInference)
        from deeplearning4j_tpu.zoo.gpt import GPT_TINY, build_gpt
        sd = build_gpt(GPT_TINY, batch=2, seq_len=8)
        sd.training_config = TrainingConfig(
            updater=Adam(1e-3), data_set_feature_mapping=["input_ids"],
            data_set_label_mapping=["targets"], fused_steps=2)
        sd.precompile(batch_size=2)
        gpt_plan = memstats.PLANS.find("window_k2")
        assert gpt_plan is not None
        assert gpt_plan.flops and gpt_plan.flops > 0
        assert gpt_plan.argument_bytes > 0    # params + window batch

        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=2, loss_function="MCXENT"))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf).init()
        pi = ParallelInference(net, mode=InferenceMode.INPLACE,
                               warmup_buckets=[4])
        try:
            storage = StatsStorage()
            for p in memstats.PLANS.plans():
                storage.put(p.to_record())
            html = render_report(storage)
            assert "compiled-program memory plans" in html
            assert "window_k2" in html            # the gpt_tiny window
            assert "output_b4" in html            # the serving bucket
        finally:
            pi.shutdown()


# ---------------------------------------------------------------------------
# /memory route

class TestMemoryRoute:
    def test_memory_route(self):
        from deeplearning4j_tpu.monitor import serve
        st = StatsStorage()
        st.put(memstats.memory_record(epoch=0, iteration=3))
        sd = _mlp(fused_steps=2)
        sd.precompile(batch_size=8)
        srv = serve(port=0, storage=st)
        try:
            code, body = _get(srv.url + "/memory")
            assert code == 200
            data = json.loads(body)
            assert data["type"] == "memory"
            assert data["devices"]
            assert any(p["program"] == "window_k2"
                       for p in data["plans"])
            assert data["last_record"]["iteration"] == 3
            code, body = _get(srv.url + "/")
            assert "/memory" in body
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# OOM forensics

class TestOOMForensics:
    @pytest.mark.chaos
    def test_fit_converts_resource_exhausted(self):
        sd = _mlp(fused_steps=4)
        chaos = ChaosMonkey(seed=0)
        with chaos.resource_exhausted(at_call=2):
            with pytest.raises(MemoryExhaustedError) as ei:
                sd.fit(_it(), epochs=1, listeners=[_quiet()])
        err = ei.value
        assert err.program == "window_k4"
        assert err.snapshot, "no device snapshot attached"
        assert err.census is not None
        assert "RESOURCE_EXHAUSTED" in str(err.__cause__)
        # the rendered one-pager names usage per device
        assert "MiB in use" in str(err)

    @pytest.mark.chaos
    def test_oom_e2e_ftf_diagnoses_and_healthz_503(self, tmp_path):
        """Acceptance: injected OOM during a fit produces a
        MemoryExhaustedError naming the active program and per-device
        usage, an oom fault record, a rendered report panel, and a
        503-ing /healthz — instead of a raw backend crash. And FTF
        does NOT burn its retry budget on it."""
        from deeplearning4j_tpu.monitor import serve
        sd = _mlp(fused_steps=4, sentinel=True)
        storage = StatsStorage()
        mgr = CheckpointManager(tmp_path / "ckpt", keep_last_n=2)
        ftf = FaultTolerantFit(
            sd, mgr, policy=RetryPolicy(max_retries=3, backoff_base=0.0),
            checkpoint_every_n_epochs=1, stats_storage=storage)
        chaos = ChaosMonkey(seed=0)
        with chaos.resource_exhausted(at_call=3):
            with pytest.raises(MemoryExhaustedError):
                ftf.fit(_it(), epochs=2, listeners=[_quiet()])
        oom = [r for r in storage.of_type("faults")
               if r.get("event") == "oom"]
        assert len(oom) == 1
        assert oom[0]["program"] == "window_k4"
        assert oom[0]["devices"], "forensics lost the device usage"
        # non-retryable: no rollback was attempted for the OOM
        assert not [r for r in storage.of_type("faults")
                    if r.get("event") == "rollback"]
        # health: sticky failed
        snap = health_snapshot(storage)
        assert snap["healthy"] is False
        assert snap["last_fault_event"] == "oom"
        srv = serve(port=0, storage=storage)
        try:
            code, body = _get(srv.url + "/healthz")
            assert code == 503
            assert json.loads(body)["fault_state"] == "failed"
        finally:
            srv.close()
        html = render_report(storage)
        assert "OOM events" in html and "window_k4" in html

    @pytest.mark.chaos
    def test_serving_oom_structured_and_healthz(self):
        from deeplearning4j_tpu.monitor import serve
        from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           OutputLayer)
        from deeplearning4j_tpu.serving import (InferenceMode,
                                                ParallelInference)
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=2, loss_function="MCXENT"))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf).init()
        st = StatsStorage()
        pi = ParallelInference(net, mode=InferenceMode.INPLACE,
                               stats_storage=st)
        chaos = ChaosMonkey(seed=0)
        try:
            x = np.ones((2, 4), np.float32)
            pi.output(x)                         # healthy baseline
            with chaos.oom_serving(pi, at_call=1):
                with pytest.raises(MemoryExhaustedError) as ei:
                    pi.output(x)
            assert ei.value.program.startswith("serving_b")
            oom = [r for r in st.of_type("faults")
                   if r.get("event") == "oom"]
            assert oom and oom[0]["origin"] == "serving"
            srv = serve(port=0, storage=st)
            try:
                code, _ = _get(srv.url + "/healthz")
                assert code == 503
            finally:
                srv.close()
        finally:
            pi.shutdown()


# ---------------------------------------------------------------------------
# headroom guards

class TestHeadroomGuards:
    def _server_with_checkpoint(self, tmp_path):
        from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           OutputLayer)
        from deeplearning4j_tpu.serving import (InferenceMode,
                                                ParallelInference)
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=2, loss_function="MCXENT"))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf).init()
        mgr = CheckpointManager(tmp_path / "ckpt", keep_last_n=2)
        mgr.save(1, model=net, blocking=True)
        pi = ParallelInference(net, mode=InferenceMode.INPLACE)
        return pi, mgr

    def test_reload_refused_when_headroom_too_small(self, tmp_path,
                                                    monkeypatch):
        pi, mgr = self._server_with_checkpoint(tmp_path)
        try:
            x = np.ones((1, 4), np.float32)
            before = pi.output(x)
            monkeypatch.setattr(memstats, "projected_headroom",
                                lambda snap=None: 16)
            with pytest.raises(MemoryHeadroomError) as ei:
                pi.reload_from(mgr)
            assert ei.value.headroom_bytes == 16
            assert ei.value.required_bytes > 16
            assert pi.metrics.counters.get("reloads", 0) == 0
            # nothing was swapped: the server serves exactly what it
            # served before the refusal
            np.testing.assert_array_equal(pi.output(x), before)
        finally:
            pi.shutdown()

    def test_reload_ok_without_limits_and_with_guard_off(self, tmp_path,
                                                         monkeypatch):
        pi, mgr = self._server_with_checkpoint(tmp_path)
        try:
            # CPU: no bytes_limit → guard is a no-op, reload succeeds
            rep = pi.reload_from(mgr)
            assert rep["arrays_swapped"] > 0
            # guard off bypasses even a tiny headroom
            monkeypatch.setattr(memstats, "projected_headroom",
                                lambda snap=None: 1)
            rep = pi.reload_from(mgr, headroom_guard=False)
            assert rep["arrays_swapped"] > 0
        finally:
            pi.shutdown()

    def test_warmup_refused_when_headroom_too_small(self, monkeypatch):
        from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                           MultiLayerNetwork,
                                           NeuralNetConfiguration,
                                           OutputLayer)
        from deeplearning4j_tpu.serving import (InferenceMode,
                                                ParallelInference)
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=2, loss_function="MCXENT"))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf).init()
        pi = ParallelInference(net, mode=InferenceMode.SEQUENTIAL,
                               workers=1)
        try:
            monkeypatch.setattr(memstats, "projected_headroom",
                                lambda snap=None: 0)
            with pytest.raises(MemoryHeadroomError):
                pi.warmup([4])
        finally:
            pi.shutdown()


# ---------------------------------------------------------------------------
# bit-identity of the whole memory rail

class TestBitIdentity:
    def test_fused_run_bit_identical_memory_on_vs_off(self):
        X = np.random.default_rng(5).normal(size=(64, 8)) \
            .astype(np.float32)
        Y = np.eye(2, dtype=np.float32)[
            np.random.default_rng(6).integers(0, 2, 64)]

        def run(mem_on):
            memstats.PLANS.reset()
            memstats.disable_plan_capture()
            sd = _mlp(fused_steps=4, sentinel=True, seed=0)
            it = ArrayDataSetIterator(X, Y, batch_size=8)
            storage = StatsStorage()
            listeners = [_quiet(),
                         MonitorListener(storage, frequency=4,
                                         memory=mem_on)]
            hist = sd.fit(it, epochs=2, listeners=listeners)
            return (hist.loss_curve.losses,
                    {n: np.asarray(a)
                     for n, a in sd.trainable_params().items()},
                    storage)

        losses_off, params_off, st_off = run(False)
        losses_on, params_on, st_on = run(True)
        assert losses_on == losses_off
        for n in params_off:
            np.testing.assert_array_equal(params_on[n], params_off[n])
        assert st_on.of_type("memory") and not st_off.of_type("memory")
