"""serving/ subsystem tests (reference test model: deeplearning4j
parallelwrapper ParallelInferenceTest — mode coverage, output parity
with the wrapped network, queue behavior under load) plus regression
tests for the satellite fixes that rode along with the subsystem.

The acceptance bar: BATCHED mode with bucketed padding serves 256
mixed-size requests with <= 4 jit compilations (counted by wrapping the
graph-compile entry point) and BIT-identical outputs vs per-request
``MultiLayerNetwork.output()``; overflow/timeout paths raise typed
errors instead of hanging.
"""
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.nn import (ComputationGraph, DenseLayer, InputType,
                                   MergeVertex, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.serving import (
    Batch, BucketSpec, DynamicBatcher, InferenceMode, InferenceRequest,
    LatencyHistogram, LoadGenerator, ParallelInference, RequestQueue,
    RequestTimeoutError, ServerClosedError, ServerOverloadedError,
    ServingMetrics, pad_to_bucket, pow2_buckets)
from deeplearning4j_tpu.ui.stats import StatsStorage

N_IN, N_OUT = 8, 3


def _net(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(1e-3)).list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=N_OUT, loss_function="MCXENT"))
            .set_input_type(InputType.feed_forward(N_IN))
            .build())
    return MultiLayerNetwork(conf).init()


def _req(rows=1, deadline=None, seed=0):
    x = np.random.default_rng(seed).normal(size=(rows, N_IN)) \
        .astype(np.float32)
    return InferenceRequest(x=[x], future=Future(), rows=rows,
                           deadline=deadline)


class _CompileCounter:
    """Counting wrapper over the graph-compile entry point: SameDiff
    traces a python fn exactly once per compiled (outputs, shape)
    signature, so counting _trace_fn calls counts jit compilations."""

    def __enter__(self):
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        self._cls = SameDiff
        self._orig = SameDiff._trace_fn
        self.count = 0
        counter = self

        def wrapper(sd_self, *a, **k):
            counter.count += 1
            return counter._orig(sd_self, *a, **k)

        SameDiff._trace_fn = wrapper
        return self

    def __exit__(self, *exc):
        self._cls._trace_fn = self._orig
        return False


# ---------------------------------------------------------------------------
# acceptance: 256 mixed-size requests, <= 4 compiles, bit-identical


def test_batched_256_mixed_requests_4_compiles_bit_identical(served_batches):
    net = _net()
    rng = np.random.default_rng(42)
    reqs = [rng.normal(size=(int(rng.integers(1, 9)), N_IN))
            .astype(np.float32) for _ in range(256)]
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=2,
                           max_batch_size=32, max_delay_ms=2.0,
                           max_queue_len=512)
    ran = served_batches(pi)
    try:
        with _CompileCounter() as cc:
            futs = [pi.submit(x) for x in reqs]
            outs = [f.result(timeout=60) for f in futs]
        assert cc.count <= 4, f"{cc.count} compiles for 256 requests"
        assert pi.metrics.counters["compiles"] <= 4
        # bit-identical to the direct path over the same padded batch
        for x, served in zip(reqs, outs):
            direct = ran.direct(net, x)
            assert served.shape == (x.shape[0], N_OUT)
            assert np.array_equal(served, direct), \
                "served output differs from direct output()"
        assert pi.metrics.counters["requests_served"] == 256
        assert pi.metrics.counters["rows_served"] == \
            sum(r.shape[0] for r in reqs)
    finally:
        pi.shutdown()


def test_sequential_mode_parity():
    net = _net()
    rng = np.random.default_rng(1)
    pi = ParallelInference(net, mode=InferenceMode.SEQUENTIAL, workers=2)
    try:
        xs = [rng.normal(size=(n, N_IN)).astype(np.float32)
              for n in (1, 5, 3)]
        outs = [pi.output(x) for x in xs]
        for x, o in zip(xs, outs):
            assert np.array_equal(o, net.output(x).to_numpy())
    finally:
        pi.shutdown()


def test_inplace_mode_parity_and_single_example():
    net = _net()
    x = np.random.default_rng(2).normal(size=(4, N_IN)).astype(np.float32)
    with ParallelInference(net, mode=InferenceMode.INPLACE) as pi:
        assert np.array_equal(pi.output(x), net.output(x).to_numpy())
        # unbatched single example: row dim added then squeezed back
        one = pi.output(x[0])
        assert one.shape == (N_OUT,)
        assert np.array_equal(one, net.output(x[:1]).to_numpy()[0])


def test_computation_graph_served():
    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(Adam(1e-3)).graph_builder()
            .add_inputs("inA", "inB")
            .set_input_types(InputType.feed_forward(3),
                             InputType.feed_forward(2))
            .add_layer("dA", DenseLayer(n_out=8, activation="tanh"), "inA")
            .add_layer("dB", DenseLayer(n_out=8, activation="tanh"), "inB")
            .add_vertex("merge", MergeVertex(), "dA", "dB")
            .add_layer("out", OutputLayer(n_out=2), "merge")
            .set_outputs("out").build())
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    a = rng.normal(size=(4, 3)).astype(np.float32)
    b = rng.normal(size=(4, 2)).astype(np.float32)
    # multi-input graphs serve in SEQUENTIAL mode (tuple submit)
    with ParallelInference(net, mode=InferenceMode.SEQUENTIAL) as pi:
        served = pi.output((a, b))
    direct = net.output(a, b)[0].to_numpy()
    assert np.array_equal(served, direct)
    # BATCHED refuses multi-input models with a clear error
    with pytest.raises(ValueError, match="single-input"):
        ParallelInference(net, mode=InferenceMode.BATCHED)


def test_inplace_rejects_timeout_and_uninit_graph_is_guarded():
    net = _net()
    with ParallelInference(net, mode=InferenceMode.INPLACE) as pi:
        with pytest.raises(ValueError, match="no queue"):
            pi.output(np.zeros((1, N_IN), np.float32), timeout_ms=5)
    with pytest.raises(ValueError, match="no queue wait"):
        ParallelInference(net, mode=InferenceMode.INPLACE,
                          default_timeout_ms=5)
    # serving an uninitialized network fails with a clear message
    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(Adam(1e-3)).graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(3))
            .add_layer("out", OutputLayer(n_out=2), "in")
            .set_outputs("out").build())
    with pytest.raises(RuntimeError, match="init"):
        ParallelInference(ComputationGraph(conf))


def test_update_model_pulls_new_params():
    net = _net()
    rng = np.random.default_rng(3)
    x = rng.normal(size=(4, N_IN)).astype(np.float32)
    with ParallelInference(net, mode=InferenceMode.INPLACE) as pi:
        before = pi.output(x)
        X = rng.normal(size=(64, N_IN)).astype(np.float32)
        Y = np.eye(N_OUT, dtype=np.float32)[
            rng.integers(0, N_OUT, size=64)]
        net.fit(X, Y, epochs=1, batch_size=32)
        pi.update_model()
        after = pi.output(x)
        assert not np.array_equal(before, after)
        assert np.array_equal(after, net.output(x).to_numpy())


# ---------------------------------------------------------------------------
# queue: backpressure, deadlines, drain


def test_queue_backpressure_overflow_is_typed():
    q = RequestQueue(max_queue_len=2)
    q.put(_req())
    q.put(_req())
    with pytest.raises(ServerOverloadedError):
        q.put(_req())


def test_queue_take_budget_and_strict():
    q = RequestQueue(8)
    for s in (3, 3, 3):
        q.put(_req(rows=s))
    got = q.take(max_rows=8, timeout=0, strict=True)
    assert [r.rows for r in got] == [3, 3]       # third would overshoot
    # non-strict lets an oversize head through alone
    q2 = RequestQueue(8)
    q2.put(_req(rows=5))
    got = q2.take(max_rows=1, timeout=0)
    assert [r.rows for r in got] == [5]
    # strict never pops an oversize head
    q3 = RequestQueue(8)
    q3.put(_req(rows=5))
    assert q3.take(max_rows=2, timeout=0, strict=True) == []


def test_queue_deadline_expires_at_dispatch():
    q = RequestQueue(8)
    dead = _req(rows=1, deadline=time.monotonic() - 0.001)
    live = _req(rows=1)
    q.put(dead)
    q.put(live)
    got = q.take(max_rows=4, timeout=0)
    assert got == [live]
    with pytest.raises(RequestTimeoutError):
        dead.future.result(timeout=0)
    assert q.timed_out_count() == 1


def test_queue_close_without_drain_fails_pending():
    q = RequestQueue(8)
    r = _req()
    q.put(r)
    q.close(drain=False)
    with pytest.raises(ServerClosedError):
        r.future.result(timeout=0)
    with pytest.raises(ServerClosedError):
        q.put(_req())


def test_server_backpressure_rejection():
    net = _net()
    gate = threading.Event()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=1, buckets=(1,), max_queue_len=2,
                           max_delay_ms=0.5)
    orig = pi._execute
    pi._execute = lambda *a, **k: (gate.wait(10), orig(*a, **k))[1]
    try:
        first = pi.submit(np.zeros((1, N_IN), np.float32))
        deadline = time.monotonic() + 5
        while pi._queue.pending() and time.monotonic() < deadline:
            time.sleep(0.005)        # worker picks up the first request
        pi.submit(np.zeros((1, N_IN), np.float32))
        pi.submit(np.zeros((1, N_IN), np.float32))
        with pytest.raises(ServerOverloadedError):
            pi.submit(np.zeros((1, N_IN), np.float32))
        assert pi.metrics.counters["requests_rejected"] == 1
    finally:
        gate.set()
        pi.shutdown()
    assert first.result(timeout=10) is not None


def test_server_deadline_expiry_typed_not_hanging():
    net = _net()
    gate = threading.Event()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=1, buckets=(1,), max_queue_len=8,
                           max_delay_ms=0.5)
    orig = pi._execute
    pi._execute = lambda *a, **k: (gate.wait(10), orig(*a, **k))[1]
    try:
        pi.submit(np.zeros((1, N_IN), np.float32))      # occupies the worker
        deadline = time.monotonic() + 5
        while pi._queue.pending() and time.monotonic() < deadline:
            time.sleep(0.005)
        doomed = pi.submit(np.zeros((1, N_IN), np.float32), timeout_ms=20)
        time.sleep(0.05)                                # deadline passes
        gate.set()
        with pytest.raises(RequestTimeoutError):
            doomed.result(timeout=10)
        assert pi.metrics.counters["requests_timed_out"] == 1
    finally:
        gate.set()
        pi.shutdown()


def test_drain_on_shutdown_serves_queued_work(served_batches):
    net = _net()
    rng = np.random.default_rng(9)
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=2,
                           max_batch_size=16, max_delay_ms=1.0,
                           max_queue_len=128)
    ran = served_batches(pi)
    xs = [rng.normal(size=(2, N_IN)).astype(np.float32) for _ in range(40)]
    futs = [pi.submit(x) for x in xs]
    pi.shutdown(drain=True)
    for x, f in zip(xs, futs):
        assert np.array_equal(f.result(timeout=0), ran.direct(net, x))
    with pytest.raises(ServerClosedError):
        pi.submit(xs[0])


def test_shutdown_without_drain_fails_pending():
    net = _net()
    gate = threading.Event()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=1, buckets=(1,), max_queue_len=8,
                           max_delay_ms=0.5)
    orig = pi._execute
    pi._execute = lambda *a, **k: (gate.wait(10), orig(*a, **k))[1]
    pi.submit(np.zeros((1, N_IN), np.float32))
    deadline = time.monotonic() + 5
    while pi._queue.pending() and time.monotonic() < deadline:
        time.sleep(0.005)
    pending = pi.submit(np.zeros((1, N_IN), np.float32))
    gate.set()
    pi.shutdown(drain=False)
    with pytest.raises(ServerClosedError):
        pending.result(timeout=10)


# ---------------------------------------------------------------------------
# batcher + buckets


def test_pow2_buckets():
    assert pow2_buckets(32) == (4, 8, 16, 32)
    assert pow2_buckets(8, n_buckets=2) == (4, 8)
    assert pow2_buckets(1) == (1,)


def test_bucket_spec_rounds_up():
    spec = BucketSpec((4, 8, 16, 32))
    assert spec.bucket_for(1) == 4
    assert spec.bucket_for(4) == 4
    assert spec.bucket_for(5) == 8
    assert spec.bucket_for(32) == 32
    with pytest.raises(ValueError):
        spec.bucket_for(33)


def test_pad_to_bucket_zero_pads():
    a = np.ones((3, 2), np.float32)
    b = np.full((2, 2), 2.0, np.float32)
    out = pad_to_bucket([a, b], 8)
    assert out.shape == (8, 2)
    np.testing.assert_array_equal(out[:3], a)
    np.testing.assert_array_equal(out[3:5], b)
    np.testing.assert_array_equal(out[5:], 0.0)


def test_batcher_coalesces_and_pads():
    q = RequestQueue(16)
    for i in range(5):
        q.put(_req(rows=3, seed=i))
    batcher = DynamicBatcher(q, max_batch_size=8, max_delay_ms=1.0,
                             buckets=(4, 8))
    batch = batcher.next_batch(poll_timeout=0.5)
    assert isinstance(batch, Batch)
    assert len(batch.requests) == 2         # 3+3 rows; a third overshoots
    assert batch.rows == 6
    assert batch.bucket == 8
    assert batch.padding == 2
    assert batch.features.shape == (8, N_IN)
    np.testing.assert_array_equal(batch.features[6:], 0.0)


def test_batch_resolve_scatters_rows():
    reqs = [_req(rows=2, seed=0), _req(rows=3, seed=1)]
    batch = Batch(requests=reqs,
                  features=np.zeros((8, N_IN), np.float32), rows=5,
                  bucket=8)
    out = np.arange(8 * N_OUT, dtype=np.float32).reshape(8, N_OUT)
    batch.resolve([out])
    np.testing.assert_array_equal(reqs[0].future.result(timeout=0), out[:2])
    np.testing.assert_array_equal(reqs[1].future.result(timeout=0), out[2:5])


# ---------------------------------------------------------------------------
# metrics


def test_latency_histogram_percentiles():
    h = LatencyHistogram()
    for ms in (1.0, 2.0, 3.0, 100.0):
        h.record(ms)
    assert h.count == 4
    assert h.percentile(50) <= h.percentile(95) <= h.percentile(99)
    assert h.percentile(99) <= h.max_ms
    assert h.mean() == pytest.approx(26.5)
    s = h.summary()
    assert set(s) == {"count", "low_sample", "mean", "p50", "p95", "p99",
                      "max"}
    assert s["low_sample"] is True      # 4 samples: tails are suspect


def test_metrics_record_through_stats_storage(tmp_path):
    net = _net()
    st = StatsStorage(str(tmp_path / "serving.jsonl"))
    pi = ParallelInference(net, mode=InferenceMode.BATCHED,
                           max_delay_ms=1.0, stats_storage=st)
    xs = np.random.default_rng(0).normal(size=(6, 4, N_IN)) \
        .astype(np.float32)
    for x in xs:
        pi.output(x)
    pi.shutdown()                   # publishes the final snapshot
    recs = st.of_type("serving")
    assert len(recs) == 1
    rec = recs[0]
    assert rec["counters"]["requests_served"] == 6
    assert rec["counters"]["rows_served"] == 24
    for fam in ("queue_wait", "e2e", "exec"):
        assert rec["latency_ms"][fam]["count"] > 0
        assert rec["latency_ms"][fam]["p99"] >= rec["latency_ms"][fam]["p50"]
    assert 0.0 <= rec["batch"]["padding_waste"] < 1.0
    # round-trips through the JSONL file like any other stats record
    loaded = StatsStorage.load(str(tmp_path / "serving.jsonl"))
    assert loaded.of_type("serving")[0]["counters"]["requests_served"] == 6
    assert "ServingMetrics" in pi.metrics.stats()


def test_padding_waste_accounting():
    m = ServingMetrics()
    m.observe_batch(rows=6, padding=2, exec_ms=1.0)
    m.observe_batch(rows=8, padding=0, exec_ms=1.0)
    assert m.padding_waste() == pytest.approx(2 / 16)
    assert m.mean_batch_size() == pytest.approx(7.0)


# ---------------------------------------------------------------------------
# load generator


def test_loadgen_closed_loop():
    net = _net()
    with ParallelInference(net, mode=InferenceMode.BATCHED,
                           max_delay_ms=1.0, max_queue_len=64) as pi:
        lg = LoadGenerator(
            pi, lambda rng, i: rng.normal(size=(2, N_IN))
            .astype(np.float32), seed=0)
        res = lg.run_closed(n_requests=24, concurrency=3)
    assert res.n_ok == 24 and res.n_issued == 24
    assert res.throughput_rps > 0
    assert len(res.latencies_ms) == 24
    assert res.percentile(50) <= res.percentile(99)
    assert "LoadResult" in res.stats()


def test_loadgen_open_loop():
    net = _net()
    with ParallelInference(net, mode=InferenceMode.BATCHED,
                           max_delay_ms=1.0, max_queue_len=64) as pi:
        lg = LoadGenerator(
            pi, lambda rng, i: rng.normal(size=(1, N_IN))
            .astype(np.float32), seed=1)
        res = lg.run_open(n_requests=16, rate_rps=400.0)
    assert res.n_ok + res.n_rejected + res.n_timed_out == 16
    assert res.n_ok > 0


# ---------------------------------------------------------------------------
# satellite regressions


def test_calibration_per_class_bins_only_label_column():
    """evaluation/calibration.py: residualPlotByLabelClass counts ONE
    entry per row (the label column), not C (satellite fix)."""
    from deeplearning4j_tpu.evaluation.calibration import (
        EvaluationCalibration)
    ec = EvaluationCalibration(histogram_bins=10)
    preds = np.array([[0.95, 0.05],      # label 0: residual col0 = 0.05
                      [0.30, 0.70],      # label 1: residual col1 = 0.30
                      [0.55, 0.45]])     # label 0: residual col0 = 0.45
    ec.eval(np.array([0, 1, 0]), preds)
    h0 = ec.residual_plot(0)
    assert h0.bin_counts.sum() == 2              # 2 rows labeled 0 -> 2
    assert h0.bin_counts[0] == 1                 # 0.05 -> bin 0
    assert h0.bin_counts[4] == 1                 # 0.45 -> bin 4
    h1 = ec.residual_plot(1)
    assert h1.bin_counts.sum() == 1
    assert h1.bin_counts[3] == 1                 # 0.30 -> bin 3
    p0 = ec.probability_histogram(0)
    assert p0.bin_counts.sum() == 2              # cols 0 of rows labeled 0
    assert p0.bin_counts[9] == 1                 # p=0.95
    assert p0.bin_counts[5] == 1                 # p=0.55
    # all-classes histograms still count every (row, class) entry
    assert ec.residual_plot_all_classes().bin_counts.sum() == 6


def test_fastcsv_io_vs_bad_cell_row0_disambiguated(tmp_path):
    """native/fastcsv: I/O failure (CSV_EIO) no longer collides with
    'bad cell at data row 0' (satellite fix)."""
    from deeplearning4j_tpu.native import native_available
    from deeplearning4j_tpu.native.fastcsv import CSV_EIO, read_csv_f32
    if not native_available("fastcsv"):
        pytest.skip("no C++ toolchain")
    p = tmp_path / "bad0.csv"
    p.write_text("oops,2\n3,4\n")
    with pytest.raises(ValueError, match="non-numeric cell at data row 0"):
        read_csv_f32(str(p))
    with pytest.raises(ValueError, match="cannot read"):
        read_csv_f32(str(tmp_path / "does_not_exist.csv"))
    # the raw ABI: bad cell at row r returns -(r+2), I/O returns INT_MIN
    import ctypes
    from deeplearning4j_tpu.native.build import load
    lib = load("fastcsv")
    out = np.empty((2, 2), np.float32)
    rc = lib.csv_parse_f32(str(p).encode(), b",", 0,
                           out.ctypes.data_as(
                               ctypes.POINTER(ctypes.c_float)), 2, 2)
    assert rc == -2                               # row 0 -> -(0+2)
    rc = lib.csv_parse_f32(b"/nonexistent/x.csv", b",", 0,
                           out.ctypes.data_as(
                               ctypes.POINTER(ctypes.c_float)), 2, 2)
    assert rc == CSV_EIO


def test_best_score_termination_is_strict():
    """autodiff/earlystopping: reaching the target exactly does NOT
    terminate; beating it does (satellite fix)."""
    from deeplearning4j_tpu.autodiff.earlystopping import (
        BestScoreEpochTerminationCondition)
    cond = BestScoreEpochTerminationCondition(0.5)
    assert not cond.terminate(0, 0.5, False)      # equal: keep training
    assert not cond.terminate(0, 0.6, False)
    assert cond.terminate(0, 0.499, True)         # strictly better: stop


def test_submit_rejects_wrong_feature_shape():
    """A mismatched request must die at admission with ValueError, not
    poison a coalesced batch (which would strand other futures)."""
    net = _net()
    with ParallelInference(net, mode=InferenceMode.BATCHED,
                           max_delay_ms=1.0) as pi:
        with pytest.raises(ValueError, match="expects shape"):
            pi.submit(np.zeros((2, N_IN + 1), np.float32))
        # well-formed traffic still serves afterwards
        x = np.zeros((2, N_IN), np.float32)
        assert np.array_equal(pi.output(x), net.output(x).to_numpy())


def test_timeout_callback_may_reenter_queue_without_deadlock():
    """Futures complete OUTSIDE the queue lock: a done-callback that
    re-submits (retry pattern) must not deadlock the worker."""
    net = _net()
    gate = threading.Event()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=1, buckets=(1,), max_queue_len=8,
                           max_delay_ms=0.5)
    orig = pi._execute
    pi._execute = lambda *a, **k: (gate.wait(10), orig(*a, **k))[1]
    retried = []
    try:
        pi.submit(np.zeros((1, N_IN), np.float32))   # occupies the worker
        deadline = time.monotonic() + 5
        while pi._queue.pending() and time.monotonic() < deadline:
            time.sleep(0.005)
        doomed = pi.submit(np.zeros((1, N_IN), np.float32), timeout_ms=20)
        doomed.add_done_callback(
            lambda f: retried.append(
                pi.submit(np.zeros((1, N_IN), np.float32))))
        time.sleep(0.05)
        gate.set()
        with pytest.raises(RequestTimeoutError):
            doomed.result(timeout=10)
        assert len(retried) == 1
        assert retried[0].result(timeout=10) is not None
    finally:
        gate.set()
        pi.shutdown()


def test_switch_gating_positions_accumulate_in_int32():
    """parallel/moe: queue positions come from an int32 cumsum (exact at
    any token count), not float32 (satellite fix)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import switch_gating
    x = jnp.zeros((16, 4), jnp.float32)
    w = jnp.zeros((4, 2), jnp.float32)
    jaxpr = str(jax.make_jaxpr(
        lambda x, w: switch_gating(x, w, capacity=4))(x, w))
    cumsum_lines = [ln for ln in jaxpr.splitlines() if "cumsum" in ln]
    assert cumsum_lines, "cumsum disappeared from switch_gating"
    assert all("f32" not in ln for ln in cumsum_lines), \
        f"float cumsum in switch_gating: {cumsum_lines}"
    # capacity enforcement stays exact: all tokens to one expert, cap 4
    gate_w = jnp.asarray(np.array([[10.0, -10.0]] * 4, np.float32))
    ones = jnp.asarray(np.ones((16, 4), np.float32))
    dispatch, combine, _ = switch_gating(ones, gate_w, capacity=4)
    assert float(jnp.sum(dispatch)) == 4.0        # first 4 kept, 12 dropped
    # kept tokens are the FIRST four in arrival order
    np.testing.assert_array_equal(
        np.asarray(jnp.sum(dispatch, axis=(1, 2))),
        np.array([1, 1, 1, 1] + [0] * 12, np.float32))
