"""Serving resilience rail tests (serving/resilience.py + the
inference.py surgery): SLO admission shedding, circuit breaker with
pinned /healthz 200→503→200 transitions, supervised workers with
exactly-once crash requeue, bisecting poisoned-batch isolation
(bit-identical healthy co-batched answers), reply-time deadline
re-check, and checkpoint-driven hot reload with canary rollback.

The chaos e2e drills follow the PR-4 convention: seed-driven injectors
from faults/chaos.py, each test ``@pytest.mark.chaos`` so the conftest
SIGALRM guard bounds a wedged recovery loop to one failing test.
"""
import re
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import Future

import numpy as np
import pytest

from deeplearning4j_tpu.checkpoint import CheckpointManager
from deeplearning4j_tpu.faults import ChaosMonkey
from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.serving import (
    InferenceMode, InferenceRequest, LoadGenerator, ParallelInference,
    PoisonedRequestError, ReloadFailedError, RequestQueue,
    RequestTimeoutError, ResilienceConfig, ServerClosedError,
    ServerOverloadedError, ServingError, ServingMetrics,
    ServingTimeoutError)
from deeplearning4j_tpu.serving.resilience import (AdmissionController,
                                                   CircuitBreaker)
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


def _wait_until(cond, timeout=10.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


class _Die(BaseException):
    """Escapes the worker's Exception guard — SIGKILL-grade worker
    death for supervision drills."""


# ---------------------------------------------------------------------------
# circuit breaker unit


def test_breaker_state_machine_closed_open_half_open():
    clock = {"t": 0.0}
    transitions = []
    br = CircuitBreaker(failure_threshold=3, reset_timeout_s=1.0,
                        on_transition=lambda o, n: transitions.append((o, n)),
                        clock=lambda: clock["t"])
    assert br.state == "closed"
    br.on_failure()
    br.on_failure()
    assert br.state == "closed"
    br.on_success()                 # a success resets the streak
    br.on_failure()
    br.on_failure()
    br.on_failure()
    assert br.state == "open"
    assert br.reject_for() == pytest.approx(1.0)
    ok, wait = br.acquire()
    assert not ok and wait == pytest.approx(1.0)
    clock["t"] = 1.5                # probe window reached
    assert br.reject_for() is None  # submits admitted again
    ok, _ = br.acquire()            # first worker owns the probe
    assert ok and br.state == "half_open"
    ok2, _ = br.acquire()           # concurrent probe denied
    assert not ok2
    br.on_failure()                 # probe failed -> re-open
    assert br.state == "open"
    clock["t"] = 3.0
    ok, _ = br.acquire()
    assert ok
    br.on_success()                 # probe succeeded -> closed
    assert br.state == "closed"
    assert ("closed", "open") in transitions
    assert ("open", "half_open") in transitions
    assert ("half_open", "open") in transitions
    assert ("half_open", "closed") in transitions


def test_breaker_release_returns_unused_probe():
    clock = {"t": 0.0}
    br = CircuitBreaker(failure_threshold=1, reset_timeout_s=0.5,
                        clock=lambda: clock["t"])
    br.on_failure()
    clock["t"] = 1.0
    ok, _ = br.acquire()
    assert ok and br.state == "half_open"
    br.release()                    # dispatched nothing (empty poll)
    ok2, _ = br.acquire()           # the probe is available again
    assert ok2


# ---------------------------------------------------------------------------
# admission controller unit


def test_admission_estimate_math_and_cold_start():
    ac = AdmissionController(window=16, percentile=95.0, min_samples=4)
    assert ac.estimate_wait_ms(64, 32) is None       # cold: never sheds
    for _ in range(4):
        ac.observe(10.0)
    assert ac.estimate_wait_ms(64, 32) == pytest.approx(20.0)
    assert ac.estimate_wait_ms(1, 32) == pytest.approx(10.0)
    assert ac.estimate_wait_ms(0, 32) == pytest.approx(0.0)
    # sequential convention: one request per dispatch
    assert ac.estimate_wait_ms(3, 1) == pytest.approx(30.0)


def test_overloaded_error_carries_retry_after():
    assert ServerOverloadedError("x", retry_after_s=1.5).retry_after_s == 1.5
    assert ServerOverloadedError("y").retry_after_s is None
    # ServingTimeoutError stays catchable as RequestTimeoutError (the
    # loadgen/back-compat contract)
    assert issubclass(ServingTimeoutError, RequestTimeoutError)


def test_resilience_config_normalize():
    assert ResilienceConfig.normalize(None) is None
    assert ResilienceConfig.normalize(False) is None
    assert isinstance(ResilienceConfig.normalize(True), ResilienceConfig)
    cfg = ResilienceConfig(breaker_reset_s=9.0)
    assert ResilienceConfig.normalize(cfg) is cfg
    with pytest.raises(TypeError):
        ResilienceConfig.normalize("yes")


# ---------------------------------------------------------------------------
# queue: requeue + rows accounting + reply-time deadline


def test_queue_requeue_front_and_rows_accounting():
    q = RequestQueue(4)
    a, b = _req(rows=2, seed=0), _req(rows=3, seed=1)
    q.put(a)
    q.put(b)
    assert q.pending_rows() == 5
    got = q.take(max_rows=2, timeout=0)
    assert len(got) == 1 and got[0] is a
    assert q.pending_rows() == 3
    q.requeue(a)                    # crash recovery: back to the FRONT
    assert q.pending_rows() == 5
    got2 = q.take(max_rows=8, timeout=0)
    assert got2[0] is a and got2[1] is b
    assert q.pending_rows() == 0
    q.close(drain=True)
    q.requeue(a)                    # allowed mid-drain
    q2 = RequestQueue(2)
    q2.close(drain=False)
    with pytest.raises(ServerClosedError):
        q2.requeue(_req())


def test_complete_after_deadline_is_servingtimeout():
    req = _req(rows=1, deadline=time.monotonic() - 0.01)
    assert req.complete([np.zeros((1, N_OUT), np.float32)]) is False
    with pytest.raises(ServingTimeoutError):
        req.future.result(timeout=0)
    live = _req(rows=1, deadline=time.monotonic() + 60)
    assert live.complete([np.zeros((1, N_OUT), np.float32)]) is True
    assert live.future.result(timeout=0).shape == (1, N_OUT)


def test_deadline_expiring_during_exec_surfaces_timeout():
    """Satellite: a request that expires DURING exec must not complete
    as a stale success — its future gets ServingTimeoutError and the
    deadline timeout is recorded."""
    net = _net()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=4, buckets=(4,), max_delay_ms=0.5)
    try:
        x = np.zeros((2, N_IN), np.float32)
        pi.output(x)                # precompile: the timed exec is fast
        orig = pi._execute
        pi._execute = lambda *a, **k: (time.sleep(0.12), orig(*a, **k))[1]
        fut = pi.submit(x, timeout_ms=50)
        with pytest.raises(ServingTimeoutError):
            fut.result(timeout=10)
        assert pi.metrics.counters["requests_timed_out"] == 1
        assert pi.metrics.timeout_causes.get("deadline") == 1
    finally:
        pi._execute = orig
        pi.shutdown()


# ---------------------------------------------------------------------------
# SLO admission shedding


def test_slo_admission_sheds_doomed_requests():
    net = _net()
    gate = threading.Event()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=4, buckets=(4,), max_queue_len=64,
                           max_delay_ms=0.5, resilience=True)
    orig = pi._execute
    pi._execute = lambda *a, **k: (gate.wait(10), orig(*a, **k))[1]
    try:
        # warm the estimator: rolling p95 exec = 50 ms
        for _ in range(pi.admission.min_samples):
            pi.admission.observe(50.0)
        first = pi.submit(np.zeros((4, N_IN), np.float32))
        assert _wait_until(lambda: pi._queue.pending() == 0)
        filler = pi.submit(np.zeros((4, N_IN), np.float32))
        # 4 queued rows + 1 own row -> 2 dispatches x 50 ms = 100 ms
        # estimated wait > the 20 ms deadline: shed at submit, typed
        with pytest.raises(ServerOverloadedError) as ei:
            pi.submit(np.zeros((1, N_IN), np.float32), timeout_ms=20)
        assert ei.value.retry_after_s is not None
        assert ei.value.retry_after_s > 0
        assert pi.metrics.counters["requests_shed"] == 1
        # a deadline the estimate fits IS admitted; no-deadline requests
        # are never SLO-shed
        roomy = pi.submit(np.zeros((1, N_IN), np.float32),
                          timeout_ms=60_000)
        free = pi.submit(np.zeros((1, N_IN), np.float32))
        gate.set()
        for f in (first, filler, roomy, free):
            assert f.result(timeout=30) is not None
        assert pi.metrics.counters["requests_shed"] == 1
    finally:
        gate.set()
        pi.shutdown()


# ---------------------------------------------------------------------------
# poisoned-batch isolation


def test_poisoned_request_quarantined_healthy_bit_identical(served_batches):
    net = _net()
    chaos = ChaosMonkey(seed=5)
    storage = StatsStorage()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=8, max_delay_ms=25.0,
                           resilience=True, stats_storage=storage)
    ran = served_batches(pi)
    try:
        rng = np.random.default_rng(4)
        xs = [rng.normal(size=(2, N_IN)).astype(np.float32)
              for _ in range(3)]
        futs = [pi.submit(x) for x in xs]
        pf = pi.submit(chaos.poison_request(xs[0]))
        with pytest.raises(PoisonedRequestError) as ei:
            pf.result(timeout=60)
        assert ei.value.request_id is not None
        for f, x in zip(futs, xs):
            out = f.result(timeout=60)
            assert np.array_equal(out, ran.direct(net, x)), \
                "healthy co-batched request lost bit-identity"
        assert pi.metrics.counters["poisoned_quarantined"] == 1
        # the poison was co-batched (the coalescing window held all 4),
        # so isolation had to bisect
        assert pi.metrics.counters["bisect_splits"] >= 1
    finally:
        pi.shutdown()
    events = [r.get("event") for r in storage.of_type("faults")]
    assert "quarantine" in events


@pytest.mark.chaos
def test_transient_exec_faults_absorbed_zero_healthy_failures():
    """Satellite soak: deterministic transient exec failures under
    closed-loop load — every healthy request is served (the bisection
    retries absorb the faults), none fails or times out."""
    net = _net()
    chaos = ChaosMonkey(seed=11)
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=2,
                           max_batch_size=8, max_delay_ms=1.0,
                           max_queue_len=512, resilience=True)
    try:
        lg = LoadGenerator(
            pi, lambda rng, i: rng.normal(size=(2, N_IN))
            .astype(np.float32), seed=2)
        with chaos.failing_exec(pi, n=6, every=5) as state:
            res = lg.run_closed(n_requests=96, concurrency=4)
        assert state["left"] == 0, "injector never fired fully"
        assert res.n_failed == 0 and res.n_timed_out == 0 \
            and res.n_rejected == 0
        assert res.n_ok == 96
        assert pi.metrics.counters["exec_faults"] >= 6
        assert pi.metrics.counters["poisoned_quarantined"] == 0
    finally:
        pi.shutdown()


# ---------------------------------------------------------------------------
# circuit breaker e2e: /healthz 200 -> 503 -> 200 pinned


def _probe(url, route):
    try:
        with urllib.request.urlopen(url + route, timeout=5) as r:
            return r.status
    except urllib.error.HTTPError as e:
        return e.code


@pytest.mark.chaos
def test_breaker_opens_sheds_and_heals_healthz_pinned():
    net = _net()
    storage = StatsStorage()
    cfg = ResilienceConfig(breaker_failure_threshold=3,
                           breaker_reset_s=1.0, single_retries=0,
                           admission=False)
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=4, buckets=(4,),
                           max_delay_ms=0.5, resilience=cfg,
                           stats_storage=storage, telemetry_port=0)
    chaos = ChaosMonkey(seed=3)
    url = pi.telemetry.url
    try:
        assert _probe(url, "/healthz") == 200
        x = np.zeros((1, N_IN), np.float32)
        with chaos.failing_exec(pi, n=3, every=1):
            deadline = time.monotonic() + 20
            while pi.breaker.state != "open" and \
                    time.monotonic() < deadline:
                try:
                    f = pi.submit(x)
                except ServerOverloadedError:
                    break
                with pytest.raises(ServingError):
                    f.result(timeout=30)    # every admitted future typed
        assert pi.breaker.state == "open"
        assert _probe(url, "/healthz") == 503
        assert _probe(url, "/readyz") == 503
        with pytest.raises(ServerOverloadedError) as ei:
            pi.submit(x)                    # open: shed with backoff hint
        assert ei.value.retry_after_s is not None
        assert pi.metrics.counters["requests_shed"] >= 1
        assert pi.metrics.counters["breaker_opens"] == 1
        # injector exhausted: after the reset window a probe batch heals
        assert _wait_until(lambda: pi.breaker.reject_for() is None,
                           timeout=5)
        ok = pi.submit(x)
        assert ok.result(timeout=30) is not None
        assert _wait_until(lambda: pi.breaker.state == "closed", timeout=10)
        assert _probe(url, "/healthz") == 200
        assert _probe(url, "/readyz") == 200
        events = [(r.get("event"), r.get("cause"))
                  for r in storage.of_type("faults")]
        assert ("fault", "breaker_open") in events
        assert ("recovered", "breaker_closed") in events
    finally:
        pi.shutdown()


# ---------------------------------------------------------------------------
# worker supervision


@pytest.mark.chaos
def test_worker_crash_requeues_inflight_exactly_once():
    net = _net()
    storage = StatsStorage()
    cfg = ResilienceConfig(worker_backoff_base_s=0.01,
                           worker_backoff_max_s=0.05)
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=4, max_delay_ms=1.0,
                           resilience=cfg, stats_storage=storage)
    try:
        orig = pi._execute
        state = {"kills": 1}

        def killer(features, real_rows=None):
            if state["kills"] > 0:
                state["kills"] -= 1
                raise _Die("chaos: worker death mid-dispatch")
            return orig(features, real_rows=real_rows)

        pi._execute = killer
        x = np.random.default_rng(0).normal(size=(2, N_IN)) \
            .astype(np.float32)
        fut = pi.submit(x)
        out = fut.result(timeout=60)    # requeued + served post-restart
        assert np.array_equal(out, net.output(x).to_numpy())
        assert pi.metrics.counters["worker_restarts"] >= 1
        assert pi.metrics.counters["requests_requeued"] == 1
        events = [(r.get("event"), r.get("cause"))
                  for r in storage.of_type("faults")]
        assert ("fault", "worker_crash") in events
        assert ("recovered", "worker_restart") in events
    finally:
        pi._execute = orig
        pi.shutdown()


@pytest.mark.chaos
def test_request_lost_to_two_crashes_fails_typed():
    net = _net()
    cfg = ResilienceConfig(worker_backoff_base_s=0.01,
                           worker_backoff_max_s=0.05)
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=4, max_delay_ms=1.0,
                           resilience=cfg)
    try:
        orig = pi._execute
        state = {"kills": 2}

        def killer(features, real_rows=None):
            if state["kills"] > 0:
                state["kills"] -= 1
                raise _Die("chaos: worker death mid-dispatch")
            return orig(features, real_rows=real_rows)

        pi._execute = killer
        fut = pi.submit(np.zeros((2, N_IN), np.float32))
        with pytest.raises(ServingError, match="twice"):
            fut.result(timeout=60)      # exactly-once: no third dispatch
        assert pi.metrics.counters["worker_restarts"] >= 2
        assert pi.metrics.counters["requests_requeued"] == 1
        # the server still serves after healing
        x = np.zeros((2, N_IN), np.float32)
        assert np.array_equal(pi.output(x), net.output(x).to_numpy())
    finally:
        pi._execute = orig
        pi.shutdown()


@pytest.mark.chaos
def test_persistent_guard_errors_escalate_to_worker_restart():
    """Review regression: construction-time workers must read the
    die-after-N escalation from the CONFIG (the supervisor attribute is
    not yet assigned when they start) — a persistently failing worker
    loop gets the worker replaced, not retried forever."""
    net = _net()
    cfg = ResilienceConfig(worker_max_consecutive_errors=3,
                           worker_backoff_base_s=0.01,
                           worker_backoff_max_s=0.05)
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_delay_ms=0.5, resilience=cfg)
    try:
        state = {"left": 4}
        orig = pi._batcher.next_batch

        def flaky(poll_timeout=0.1):
            if state["left"] > 0:
                state["left"] -= 1
                raise RuntimeError("chaos: persistent loop bug")
            return orig(poll_timeout=poll_timeout)

        pi._batcher.next_batch = flaky
        # worker 1 dies after 3 consecutive guard errors; its
        # replacement eats the 4th, then the injector is spent
        assert _wait_until(
            lambda: pi.metrics.counters["worker_restarts"] >= 1,
            timeout=20)
        x = np.zeros((2, N_IN), np.float32)
        assert np.array_equal(pi.output(x), net.output(x).to_numpy())
    finally:
        pi.shutdown()


@pytest.mark.chaos
def test_worker_crash_holding_half_open_probe_does_not_wedge():
    """Review regression: a worker that dies while owning the
    half-open probe must not leave _probe_inflight latched — the
    supervisor's crash handler releases it, so the next probe can
    dispatch and the breaker can heal."""
    net = _net()
    cfg = ResilienceConfig(breaker_failure_threshold=1,
                           breaker_reset_s=0.2, single_retries=0,
                           worker_backoff_base_s=0.01,
                           worker_backoff_max_s=0.05)
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=4, buckets=(4,),
                           max_delay_ms=0.5, resilience=cfg)
    chaos = ChaosMonkey(seed=7)
    try:
        x = np.zeros((1, N_IN), np.float32)
        with chaos.failing_exec(pi, n=1, every=1):
            f = pi.submit(x)
            with pytest.raises(ServingError):
                f.result(timeout=30)        # opens the breaker
        assert pi.breaker.state == "open"
        assert _wait_until(lambda: pi.breaker.reject_for() is None,
                           timeout=5)
        # the PROBE dispatch dies worker-and-all
        orig = pi._execute
        state = {"kills": 1}

        def killer(features, real_rows=None):
            if state["kills"] > 0:
                state["kills"] -= 1
                raise _Die("chaos: probe-owning worker death")
            return orig(features, real_rows=real_rows)

        pi._execute = killer
        probe_req = pi.submit(x)
        # supervisor releases the leaked probe + requeues; the next
        # probe serves the request and closes the breaker
        assert probe_req.result(timeout=60) is not None
        assert _wait_until(lambda: pi.breaker.state == "closed",
                           timeout=30)
    finally:
        pi._execute = orig
        pi.shutdown()


@pytest.mark.chaos
def test_guard_level_error_releases_half_open_probe():
    """Review regression: an exception the worker guard absorbs while
    the worker HOLDS the half-open probe (e.g. next_batch raising after
    acquire) must release the probe — a leaked probe would gate every
    worker's dispatch forever with no escalation path."""
    net = _net()
    cfg = ResilienceConfig(breaker_failure_threshold=1,
                           breaker_reset_s=0.2, single_retries=0)
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=4, buckets=(4,),
                           max_delay_ms=0.5, resilience=cfg)
    chaos = ChaosMonkey(seed=1)
    try:
        x = np.zeros((1, N_IN), np.float32)
        with chaos.failing_exec(pi, n=1, every=1):
            with pytest.raises(ServingError):
                pi.submit(x).result(timeout=30)     # opens the breaker
        assert pi.breaker.state == "open"
        state = {"left": 1}
        orig = pi._batcher.next_batch

        def flaky(poll_timeout=0.1):
            # fire exactly while this worker owns the half-open probe
            if state["left"] > 0 and pi.breaker.state == "half_open":
                state["left"] -= 1
                raise RuntimeError("chaos: guard error holding the probe")
            return orig(poll_timeout=poll_timeout)

        pi._batcher.next_batch = flaky
        assert _wait_until(lambda: pi.breaker.reject_for() is None,
                           timeout=5)
        # without the guard's release() this request is never dispatched
        assert pi.submit(x).result(timeout=30) is not None
        assert state["left"] == 0, "injector never fired"
        assert _wait_until(lambda: pi.breaker.state == "closed",
                           timeout=10)
    finally:
        pi.shutdown()


def test_bisection_of_one_poisoned_request_does_not_open_breaker(
        served_batches):
    """Review regression: the bisection's internal retries of a single
    RAISING poisoned request must not count as consecutive breaker
    failures — only the top-level exec outcome feeds the breaker."""
    net = _net()
    cfg = ResilienceConfig(breaker_failure_threshold=3,
                           breaker_reset_s=60.0, single_retries=1)
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_batch_size=8, max_delay_ms=25.0,
                           resilience=cfg)
    ran = served_batches(pi)
    try:
        orig = pi._execute

        def nan_raises(features, real_rows=None):
            # a garbage request the device genuinely rejects
            if np.isnan(np.asarray(features[0])).any():
                raise RuntimeError("exec rejects this batch")
            return orig(features, real_rows=real_rows)

        pi._execute = nan_raises
        rng = np.random.default_rng(8)
        xs = [rng.normal(size=(1, N_IN)).astype(np.float32)
              for _ in range(3)]
        futs = [pi.submit(x) for x in xs]
        pf = pi.submit(np.full((1, N_IN), np.nan, np.float32))
        with pytest.raises(PoisonedRequestError):
            pf.result(timeout=60)
        for f, x in zip(futs, xs):
            assert np.array_equal(f.result(timeout=60), ran.direct(net, x))
        # the bisection issued several failing execs for the poison,
        # but the breaker saw only the ONE top-level failure
        assert pi.metrics.counters["bisect_splits"] >= 1
        assert pi.breaker.state == "closed"
        assert pi.metrics.counters["breaker_opens"] == 0
    finally:
        pi._execute = orig
        pi.shutdown()


def test_worker_guard_records_instead_of_silent_continue():
    """Satellite: the last-ditch guard must record the exception
    (metrics + fault-rail record), not swallow it silently."""
    net = _net()
    storage = StatsStorage()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=1,
                           max_delay_ms=0.5, stats_storage=storage)
    try:
        state = {"left": 2}
        orig = pi._batcher.next_batch

        def flaky(poll_timeout=0.1):
            if state["left"] > 0:
                state["left"] -= 1
                raise RuntimeError("chaos: worker loop bug")
            return orig(poll_timeout=poll_timeout)

        pi._batcher.next_batch = flaky
        x = np.zeros((2, N_IN), np.float32)
        out = pi.output(x)              # still serves afterwards
        assert np.array_equal(out, net.output(x).to_numpy())
        assert _wait_until(
            lambda: pi.metrics.failure_causes.get("worker_guard", 0) >= 2)
        assert any(r.get("event") == "worker_error"
                   and r.get("cause") == "worker_guard"
                   for r in storage.of_type("faults"))
    finally:
        pi.shutdown()


# ---------------------------------------------------------------------------
# submit vs shutdown(drain=True) race


def test_concurrent_submit_vs_drain_shutdown_no_dropped_futures(
        served_batches):
    """Satellite: every submit() that returns a future resolves it —
    drain serves the queue; a submit racing the close gets a typed
    error AT THE CALL SITE, never a silently-dropped future."""
    net = _net()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=2,
                           max_batch_size=8, max_delay_ms=0.5,
                           max_queue_len=1024)
    ran = served_batches(pi)
    x = np.random.default_rng(1).normal(size=(2, N_IN)).astype(np.float32)
    accepted = []
    lock = threading.Lock()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                f = pi.submit(x)
            except (ServerClosedError, ServerOverloadedError):
                if pi._closed:
                    return
                continue
            with lock:
                accepted.append(f)

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.1)
    pi.shutdown(drain=True)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert accepted, "race produced no admitted requests"
    # every payload is the same x: an answer is the model's output for
    # x at one of the places x had in one of the batches that ran
    direct = {d.tobytes() for d in ran.directs(net, x)}
    for f in accepted:
        assert f.result(timeout=30).tobytes() in direct


# ---------------------------------------------------------------------------
# checkpoint-driven hot reload


def _ulp_equal(a, b, atol=1e-5):
    """Exact up to co-batching rounding noise: XLA CPU execution of
    TRAINED nets is value-dependently off by a few ulps vs a solo exec
    depending on batch composition (pre-existing plain-path property,
    recorded in .claude/skills/verify/SKILL.md) — the reload test
    streams hundreds of co-batched copies, so composition varies run
    to run. atol=1e-5 is ~100x the observed noise and ~100x below the
    distance between the two parameter regimes being distinguished."""
    return np.array_equal(a, b) or \
        (a.shape == b.shape and np.allclose(a, b, rtol=0.0, atol=atol))


def test_hot_reload_mid_traffic_drops_nothing(tmp_path):
    net = _net()
    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, N_IN)).astype(np.float32)
    Y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, 64)]
    net.fit(X, Y, epochs=1, batch_size=32)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(1, model=net, blocking=True)
    x = rng.normal(size=(2, N_IN)).astype(np.float32)
    ckpt_out = net.output(x).to_numpy()     # outputs at the snapshot
    net.fit(X, Y, epochs=2, batch_size=32)  # train PAST the snapshot
    live_out = net.output(x).to_numpy()
    # the two regimes must sit far outside the _ulp_equal noise bound,
    # or the regime checks below could not discriminate them
    assert float(np.max(np.abs(ckpt_out - live_out))) > 1e-3
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=2,
                           max_delay_ms=1.0, max_queue_len=1024,
                           resilience=True)
    try:
        assert _ulp_equal(pi.output(x), live_out)
        results = []
        stop = threading.Event()

        def stream():
            while not stop.is_set():
                try:
                    results.append(pi.submit(x))
                except ServerOverloadedError:
                    time.sleep(0.001)

        t = threading.Thread(target=stream, daemon=True)
        t.start()
        time.sleep(0.03)
        report = pi.reload_from(mgr)        # hot swap, mid-traffic
        time.sleep(0.03)
        stop.set()
        t.join(timeout=10)
        assert report["step"] == 1 and report["arrays_swapped"] > 0
        assert report["rolled_back"] is False
        # the streamer may have filled the queue faster than workers
        # drain on a loaded machine — the probe backs off like any
        # well-behaved client instead of failing on the typed shed
        deadline = time.monotonic() + 30
        while True:
            try:
                probe = pi.output(x)
                break
            except ServerOverloadedError:
                assert time.monotonic() < deadline, "queue never drained"
                time.sleep(0.01)
        assert _ulp_equal(probe, ckpt_out)
        # zero dropped: every streamed request resolved with a real
        # answer (pre-swap params or post-swap params, nothing else)
        assert results
        for f in results:
            out = f.result(timeout=30)
            assert _ulp_equal(out, ckpt_out) or _ulp_equal(out, live_out)
        assert pi.metrics.counters["reloads"] == 1
        assert pi.metrics.resilience.get("last_reload_step") == 1
    finally:
        pi.shutdown()


def test_reload_canary_failure_rolls_back(tmp_path):
    from deeplearning4j_tpu.checkpoint.state import capture_training_state
    net = _net()
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    state = capture_training_state(net.samediff, epoch=0)
    state.arrays = {n: (np.full_like(a, np.nan)
                        if np.issubdtype(a.dtype, np.floating) else a)
                    for n, a in state.arrays.items()}
    mgr.save(7, state=state, blocking=True)     # a poisoned checkpoint
    storage = StatsStorage()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED,
                           max_delay_ms=1.0, resilience=True,
                           stats_storage=storage)
    try:
        x = np.random.default_rng(2).normal(size=(2, N_IN)) \
            .astype(np.float32)
        before = pi.output(x)
        with pytest.raises(ReloadFailedError) as ei:
            pi.reload_from(mgr)
        assert ei.value.rolled_back
        assert "non-finite" in str(ei.value)
        assert pi.metrics.counters["reload_rollbacks"] == 1
        assert pi.metrics.counters["reloads"] == 0
        # previous params restored: serving is bit-identical to before
        assert np.array_equal(pi.output(x), before)
        assert any(r.get("event") == "reload" and r.get("rolled_back")
                   for r in storage.of_type("faults"))
    finally:
        pi.shutdown()


def test_reload_strict_rejects_shape_mismatch(tmp_path):
    """Review regression: strict reload must reject same-name arrays
    whose SHAPES changed (silently swapping the matching subset would
    serve a chimera of old and new parameters)."""
    from deeplearning4j_tpu.checkpoint.state import capture_training_state
    net = _net()
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    state = capture_training_state(net.samediff, epoch=0)
    name = sorted(state.arrays)[0]
    state.arrays[name] = np.zeros(
        tuple(d + 1 for d in np.shape(state.arrays[name])), np.float32)
    mgr.save(2, state=state, blocking=True)
    with ParallelInference(net, mode=InferenceMode.INPLACE,
                           resilience=True) as pi:
        with pytest.raises(ReloadFailedError, match="different shapes"):
            pi.reload_from(mgr)
        assert pi.metrics.counters["reloads"] == 0
        # non-strict swaps the matching subset (and says how many)
        report = pi.reload_from(mgr, strict=False)
        assert report["arrays_swapped"] == len(state.arrays) - 1


def test_reload_requires_committed_checkpoint(tmp_path):
    net = _net()
    mgr = CheckpointManager(str(tmp_path / "empty"))
    with ParallelInference(net, mode=InferenceMode.INPLACE) as pi:
        with pytest.raises(ReloadFailedError, match="no committed"):
            pi.reload_from(mgr)


# ---------------------------------------------------------------------------
# the acceptance e2e: transient faults + poison + hot reload, one run


@pytest.mark.chaos
def test_chaos_e2e_selfheal_serving(tmp_path, served_batches):
    """ISSUE 9 acceptance: under injected transient exec failures plus
    one poisoned request, exactly the poisoned request is quarantined,
    every healthy request is served bit-identically to a fault-free
    run, and a mid-traffic hot reload drops zero requests."""
    net = _net()
    rng = np.random.default_rng(9)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(3, model=net, blocking=True)       # reload target == live
    xs = [rng.normal(size=(int(rng.integers(1, 4)), N_IN))
          .astype(np.float32) for _ in range(24)]
    chaos = ChaosMonkey(seed=13)
    storage = StatsStorage()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED, workers=2,
                           max_batch_size=8, max_delay_ms=2.0,
                           max_queue_len=256, resilience=True,
                           stats_storage=storage)
    ran = served_batches(pi)
    try:
        poison = chaos.poison_request(xs[0])
        with chaos.failing_exec(pi, n=4, every=5):
            futs = [pi.submit(x) for x in xs[:12]]
            pf = pi.submit(poison)
            report = pi.reload_from(mgr)        # mid-traffic hot swap
            futs += [pi.submit(x) for x in xs[12:]]
            outs = [f.result(timeout=60) for f in futs]
            with pytest.raises(PoisonedRequestError):
                pf.result(timeout=60)
        assert report["rolled_back"] is False
        for x, o in zip(xs, outs):
            # the fault-free run of the same padded batch
            assert np.array_equal(o, ran.direct(net, x)), \
                "healthy request not bit-identical to the fault-free run"
        assert pi.metrics.counters["poisoned_quarantined"] == 1
        assert pi.metrics.counters["exec_faults"] >= 1
        assert pi.metrics.counters["reloads"] == 1
        # futures resolve BEFORE the worker's observe_request accounting
        # — poll rather than race the last batch's metric update
        assert _wait_until(
            lambda: pi.metrics.counters["requests_served"] == len(xs))
    finally:
        pi.shutdown()
    events = [r.get("event") for r in storage.of_type("faults")]
    assert "quarantine" in events and "reload" in events


# ---------------------------------------------------------------------------
# observability wiring


def test_fold_serving_resilience_gauges_and_report_panel():
    from deeplearning4j_tpu.monitor.registry import MetricsRegistry
    from deeplearning4j_tpu.ui.report import render_report
    m = ServingMetrics()
    m.inc("requests_shed", 3)
    m.inc("worker_restarts")
    m.inc("reloads")
    m.set_resilience(breaker_state="open", last_reload_step=12,
                     last_reload_failed=False)
    reg = MetricsRegistry()
    reg.fold_serving(m)
    text = reg.to_prometheus_text()

    def gauge(name):
        mt = re.search(rf"^{name} (\S+)$", text, re.M)
        assert mt, f"{name} missing from exposition"
        return float(mt.group(1))

    assert gauge("dl4j_serving_requests_shed_total") == 3
    assert gauge("dl4j_serving_breaker_state") == 2          # open
    assert gauge("dl4j_serving_last_reload_step") == 12
    assert gauge("dl4j_serving_last_reload_failed") == 0
    assert "resilience:" in m.stats()
    st = StatsStorage()
    st.put(m.to_record())
    st.put({"type": "faults", "event": "quarantine", "origin": "serving",
            "cause": None, "t": time.time(), "request_id": 5})
    html = render_report(st)
    assert "Serving" in html
    assert "breaker" in html
    assert "quarantine" in html
    assert "unrendered record types" not in html


def test_breaker_state_surfaces_in_telemetry_provider():
    net = _net()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED,
                           max_delay_ms=0.5, resilience=True)
    try:
        snap = pi._telemetry_health()
        assert snap["breaker_state"] == "closed"
        assert snap["healthy"] and snap["ready"]
    finally:
        pi.shutdown()
