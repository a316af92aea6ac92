"""ParallelInference: a thread-safe, batching model server.

Reference parity: deeplearning4j-parallelwrapper's ParallelInference
(parallelism/ParallelInference.java:54) — the L7 layer that turns a
trained network into a shared inference service. The reference clones
the model once per worker thread and pins workers to devices; modes:

- ``SEQUENTIAL``: each request runs alone, in arrival order;
- ``BATCHED``: concurrent requests coalesce into one model invocation
  (BatchedInferenceObservable);
- ``INPLACE``: no queue — the holder model is invoked directly in the
  calling thread (lowest latency, no coalescing).

TPU-native redesign: worker replicas do NOT clone parameters — they
share ONE inference graph whose jit cache (one compiled XLA program per
bucket shape, see serving/batching.py) is the shared "replica". Device
execution is serialized behind a lock (a single XLA stream saturates
the chip; thread-level concurrency buys host-side overlap of padding /
scatter with device compute, not parallel kernels). Backpressure,
deadlines and drain come from serving/queue.py; counters and latency
histograms from serving/metrics.py; an optional per-batch
ProfilerSession drops xplane traces for the profiler/ tooling.
"""
from __future__ import annotations

import enum
import os
import threading
import time
from concurrent.futures import Future
from typing import Callable, List, NamedTuple, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.compilecache.cache import COMPILE_STATS
from deeplearning4j_tpu.monitor.trace import TRACER as _tracer
from deeplearning4j_tpu.serving.batching import (Batch, DynamicBatcher,
                                                 pad_to_bucket,
                                                 scatter_rows)
from deeplearning4j_tpu.serving.metrics import ServingMetrics
from deeplearning4j_tpu.serving.queue import (
    InferenceRequest, RequestQueue, RequestTimeoutError, ServerClosedError,
    ServerOverloadedError, ServingError, ServingTimeoutError,
    collapse_outputs)
from deeplearning4j_tpu.serving.resilience import (
    AdmissionController, CircuitBreaker, InflightSlot, PoisonedRequestError,
    ReloadFailedError, ResilienceConfig, WorkerSupervisor)


class InferenceMode(enum.Enum):
    """Request scheduling policy (reference: ParallelInference
    InferenceMode)."""

    SEQUENTIAL = "sequential"
    BATCHED = "batched"
    INPLACE = "inplace"


class ServingSpec(NamedTuple):
    """A network's serving contract: inference graph + IO names + the
    sync that pulls current trained parameters into it (produced by
    ``MultiLayerNetwork.serving_spec()`` / ``ComputationGraph
    .serving_spec()``)."""

    sd: object                      # inference-mode SameDiff
    input_names: List[str]
    output_names: List[str]
    sync: Callable[[], None]


def _extract_spec(model) -> ServingSpec:
    if hasattr(model, "serving_spec"):
        return ServingSpec(*model.serving_spec())
    raise TypeError(
        f"{type(model).__name__} is not servable: expected a "
        f"MultiLayerNetwork / ComputationGraph (anything exposing "
        f"serving_spec())")


class ParallelInference:
    """Shared, thread-safe inference front-end over a trained network.

    ::

        pi = ParallelInference(net, mode=InferenceMode.BATCHED,
                               max_batch_size=32, max_delay_ms=3.0)
        y = pi.output(x)                  # blocking
        fut = pi.submit(x)                # async -> Future
        ...
        pi.shutdown()                     # drains the queue

    ``output``/``submit`` accept a (rows, *features) array, one
    unbatched example (*features), or — for multi-input graphs in
    SEQUENTIAL/INPLACE mode — a tuple of per-input arrays. Results
    mirror the wrapped model's ``output()`` (single array, or a list for
    multi-output graphs). Overload raises
    :class:`ServerOverloadedError` at submit; expired deadlines surface
    as :class:`RequestTimeoutError` from the future.

    ``warmup_buckets`` kills the serving cold-start: ``True`` AOT-
    precompiles every batching bucket shape at construction (before any
    worker serves), a sequence of ints precompiles exactly those row
    counts — so the first live request of each bucket never waits
    seconds on XLA (the p99 cliff a lazy bucket miss causes). Warmed
    shapes are bit-identical to lazily-compiled ones and the
    ``compiles`` metric stays 0 for them (``warmup_compiles`` counts
    the prebuilt set). See docs/cold_start.md.

    ``resilience=True`` (or a :class:`ResilienceConfig`) arms the
    serving resilience rail (serving/resilience.py, docs/serving.md
    "Resilience"): SLO admission shedding, a circuit breaker on
    consecutive exec failures (surfaced through /healthz and /readyz),
    supervised workers with crash requeue, and bisecting poisoned-batch
    isolation. ``reload_from(manager)`` hot-swaps parameters from a
    committed checkpoint with a canary exec and automatic rollback.
    """

    def __init__(self, model,
                 mode: InferenceMode = InferenceMode.BATCHED,
                 workers: int = 2,
                 max_batch_size: int = 32,
                 max_delay_ms: float = 5.0,
                 max_queue_len: int = 256,
                 buckets: Optional[Sequence[int]] = None,
                 default_timeout_ms: Optional[float] = None,
                 stats_storage=None,
                 profile_dir: Optional[str] = None,
                 warmup_buckets=None,
                 telemetry_port: Optional[int] = None,
                 resilience=None,
                 memory_sample_every: Optional[int] = 64,
                 analyze=True):
        self.model = model
        self.mode = InferenceMode(mode)
        self.max_batch_size = int(max_batch_size)
        if self.mode is InferenceMode.INPLACE and \
                default_timeout_ms is not None:
            raise ValueError("INPLACE mode executes synchronously in the "
                             "calling thread — there is no queue wait for "
                             "default_timeout_ms to bound")
        self.default_timeout_ms = default_timeout_ms
        self.metrics = ServingMetrics()
        self.stats_storage = stats_storage
        self.profile_dir = profile_dir
        self._spec = _extract_spec(model)
        # pre-compile static analysis of the serving graph (analyze/,
        # docs/static_analysis.md): shape/hygiene/numerics findings as
        # named diagnostics BEFORE the first bucket compiles. True =
        # warn on error findings; "strict" = raise GraphAnalysisError;
        # False = off. The report lands in self.analysis and — when a
        # stats_storage is attached — as a {"type": "analysis"} record.
        self.analysis = None
        if analyze:
            from deeplearning4j_tpu.analyze import (GraphAnalysisWarning,
                                                    analyze_inference)
            self.analysis = analyze_inference(
                self._spec.sd, outputs=self._spec.output_names,
                inputs=self._spec.input_names)
            if stats_storage is not None:
                stats_storage.put(self.analysis.to_record())
            errs = self.analysis.errors()
            if errs:
                if str(analyze).lower() == "strict":
                    self.analysis.raise_if_errors()
                import warnings as _warnings
                _warnings.warn(
                    f"serving-graph static analysis found {len(errs)} "
                    f"error(s); pi.analysis.render() has the located "
                    f"diagnostics:\n"
                    + "\n".join(f.render() for f in errs[:5]),
                    GraphAnalysisWarning, stacklevel=2)
        if self.mode is InferenceMode.BATCHED and \
                len(self._spec.input_names) != 1:
            raise ValueError(
                f"BATCHED mode needs a single-input model; "
                f"{type(model).__name__} has inputs "
                f"{self._spec.input_names} — use SEQUENTIAL or INPLACE")
        self._ph_shapes = [self._placeholder_shape(n)
                           for n in self._spec.input_names]
        self._feat_rank = (len(self._ph_shapes[0])
                           if self._ph_shapes[0] is not None else None)
        self._exec_lock = threading.Lock()
        self._shapes_seen = set()
        # HBM telemetry at serving batch boundaries (monitor/memstats):
        # every Nth _execute publishes a {"type": "memory"} record into
        # stats_storage — pure host reads, off the exec lock. None = off.
        self._mem_every = (max(1, int(memory_sample_every))
                           if memory_sample_every else None)
        self._mem_count = 0
        self._req_id = 0
        self._id_lock = threading.Lock()
        self._closed = False
        self._spec.sync()           # pull current trained params once
        self._queue = RequestQueue(
            max_queue_len,
            on_timeout=lambda req: self.metrics.record_timeout("deadline"))
        self._batcher = DynamicBatcher(
            self._queue, max_batch_size=self.max_batch_size,
            max_delay_ms=max_delay_ms, buckets=buckets) \
            if self.mode is InferenceMode.BATCHED else None
        self.max_queue_len = int(max_queue_len)
        # resilience rail (serving/resilience.py, docs/serving.md
        # "Resilience"): SLO admission + circuit breaker here, worker
        # supervision at spawn below, bisection in _exec_group
        self.resilience = ResilienceConfig.normalize(resilience)
        self.admission: Optional[AdmissionController] = None
        self.breaker: Optional[CircuitBreaker] = None
        if self.resilience is not None:
            if self.resilience.admission:
                self.admission = AdmissionController(
                    window=self.resilience.window,
                    percentile=self.resilience.percentile,
                    min_samples=self.resilience.min_exec_samples)
            if self.resilience.breaker_failure_threshold > 0:
                self.breaker = CircuitBreaker(
                    failure_threshold=(
                        self.resilience.breaker_failure_threshold),
                    reset_timeout_s=self.resilience.breaker_reset_s,
                    on_transition=self._breaker_transition)
                self.metrics.set_resilience(breaker_state="closed")
        # live telemetry endpoint (monitor/server.py): /metrics serves
        # the serving counters/latency lanes via a scrape hook (pull
        # model — no publisher thread), /readyz reports queue depth and
        # goes 503 on overload or shutdown (the SLO shed-load signal).
        # None = off; 0 = pick a free loopback port (telemetry.url).
        self.telemetry = None
        if telemetry_port is not None:
            from deeplearning4j_tpu.monitor.server import TelemetryServer
            self.telemetry = TelemetryServer(storage=stats_storage,
                                             port=telemetry_port)
            self.telemetry.add_scrape_hook(
                lambda reg: reg.fold_serving(self.metrics))
            self.telemetry.add_health_provider("serving",
                                               self._telemetry_health)
        self.warmup_report: Optional[dict] = None
        if warmup_buckets:
            # before any worker thread exists: warmed shapes must be in
            # the execution cache before the first request can race them
            self.warmup(None if warmup_buckets is True else warmup_buckets)
        self._workers: List[threading.Thread] = []
        self._supervisor: Optional[WorkerSupervisor] = None
        if self.mode is not InferenceMode.INPLACE:
            if self.resilience is not None and self.resilience.supervise:
                self._supervisor = WorkerSupervisor(
                    spawn=self._spawn_worker,
                    n_workers=max(1, int(workers)),
                    queue=self._queue, metrics=self.metrics,
                    backoff_base_s=self.resilience.worker_backoff_base_s,
                    backoff_max_s=self.resilience.worker_backoff_max_s,
                    publish=self._publish_fault,
                    # a worker that dies holding the half-open probe
                    # must not gate dispatch forever
                    on_crash=(self.breaker.release
                              if self.breaker is not None else None))
            else:
                for i in range(max(1, int(workers))):
                    self._workers.append(
                        self._spawn_worker(i, InflightSlot()))

    # ------------------------------------------------------------------
    def _placeholder_shape(self, input_name: str):
        try:
            shape = self._spec.sd._vars[input_name].shape
            return tuple(shape) if shape is not None else None
        except Exception:
            return None

    def _next_id(self) -> int:
        with self._id_lock:
            self._req_id += 1
            return self._req_id

    # -- AOT warmup (compilecache/, docs/cold_start.md) -----------------
    def warmup(self, buckets: Optional[Sequence[int]] = None) -> dict:
        """AOT-precompile the serving program for each bucket row count
        so live traffic never waits on XLA.

        ``buckets=None`` takes the batching tier's bucket spec (BATCHED
        mode) or the default pow2 ladder up to ``max_batch_size``
        (SEQUENTIAL/INPLACE — where requests execute at their own row
        count, so only warmed sizes are covered; off-ladder sizes still
        compile lazily). Requires static feature dims on every input.
        Returns (and stores as ``warmup_report``) the bucket list, the
        compile/cache-hit accounting, ``seconds`` (the length of the
        ``serving.warmup`` span) and ``programs``, one row a bucket
        built by this call (``GenerativeServer.warmup``) — on a warm
        restart with a persistent cache configured, every entry is a
        cache hit and warmup is ~free."""
        with COMPILE_STATS.span("serving.warmup", cat="serving") as phase:
            report = self._warmup(buckets)
        report["seconds"] = round(phase.dur, 4)
        self.warmup_report = report
        return report

    def _warmup(self, buckets: Optional[Sequence[int]]) -> dict:
        from deeplearning4j_tpu.compilecache import install_compile_watcher
        install_compile_watcher()
        if buckets is None:
            if self._batcher is not None:
                buckets = self._batcher.spec.buckets
            else:
                from deeplearning4j_tpu.serving.batching import pow2_buckets
                # single-example requests are the common case and run at
                # their own row count in these modes — always include
                # bucket 1 (the pow2 ladder stops halving above it for
                # large max_batch_size)
                buckets = (1,) + tuple(pow2_buckets(self.max_batch_size))
        bucket_list = sorted({int(b) for b in buckets})
        if not bucket_list or bucket_list[0] <= 0:
            raise ValueError(f"invalid warmup buckets {buckets!r}")
        for name, shp in zip(self._spec.input_names, self._ph_shapes):
            if shp is None or any(d is None or d == -1 for d in shp[1:]):
                raise ValueError(
                    f"cannot warm up input {name!r}: feature dims {shp} "
                    f"are not static — pass concrete shapes to the "
                    f"model, or skip warmup for this graph")
        mark = COMPILE_STATS.mark()
        programs = []
        for b in bucket_list:
            ph = {name: (b,) + tuple(int(d) for d in shp[1:])
                  for name, shp in zip(self._spec.input_names,
                                       self._ph_shapes)}
            # _exec_lock: warmup() is public and may be called on a LIVE
            # server (pre-warming a new bucket) — the graph's compile
            # caches are only safe under the same lock _execute holds
            with self._exec_lock:
                from deeplearning4j_tpu.monitor import memstats
                at = COMPILE_STATS.mark()
                self._spec.sd.precompile_output(ph,
                                                self._spec.output_names)
                if COMPILE_STATS.precompiles > at["precompiles"]:
                    programs.append(
                        COMPILE_STATS.program_row(f"output_b{b}", at))
                # headroom guard (docs/serving.md "Resilience"): refuse
                # to mark a bucket warm whose compiled plan (temps +
                # outputs — arguments are the already-resident params)
                # exceeds the projected HBM headroom; a typed refusal
                # HERE beats a RESOURCE_EXHAUSTED on the first live
                # request that lands in the bucket. No-op where the
                # backend reports no bytes_limit (CPU). Looked up by
                # the exact shape SIGNATURE, not the label — labels
                # like "output_b4" alias across models in one process.
                plan = memstats.PLANS.get(tuple(sorted(
                    (n, tuple(shape)) for n, shape in ph.items())))
                if plan is not None:
                    need = int(plan.temp_bytes or 0) \
                        + int(plan.output_bytes or 0)
                    memstats.check_headroom(
                        need, f"serving warmup bucket {b} "
                              f"({type(self.model).__name__})")
                # mark the shape as seen (under the SAME lock hold — a
                # worker dispatching this bucket between compile and
                # mark would count a spurious lazy `compiles`) so the
                # metric counts only genuinely-unwarmed traffic
                # compiles; already-seen buckets (a repeat warmup() on
                # a live server) must not re-count
                sig = tuple(tuple(ph[n]) for n in self._spec.input_names)
                if sig not in self._shapes_seen:
                    self._shapes_seen.add(sig)
                    self.metrics.inc("warmup_compiles")
        return {
            "buckets": bucket_list, "programs": programs,
            **{k: v for k, v in COMPILE_STATS.delta(mark).items()
               if k in ("backend_compiles", "cache_hits", "cache_misses")}}

    def _prepare(self, x) -> tuple:
        """-> (list of per-input arrays with a batch dim, squeeze flag)."""
        if isinstance(x, (tuple, list)):
            arrs = [np.asarray(a) for a in x]
        else:
            arrs = [np.asarray(x)]
        if len(arrs) != len(self._spec.input_names):
            raise ValueError(
                f"model has {len(self._spec.input_names)} inputs "
                f"{self._spec.input_names}; got {len(arrs)} arrays")
        squeeze = False
        if len(arrs) == 1 and self._feat_rank is not None and \
                arrs[0].ndim == self._feat_rank - 1:
            arrs = [arrs[0][None]]      # single example: add the row dim
            squeeze = True
        if arrs[0].ndim == 0:
            raise ValueError("scalar input is not a request")
        # reject wrong feature shapes at admission: a mismatched request
        # must not reach a coalesced batch (it would fail the whole
        # dispatch, or worse, a worker thread)
        for arr, ph, name in zip(arrs, self._ph_shapes,
                                 self._spec.input_names):
            if ph is None:
                continue
            if arr.ndim != len(ph) or any(
                    d is not None and d != a
                    for d, a in zip(ph[1:], arr.shape[1:])):
                raise ValueError(
                    f"input {name!r} expects shape {ph} (leading dim = "
                    f"rows); got {arr.shape}")
        return arrs, squeeze

    # -- execution core (shared by every mode/worker) -------------------
    def _execute(self, features: List[np.ndarray],
                 real_rows: Optional[int] = None) -> List[np.ndarray]:
        """Run one forward. One compiled program per distinct input
        shape, shared by all workers (the jit cache lives on the
        inference graph); the lock serializes device execution AND makes
        the graph's internal caches safe under concurrent callers."""
        sig = tuple(tuple(f.shape) for f in features)
        rows = features[0].shape[0]
        real = rows if real_rows is None else real_rows
        ph = dict(zip(self._spec.input_names, features))
        t0 = time.perf_counter()
        with self._exec_lock, \
                _tracer.span("serving.exec", cat="serving", rows=real,
                             padding=rows - real):
            first_exec = sig not in self._shapes_seen
            if first_exec:
                self._shapes_seen.add(sig)
                self.metrics.inc("compiles")
            prof = self._profiler_session()
            try:
                # blocking device boundary: the stall watchdog
                # (integrity/watchdog.py) arms an adaptive deadline so
                # a wedged exec dumps forensics + flips /healthz
                # instead of hanging the lane silently; a first
                # (compiling) shape gets the compile grace
                from deeplearning4j_tpu.integrity.watchdog import \
                    guard as _wd_guard
                with _wd_guard("serving_execute", first=first_exec):
                    res = self._spec.sd.output(ph,
                                               self._spec.output_names)
            except Exception as e:
                # RESOURCE_EXHAUSTED → structured OOM with forensics
                # (per-device usage + the bucket program) instead of a
                # raw backend crash; published on the fault rail so
                # /healthz flips 503 (docs/observability.md)
                from deeplearning4j_tpu.monitor import memstats
                if memstats.is_resource_exhausted(e):
                    err = memstats.oom_error(e, program=f"serving_b{rows}")
                    self._publish_fault("oom", program=f"serving_b{rows}",
                                        rows=rows, error=repr(e))
                    raise err from e
                raise
            finally:
                if prof is not None:
                    prof.__exit__(None, None, None)
        outs = [np.asarray(res[n].to_numpy())
                for n in self._spec.output_names]
        exec_ms = (time.perf_counter() - t0) * 1000.0
        self.metrics.observe_batch(rows=real, padding=rows - real,
                                   exec_ms=exec_ms)
        if self.admission is not None:
            self.admission.observe(exec_ms)
        if self._mem_every is not None and self.stats_storage is not None:
            with self._id_lock:     # workers race this tail concurrently
                self._mem_count += 1
                fire = self._mem_count % self._mem_every == 0
            if fire:
                from deeplearning4j_tpu.monitor import memstats
                try:
                    self.stats_storage.put(
                        memstats.memory_record(source="serving"))
                except Exception:
                    pass    # a broken stats sink must not fail requests
        return outs

    def _profiler_session(self):
        if not self.profile_dir:
            return None
        from deeplearning4j_tpu.profiler import ProfilerSession
        n = self.metrics.counters["batches_dispatched"]
        sess = ProfilerSession(
            log_dir=os.path.join(self.profile_dir, f"batch_{n:06d}"))
        try:
            return sess.__enter__()
        except Exception:
            return None             # profiling is best-effort

    # -- worker loops ---------------------------------------------------
    def _spawn_worker(self, index: int, slot: InflightSlot
                      ) -> threading.Thread:
        t = threading.Thread(target=self._worker_main, args=(slot,),
                             name=f"ParallelInference-{index}",
                             daemon=True)
        t.start()
        return t

    def _worker_main(self, slot: InflightSlot) -> None:
        try:
            self._worker_loop(slot)
            slot.exited = True          # clean drain: do not restart
        except BaseException as e:      # noqa: BLE001 — supervisor's cue
            slot.crashed = e            # the supervisor requeues slot's
            #                             in-flight and respawns; without
            #                             one the crash is at least
            #                             visible in the failure metrics

    def _worker_loop(self, slot: InflightSlot) -> None:
        if self.mode is InferenceMode.BATCHED:
            loop_body = self._batched_step
        else:
            loop_body = self._sequential_step
        # gate on the CONFIG, not self._supervisor: the supervisor's
        # constructor spawns these threads before ParallelInference's
        # `self._supervisor =` assignment completes, so reading the
        # attribute here would race to None and permanently disable the
        # die-after-N escalation for every construction-time worker
        max_con = (self.resilience.worker_max_consecutive_errors
                   if self.resilience is not None and
                   self.resilience.supervise else None)
        consecutive = 0
        while True:
            try:
                progressed = loop_body(slot)
                consecutive = 0
                if progressed:
                    # evidence for the supervisor: this worker actually
                    # dispatched (a crash-looping worker is briefly
                    # alive without ever getting here)
                    slot.progressed = True
            except Exception as e:
                # last-ditch guard: per-request failure paths live
                # inside the step fns; anything reaching here is
                # unexpected. It is RECORDED (metrics + a fault-rail
                # record), never swallowed silently — and under a
                # supervisor a persistent failure kills the worker so
                # a fresh one can take over.
                consecutive += 1
                if self.breaker is not None:
                    # the step may have died while HOLDING the half-open
                    # probe (e.g. next_batch raised after acquire) — a
                    # leaked probe gates every worker's dispatch forever
                    self.breaker.release()
                stranded = slot.requests
                slot.requests = None
                for r in stranded or []:
                    r.fail(e)       # no-op for already-resolved futures
                self.metrics.record_failure(
                    e, cause="worker_guard",
                    n=max(1, len(stranded or [])))
                self._publish_fault("worker_error", cause="worker_guard",
                                    error=repr(e), consecutive=consecutive,
                                    stranded=len(stranded or []))
                if max_con is not None and consecutive >= max_con:
                    raise
                time.sleep(0.01)
                progressed = True
            if not progressed and self._queue.finished:
                return

    def _breaker_gate(self) -> Optional[bool]:
        """Dispatch-side breaker check. None → proceed (probe acquired
        if half-open); True/False → return that from the step fn (the
        breaker is open: nothing was popped, or the drain shed)."""
        if self.breaker is None:
            return None
        allowed, wait_s = self.breaker.acquire()
        if allowed:
            return None
        if self._queue.closed:
            # drain under an open breaker: futures must not be held
            # hostage until the probe window — shed them typed
            reqs = self._queue.take(self.max_batch_size, timeout=0,
                                    strict=False)
            if not reqs:
                return False
            err = ServerOverloadedError(
                "circuit breaker open during shutdown drain",
                retry_after_s=round(wait_s, 3))
            for r in reqs:
                r.fail(err)
            self.metrics.inc("requests_shed", len(reqs))
            return True
        time.sleep(min(0.05, max(wait_s, 0.001)))
        return False

    def _batched_step(self, slot: InflightSlot) -> bool:
        gated = self._breaker_gate()
        if gated is not None:
            return gated
        # the span is discarded on an empty poll — an idle server must
        # not fill the trace ring with 50 ms waits
        with _tracer.span("serving.batch", cat="serving") as bsp:
            batch = self._batcher.next_batch(poll_timeout=0.05)
            if batch is None:
                bsp.discard()
                if self.breaker is not None:
                    self.breaker.release()      # unused half-open probe
                return False
            bsp.set(rows=batch.rows, bucket=batch.bucket,
                    requests=len(batch.requests))
        # slot stays populated if an exception ESCAPES (worker death /
        # guard): the supervisor requeues exactly what was in flight.
        # It is cleared only once every popped future is resolved.
        slot.requests = batch.requests
        if self.resilience is not None and \
                self.resilience.isolate_poisoned:
            self._exec_group(batch.requests, created_t=batch.created_t,
                             features=batch.features)
            slot.requests = None
            return True
        try:
            outs = self._execute([batch.features], real_rows=batch.rows)
        except Exception as e:
            if self.breaker is not None:
                self.breaker.on_failure()
            self.metrics.inc("exec_faults")
            self.metrics.record_failure(e, n=len(batch.requests))
            batch.fail(e)
            slot.requests = None
            return True
        if self.breaker is not None:
            self.breaker.on_success()
        self._resolve_rows(batch.requests, outs, batch.created_t)
        slot.requests = None
        return True

    def _sequential_step(self, slot: InflightSlot) -> bool:
        gated = self._breaker_gate()
        if gated is not None:
            return gated
        reqs = self._queue.take(max_rows=1, timeout=0.05)
        if not reqs:
            if self.breaker is not None:
                self.breaker.release()          # unused half-open probe
            return False
        req = reqs[0]
        slot.requests = reqs            # cleared only once resolved (see
        t_pop = time.monotonic()        # _batched_step)
        try:
            outs = self._execute(list(req.x))
        except Exception as e:
            if self.breaker is not None:
                self.breaker.on_failure()
            self.metrics.inc("exec_faults")
            self.metrics.record_failure(e)
            req.fail(e)
            slot.requests = None
            return True
        if self.breaker is not None:
            self.breaker.on_success()
        with _tracer.span("serving.reply", cat="serving", requests=1):
            completed = req.complete(outs)
        slot.requests = None
        if not completed:
            self.metrics.record_timeout("deadline")
            return True
        done = time.monotonic()
        self.metrics.observe_request(
            queue_wait_ms=(t_pop - req.enqueue_t) * 1000.0,
            e2e_ms=(done - req.enqueue_t) * 1000.0)
        return True

    # -- resilient dispatch: bisecting poisoned-batch isolation ---------
    def _resolve_rows(self, reqs: Sequence[InferenceRequest],
                      outs: List[np.ndarray], created_t: float) -> None:
        """Scatter per-request row slices to futures, re-checking each
        deadline at reply time (a request that expired during exec gets
        ServingTimeoutError, not a stale success), and record latency
        for the completed ones."""
        with _tracer.span("serving.reply", cat="serving",
                          requests=len(reqs)):
            expired_ids = {id(r) for r in scatter_rows(reqs, outs)}
        if expired_ids:
            self.metrics.record_timeout("deadline", n=len(expired_ids))
        done = time.monotonic()
        for req in reqs:
            if id(req) in expired_ids:
                continue
            self.metrics.observe_request(
                queue_wait_ms=(created_t - req.enqueue_t) * 1000.0,
                e2e_ms=(done - req.enqueue_t) * 1000.0)

    def _nonfinite_requests(self, reqs: Sequence[InferenceRequest],
                            outs: List[np.ndarray]
                            ) -> List[InferenceRequest]:
        """Requests whose output rows contain non-finite values — how a
        NaN/garbage input actually manifests (XLA does not raise on it).
        Non-floating outputs (class indices, ...) are skipped."""
        float_outs = [o for o in outs
                      if np.issubdtype(np.asarray(o).dtype, np.floating)]
        if not float_outs:
            return []
        bad: List[InferenceRequest] = []
        off = 0
        for req in reqs:
            for o in float_outs:
                if not np.all(np.isfinite(o[off:off + req.rows])):
                    bad.append(req)
                    break
            off += req.rows
        return bad

    def _group_features(self, reqs: Sequence[InferenceRequest]) -> tuple:
        rows = sum(r.rows for r in reqs)
        bucket = self._batcher.spec.bucket_for(rows)
        features = pad_to_bucket(
            [np.asarray(r.x[0] if isinstance(r.x, (list, tuple))
                        else r.x) for r in reqs], bucket)
        return features, rows

    def _exec_group(self, reqs: List[InferenceRequest], created_t: float,
                    features: Optional[np.ndarray] = None,
                    top: bool = True) -> None:
        """Bisecting dispatch: execute ``reqs`` as one padded program;
        on failure (a raise, or — with ``check_finite_outputs`` — any
        non-finite output row) split in half and retry each side, down
        to singletons, so exactly the poisoned request is quarantined
        with :class:`PoisonedRequestError` while every healthy request
        resolves bit-identically to a fault-free run (row independence
        of the batched forward + bucket padding, docs/serving.md).
        Every request's future is resolved by the time this returns.

        Only the TOP-level exec outcome feeds the circuit breaker: the
        bisection's internal retries of one poisoned raising request
        would otherwise count log2(batch)+retries consecutive
        "failures" and open the breaker on a healthy device."""
        cfg = self.resilience
        rows = sum(r.rows for r in reqs)
        if features is None:
            features, rows = self._group_features(reqs)
        exc: Optional[BaseException] = None
        outs = None
        try:
            outs = self._execute([features], real_rows=rows)
        except Exception as e:
            exc = e
            self.metrics.inc("exec_faults")
            if top and self.breaker is not None:
                self.breaker.on_failure()
        if outs is not None:
            if top and self.breaker is not None:
                self.breaker.on_success()
            bad = self._nonfinite_requests(reqs, outs) \
                if cfg.check_finite_outputs else []
            if not bad:
                self._resolve_rows(reqs, outs, created_t)
                return
        if len(reqs) == 1:
            req = reqs[0]
            if exc is not None:
                # a RAISING singleton may have hit a transient exec
                # fault rather than carrying poison — retry before
                # declaring it poisoned (a non-finite OUTPUT is a pure
                # function of the input; no retry can change it)
                for _ in range(max(0, cfg.single_retries)):
                    try:
                        outs = self._execute([features], real_rows=rows)
                    except Exception as e:
                        exc = e
                        self.metrics.inc("exec_faults")
                        continue
                    if not (cfg.check_finite_outputs and
                            self._nonfinite_requests(reqs, outs)):
                        self._resolve_rows(reqs, outs, created_t)
                        return
                    break
            err = PoisonedRequestError(
                f"request {req.id} quarantined: "
                + (f"exec fails on it alone ({exc!r})" if exc is not None
                   else "its output rows are non-finite"),
                request_id=req.id)
            err.__cause__ = exc
            req.fail(err)
            self.metrics.inc("poisoned_quarantined")
            self.metrics.record_failure(err, cause="poisoned")
            self._publish_fault(
                "quarantine", request_id=req.id,
                error=repr(exc) if exc is not None
                else "non-finite outputs")
            return
        self.metrics.inc("bisect_splits")
        mid = len(reqs) // 2
        self._exec_group(reqs[:mid], created_t, top=False)
        self._exec_group(reqs[mid:], created_t, top=False)

    # -- client API -----------------------------------------------------
    def submit(self, x, timeout_ms: Optional[float] = None) -> Future:
        """Enqueue one request; returns a Future resolving to the model
        output rows for exactly this request. Raises
        :class:`ServerOverloadedError` (queue full) or
        :class:`ServerClosedError` (after shutdown) at the call site."""
        if self._closed:
            raise ServerClosedError("ParallelInference is shut down")
        features, squeeze = self._prepare(x)
        if self.mode is InferenceMode.BATCHED and \
                features[0].shape[0] > self.max_batch_size:
            raise ValueError(
                f"request of {features[0].shape[0]} rows exceeds "
                f"max_batch_size {self.max_batch_size}; split it or call "
                f"the model's output() directly")
        self.metrics.inc("requests_submitted")
        if self.mode is InferenceMode.INPLACE:
            if timeout_ms is not None:
                raise ValueError("INPLACE mode has no queue; timeout_ms "
                                 "is not applicable (use BATCHED or "
                                 "SEQUENTIAL for deadline-bounded "
                                 "requests)")
            return self._inplace(features, squeeze)
        timeout_ms = timeout_ms if timeout_ms is not None \
            else self.default_timeout_ms
        deadline = time.monotonic() + timeout_ms / 1000.0 \
            if timeout_ms is not None else None
        self._admit(features[0].shape[0], timeout_ms)
        fut: Future = Future()
        req = InferenceRequest(x=features, future=fut,
                               rows=features[0].shape[0], deadline=deadline,
                               squeeze=squeeze, id=self._next_id())
        with _tracer.span("serving.enqueue", cat="serving", id=req.id,
                          rows=req.rows):
            try:
                self._queue.put(req)
            except ServerOverloadedError:
                self.metrics.inc("requests_rejected")
                raise
        return fut

    def _inplace(self, features: List[np.ndarray], squeeze: bool) -> Future:
        fut: Future = Future()
        t0 = time.monotonic()
        try:
            outs = self._execute(features)
        except Exception as e:
            self.metrics.record_failure(e)
            fut.set_exception(e)
            return fut
        fut.set_result(collapse_outputs(outs, squeeze))
        self.metrics.observe_request(
            queue_wait_ms=0.0, e2e_ms=(time.monotonic() - t0) * 1000.0)
        return fut

    def output(self, x, timeout_ms: Optional[float] = None):
        """Blocking convenience around :meth:`submit` (reference:
        ParallelInference.output)."""
        return self.submit(x, timeout_ms=timeout_ms).result()

    def _admit(self, rows: int, timeout_ms: Optional[float]) -> None:
        """Resilience admission (serving/resilience.py): shed while the
        circuit breaker is open, and shed deadline-carrying requests
        whose estimated queue wait already exceeds their deadline —
        both as :class:`ServerOverloadedError` with a ``retry_after_s``
        backoff hint, at the call site, instead of letting the request
        expire in queue."""
        if self.breaker is not None:
            wait = self.breaker.reject_for()
            if wait is not None:
                self.metrics.inc("requests_shed")
                raise ServerOverloadedError(
                    f"circuit breaker open "
                    f"({self.breaker.failure_threshold} consecutive exec "
                    f"failures); next probe in {wait:.2f}s",
                    retry_after_s=round(wait, 3))
        if self.admission is None or timeout_ms is None:
            return
        if self.mode is InferenceMode.BATCHED:
            est = self.admission.estimate_wait_ms(
                self._queue.pending_rows() + rows, self.max_batch_size)
        else:           # sequential: one request per dispatch
            est = self.admission.estimate_wait_ms(
                self._queue.pending() + 1, 1)
        if est is not None and est > timeout_ms:
            self.metrics.inc("requests_shed")
            raise ServerOverloadedError(
                f"estimated queue wait {est:.1f} ms exceeds the "
                f"{timeout_ms:.1f} ms deadline — shed at admission "
                f"(queue depth x p{self.admission.percentile:g} exec "
                f"time)", retry_after_s=round(est / 1000.0, 3))

    def _publish_fault(self, event: str, **fields) -> None:
        """One ``{"type": "faults"}`` record on the PR-4 rail (shared
        with /healthz state folding). No-op without stats_storage."""
        if self.stats_storage is None:
            return
        try:
            self.stats_storage.put({"type": "faults", "event": event,
                                    "t": time.time(), "origin": "serving",
                                    **fields})
        except Exception:
            pass        # a broken stats sink must not take a worker down

    def _breaker_transition(self, old: str, new: str) -> None:
        self.metrics.set_resilience(breaker_state=new)
        if new == "open":
            self.metrics.inc("breaker_opens")
            self._publish_fault("fault", cause="breaker_open",
                                threshold=self.breaker.failure_threshold
                                if self.breaker is not None else None)
        elif new == "closed" and old in ("open", "half_open"):
            self._publish_fault("recovered", cause="breaker_closed")
        elif new == "half_open":
            self._publish_fault("breaker_probe", cause="breaker_half_open")

    def update_model(self) -> None:
        """Re-pull trained parameters into the serving graph (reference:
        ParallelInference.updateModel) — call after further fit()."""
        with self._exec_lock:
            self._spec.sync()

    # -- checkpoint-driven hot reload -----------------------------------
    def _canary_input(self, canary) -> dict:
        if canary is not None:
            if isinstance(canary, dict):
                return canary
            arrs = list(canary) if isinstance(canary, (tuple, list)) \
                else [canary]
            return {n: np.asarray(a)
                    for n, a in zip(self._spec.input_names, arrs)}
        ph = {}
        for name, shp in zip(self._spec.input_names, self._ph_shapes):
            if shp is None or any(d is None or d == -1 for d in shp[1:]):
                raise ReloadFailedError(
                    f"cannot build a default canary for input {name!r} "
                    f"(feature dims {shp} are not static) — pass canary=")
            ph[name] = np.zeros((1,) + tuple(int(d) for d in shp[1:]),
                                np.float32)
        return ph

    def reload_from(self, manager, step: Optional[int] = None,
                    canary=None, strict: bool = True,
                    headroom_guard: bool = True) -> dict:
        """Hot-swap serving parameters to a committed checkpoint, with
        no restart and no dropped requests.

        Reads ``step`` (default: the newest committed step) from a
        ``checkpoint.CheckpointManager``, swaps the matching parameter/
        state arrays into the serving graph BETWEEN batches (under the
        exec lock — in-flight dispatches finish on the old parameters,
        the next dispatch runs the new ones), then canary-execs a
        golden input (``canary=``, default zeros) and requires every
        floating output to be finite. A failed canary **rolls back** to
        the previous parameters and raises :class:`ReloadFailedError`
        (``rolled_back=True``) — the server keeps serving exactly what
        it served before the attempt. Returns the reload report dict;
        counters: ``reloads`` / ``reload_rollbacks``; a
        ``{"type": "faults"}`` ``reload`` record lands on the rail.

        The swap pours checkpoint arrays in by NAME (the same contract
        as ``update_model()``'s train→infer sync); a later
        ``update_model()`` re-syncs from the live training graph and
        overwrites a reload.

        ``headroom_guard`` (default on): refuse with a typed
        :class:`~deeplearning4j_tpu.memory.MemoryHeadroomError` —
        before anything is swapped — when the incoming arrays plus the
        canary program's temps exceed the projected HBM headroom
        (old and new parameters coexist through the swap; a mid-swap
        OOM would be strictly worse than a refusal). No-op on backends
        that report no memory limit."""
        import jax.numpy as jnp
        t0 = time.perf_counter()
        if step is None:
            res = manager.restore_latest()
            if res is None:
                raise ReloadFailedError(
                    "no committed checkpoint to reload from")
            step, state = res
        else:
            state = manager.restore(int(step))
        sd = self._spec.sd
        with self._exec_lock:
            live = set(sd.trainable_params()) | set(sd.state_vars_map())
            missing = sorted(live - set(state.arrays))
            if strict and missing:
                raise ReloadFailedError(
                    f"checkpoint step {step} does not cover serving "
                    f"parameters {missing[:5]}"
                    f"{'...' if len(missing) > 5 else ''} — the graph "
                    f"changed since the snapshot; pass strict=False to "
                    f"swap the matching subset",
                    report={"step": int(step)})
            mismatched = sorted(
                n for n, arr in state.arrays.items()
                if n in live and n in sd._arrays
                and tuple(sd._arrays[n].shape) != tuple(np.shape(arr)))
            if strict and mismatched:
                # same names, different shapes is still "the graph
                # changed since the snapshot" — silently swapping the
                # matching subset would serve a chimera of old and new
                # parameters behind a success report
                raise ReloadFailedError(
                    f"checkpoint step {step} arrays {mismatched[:5]}"
                    f"{'...' if len(mismatched) > 5 else ''} have "
                    f"different shapes than the serving graph; pass "
                    f"strict=False to swap the matching subset",
                    report={"step": int(step)})
            swap = {n: arr for n, arr in state.arrays.items()
                    if n in live and n in sd._arrays
                    and tuple(sd._arrays[n].shape) == tuple(np.shape(arr))}
            if headroom_guard:
                # old and new parameter sets coexist on-device through
                # the swap + canary (the rollback path needs the old
                # arrays alive), so the incoming bytes — plus the
                # canary program's temps — must fit the projected HBM
                # headroom. A typed refusal here (MemoryHeadroomError,
                # nothing swapped, server keeps serving) beats an OOM
                # mid-swap. No-op where no device reports a limit.
                from deeplearning4j_tpu.monitor import memstats
                incoming = sum(int(np.asarray(a).nbytes)
                               for a in swap.values())
                # the canary program's temps, when its exact shape was
                # warmed (sig lookup — a LABEL lookup would alias
                # across models in one process); a miss just omits the
                # canary term, the incoming-bytes check still applies
                canary_plan = None
                try:
                    cin = self._canary_input(canary)
                    canary_plan = memstats.PLANS.get(tuple(sorted(
                        (n, tuple(np.shape(v))) for n, v in cin.items())))
                except Exception:
                    pass
                if canary_plan is not None:
                    incoming += int(canary_plan.temp_bytes or 0) \
                        + int(canary_plan.output_bytes or 0)
                memstats.check_headroom(
                    incoming, f"hot reload of checkpoint step {step}")
            prev = {n: sd._arrays[n] for n in swap}
            with _tracer.span("serving.reload", cat="serving",
                              step=int(step), arrays=len(swap)):
                for n, arr in swap.items():
                    sd._arrays[n] = jnp.asarray(arr)
                failure = None
                try:
                    ph = self._canary_input(canary)
                    out = sd.output(ph, self._spec.output_names)
                    for n in self._spec.output_names:
                        o = np.asarray(out[n].to_numpy())
                        if np.issubdtype(o.dtype, np.floating) and \
                                not np.all(np.isfinite(o)):
                            failure = (f"canary produced non-finite "
                                       f"values in output {n!r}")
                            break
                except Exception as e:      # noqa: BLE001 — rollback path
                    failure = f"canary exec failed: {type(e).__name__}: {e}"
                if failure is not None:
                    for n, arr in prev.items():
                        sd._arrays[n] = arr
        report = {"step": int(step), "arrays_swapped": len(swap),
                  "rolled_back": failure is not None,
                  "seconds": round(time.perf_counter() - t0, 4)}
        if failure is not None:
            report["failure"] = failure
            self.metrics.inc("reload_rollbacks")
            self.metrics.set_resilience(last_reload_step=int(step),
                                        last_reload_failed=True)
            self._publish_fault("reload", step=int(step), failed=failure,
                                rolled_back=True)
            raise ReloadFailedError(
                f"hot reload of step {step} rolled back: {failure}",
                report=report, rolled_back=True)
        self.metrics.inc("reloads")
        self.metrics.set_resilience(last_reload_step=int(step),
                                    last_reload_failed=False)
        self._publish_fault("reload", step=int(step), arrays=len(swap),
                            seconds=report["seconds"])
        return report

    def _telemetry_health(self) -> dict:
        """Health-provider payload for the telemetry endpoint: serving
        queue depth vs capacity plus the circuit-breaker state. Not-
        healthy while the breaker is open (consecutive exec failures:
        the /healthz 503 window); not-ready when closed or the queue is
        full (admission would raise ServerOverloadedError — the signal
        an SLO-aware load balancer sheds on)."""
        depth = self._queue.pending()
        breaker_state = self.breaker.state if self.breaker is not None \
            else None
        healthy = not self._closed and breaker_state != "open"
        snap = {"queue_depth": depth,
                "queue_capacity": self.max_queue_len,
                "ready": healthy and depth < self.max_queue_len,
                "healthy": healthy}
        if breaker_state is not None:
            snap["breaker_state"] = breaker_state
        return snap

    # -- lifecycle ------------------------------------------------------
    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop intake; with ``drain`` (default) serve what is queued,
        otherwise fail pending futures with ServerClosedError. Further
        submits raise :class:`ServerClosedError`. Idempotent. The
        telemetry endpoint (``telemetry_port=``) stays up through the
        drain — /readyz reports not-ready immediately — and closes
        last."""
        if self._closed:
            return
        self._closed = True
        self._queue.close(drain=drain)
        if self._supervisor is not None:
            self._supervisor.stop(timeout=timeout)
        for t in self._workers:
            t.join(timeout=timeout)
        if self.stats_storage is not None:
            self.metrics.publish(self.stats_storage)
        if self.telemetry is not None:
            self.telemetry.close()

    def __enter__(self) -> "ParallelInference":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.shutdown(drain=exc_type is None)


__all__ = ["InferenceMode", "ParallelInference", "ServingSpec",
           "ServingError", "ServerOverloadedError", "ServerClosedError",
           "RequestTimeoutError", "ServingTimeoutError",
           "ResilienceConfig", "PoisonedRequestError", "ReloadFailedError"]
