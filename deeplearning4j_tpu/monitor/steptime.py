"""Per-window step-time attribution: data-wait vs dispatch vs flush.

Where a step's wall time goes used to be found by HAND-instrumenting
the loop; this module makes that breakdown a standing observable. The window executor (autodiff/window.py) and the
per-step tier (samediff.fit) already emit ``window``/``step`` spans
with ``data_wait`` / ``dispatch`` / ``flush`` children into
``monitor.trace.TRACER``; :class:`MonitorListener` drains those spans
at the flush boundaries the host ALREADY syncs on — no extra device
syncs, so a clean run's losses stay bit-identical with monitoring on
or off (asserted in tests/test_monitor.py) — and publishes:

- ``{"type": "steptime"}`` breakdown records (per listener flush:
  wall seconds attributed to data-wait / dispatch / flush / other,
  rolling step-time percentiles) into the run's StatsStorage, rendered
  by ui/report.py as a stacked chart;
- ``{"type": "metrics"}`` registry snapshots at epoch boundaries;
- ``{"type": "trace"}`` span dumps (bounded) at training end, rendered
  as the report's swimlane timeline;
- straggler flags: :class:`StragglerWatcher` keeps an EMA of step time
  and records a ``{"type": "steptime", "event": "straggler"}`` record
  when a window's per-step time spikes past ``threshold ×`` the EMA —
  the step-time rail analogous to the faults rail's LossSpikeWatcher.

Semantics of the stages (host wall time, per window):

- ``data_wait`` — the consumer blocked on the stager queue / iterator
  (a data-bound run shows this dominating);
- ``dispatch``  — enqueueing the compiled window program (async; this
  is HOST dispatch overhead, not device compute — a dispatch-bound run
  shows many short windows with high dispatch share);
- ``flush``     — the device→host loss-burst sync at listener
  boundaries (the only place a healthy fused run actually waits on the
  device, so device-bound time surfaces here);
- ``other``     — window wall time not inside any child span
  (listener callbacks, checkpoint capture on the training thread, …).
"""
from __future__ import annotations

import bisect
import time
from typing import Dict, List, Optional, Sequence

from deeplearning4j_tpu.monitor.registry import MetricsRegistry
from deeplearning4j_tpu.monitor.trace import TRACER, Span, Tracer

#: span names treated as one attributed training step unit
_WINDOW_NAMES = ("window", "step")
_STAGE_NAMES = ("data_wait", "dispatch", "flush")


def window_rows(spans: Sequence[Span]) -> List[dict]:
    """Group a span batch into per-window rows: each ``window``/``step``
    span plus the stage children recorded under it. Returns dicts with
    ``k`` (steps in the window), ``dur_s``, per-stage seconds and the
    derived ``other_s``."""
    rows: Dict[int, dict] = {}
    for sp in spans:
        if sp.name in _WINDOW_NAMES:
            rows[sp.sid] = {
                "name": sp.name, "sid": sp.sid, "t0": sp.t0,
                "dur_s": sp.dur, "k": int(sp.args.get("k", 1)),
                "iteration": sp.args.get("iteration"),
                **{f"{s}_s": 0.0 for s in _STAGE_NAMES}}
    for sp in spans:
        if sp.name in _STAGE_NAMES and sp.parent in rows:
            rows[sp.parent][f"{sp.name}_s"] += sp.dur
    out = []
    for row in sorted(rows.values(), key=lambda r: r["t0"]):
        row["other_s"] = max(0.0, row["dur_s"] - sum(
            row[f"{s}_s"] for s in _STAGE_NAMES))
        out.append(row)
    return out


class RollingPercentiles:
    """Rolling-window order statistics over the last ``window`` values
    (bisect-maintained sorted list: O(log n) insert, O(1) percentile)."""

    def __init__(self, window: int = 512):
        self.window = int(window)
        self._ring: List[float] = []
        self._sorted: List[float] = []
        self._next = 0

    def add(self, value: float) -> None:
        v = float(value)
        if len(self._ring) < self.window:
            self._ring.append(v)
        else:
            old = self._ring[self._next]
            del self._sorted[bisect.bisect_left(self._sorted, old)]
            self._ring[self._next] = v
            self._next = (self._next + 1) % self.window
        bisect.insort(self._sorted, v)

    def __len__(self) -> int:
        return len(self._sorted)

    def percentile(self, p: float) -> float:
        if not self._sorted:
            return 0.0
        idx = min(len(self._sorted) - 1,
                  max(0, int(round(p / 100.0 * (len(self._sorted) - 1)))))
        return self._sorted[idx]


class StragglerWatcher:
    """EMA step-time spike detector.

    ``observe(step_s, ...)`` returns a straggler event dict (and
    optionally records it) when a step time exceeds ``threshold ×`` the
    exponential moving average, after ``warmup`` observations. State
    resets via ``reset()`` — FaultTolerantFit calls it on rollback so
    replayed timelines are judged fresh (same contract as the faults
    watchers)."""

    def __init__(self, threshold: float = 3.0, alpha: float = 0.1,
                 warmup: int = 8, storage=None):
        if threshold <= 1.0:
            raise ValueError("threshold must be > 1 (it multiplies the "
                             "EMA)")
        self.threshold = float(threshold)
        self.alpha = float(alpha)
        self.warmup = int(warmup)
        self.storage = storage
        self.events: List[dict] = []
        self.reset()

    def reset(self) -> None:
        self._ema: Optional[float] = None
        self._seen = 0

    def observe(self, step_s: float, iteration=None,
                k: int = 1) -> Optional[dict]:
        step_s = float(step_s)
        self._seen += 1
        ema = self._ema
        if ema is not None and self._seen > self.warmup and \
                step_s > self.threshold * ema:
            ev = {"type": "steptime", "event": "straggler",
                  "t": time.time(), "step_s": round(step_s, 6),
                  "ema_s": round(ema, 6),
                  "ratio": round(step_s / ema, 3), "k": int(k)}
            if iteration is not None:
                ev["iteration"] = int(iteration)
            self.events.append(ev)
            if self.storage is not None:
                self.storage.put(ev)
            # the spike does NOT feed the EMA: one straggler must not
            # raise the bar for detecting the next one
            return ev
        self._ema = step_s if ema is None else \
            (1.0 - self.alpha) * ema + self.alpha * step_s
        return None


class MonitorListener:
    """The observability listener: span-fed step-time breakdowns,
    straggler flags, and metrics-registry snapshots, all riding the
    flush boundaries fit() already syncs on.

    ::

        enable_tracing()
        mon = MonitorListener(storage)
        sd.fit(it, epochs=3, listeners=[mon, ...])
        write_report(storage, "report.html")   # timeline + breakdown

    Works on every fit tier that delivers listener bursts (fused
    windows and per-step; the scanned tier has no listeners by
    definition). With tracing disabled it degrades to publishing
    dispatch-derived metrics only — it never forces a device sync
    either way.
    """

    needs_params = False

    def __init__(self, storage, registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None, frequency: int = 10,
                 straggler: Optional[StragglerWatcher] = None,
                 rolling_window: int = 512, trace_record_spans: int = 400,
                 serve_port: Optional[int] = None,
                 serve_host: str = "127.0.0.1",
                 memory: bool = True):
        self.storage = storage
        self.registry = registry if registry is not None else \
            MetricsRegistry()
        self.tracer = tracer if tracer is not None else TRACER
        self.frequency = max(1, int(frequency))
        self.straggler = straggler
        if self.straggler is not None and self.straggler.storage is None:
            self.straggler.storage = storage
        self.rolling = RollingPercentiles(rolling_window)
        self.trace_record_spans = int(trace_record_spans)
        self._mark = 0
        self._dropped = 0
        self._compile_snap: Optional[dict] = None
        # live telemetry endpoint (monitor/server.py): serve_port=0
        # picks a free port; the server shares this listener's storage,
        # registry and tracer, and stays up after training ends (a
        # dashboard scraping between fits must not 404). None = off.
        self._serve_port = serve_port
        self._serve_host = serve_host
        self.server = None
        self._last_flush_t: Optional[float] = None
        self._last_iteration: Optional[int] = None
        # HBM telemetry (monitor/memstats.py): one {"type": "memory"}
        # record per listener flush — pure host reads at boundaries the
        # host ALREADY syncs on, so clean runs stay bit-identical —
        # plus plan capture for lazily-compiled programs and the live
        # MFU-estimate gauge. memory=False turns the whole rail off.
        self.memory = bool(memory)
        # streaming-pipeline telemetry (datapipe/): (pipeline id,
        # cumulative-counter snapshot) for per-flush deltas; None until
        # the first flush sees a registered pipeline
        self._datapipe_snap: Optional[tuple] = None
        self._published_plans: set = set()
        # id -> report (the ref pins the object so a recycled id can't
        # suppress a fresh report's publish); bounded FIFO — a
        # long-lived listener over many graph versions must not pin
        # every report forever
        self._published_analyses: dict = {}
        self._published_analyses_cap = 32

    def reset(self) -> None:
        """Rollback hook (faults/recovery.py resets stateful listeners):
        discard EMA/rolling state from the abandoned timeline."""
        self.rolling = RollingPercentiles(self.rolling.window)
        if self.straggler is not None:
            self.straggler.reset()

    # -- listener protocol ----------------------------------------------
    def on_training_start(self, sd) -> None:
        self._mark = self.tracer.mark()
        # static-analysis findings (analyze/): fit() stores its report
        # on the graph before listeners start — publish each report
        # ONCE (repeat fits of the same graph version reuse the cached
        # report object) and fold through the storage's incremental
        # fold mark like every other record
        report = getattr(sd, "last_analysis", None)
        if report is not None and id(report) not in self._published_analyses:
            self._published_analyses[id(report)] = report
            while len(self._published_analyses) > \
                    self._published_analyses_cap:
                self._published_analyses.pop(
                    next(iter(self._published_analyses)))
            self.storage.put(report.to_record())
            self.registry.fold_storage(self.storage)
        if self.memory:
            # arm lazy-compile plan capture: a monitored fit's first
            # dispatch per shape compiles through the AOT path (same
            # lowering, one compile either way) so its memory plan —
            # and the MFU numerator — is inspectable
            from deeplearning4j_tpu.monitor import memstats
            memstats.enable_plan_capture()
        if self._serve_port is not None and self.server is None:
            from deeplearning4j_tpu.monitor.server import TelemetryServer
            self.server = TelemetryServer(
                storage=self.storage, registry=self.registry,
                tracer=self.tracer, host=self._serve_host,
                port=self._serve_port)
            self.server.add_health_provider("training", self._heartbeat)

    def on_epoch_start(self, sd, epoch: int) -> None:
        pass

    def _heartbeat(self) -> dict:
        """Health-provider payload for the telemetry server: the wall
        time and iteration of the last listener flush — /healthz's
        last-step-age source that works even before any record with a
        wall timestamp lands in the storage."""
        out = {}
        if self._last_flush_t is not None:
            out["last_step_t"] = self._last_flush_t
        if self._last_iteration is not None:
            out["last_iteration"] = self._last_iteration
        return out

    def tensorstats_done(self, sd, epoch: int, records) -> None:
        """The tensorstats rail (monitor/tensorstats.py): persist every
        fetched per-layer record and fold it into ``dl4j_layer_*`` —
        through the storage's incremental fold mark (see
        ``iterations_done``), never per-record."""
        for rec in records:
            self.storage.put(rec)
        self.registry.fold_storage(self.storage)

    def _publish_memory(self, epoch: int, iterations,
                        prev_flush_t: Optional[float],
                        now: float) -> None:
        """The memory half of a flush: one ``{"type": "memory"}``
        record (pure host reads — no device sync) plus, when an active
        program plan is known, the live MFU-estimate gauge (plan flops
        per step ÷ measured step time ÷ device peak)."""
        from deeplearning4j_tpu.monitor import memstats
        rec = memstats.memory_record(
            epoch=epoch,
            iteration=int(iterations[-1]) if iterations else None)
        self.storage.put(rec)
        step_s = self.rolling.percentile(50) if len(self.rolling) else 0.0
        if not step_s and prev_flush_t is not None and iterations:
            # tracing disabled: no span-derived step times — fall back
            # to flush wall time over the burst's step count
            step_s = max(0.0, now - prev_flush_t) / max(1, len(iterations))
        if step_s:
            est = memstats.mfu_estimate(step_s)
            if est is not None:
                mfu, fps = est
                self.registry.set_gauge(
                    "mfu_estimate", round(mfu, 6),
                    help="live MFU estimate: active-plan flops/step / "
                         "measured step time / device peak flops")
                self.registry.set_gauge(
                    "plan_flops_per_step", fps,
                    help="active compiled program's flops per train "
                         "step (cost_analysis)")

    def _publish_datapipe(self, sd, epoch: int,
                          steptime_rec: Optional[dict],
                          prev_flush_t: Optional[float],
                          now: float) -> None:
        """The data-plane half of a flush: one ``{"type": "datapipe"}``
        record of per-flush DELTAS of the registered streaming
        pipeline's cumulative counters (records/sec, retries,
        quarantines, supervision decisions, per-worker utilization) —
        pure host reads, published only when a pipeline is active."""
        dp = getattr(sd, "_active_datapipe", None)
        if dp is None or not hasattr(dp, "stats"):
            return
        snap = dp.stats()
        # snapshot keyed by pipeline IDENTITY — the OBJECT, pinned, not
        # id(): a listener reused across fits with different pipelines
        # must not delta the new pipeline's counters against the old
        # one's, and a recycled CPython id would alias them (the same
        # recycled-id class the analysis-report pin set guards against)
        prev_dp, prev = self._datapipe_snap or (None, {})
        if prev_dp is not None and prev_dp is not dp:
            prev = {}
        self._datapipe_snap = (dp, snap)
        rec = {"type": "datapipe", "t": now, "epoch": int(epoch)}
        for key in ("records", "batches", "read_retries", "shard_reads",
                    "bytes_read", "rows_quarantined", "records_withheld",
                    "worker_restarts", "requeues", "slow_reads"):
            rec[key] = max(0, snap.get(key, 0) - prev.get(key, 0))
        for key in ("quarantined_shards", "passes_started", "workers"):
            if snap.get(key) is not None:
                rec[key] = snap[key]
        dt = max(1e-9, now - prev_flush_t) if prev_flush_t else None
        if dt is not None:
            rec["records_per_sec"] = round(rec["records"] / dt, 2)
        if steptime_rec:
            wall = steptime_rec.get("wall_s") or 0.0
            if wall:
                rec["data_wait_frac"] = round(
                    steptime_rec.get("data_wait_s", 0.0) / wall, 4)
        busy = snap.get("worker_busy_s") or {}
        prev_busy = prev.get("worker_busy_s") or {}
        if dt is not None and busy:
            rec["worker_utilization"] = {
                str(w): round(min(1.0, max(
                    0.0, busy.get(w, 0.0)
                    - prev_busy.get(w, 0.0)) / dt), 4)
                for w in busy}
        self.storage.put(rec)

    def iterations_done(self, sd, epoch: int, iterations, losses) -> None:
        now = time.time()
        prev_flush_t = self._last_flush_t
        self._last_flush_t = now
        if iterations:
            self._last_iteration = int(iterations[-1])
        spans, self._mark, dropped = self.tracer.drain(self._mark)
        self._dropped += dropped
        rows = window_rows(spans)
        if self.memory:
            self._publish_memory(epoch, iterations, prev_flush_t, now)
        if not rows:
            self._publish_datapipe(sd, epoch, None, prev_flush_t, now)
            self.registry.fold_storage(self.storage)
            return
        rec = {"type": "steptime", "epoch": int(epoch), "t": time.time(),
               "windows": len(rows), "steps": sum(r["k"] for r in rows),
               "wall_s": round(sum(r["dur_s"] for r in rows), 6)}
        # stage spans OUTSIDE any drained window (the epoch-end flush,
        # and the flush fired between a window's close and this
        # delivery) still belong to this burst's wall time — count them
        # into the totals so flush time is never silently dropped
        window_sids = {r["sid"] for r in rows}
        orphans = {s: 0.0 for s in _STAGE_NAMES}
        for sp in spans:
            if sp.name in _STAGE_NAMES and sp.parent not in window_sids:
                orphans[sp.name] += sp.dur
        for stage in ("data_wait", "dispatch", "flush"):
            rec[f"{stage}_s"] = round(
                sum(r[f"{stage}_s"] for r in rows) + orphans[stage], 6)
        rec["other_s"] = round(sum(r["other_s"] for r in rows), 6)
        for r in rows:
            # per-step time EXCLUDES the flush child: the flush is a
            # burst sync amortized over the whole cadence, carried by
            # whichever window crossed the boundary — folding it in
            # would make the straggler watcher flag every flush-carrying
            # window of a healthy sparse-cadence run (flush cost is
            # reported separately in flush_s)
            step_s = max(0.0, r["dur_s"] - r["flush_s"]) / max(1, r["k"])
            self.rolling.add(step_s)
            if self.straggler is not None:
                self.straggler.observe(step_s, iteration=r.get("iteration"),
                                       k=r["k"])
        rec["step_ms_p50"] = round(1e3 * self.rolling.percentile(50), 4)
        rec["step_ms_p95"] = round(1e3 * self.rolling.percentile(95), 4)
        rec["step_ms_max"] = round(1e3 * self.rolling.percentile(100), 4)
        if iterations:
            rec["iteration"] = int(iterations[-1])
        if self._dropped:
            rec["spans_dropped"] = self._dropped
        self.storage.put(rec)
        self._publish_datapipe(sd, epoch, rec, prev_flush_t, now)
        # fold through the storage's incremental per-(registry, storage)
        # high-water mark, NOT per-record: a TelemetryServer sharing
        # this registry folds the same storage on every /metrics scrape,
        # and the shared mark is what keeps counter-typed series (the
        # fold adapters are not idempotent) from reading 2x. This also
        # picks up records other writers (checkpoint manager, fault
        # rail, serving) put into the same storage between flushes.
        self.registry.fold_storage(self.storage)

    def on_epoch_end(self, sd, epoch: int, mean_loss) -> None:
        self.registry.fold_dispatch(getattr(sd, "last_fit_stats", None),
                                    epoch=epoch)
        # compile accounting rides the same cadence: whenever the
        # process-wide counters moved since the last publish (first
        # epoch covers compiles that predate the fit, e.g. precompile),
        # fold them and emit the {"type": "compile"} record — without
        # this a monitored run never surfaces the cache-hit/miss split
        # and ui/report's Compilation section only exists for callers
        # that publish COMPILE_STATS by hand
        from deeplearning4j_tpu.compilecache import COMPILE_STATS
        snap = COMPILE_STATS.snapshot()
        if any(snap.values()) and snap != self._compile_snap:
            self._compile_snap = snap
            self.registry.fold_compile(COMPILE_STATS)
            COMPILE_STATS.publish(self.storage)
        if self.memory:
            # plans captured for THIS graph (precompile, serving
            # warmup, lazy-compile promotion) become {"type":
            # "memory_plan"} records — the per-executable footprint
            # ui/report's Memory panel charts. Filtered by graph
            # identity: the registry is process-global, and a second
            # model's listener must not republish the first model's
            # plans into its own storage as if they were its run's.
            from deeplearning4j_tpu.monitor import memstats
            gid = memstats.graph_key(sd)
            for plan in memstats.PLANS.plans():
                if plan.graph is not None and plan.graph != gid:
                    continue
                key = (plan.label, plan.sig)
                if key in self._published_plans:
                    continue
                self._published_plans.add(key)
                self.storage.put(plan.to_record())
            self.registry.fold_storage(self.storage)
        self.registry.publish(self.storage)

    def on_training_end(self, sd) -> None:
        spans = self.tracer.spans()
        if spans:
            t0 = self.tracer.epoch
            tail = spans[-self.trace_record_spans:]
            self.storage.put({
                "type": "trace", "t": time.time(),
                "spans_total": len(spans), "spans": [
                    s.to_dict(t0) for s in tail]})


__all__ = ["MonitorListener", "RollingPercentiles", "StragglerWatcher",
           "window_rows"]
