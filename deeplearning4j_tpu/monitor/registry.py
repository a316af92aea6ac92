"""MetricsRegistry — one labeled namespace over every subsystem's signals.

Serving counters (``serving/metrics.ServingMetrics``), the fit tiers'
dispatch accounting (``sd.last_fit_stats``), checkpoint commit timings,
fault-rail events and step-time breakdowns each grew up with their own
record shape. This registry folds them into ONE namespace of labeled
counters / gauges / histograms so a scrape endpoint, a dashboard, or a
test can ask "how is this process doing" without knowing five schemas:

    reg = MetricsRegistry()
    reg.fold_serving(server.metrics)
    reg.fold_dispatch(sd.last_fit_stats)
    reg.fold_storage(stats_storage)        # checkpoint/faults/steptime
    print(reg.to_prometheus_text())        # standard exposition format
    reg.publish(stats_storage)             # {"type": "metrics"} record

Metric identity is ``name + sorted(labels)``; all operations are
thread-safe behind one registry lock (recording is dict math — no I/O).
Naming follows the Prometheus conventions: ``<namespace>_<subsystem>_
<metric>_<unit>``, counters end in ``_total``, histograms expose
``_bucket``/``_sum``/``_count`` series.

The reference has no analogue — deeplearning4j-ui charts families
straight off StatsStorage; the registry is what lets the SAME numbers
feed StatsStorage records (ui/report.py), a Prometheus scrape, and
assertions in tests without three collection paths.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

LabelKey = Tuple[Tuple[str, str], ...]

# log-spaced seconds buckets: 100 µs .. 100 s (checkpoint commits and
# window flushes live at opposite ends of this range)
_DEFAULT_BUCKETS = tuple(
    round(b, 6) for e in range(-4, 3) for b in (10.0 ** e, 2.5 * 10.0 ** e,
                                                5.0 * 10.0 ** e))

# log-spaced dimensionless buckets for update:param ratios (healthy
# training sits around 1e-4..1e-2; the edges are the dead/exploding
# regimes LayerHealthWatcher flags)
_RATIO_BUCKETS = tuple(10.0 ** e for e in range(-9, 2))

#: wall-clock process start, for dl4j_process_uptime_seconds
_PROCESS_START_T = time.time()


def _process_self_metrics() -> Dict[str, float]:
    """Process self-telemetry exported with every scrape: uptime, and
    resident-set bytes where the platform exposes them (/proc — Linux;
    silently absent elsewhere)."""
    out = {"process_uptime_seconds":
           round(max(0.0, time.time() - _PROCESS_START_T), 3)}
    try:
        import os
        with open("/proc/self/statm", "r", encoding="ascii") as fh:
            pages = int(fh.read().split()[1])
        out["process_rss_bytes"] = float(
            pages * os.sysconf("SC_PAGE_SIZE"))
    except (OSError, ValueError, IndexError):
        pass
    return out


def _label_key(labels: Dict[str, object]) -> LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _fmt_labels(key: LabelKey, extra: Sequence[Tuple[str, str]] = ()) -> str:
    items = list(key) + list(extra)
    if not items:
        return ""
    body = ",".join(f'{k}="{_escape(v)}"' for k, v in items)
    return "{" + body + "}"


def _escape(v: str) -> str:
    return str(v).replace("\\", r"\\").replace('"', r'\"') \
        .replace("\n", r"\n")


class _Histogram:
    """Cumulative-bucket histogram (prometheus ``le`` semantics)."""

    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets: Sequence[float]):
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self.counts = [0] * (len(self.buckets) + 1)   # + the +Inf bucket
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        v = float(value)
        idx = len(self.buckets)
        for i, b in enumerate(self.buckets):
            if v <= b:
                idx = i
                break
        self.counts[idx] += 1
        self.sum += v
        self.count += 1


class _Family:
    """One metric name: type, help text, per-label-set values."""

    __slots__ = ("name", "kind", "help", "values", "buckets")

    def __init__(self, name: str, kind: str, help_: str,
                 buckets: Optional[Sequence[float]] = None):
        self.name = name
        self.kind = kind                  # counter | gauge | histogram
        self.help = help_
        self.values: Dict[LabelKey, object] = {}
        self.buckets = buckets


class MetricsRegistry:
    """Thread-safe labeled counters / gauges / histograms with
    Prometheus text export and ui/stats publication."""

    def __init__(self, namespace: str = "dl4j"):
        import weakref
        self.namespace = namespace
        self._lock = threading.Lock()
        self._families: Dict[str, _Family] = {}
        # per-storage fold high-water marks: fold_storage() must be
        # idempotent over a growing storage (a scrape endpoint re-folds
        # on every scrape; counters would otherwise double-count).
        # _fold_lock serializes whole folds — a /metrics scrape thread
        # and the MonitorListener's flush thread fold the SAME storage
        # into the same registry, and racing on the mark would fold the
        # same records twice (a separate lock: the fold body takes
        # self._lock per metric op)
        self._fold_marks: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        self._fold_lock = threading.Lock()

    # -- core recording -------------------------------------------------
    def _family(self, name: str, kind: str, help_: str,
                buckets: Optional[Sequence[float]] = None) -> _Family:
        fam = self._families.get(name)
        if fam is None:
            fam = self._families[name] = _Family(name, kind, help_, buckets)
        elif fam.kind != kind:
            raise ValueError(
                f"metric {name!r} already registered as {fam.kind}, "
                f"not {kind}")
        if help_ and not fam.help:
            fam.help = help_
        return fam

    def inc(self, name: str, value: float = 1.0, help: str = "",
            **labels) -> None:
        """Add ``value`` to a counter (monotonic; use gauges for
        levels)."""
        if value < 0:
            raise ValueError(f"counter {name!r} cannot decrease")
        key = _label_key(labels)
        with self._lock:
            fam = self._family(name, "counter", help)
            fam.values[key] = float(fam.values.get(key, 0.0)) + float(value)

    def set_gauge(self, name: str, value: float, help: str = "",
                  **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            fam = self._family(name, "gauge", help)
            fam.values[key] = float(value)

    def observe(self, name: str, value: float, help: str = "",
                buckets: Optional[Sequence[float]] = None,
                **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            fam = self._family(name, "histogram", help,
                               buckets or _DEFAULT_BUCKETS)
            h = fam.values.get(key)
            if h is None:
                h = fam.values[key] = _Histogram(fam.buckets)
            h.observe(value)

    # -- readout --------------------------------------------------------
    def get(self, name: str, **labels):
        """Current value of a counter/gauge (None if absent)."""
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                return None
            return fam.values.get(_label_key(labels))

    def collect(self) -> Dict[str, object]:
        """Flat ``{"name{label=\"v\"}": value}`` snapshot (histograms
        contribute ``_sum``/``_count``)."""
        out: Dict[str, object] = {}
        with self._lock:
            for fam in self._families.values():
                full = f"{self.namespace}_{fam.name}"
                for key, val in fam.values.items():
                    if isinstance(val, _Histogram):
                        out[f"{full}_sum{_fmt_labels(key)}"] = \
                            round(val.sum, 9)
                        out[f"{full}_count{_fmt_labels(key)}"] = val.count
                    else:
                        out[f"{full}{_fmt_labels(key)}"] = val
        return out

    def to_prometheus_text(self) -> str:
        """The Prometheus text exposition format (0.0.4): HELP/TYPE
        headers + one sample per line, histograms with cumulative
        ``le`` buckets."""
        lines: List[str] = []
        with self._lock:
            for name in sorted(self._families):
                fam = self._families[name]
                full = f"{self.namespace}_{fam.name}"
                if fam.help:
                    lines.append(f"# HELP {full} {_escape(fam.help)}")
                lines.append(f"# TYPE {full} {fam.kind}")
                for key in sorted(fam.values):
                    val = fam.values[key]
                    if isinstance(val, _Histogram):
                        cum = 0
                        for b, c in zip(val.buckets, val.counts):
                            cum += c
                            lines.append(
                                f"{full}_bucket"
                                f"{_fmt_labels(key, [('le', repr(b))])} "
                                f"{cum}")
                        lines.append(
                            f"{full}_bucket"
                            f"{_fmt_labels(key, [('le', '+Inf')])} "
                            f"{val.count}")
                        lines.append(f"{full}_sum{_fmt_labels(key)} "
                                     f"{val.sum!r}")
                        lines.append(f"{full}_count{_fmt_labels(key)} "
                                     f"{val.count}")
                    else:
                        lines.append(f"{full}{_fmt_labels(key)} {val!r}")
            # process self-telemetry: synthesized at scrape time, never
            # stored (uptime/RSS are instantaneous reads, not state)
            for name, val in sorted(_process_self_metrics().items()):
                full = f"{self.namespace}_{name}"
                lines.append(f"# HELP {full} process self-telemetry")
                lines.append(f"# TYPE {full} gauge")
                lines.append(f"{full} {val!r}")
        return "\n".join(lines) + "\n"

    def to_record(self) -> dict:
        """One ``{"type": "metrics"}`` record in the ui/stats JSON-lines
        convention (ui/stats.py module docstring)."""
        return {"type": "metrics", "t": time.time(),
                "namespace": self.namespace, "metrics": self.collect()}

    def publish(self, storage) -> dict:
        """Append the current snapshot to a ui.stats.StatsStorage."""
        rec = self.to_record()
        storage.put(rec)
        return rec

    # -- adapters: fold the existing per-subsystem shapes ---------------
    def fold_serving(self, metrics_or_record) -> None:
        """Fold a ``serving.ServingMetrics`` (or its ``to_record()``
        dict / a stored ``{"type": "serving"}`` record) into
        ``serving_*`` metrics."""
        rec = metrics_or_record
        if hasattr(rec, "to_record"):
            rec = rec.to_record()
        for name, v in rec.get("counters", {}).items():
            self.set_gauge(f"serving_{name}_total", v,
                           help="serving lifetime counter")
        for cause, n in rec.get("failure_causes", {}).items():
            self.set_gauge("serving_failures_by_cause_total", n,
                           help="failed requests by cause", cause=cause)
        for cause, n in rec.get("timeout_causes", {}).items():
            self.set_gauge("serving_timeouts_by_cause_total", n,
                           help="timed-out requests by cause", cause=cause)
        for lane, summ in rec.get("latency_ms", {}).items():
            for stat in ("mean", "p50", "p95", "p99", "max"):
                if stat in summ:
                    self.set_gauge(
                        "serving_latency_ms", summ[stat],
                        help="serving latency summary", lane=lane,
                        stat=stat)
            # low-sample propagation (serving/metrics.py summary()):
            # a p99 read from < 32 samples is the max, not a p99 —
            # dashboards alerting on serving_latency_ms must be able
            # to gate on this flag per lane instead of paging on a
            # cold-start artifact
            if "count" in summ:
                self.set_gauge("serving_latency_count", summ["count"],
                               help="samples behind the latency "
                                    "summary", lane=lane)
            if "low_sample" in summ:
                self.set_gauge("serving_latency_low_sample",
                               1 if summ["low_sample"] else 0,
                               help="1 when the lane's percentiles "
                                    "rest on < 32 samples", lane=lane)
        batch = rec.get("batch", {})
        if batch:
            self.set_gauge("serving_batch_mean_size",
                           batch.get("mean_size", 0.0))
            self.set_gauge("serving_batch_padding_waste_ratio",
                           batch.get("padding_waste", 0.0))
        # resilience rail (serving/resilience.py): breaker state as an
        # enum gauge (0 closed / 1 half-open / 2 open — the /healthz
        # 503 signal on a dashboard) + last hot-reload provenance; the
        # shed/requeue/restart/quarantine/reload counters already
        # export through the generic serving_<counter>_total loop above
        # generative tier (serving/generative.py): occupancy + token
        # throughput gauges; the token/prefill/step counters already
        # export through the generic serving_<counter>_total loop
        gen = rec.get("generative") or {}
        if gen:
            self.set_gauge("serving_slot_occupancy_ratio",
                           gen.get("slot_occupancy", 0.0),
                           help="mean active slots / max_slots per "
                                "decode step")
            self.set_gauge("serving_tokens_per_sec",
                           gen.get("tokens_per_sec", 0.0),
                           help="lifetime generated-token rate")
            self.set_gauge("serving_max_slots",
                           gen.get("max_slots", 0),
                           help="KV cache slots")
            # speculative decoding (satellite lane): the acceptance
            # rate IS the speedup knob — accepted draft tokens ride a
            # verify dispatch for free; .get() defense keeps records
            # written before the lane existed folding cleanly
            if gen.get("spec_rounds"):
                self.set_gauge("serving_draft_acceptance_rate",
                               gen.get("draft_acceptance_rate", 0.0),
                               help="accepted / drafted speculative "
                                    "tokens (lifetime)")
                self.set_gauge("serving_draft_tokens_rejected_total",
                               gen.get("draft_rejected", 0),
                               help="drafted tokens the target's "
                                    "verify pass rejected")
        # paged KV tier (serving/paged/): pool + prefix-cache gauges;
        # every ratio is safe_ratio'd at the source (0.0 at cold start,
        # never NaN — satellite rule for the new series)
        paged = rec.get("paged") or {}
        if paged:
            self.set_gauge("serving_pool_blocks",
                           paged.get("num_blocks", 0),
                           help="usable KV blocks in the pool")
            self.set_gauge("serving_pool_block_size",
                           paged.get("block_size", 0),
                           help="tokens per KV block")
            self.set_gauge("serving_pool_bytes_per_token",
                           paged.get("kv_bytes_per_token", 0),
                           help="bytes one cached token costs over all "
                                "layers (K and V rows, or a latent row)")
            self.set_gauge("serving_pool_occupancy_ratio",
                           paged.get("pool_occupancy", 0.0),
                           help="mean held blocks / pool capacity per "
                                "decode step")
            self.set_gauge("serving_prefix_hit_rate",
                           paged.get("prefix_hit_rate", 0.0),
                           help="prefix-cache hits / lookups")
            self.set_gauge("serving_blocks_per_request",
                           paged.get("blocks_per_request", 0.0),
                           help="mean KV blocks held per retired "
                                "request")
            self.set_gauge("serving_pool_cached_blocks",
                           paged.get("cached_blocks", 0),
                           help="prefix-cache registered blocks")
            self.set_gauge("serving_pool_evictions_total",
                           paged.get("evictions", 0),
                           help="prefix-cache blocks reclaimed under "
                                "pool pressure")
        res = rec.get("resilience") or {}
        state = res.get("breaker_state")
        if state is not None:
            self.set_gauge(
                "serving_breaker_state",
                {"closed": 0, "half_open": 1, "open": 2}.get(state, -1),
                help="circuit breaker: 0 closed, 1 half-open, 2 open")
        if res.get("last_reload_step") is not None:
            self.set_gauge("serving_last_reload_step",
                           res["last_reload_step"],
                           help="checkpoint step of the last hot reload")
            self.set_gauge("serving_last_reload_failed",
                           1 if res.get("last_reload_failed") else 0,
                           help="1 when the last hot reload rolled back")

    def fold_fleet(self, metrics_or_record) -> None:
        """Fold a ``serving.fleet.FleetMetrics`` (or its
        ``to_record()`` dict / a stored ``{"type": "fleet"}`` record)
        into ``fleet_*`` metrics — the cluster-tier dashboard: routing
        mix + affinity hit rate, retry/shed/death pressure, deploy and
        autoscale events, and a per-replica gauge set labeled by
        replica name (occupancy / queue depth / readiness)."""
        rec = metrics_or_record
        if hasattr(rec, "to_record"):
            rec = rec.to_record()
        for name, v in rec.get("counters", {}).items():
            self.set_gauge(f"fleet_{name}_total", v,
                           help="fleet lifetime counter")
        agg = rec.get("fleet") or {}
        self.set_gauge("fleet_replicas", agg.get("n_replicas", 0),
                       help="replicas known to the router")
        self.set_gauge("fleet_replicas_ready", agg.get("n_ready", 0),
                       help="replicas ready at the last scrape")
        self.set_gauge("fleet_affinity_hit_rate",
                       agg.get("affinity_hit_rate", 0.0),
                       help="affinity-eligible requests placed on "
                            "their rendezvous home replica")
        self.set_gauge("fleet_retries_per_request",
                       agg.get("retries_per_request", 0.0),
                       help="mean retries per routed request")
        dur = rec.get("durability")
        if dur:
            for name, h in (
                    ("resumes", "mid-stream failovers resumed from "
                                "the emitted prefix"),
                    ("tokens_salvaged", "already-decoded tokens carried "
                                        "across resumes instead of "
                                        "regenerated"),
                    ("dedup_drops", "duplicate token deliveries the "
                                    "exactly-once cursor absorbed"),
                    ("journal_records", "write-ahead journal records "
                                        "appended"),
                    ("journal_truncated_bytes", "torn-tail bytes "
                                                "dropped by recovery "
                                                "scans"),
                    ("recovered_requests", "incomplete journal entries "
                                           "replayed by recover()")):
                self.set_gauge(f"fleet_durability_{name}_total",
                               dur.get(name, 0), help=h)
            fs = dur.get("journal_fsync_ms") or {}
            self.set_gauge("fleet_durability_journal_fsync_ms_p99",
                           fs.get("p99", 0.0),
                           help="p99 journal fsync latency")
        slo = rec.get("slo")
        if slo:
            self.set_gauge("fleet_slo_window", slo.get("window", 0),
                           help="request outcomes in the rolling SLO "
                                "window")
            for outcome, n in (slo.get("outcomes") or {}).items():
                self.set_gauge("fleet_slo_requests_total", n,
                               help="request outcomes recorded by the "
                                    "SLO tracker",
                               outcome=outcome)
            for field, obj in (slo.get("objectives") or {}).items():
                labels = {"objective": field}
                self.set_gauge("fleet_slo_target_ms",
                               obj.get("target_ms", 0.0),
                               help="the objective's latency target",
                               **labels)
                self.set_gauge("fleet_slo_attainment",
                               obj.get("attainment", 1.0),
                               help="fraction of windowed requests "
                                    "that met the objective", **labels)
                self.set_gauge("fleet_slo_burn_rate",
                               obj.get("burn_rate", 0.0),
                               help="window miss fraction over the "
                                    "error budget (1.0 = burning "
                                    "exactly as provisioned)", **labels)
                self.set_gauge("fleet_slo_p50_ms",
                               obj.get("p50_ms", 0.0),
                               help="windowed p50 of the objective's "
                                    "measured value", **labels)
                self.set_gauge("fleet_slo_p99_ms",
                               obj.get("p99_ms", 0.0),
                               help="windowed p99 of the objective's "
                                    "measured value", **labels)
        for name, rep in (rec.get("replicas") or {}).items():
            labels = {"replica": name}
            self.set_gauge("fleet_replica_ready",
                           1 if rep.get("ready") else 0,
                           help="1 when the replica scraped ready",
                           **labels)
            self.set_gauge("fleet_replica_queue_depth",
                           rep.get("queue_depth", 0),
                           help="queued requests at the last scrape",
                           **labels)
            self.set_gauge("fleet_replica_occupancy",
                           rep.get("occupancy", 0.0),
                           help="max(slot, pool) occupancy at the "
                                "last scrape", **labels)
            self.set_gauge("fleet_replica_p99_decode_step_ms",
                           rep.get("p99_decode_step_ms", 0.0),
                           help="replica's rolling p99 decode step",
                           **labels)
            self.set_gauge("fleet_replica_routed_total",
                           rep.get("routed", 0),
                           help="requests the router placed here",
                           **labels)

    def fold_dispatch(self, stats: Optional[dict],
                      epoch: Optional[int] = None) -> None:
        """Fold a fit tier's dispatch accounting (``sd.last_fit_stats``
        or a stored ``{"type": "dispatch"}`` record)."""
        if not stats:
            return
        labels = {"tier": stats.get("tier", "unknown")}
        for key in ("steps_per_epoch", "dispatches_per_epoch",
                    "window_compiles", "fused_steps", "accum_steps"):
            if key in stats:
                self.set_gauge(f"fit_{key}", stats[key],
                               help="fit dispatch accounting", **labels)
        if epoch is not None:
            self.set_gauge("fit_epoch", epoch, help="last observed epoch")

    def fold_checkpoint(self, record: dict) -> None:
        """Fold one ``{"type": "checkpoint"}`` commit record."""
        self.inc("checkpoint_commits_total",
                 help="committed checkpoints")
        self.inc("checkpoint_bytes_total", record.get("bytes", 0),
                 help="bytes committed to checkpoints")
        for key, metric in (("serialize_seconds", "serialize"),
                            ("commit_seconds", "commit"),
                            ("queue_seconds", "queue")):
            if key in record:
                self.observe("checkpoint_stage_seconds", record[key],
                             help="checkpoint stage wall time",
                             stage=metric)
        self.set_gauge("checkpoint_last_step", record.get("step", 0))

    def fold_faults(self, events: Iterable[dict]) -> None:
        """Fold fault-rail events (``{"type": "faults"}`` records or
        ``FaultTolerantFit.events``)."""
        for ev in events:
            self.inc("faults_events_total",
                     help="fault-rail decisions by event",
                     event=ev.get("event", "unknown"))
            if ev.get("event") == "rollback":
                self.observe("faults_rollback_seconds",
                             ev.get("overhead_s", 0.0),
                             help="rollback wall time")

    def fold_reshard(self, record: dict) -> None:
        """Fold one ``{"type": "reshard"}`` record (checkpoint/
        reshard.py / ParallelTrainer.restore_latest) into ``reshard_*``
        metrics — how often elastic restores cross topology changes,
        how much global state they reassemble, and how long the
        re-slice costs."""
        self.inc("reshard_events_total",
                 help="elastic resharded restores (topology changes "
                      "survived)")
        self.inc("reshard_arrays_resliced_total", record.get("arrays", 0),
                 help="arrays re-sliced onto a new mesh by resharded "
                      "restores")
        self.inc("reshard_bytes_gathered_total", record.get("bytes", 0),
                 help="global-state bytes reassembled by resharded "
                      "restores")
        self.observe("reshard_seconds", record.get("seconds", 0.0),
                     help="resharded-restore wall time")
        if record.get("step") is not None:
            self.set_gauge("reshard_last_step", record["step"],
                           help="step of the last resharded restore")
        if record.get("from_shards") is not None:
            self.set_gauge("reshard_last_from_shards",
                           record["from_shards"],
                           help="shard count of the last resharded "
                                "checkpoint")

    def fold_compile(self, stats_or_record) -> None:
        """Fold XLA compile accounting (``compilecache.COMPILE_STATS``
        or a stored ``{"type": "compile"}`` record) into ``compile_*``
        gauges — the cache-hit vs miss split that tells a dashboard
        whether a restart was warm."""
        rec = stats_or_record
        if hasattr(rec, "to_record"):
            rec = rec.to_record()
        for key in ("backend_compiles", "cache_hits", "cache_misses",
                    "miss_compiles", "precompiles"):
            if key in rec:
                self.set_gauge(f"compile_{key}_total", rec[key],
                               help="XLA compiles by persistent-cache "
                                    "outcome, and programs built ahead "
                                    "of time (compilecache/)")
        # build, trace, lower, backend_compile and plan_analyze seconds
        # partition a start's wall time; cache_load is the part of
        # backend_compile that persistent-cache hits took
        for key in ("backend_compile_seconds", "cache_load_seconds",
                    "trace_seconds", "lower_seconds", "build_seconds",
                    "plan_analyze_seconds", "saved_seconds"):
            if key in rec:
                self.set_gauge(f"compile_{key}", rec[key],
                               help="cumulative compile-phase wall time")

    def fold_tensorstats(self, record: dict) -> None:
        """Fold one ``{"type": "tensorstats"}`` record (monitor/
        tensorstats.py) into per-layer ``layer_*`` gauges — grad/update/
        param L2 norms, nonfinite counts, the update:param ratio — plus
        a ``layer_update_ratio_dist`` histogram over all layers/samples
        (the dead↔exploding spectrum a dashboard alerts on). Histogram
        bin lists stay record-only: L layers x 3 families x B bins as
        label sets would swamp the namespace."""
        for layer, ent in record.get("layers", {}).items():
            for k, v in ent.items():
                if k.endswith("_hist") or v is None:
                    # None = poisoned stat (build_record sanitizes
                    # non-finite floats); the *_nonfinite counts carry
                    # the signal
                    continue
                self.set_gauge(f"layer_{k}", v,
                               help="per-layer tensor statistics "
                                    "(tensorstats)", layer=layer)
            ratio = ent.get("update_ratio")
            if ratio is not None:
                self.observe("layer_update_ratio_dist", ratio,
                             help="update:param ratio distribution over "
                                  "layers and samples",
                             buckets=_RATIO_BUCKETS)
        if record.get("iter") is not None:
            self.set_gauge("layer_stats_last_iteration", record["iter"],
                           help="iteration of the last tensorstats "
                                "sample")

    def fold_memory(self, record: dict) -> None:
        """Fold one ``{"type": "memory"}`` record (monitor/memstats.py)
        into ``hbm_*`` gauges — total and per-device bytes in use /
        peak / limit / headroom, plus the AllocationsTracker's tagged
        transfer totals (gauges, not counters: the record carries
        cumulative values)."""
        for key, metric in (("bytes_in_use", "hbm_bytes_in_use"),
                            ("peak_bytes", "hbm_peak_bytes"),
                            ("bytes_limit", "hbm_bytes_limit"),
                            ("headroom", "hbm_headroom")):
            if record.get(key) is not None:
                self.set_gauge(metric, record[key],
                               help="device HBM accounting "
                                    "(monitor/memstats.py)")
        for dev in record.get("devices", ()):
            name = dev.get("device", "?")
            for key, metric in (("bytes_in_use", "hbm_bytes_in_use"),
                                ("peak_bytes", "hbm_peak_bytes"),
                                ("bytes_limit", "hbm_bytes_limit")):
                if dev.get(key):
                    self.set_gauge(metric, dev[key],
                                   help="device HBM accounting "
                                        "(monitor/memstats.py)",
                                   device=name)
        for tag, nbytes in (record.get("tracked") or {}).items():
            self.set_gauge("memory_tracked_bytes", nbytes,
                           help="AllocationsTracker tagged transfer "
                                "totals", tag=tag)
        if record.get("live_skipped"):
            self.set_gauge("memory_live_skipped_arrays",
                           record["live_skipped"],
                           help="live arrays the fallback census could "
                                "not size (deleted/donated)")

    def fold_memory_plan(self, record: dict) -> None:
        """Fold one ``{"type": "memory_plan"}`` record into per-program
        ``plan_*`` gauges — the compiled executable's predicted
        footprint (temp/argument/output/generated-code bytes) and its
        flops (the MFU-estimate numerator)."""
        program = record.get("program", "?")
        for key in ("temp_bytes", "argument_bytes", "output_bytes",
                    "generated_code_bytes", "total_bytes"):
            if record.get(key) is not None:
                self.set_gauge(f"plan_{key}", record[key],
                               help="compiled-program memory plan "
                                    "(compiled.memory_analysis)",
                               program=program)
        for key in ("flops", "flops_per_step", "bytes_accessed"):
            if record.get(key) is not None:
                self.set_gauge(f"plan_{key}", record[key],
                               help="compiled-program cost plan "
                                    "(compiled.cost_analysis)",
                               program=program)

    def fold_analysis(self, record: dict) -> None:
        """Fold one ``{"type": "analysis"}`` record (analyze/,
        docs/static_analysis.md) into ``analysis_*`` gauges — the
        finding counts by severity a dashboard alerts on (a nonzero
        error gauge means a fit is running against a graph the
        analyzer would have failed in strict mode), plus the one-time
        analysis cost."""
        for sev, n in (record.get("counts") or {}).items():
            self.set_gauge("analysis_findings", n,
                           help="static-analysis findings by severity "
                                "(analyze/)", severity=sev)
        if record.get("rules_run") is not None:
            self.set_gauge("analysis_rules_run", record["rules_run"],
                           help="rules the last static analysis ran")
        if record.get("seconds") is not None:
            self.set_gauge("analysis_seconds", record["seconds"],
                           help="wall seconds of the last static "
                                "analysis (runs once per graph "
                                "version, pre-compile)")

    def fold_datapipe(self, record: dict) -> None:
        """Fold one ``{"type": "datapipe"}`` record (the streaming
        input pipeline's per-flush telemetry, datapipe/ +
        monitor/steptime.MonitorListener) into ``datapipe_*`` metrics:
        delta counters for records/batches delivered, IO retries,
        quarantines and supervision decisions, plus throughput /
        data-wait / per-worker-utilization gauges."""
        for key in ("records", "batches", "read_retries", "shard_reads",
                    "bytes_read", "rows_quarantined", "records_withheld",
                    "worker_restarts", "requeues", "slow_reads"):
            v = record.get(key)
            if v:
                self.inc(f"datapipe_{key}_total", v,
                         help="streaming data-plane counter (datapipe/)")
        for key, metric in (("records_per_sec", "datapipe_records_per_sec"),
                            ("data_wait_frac",
                             "datapipe_data_wait_fraction"),
                            ("quarantined_shards",
                             "datapipe_quarantined_shards"),
                            ("passes_started",
                             "datapipe_passes_started"),
                            ("workers", "datapipe_workers")):
            if record.get(key) is not None:
                self.set_gauge(metric, record[key],
                               help="streaming data-plane gauge "
                                    "(datapipe/)")
        for worker, util in (record.get("worker_utilization")
                             or {}).items():
            self.set_gauge("datapipe_worker_utilization", util,
                           help="prefetch-worker busy fraction since "
                                "the previous flush", worker=str(worker))

    def fold_integrity(self, record: dict) -> None:
        """Fold one ``{"type": "integrity"}`` record (the integrity
        rail: checkpoint scrubber cycles/quarantines and stall-watchdog
        forensics — integrity/, checkpoint/scrub.py) into
        ``integrity_*`` metrics. Stall FAULT events already count under
        ``faults_events_total{event="stall"}``; this adds the scrub
        cadence and the rot/quarantine tallies a fleet dashboard
        alerts on."""
        ev = record.get("event")
        if ev == "scrub":
            self.inc("integrity_scrub_cycles_total",
                     help="checkpoint scrub cycles completed")
            self.inc("integrity_scrubbed_dirs_total",
                     record.get("scanned", 0),
                     help="step dirs re-hashed by the scrubber")
            self.inc("integrity_scrub_bytes_total",
                     record.get("bytes", 0),
                     help="bytes re-hashed by the scrubber")
            self.inc("integrity_rotten_total", record.get("rotten", 0),
                     help="step dirs that failed scrub verification")
            self.observe("integrity_scrub_seconds",
                         record.get("seconds", 0.0),
                         help="scrub cycle wall time")
        elif ev in ("checkpoint_quarantined", "checkpoint_rotten"):
            self.inc("integrity_quarantined_total",
                     1 if ev == "checkpoint_quarantined" else 0,
                     help="rotten checkpoints moved aside "
                          "(step_N.rotten)")
            if record.get("step") is not None:
                self.set_gauge("integrity_last_rotten_step",
                               record["step"],
                               help="newest step found rotten")
        elif ev == "stall_forensics":
            self.inc("integrity_stalls_total",
                     help="stall-watchdog expiries (forensics dumped)")
            if record.get("waited_s") is not None:
                self.observe("integrity_stall_waited_seconds",
                             record["waited_s"],
                             help="how long stalled boundaries blocked")

    def fold_steptime(self, record: dict) -> None:
        """Fold one ``{"type": "steptime"}`` breakdown record
        (monitor/steptime.py)."""
        steps = record.get("steps", 0)
        if not steps:
            return
        self.inc("steptime_steps_total", steps, help="attributed steps")
        for stage in ("data_wait_s", "dispatch_s", "flush_s", "other_s"):
            if stage in record:
                self.inc(f"steptime_{stage[:-2]}_seconds_total",
                         record[stage],
                         help="per-stage wall time attributed to steps")
        for stat in ("p50", "p95", "max"):
            key = f"step_ms_{stat}"
            if key in record:
                self.set_gauge("steptime_step_ms", record[key],
                               help="rolling step-time percentiles",
                               stat=stat)

    def fold_storage(self, storage) -> None:
        """Fold everything recognizable a StatsStorage holds (serving /
        dispatch / checkpoint / faults / steptime records). Incremental
        per storage: repeated calls fold only records appended since
        the last call, so re-folding on every scrape is safe. (The
        record-level adapters above are NOT idempotent for
        counter-typed metrics — fold each record/event stream once.)"""
        with self._fold_lock:
            # held across the fold, not just the mark update: gauges are
            # last-write-wins, so two racing folders must apply their
            # slices in order (the per-metric ops take self._lock — a
            # different lock — so no deadlock)
            start = self._fold_marks.get(storage, 0)
            records = list(storage.records)
            self._fold_marks[storage] = len(records)
            new = records[start:]
            for rec in new:
                self._fold_one(rec)

    def _fold_one(self, rec: dict) -> None:
        t = rec.get("type")
        if t == "serving":
            self.fold_serving(rec)
        elif t == "fleet":
            self.fold_fleet(rec)
        elif t == "dispatch":
            self.fold_dispatch(rec, epoch=rec.get("epoch"))
        elif t == "checkpoint":
            self.fold_checkpoint(rec)
        elif t == "faults":
            self.fold_faults([rec])
        elif t == "steptime":
            self.fold_steptime(rec)
        elif t == "datapipe":
            self.fold_datapipe(rec)
        elif t == "tensorstats":
            self.fold_tensorstats(rec)
        elif t == "compile":
            self.fold_compile(rec)
        elif t == "reshard":
            self.fold_reshard(rec)
        elif t == "memory":
            self.fold_memory(rec)
        elif t == "memory_plan":
            self.fold_memory_plan(rec)
        elif t == "analysis":
            self.fold_analysis(rec)
        elif t == "integrity":
            self.fold_integrity(rec)


__all__ = ["MetricsRegistry"]
