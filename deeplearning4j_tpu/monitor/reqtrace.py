"""Request-scoped distributed tracing + fleet SLO tracking.

The substrate traces host wall time per *process* (monitor/trace.py);
the serving tier is a *fleet* (serving/fleet/): one request crosses
router -> replica -> prefill -> N decode rounds -> stream delivery,
may be shed and retried, failed over to a survivor mid-stream, or
replayed from the journal after a router crash. Nothing tied those
segments together. This module is the Dapper-style rail that does:

- :class:`TraceContext` — ``trace_id`` (the fleet request id) plus a
  segment counter, minted by ``FleetRouter.generate()`` and carried
  through EVERY hop: retries, failovers, ``submit_continuation``
  resumes and ``recover()`` replays all reuse the SAME trace_id with a
  new segment. Down in the server the existing ``serving.*`` spans get
  tagged ``trace_id=/segment=``, and batch-level decode/verify spans
  record the slot->trace_id occupancy map (``slots=``) so per-request
  time inside a shared dispatch is attributable proportionally
  (``dur / n_occupied_slots`` — the Orca/vLLM iteration-level
  scheduling problem: one dispatch serves many requests).
- :func:`assemble` — host-side waterfall assembly from drained spans:
  queue_wait / admission / prefill / per-round decode / speculation
  verify / stream-delivery phases, with retry/failover segments
  (``fleet.attempt`` spans) linked in wall-clock order.
- :class:`RequestTracer` — the sampling collector: head-sample a
  configurable fraction (deterministic in trace_id), but ALWAYS keep
  traces that breach the SLO or end in retry/failover/shed (tail-based
  keep), into a bounded LRU of assembled waterfalls. Exported as a
  Perfetto lane-per-request view (:meth:`RequestTracer.to_chrome_trace`)
  and over ``GET /requesttrace?id=`` (monitor/server.py).
- :class:`SLOTracker` — per-request outcome records (TTFT, e2e, tokens,
  replica, retries, resumes, shed/ok/failed) in a rolling window ->
  SLO attainment + error-budget burn rate per objective. Rides the
  ``{"type": "fleet"}`` record as its ``"slo"`` sub-dict (no new record
  type), folds to ``dl4j_fleet_slo_*`` gauges, serves at ``GET /slo``
  and renders as the report's SLO panel.

Everything here is host-side accounting over spans that never touch
device state: the standing contract holds — clean serving runs are
bit-identical with request tracing on or off, and the whole rail is
inert (no span buffering, no assembly) while the shared tracer is
disabled. Cost on the chip with the tracer on: not measured (ROADMAP
D5). See docs/observability.md ("Request tracing & SLOs").
"""
from __future__ import annotations

import collections
import hashlib
import threading
from typing import Dict, Iterable, List, Optional, Tuple

from deeplearning4j_tpu.monitor.trace import TRACER, Span, Tracer


# ----------------------------------------------------------------------
# propagation

class TraceContext:
    """The per-request trace identity carried across every hop.

    ``trace_id`` is the fleet request id (also the journal key and the
    pinned sampling seed — one id names the request everywhere).
    ``segment`` is the ordinal of the CURRENT attempt: the router calls
    :meth:`next_segment` per attempt, so a retry, a failover resume and
    a recover() replay each tag their spans with a fresh segment while
    keeping the trace_id. Segment numbering restarts per context (a
    replay in a restarted process starts at 0 again); waterfall
    assembly orders segments by wall-clock, not by number.
    """

    __slots__ = ("trace_id", "segment", "sampled", "origin", "_n")

    def __init__(self, trace_id: int, sampled: bool = False,
                 origin: str = "live"):
        self.trace_id = int(trace_id)
        self.sampled = bool(sampled)
        self.origin = str(origin)       # "live" | "replay"
        self.segment = 0
        self._n = 0

    def next_segment(self) -> int:
        """Advance to (and return) the next segment ordinal — one call
        per placement attempt."""
        self.segment = self._n
        self._n += 1
        return self.segment

    @property
    def segments_minted(self) -> int:
        """How many attempts have taken a segment so far (0 before the
        first :meth:`next_segment` — a count, not an ordinal)."""
        return self._n

    def span_args(self) -> dict:
        """The args every span on this hop gets tagged with."""
        return {"trace_id": self.trace_id, "segment": self.segment}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"TraceContext(trace_id={self.trace_id}, "
                f"segment={self.segment}, sampled={self.sampled}, "
                f"origin={self.origin!r})")


def head_sampled(trace_id: int, fraction: float) -> bool:
    """Deterministic head-sampling decision: a pure function of
    ``trace_id`` (NOT a random draw — the same request replays to the
    same decision on every router, which is what makes cross-process
    sampling coherent)."""
    if fraction >= 1.0:
        return True
    if fraction <= 0.0:
        return False
    h = hashlib.blake2b(str(int(trace_id)).encode("ascii"),
                        digest_size=8).digest()
    return int.from_bytes(h, "big") / 2.0 ** 64 < float(fraction)


# ----------------------------------------------------------------------
# the ONE attainment definition (a load generator's rows and the
# SLOTracker must not disagree about what "met the SLO" means)

def slo_attainment(records: Iterable[Tuple[str, Optional[float]]],
                   target_ms: float) -> float:
    """Fraction of requests that met the objective.

    ``records`` is ``(status, value_ms)`` pairs. A request attains iff
    ``status == "ok"`` AND its measured value is ``<= target_ms``; any
    non-ok outcome (shed, failed, timed out) is a miss — a request the
    fleet dropped did not meet its SLO. Ok records with no measurement
    (e.g. a zero-token generation has no TTFT) are excluded from the
    denominator. Empty input -> 1.0 (vacuous attainment)."""
    n = hit = 0
    for status, value in records:
        if status == "ok" and value is None:
            continue
        n += 1
        if status == "ok" and float(value) <= float(target_ms):
            hit += 1
    return (hit / n) if n else 1.0


def _pct(values: List[float], p: float) -> float:
    if not values:
        return 0.0
    vs = sorted(values)
    k = min(len(vs) - 1, max(0, int(round(p / 100.0 * (len(vs) - 1)))))
    return float(vs[k])


# ----------------------------------------------------------------------
# SLO tracking

class SLOTracker:
    """Rolling-window SLO attainment + error-budget burn rate.

    ``objectives`` maps an outcome field (``"ttft_ms"`` / ``"e2e_ms"``)
    to its target. ``error_budget`` is the allowed miss fraction (0.01
    = a 99% objective); ``burn_rate`` is the window's miss fraction
    over that budget — 1.0 means burning budget exactly as provisioned,
    >1 means the error budget is being spent faster than it accrues.
    Thread-safe (the router records from concurrent request threads).
    """

    def __init__(self, objectives: Optional[Dict[str, float]] = None,
                 window: int = 512, error_budget: float = 0.01,
                 worst_k: int = 5):
        self.objectives = dict(objectives if objectives is not None
                               else {"ttft_ms": 2000.0,
                                     "e2e_ms": 10000.0})
        self.error_budget = max(1e-9, float(error_budget))
        self.worst_k = int(worst_k)
        self._lock = threading.Lock()
        self._window: "collections.deque[dict]" = \
            collections.deque(maxlen=int(window))
        self.counts = {"ok": 0, "failed": 0, "timed_out": 0, "shed": 0}
        self.total = 0
        self._worst: List[dict] = []    # worst-TTFT sampled waterfalls

    # -- recording ------------------------------------------------------
    def record(self, status: str, *, ttft_ms: Optional[float] = None,
               e2e_ms: Optional[float] = None, tokens: int = 0,
               replica: Optional[str] = None, retries: int = 0,
               resumes: int = 0, trace_id: Optional[int] = None) -> dict:
        """Record one request outcome; returns the stored record."""
        rec = {"status": str(status), "ttft_ms": ttft_ms,
               "e2e_ms": e2e_ms, "tokens": int(tokens),
               "replica": replica, "retries": int(retries),
               "resumes": int(resumes), "trace_id": trace_id}
        with self._lock:
            self._window.append(rec)
            self.counts[status] = self.counts.get(status, 0) + 1
            self.total += 1
        return rec

    def breached(self, outcome: dict) -> bool:
        """True when this outcome missed ANY objective (the tail-keep
        trigger): every non-ok status breaches; an ok outcome breaches
        when a measured value exceeds its target."""
        if outcome.get("status") != "ok":
            return True
        for field, target in self.objectives.items():
            v = outcome.get(field)
            if v is not None and float(v) > float(target):
                return True
        return False

    def note_waterfall(self, waterfall: dict) -> None:
        """Keep the worst-TTFT sampled waterfalls' breakdowns (what the
        report's SLO panel shows next to the percentiles)."""
        entry = {"trace_id": waterfall.get("trace_id"),
                 "ttft_ms": waterfall.get("ttft_ms"),
                 "e2e_ms": waterfall.get("e2e_ms"),
                 "replica": waterfall.get("replica"),
                 "retries": waterfall.get("retries", 0),
                 "kept": waterfall.get("kept"),
                 "breakdown": ttft_breakdown(waterfall)}
        with self._lock:
            self._worst.append(entry)
            self._worst.sort(key=lambda e: -(e["ttft_ms"] or 0.0))
            del self._worst[self.worst_k:]

    # -- readout --------------------------------------------------------
    def attainment(self, field: str) -> float:
        target = self.objectives[field]
        with self._lock:
            recs = [(r["status"], r.get(field)) for r in self._window]
        return slo_attainment(recs, target)

    def burn_rate(self, field: str) -> float:
        return (1.0 - self.attainment(field)) / self.error_budget

    def to_dict(self) -> dict:
        """The ``"slo"`` sub-dict of the ``{"type": "fleet"}`` record."""
        with self._lock:
            win = list(self._window)
            counts = dict(self.counts)
            total = self.total
            worst = [dict(e) for e in self._worst]
        out = {"window": len(win), "total": total, "outcomes": counts,
               "error_budget": self.error_budget, "objectives": {},
               "worst_traces": worst}
        for field, target in self.objectives.items():
            vals = [float(r[field]) for r in win
                    if r.get(field) is not None]
            att = slo_attainment(
                [(r["status"], r.get(field)) for r in win], target)
            out["objectives"][field] = {
                "target_ms": float(target),
                "n": len(vals),
                "attainment": round(att, 6),
                "burn_rate": round((1.0 - att) / self.error_budget, 4),
                "p50_ms": round(_pct(vals, 50), 3),
                "p99_ms": round(_pct(vals, 99), 3)}
        return out


# ----------------------------------------------------------------------
# waterfall assembly

#: span names whose batch-level dispatch carries a slot->trace_id map
_SHARED_SPANS = ("serving.decode", "serving.draft", "serving.verify")


def _ms(seconds: float) -> float:
    return round(seconds * 1000.0, 4)


def assemble(spans: Iterable[Span], trace_id: int,
             outcome: Optional[dict] = None) -> dict:
    """Build one request's waterfall from a span set.

    Selects spans tagged ``trace_id=`` (enqueue/prefill/reply and the
    router's ``fleet.attempt`` segments) plus batch-level spans whose
    ``slots=`` occupancy map contains the trace — those contribute
    ``dur / n_occupied_slots`` (proportional attribution: the dispatch
    served that many requests at once). Returns a JSON-ready dict:
    ``segments`` (retry/failover/replay attempts in wall-clock order),
    ``phases`` (queue_wait/admission/prefill/decode/verify/reply
    totals + per-round counts), and a compact ``spans`` list for lane
    rendering. ``prefill_ms``, ``decode_ms``, ``draft_ms`` and
    ``verify_ms`` are dispatch-to-host-sync times: those spans end
    when the step's tokens are on the host, so the device's time is in
    them (``queue_wait_ms`` ends where the prefill span begins).
    ``outcome`` (the router's measurement) is merged in as the
    authoritative ttft/e2e."""
    tid = int(trace_id)
    mine: List[Span] = []
    shared: List[Tuple[Span, int]] = []
    for s in spans:
        args = s.args
        if args.get("trace_id") == tid:
            mine.append(s)
        elif s.name in _SHARED_SPANS:
            slots = args.get("slots")
            if isinstance(slots, dict) and tid in slots.values():
                shared.append((s, max(1, len(slots))))
    all_spans = mine + [s for s, _ in shared]
    t0 = min((s.t0 for s in all_spans), default=0.0)

    def named(name):
        return sorted((s for s in mine if s.name == name),
                      key=lambda s: s.t0)

    segments = []
    for s in named("fleet.attempt"):
        segments.append({"segment": s.args.get("segment"),
                         "kind": s.args.get("kind"),
                         "replica": s.args.get("replica"),
                         "outcome": s.args.get("outcome"),
                         "error": s.args.get("error"),
                         "start_ms": _ms(s.t0 - t0),
                         "dur_ms": _ms(s.dur)})

    enq = named("serving.enqueue")
    pre = named("serving.prefill")
    rep = named("serving.reply")
    by_shared: Dict[str, List[Tuple[Span, int]]] = {}
    for s, n in sorted(shared, key=lambda sn: sn[0].t0):
        by_shared.setdefault(s.name, []).append((s, n))

    queue_wait = 0.0
    if enq and pre:
        queue_wait = max(0.0, pre[0].t0 - (enq[0].t0 + enq[0].dur))
    decodes = by_shared.get("serving.decode", [])
    phases = {
        "queue_wait_ms": _ms(queue_wait),
        "admission_ms": _ms(sum(s.dur for s in enq)),
        "prefill_ms": _ms(sum(s.dur for s in pre)),
        "decode_ms": _ms(sum(s.dur / n for s, n in decodes)),
        "decode_rounds": len(decodes),
        "first_decode_ms": _ms(decodes[0][0].dur / decodes[0][1])
        if decodes else 0.0,
        "draft_ms": _ms(sum(s.dur / n for s, n in
                            by_shared.get("serving.draft", []))),
        "verify_ms": _ms(sum(s.dur / n for s, n in
                             by_shared.get("serving.verify", []))),
        "verify_rounds": len(by_shared.get("serving.verify", [])),
        "reply_ms": _ms(sum(s.dur for s in rep)),
    }

    lanes = []
    for s in sorted(mine, key=lambda s: s.t0):
        lanes.append({"name": s.name, "cat": s.cat,
                      "start_ms": _ms(s.t0 - t0), "dur_ms": _ms(s.dur),
                      "segment": s.args.get("segment"), "share": 1.0})
    for s, n in sorted(shared, key=lambda sn: sn[0].t0):
        lanes.append({"name": s.name, "cat": s.cat,
                      "start_ms": _ms(s.t0 - t0), "dur_ms": _ms(s.dur),
                      "segment": s.args.get("segment"),
                      "share": round(1.0 / n, 4)})

    wf = {"trace_id": tid, "t0_s": t0, "n_spans": len(all_spans),
          "segments": segments, "phases": phases, "spans": lanes}
    if outcome:
        for k in ("status", "ttft_ms", "e2e_ms", "tokens", "replica",
                  "retries", "resumes", "origin"):
            if k in outcome:
                wf[k] = outcome[k]
    return wf


def ttft_breakdown(waterfall: dict) -> dict:
    """Where the time-to-first-token went (the loadgen row field)."""
    ph = waterfall.get("phases") or {}
    return {k: ph.get(k, 0.0)
            for k in ("queue_wait_ms", "prefill_ms", "first_decode_ms")}


# ----------------------------------------------------------------------
# the sampling collector

class RequestTracer:
    """Per-router collector: buffers a live tracer's spans per open
    trace, decides keep (head-sample OR tail-based: SLO breach /
    retry / failover / shed), assembles kept waterfalls into a bounded
    LRU.

    Inert while the tracer is disabled: :meth:`begin` returns an
    unsampled context and buffers nothing, so the disabled path costs
    one attribute check per request. ``max_spans_per_trace`` bounds the
    per-request buffer; overflow drops the OLDEST spans (the tail of a
    long generation matters more than its middle) and is counted in
    ``spans_dropped``.
    """

    def __init__(self, tracer: Optional[Tracer] = None,
                 sample: float = 1.0, capacity: int = 64,
                 max_spans_per_trace: int = 2048,
                 slo: Optional[SLOTracker] = None):
        self.tracer = tracer if tracer is not None else TRACER
        self.sample = float(sample)
        self.capacity = int(capacity)
        self.max_spans_per_trace = int(max_spans_per_trace)
        self.slo = slo
        self._lock = threading.Lock()
        self._cursor = self.tracer.mark()
        self._open: Dict[int, "collections.deque[Span]"] = {}
        self._kept: "collections.OrderedDict[int, dict]" = \
            collections.OrderedDict()
        self.spans_dropped = 0          # per-trace buffer overflow
        self.ring_dropped = 0           # evicted from the ring unseen

    @property
    def active(self) -> bool:
        return self.tracer.enabled

    # -- lifecycle ------------------------------------------------------
    def begin(self, trace_id: int, origin: str = "live") -> TraceContext:
        """Mint the context for one request; opens a span buffer when
        the tracer is recording."""
        ctx = TraceContext(trace_id,
                           sampled=head_sampled(trace_id, self.sample),
                           origin=origin)
        if self.tracer.enabled:
            with self._lock:
                self._open[ctx.trace_id] = collections.deque(
                    maxlen=self.max_spans_per_trace)
        return ctx

    def _collect_locked(self) -> None:
        spans, self._cursor, dropped = self.tracer.drain(self._cursor)
        self.ring_dropped += dropped
        if not self._open:
            return
        for s in spans:
            args = s.args
            tid = args.get("trace_id")
            buf = self._open.get(tid) if isinstance(tid, int) else None
            if buf is not None:
                if len(buf) == buf.maxlen:
                    self.spans_dropped += 1
                buf.append(s)
                continue
            if s.name in _SHARED_SPANS:
                slots = args.get("slots")
                if isinstance(slots, dict):
                    for occupant in set(slots.values()):
                        buf = self._open.get(occupant)
                        if buf is not None:
                            if len(buf) == buf.maxlen:
                                self.spans_dropped += 1
                            buf.append(s)

    def collect(self) -> None:
        """Drain new spans from the tracer into the open-trace buffers
        (also called implicitly by :meth:`finish`)."""
        with self._lock:
            self._collect_locked()

    def finish(self, ctx: TraceContext,
               outcome: dict) -> Optional[dict]:
        """Close one request's trace: collect its spans, decide keep
        (head sample OR tail-based), assemble and retain the waterfall.
        Returns the waterfall when kept, else None."""
        with self._lock:
            self._collect_locked()
            buf = self._open.pop(ctx.trace_id, None)
        if buf is None:                 # tracing was off at begin()
            return None
        keep = ctx.sampled
        why = "head"
        if not keep:
            tail = (outcome.get("status") != "ok"
                    or int(outcome.get("retries") or 0) > 0
                    or int(outcome.get("resumes") or 0) > 0
                    or (self.slo is not None
                        and self.slo.breached(outcome)))
            if tail:
                keep, why = True, "tail"
        if not keep:
            return None
        wf = assemble(buf, ctx.trace_id, outcome)
        wf["kept"] = why
        with self._lock:
            self._kept[ctx.trace_id] = wf
            self._kept.move_to_end(ctx.trace_id)
            while len(self._kept) > self.capacity:
                self._kept.popitem(last=False)
        if self.slo is not None:
            self.slo.note_waterfall(wf)
        return wf

    # -- readout --------------------------------------------------------
    def get(self, trace_id: int) -> Optional[dict]:
        with self._lock:
            return self._kept.get(int(trace_id))

    def waterfalls(self) -> List[dict]:
        """Kept waterfalls, oldest first."""
        with self._lock:
            return list(self._kept.values())

    def summaries(self) -> List[dict]:
        """One index row per kept waterfall (the /requesttrace list)."""
        out = []
        for wf in self.waterfalls():
            out.append({"trace_id": wf["trace_id"],
                        "status": wf.get("status"),
                        "kept": wf.get("kept"),
                        "ttft_ms": wf.get("ttft_ms"),
                        "e2e_ms": wf.get("e2e_ms"),
                        "replica": wf.get("replica"),
                        "retries": wf.get("retries", 0),
                        "segments": len(wf.get("segments") or ()),
                        "n_spans": wf.get("n_spans", 0)})
        return out

    def to_chrome_trace(self,
                        trace_id: Optional[int] = None) -> dict:
        """Perfetto lane-per-REQUEST view (the process tracer's export
        is lane-per-thread): each kept waterfall renders on its own
        ``tid`` lane named after the trace, on a shared timeline, so
        retries and failovers line up across requests."""
        wfs = ([self.get(trace_id)] if trace_id is not None
               else self.waterfalls())
        wfs = [wf for wf in wfs if wf]
        events: List[dict] = []
        for wf in wfs:
            tid = wf["trace_id"]
            events.append({"ph": "M", "name": "thread_name", "pid": 0,
                           "tid": tid,
                           "args": {"name": f"request {tid}"}})
            base_us = wf.get("t0_s", 0.0) * 1e6
            for lane in wf.get("spans") or ():
                ev = {"name": lane["name"], "ph": "X",
                      "ts": round(base_us + lane["start_ms"] * 1000.0, 3),
                      "dur": round(lane["dur_ms"] * 1000.0, 3),
                      "pid": 0, "tid": tid,
                      "args": {"segment": lane.get("segment"),
                               "share": lane.get("share", 1.0)}}
                if lane.get("cat"):
                    ev["cat"] = lane["cat"]
                events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"requests": len(wfs),
                              "spans_dropped": self.spans_dropped,
                              "ring_dropped": self.ring_dropped}}


__all__ = ["TraceContext", "RequestTracer", "SLOTracker", "assemble",
           "ttft_breakdown", "slo_attainment", "head_sampled"]
