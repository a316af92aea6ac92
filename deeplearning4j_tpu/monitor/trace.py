"""Span tracer: where did the wall-clock time go?

The reference answers this with the deeplearning4j-ui stats pipeline
(BaseStatsListener's timing families) plus ad-hoc PerformanceListener
prints; neither can say that a slow epoch was data-wait vs dispatch vs
device. This tracer records host-side WALL-TIME SPANS — named, nested,
per-thread — into a fixed ring buffer, exportable as a Chrome/Perfetto
trace (``chrome://tracing`` / https://ui.perfetto.dev loads the JSON
directly).

Design constraints, in order:

1. **Near-zero cost disabled.** Instrumentation is compiled into the
   hot paths permanently (window executor, serving lifecycle,
   checkpoint commits, fault recovery); the disabled path is one
   attribute check and one question to the profiler ("is a session
   open?") returning a shared no-op span — no allocation, no lock, no
   clock read. Always-on instrumentation with an off switch, not an
   opt-in build.
2. **Thread-safe, per-thread lanes.** The window stager, serving
   workers and the checkpoint writer all trace concurrently; spans
   carry their thread id (a chrome-trace "tid" lane) and nest via a
   thread-local stack, so lanes never interleave.
3. **Bounded memory.** A ring buffer (default 65536 completed spans)
   with a monotonically increasing sequence number; consumers
   (monitor/steptime.py) incrementally drain "spans since mark"
   without copying the whole buffer, and eviction is explicit in the
   drain result (``dropped``).
4. **No device syncs of its own.** A span times the HOST between its
   two edges: a ``dispatch`` span is enqueue cost, not device compute
   (jax dispatch is async); a span whose edges enclose a host sync
   that the code makes anyway (``serving.decode``, ``fit.sync``)
   includes the device's time.
5. **One clock with the device.** While a ``jax.profiler`` session is
   open (``start_trace``, ``start_server``, ``ProfilerSession``) every
   span is also a ``jax.profiler.TraceAnnotation`` of the same name: it
   lands in the profiler's own trace, on the profiler's clock, on the
   calling thread's host line, nested as the spans nest, above the
   device's lines. The session IS the switch: ``enabled`` governs only
   the in-memory ring and its consumers (reqtrace, steptime,
   ``/trace``). Scalar args ride along when the ring is enabled too.

Usage::

    from deeplearning4j_tpu.monitor import TRACER, enable_tracing
    enable_tracing()
    with TRACER.span("window", cat="train", k=8) as sp:
        ...
        sp.set(iteration=it)
    TRACER.write_chrome_trace("trace.json")

Ring spans measure ``time.perf_counter`` and are recorded on
``__exit__`` (a crashed span still records, with the exception type in
its args).
"""
from __future__ import annotations

import collections
import functools
import itertools
import json
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple


#: ``jax.profiler.TraceAnnotation``, resolved by the first call of
#: :func:`_profiling` (importing ``monitor`` must not import jax)
_Annotation = None


def _profiling() -> bool:
    """Whether a profiler session is open. The first call resolves
    jax's annotation class and rebinds this name to the class's own
    check, so every later call is that check and nothing else."""
    global _Annotation, _profiling
    from jax.profiler import TraceAnnotation
    _Annotation = TraceAnnotation
    _profiling = TraceAnnotation.is_enabled
    return _profiling()


def _scalars(args: dict) -> dict:
    """The args an annotation can carry (a bool is an int)."""
    return {k: v for k, v in args.items()
            if isinstance(v, (int, float, str))}


class _NullSpan:
    """The disabled path: a shared, stateless, no-op span."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, **args) -> "_NullSpan":
        return self

    def discard(self) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _ProfilerSpan:
    """Ring disabled, profiler session open: the annotation alone, by
    name (args are the ring's business)."""

    __slots__ = ("_ann",)

    def __init__(self, name: str):
        self._ann = _Annotation(name)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._ann.__exit__(exc_type, exc, tb)
        return False

    def set(self, **args) -> "_ProfilerSpan":
        return self

    def discard(self) -> None:
        """A mirrored event cannot be taken back."""


class Span:
    """One live (then completed) span. Create via :meth:`Tracer.span`."""

    __slots__ = ("tracer", "name", "cat", "args", "t0", "dur", "tid",
                 "thread_name", "seq", "sid", "parent", "_discarded",
                 "_ann")

    #: process-wide id source — `next()` is atomic under the GIL
    _ids = itertools.count(1)

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = 0.0
        self.dur = 0.0
        self.tid = 0
        self.thread_name = ""
        self.seq = -1          # assigned when recorded
        self.sid = 0           # assigned when entered
        self.parent = 0        # sid of the enclosing span on this thread
        self._discarded = False
        self._ann = None       # the profiler's twin, while a session is open

    def __enter__(self) -> "Span":
        t = threading.current_thread()
        self.tid = t.ident or 0
        self.thread_name = t.name
        self.sid = next(Span._ids)
        stack = self.tracer._stack()
        if stack:
            self.parent = stack[-1].sid
        stack.append(self)
        if _profiling():
            self._ann = _Annotation(self.name, **_scalars(self.args))
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.dur = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:               # unbalanced nesting: repair
            stack.remove(self)
        if exc_type is not None:
            self.args = dict(self.args, error=exc_type.__name__)
        if not self._discarded:
            self.tracer._record(self)
        return False

    def set(self, **args) -> "Span":
        """Attach/overwrite span args (shows up in the chrome trace)."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**_scalars(args))
        return self

    def discard(self) -> None:
        """Drop this span from the ring on exit (e.g. a data_wait that
        found end-of-stream instead of data). Its twin in an open
        profiler session stays: that event cannot be taken back."""
        self._discarded = True

    def to_dict(self, t0: float) -> dict:
        """Compact dict form (seconds relative to the tracer epoch)."""
        return {"name": self.name, "cat": self.cat,
                "ts": round(self.t0 - t0, 9), "dur": round(self.dur, 9),
                "tid": self.tid, "thread": self.thread_name,
                "sid": self.sid, "parent": self.parent,
                "args": dict(self.args)}


class Tracer:
    """Thread-safe ring-buffered span tracer (see module docstring).

    One module-level instance (:data:`TRACER`) is shared by all
    instrumented subsystems; ``enabled`` flips instrumentation from
    no-op to recording in place, so call sites can hold the reference
    forever.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = False):
        self.enabled = bool(enabled)
        self._capacity = int(capacity)
        self._lock = threading.Lock()
        self._buf: "collections.deque[Span]" = \
            collections.deque(maxlen=self._capacity)
        self._seq = 0                     # completed spans ever recorded
        self._tls = threading.local()
        self._t0 = time.perf_counter()    # trace epoch
        self._meta_t0 = time.time()       # wall-clock anchor for humans

    # -- recording ------------------------------------------------------
    def span(self, name: str, cat: str = "", **args):
        """Open a span context manager. THE hot call: with the ring
        disabled and no profiler session open it returns a shared no-op
        singleton (no allocation, no clock)."""
        if self.enabled:
            return Span(self, name, cat, args)
        if _profiling():
            return _ProfilerSpan(name)
        return _NULL_SPAN

    def traced(self, name: Optional[str] = None, cat: str = ""):
        """Decorator form: ``@TRACER.traced()`` spans every call."""
        def deco(fn: Callable):
            span_name = name or fn.__qualname__

            @functools.wraps(fn)
            def wrapper(*a, **kw):
                with self.span(span_name, cat=cat):
                    return fn(*a, **kw)
            return wrapper
        return deco

    def record_completed(self, name: str, cat: str = "", dur: float = 0.0,
                         **args) -> None:
        """Record an already-measured span — a duration reported by a
        callback (e.g. a ``jax.monitoring`` compile event) that was
        never entered as a context manager. The span ends NOW and
        started ``dur`` seconds ago, lands in the current thread's lane,
        and nests under whatever span is open on this thread. An
        annotation cannot be backdated, so an open profiler session gets
        a marker at the span's END that carries its length."""
        if _profiling():
            with _Annotation(name, dur_ms=float(dur) * 1e3):
                pass
        if not self.enabled:
            return
        sp = Span(self, name, cat, args)
        t = threading.current_thread()
        sp.tid = t.ident or 0
        sp.thread_name = t.name
        sp.sid = next(Span._ids)
        stack = self._stack()
        if stack:
            sp.parent = stack[-1].sid
        sp.dur = float(dur)
        sp.t0 = time.perf_counter() - sp.dur
        self._record(sp)

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _record(self, span: Span) -> None:
        with self._lock:
            span.seq = self._seq
            self._seq += 1
            self._buf.append(span)

    # -- lifecycle ------------------------------------------------------
    def enable(self) -> "Tracer":
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        return self

    def reset(self, capacity: Optional[int] = None) -> "Tracer":
        """Clear the buffer (and optionally resize) in place."""
        with self._lock:
            if capacity is not None:
                self._capacity = int(capacity)
            self._buf = collections.deque(maxlen=self._capacity)
            self._seq = 0
            self._t0 = time.perf_counter()
            self._meta_t0 = time.time()
        return self

    # -- readout --------------------------------------------------------
    @property
    def epoch(self) -> float:
        """perf_counter value all exported timestamps are relative to."""
        return self._t0

    def mark(self) -> int:
        """Current sequence high-water mark (pass to :meth:`drain`)."""
        with self._lock:
            return self._seq

    def drain(self, since: int = 0) -> Tuple[List[Span], int, int]:
        """Spans recorded after sequence mark ``since`` →
        ``(spans, new_mark, dropped)``. ``dropped`` counts spans that
        were evicted from the ring before this drain saw them."""
        with self._lock:
            n_new = self._seq - since
            if n_new <= 0:
                return [], self._seq, 0
            take = min(n_new, len(self._buf))
            spans = list(itertools.islice(
                self._buf, len(self._buf) - take, len(self._buf)))
            return spans, self._seq, n_new - take

    def spans(self) -> List[Span]:
        """Snapshot of the whole ring (oldest first)."""
        with self._lock:
            return list(self._buf)

    # -- export ---------------------------------------------------------
    def to_chrome_trace(self, since: Optional[int] = None) -> dict:
        """Chrome Trace Event JSON (the ``{"traceEvents": [...]}``
        object form). Loadable by chrome://tracing and Perfetto.
        Timestamps are microseconds from the tracer epoch; each thread
        is one lane, named via metadata events.

        With ``since`` (a sequence mark from a previous export's
        ``otherData["next"]`` or :meth:`mark`), only spans recorded
        after that mark are exported — the incremental form a polling
        collector uses instead of re-downloading the whole ring;
        ``otherData`` then carries the ``next`` cursor and the
        ``dropped`` eviction count."""
        if since is None:
            spans, next_mark, dropped = self.spans(), self.mark(), None
        else:
            spans, next_mark, dropped = self.drain(int(since))
        events: List[dict] = []
        threads: Dict[int, str] = {}
        for sp in spans:
            threads.setdefault(sp.tid, sp.thread_name)
        for tid, tname in sorted(threads.items()):
            events.append({"ph": "M", "name": "thread_name", "pid": 0,
                           "tid": tid, "args": {"name": tname}})
        for sp in sorted(spans, key=lambda s: s.t0):
            ev = {"name": sp.name, "ph": "X",
                  "ts": round((sp.t0 - self._t0) * 1e6, 3),
                  "dur": round(sp.dur * 1e6, 3),
                  "pid": 0, "tid": sp.tid}
            if sp.cat:
                ev["cat"] = sp.cat
            if sp.args:
                ev["args"] = {k: (v if isinstance(v, (int, float, str,
                                                      bool, type(None)))
                                  else repr(v))
                              for k, v in sp.args.items()}
            events.append(ev)
        other = {"tracer_epoch_unix_s": self._meta_t0,
                 "spans": len(spans), "recorded_total": next_mark,
                 "next": next_mark}
        if dropped is not None:
            other["dropped"] = dropped
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": other}

    def write_chrome_trace(self, path: str) -> str:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(self.to_chrome_trace(), fh)
        return path


#: The process-wide tracer every instrumented subsystem records into.
TRACER = Tracer()


def get_tracer() -> Tracer:
    return TRACER


def enable_tracing(capacity: Optional[int] = None,
                   reset: bool = False) -> Tracer:
    """Turn span recording on (optionally resetting/resizing the ring)."""
    if reset or capacity is not None:
        TRACER.reset(capacity=capacity)
    return TRACER.enable()


def disable_tracing() -> Tracer:
    return TRACER.disable()


#: The canonical span registry: every span NAME the package records,
#: mapped to ``(category, well-known arg keys)``. Downstream consumers
#: key on these literals — waterfall assembly (monitor/reqtrace.py)
#: selects ``serving.*``/``fleet.attempt`` by name, steptime attribution
#: selects the train-tier stages, report lanes color by name — so a
#: rename is a silent data loss everywhere at once. The span-name lint
#: (tests/test_static_lint.py) walks every ``span("...")`` /
#: ``record_completed("...")`` / ``_dispatch(..., "...")`` literal in
#: the package and asserts BOTH directions: every recorded name is
#: cataloged, and every cataloged name is still recorded somewhere.
#: Arg keys are the documented contract (e.g. ``trace_id``/``segment``
#: land on any serving span once request tracing propagates a
#: TraceContext; ``slots`` is the batch-level occupancy map).
SPAN_CATALOG: Dict[str, Tuple[str, Tuple[str, ...]]] = {
    # train tier (autodiff/samediff.py, autodiff/window.py)
    "window": ("train", ("k", "iteration")),
    "step": ("train", ("k",)),
    "data_wait": ("train", ()),
    "dispatch": ("train", ("k",)),
    "flush": ("train", ("steps",)),
    "h2d_stage": ("train", ("k",)),
    "integrity.replay_probe": ("integrity", ("k",)),
    # the scanned-epoch tier's boundary (SameDiff._fit_scanned): fit >
    # fit.stage (the working copies of parameters, state and updater
    # state, made by `programs` dispatches over `leaves` arrays:
    # autodiff/staging.py; the stacked batches), fit.dispatch (each
    # scanned epoch), fit.sync (sentinel and panic reads, the one fetch
    # of the epoch means), fit.commit (arrays, updater state and
    # counters written back)
    "fit": ("train", ("tier", "steps", "epochs")),
    "fit.stage": ("train", ("programs", "leaves")),
    # first=1: the dispatch that traces, lowers and compiles or loads
    # the epoch's program; it carries that program's row (trace_s,
    # lower_s, backend_s, cache_hit) and its self time is build_seconds
    "fit.dispatch": ("train", ("epoch", "first", "trace_s", "lower_s",
                               "backend_s", "cache_hit")),
    "fit.sync": ("train", ()),
    "fit.commit": ("train", ()),
    # the phases of a start (CompileStats.span: each one's self time is
    # COMPILE_STATS.build_seconds, compile.plan_analyze's is
    # plan_analyze_seconds; with the compile events below they partition
    # the wall time under them). fit.build: what a fit does before its
    # first fit.stage on a graph it has not run at this version, once in
    # fit() (cache placement, static analysis, mesh placement) and once
    # under fit (step parts, the jitted epoch function). model.build: a
    # zoo builder of a graph, a spec or a spec's programs. serving.build:
    # a generative server's constructor up to its worker's start >
    # serving.build.params (the spec's parameters; their placement on a
    # mesh, under serving.build.pool), serving.build.pool (tiers, pool
    # arrays, dispatchers, the draft's) and serving.warmup > one
    # compile.precompile and one compile.plan_analyze a program
    "fit.build": ("train", ()),
    "model.build": ("model", ("family",)),
    "serving.build": ("serving", ()),
    "serving.build.params": ("serving", ()),
    "serving.build.pool": ("serving", ()),
    # compile pipeline (compilecache/, samediff precompile, memstats).
    # compile.trace / .lower / .backend are markers of jax's own events,
    # each as long as the part of its event that no event inside it has
    # counted (a jit traced inside a trace)
    "compile.precompile": ("compile", ("target",)),
    "compile.plan_capture": ("compile", ("target",)),
    "compile.plan_analyze": ("compile", ("target",)),
    "compile.backend": ("compile", ("cache_hit",)),
    "compile.trace": ("compile", ()),
    "compile.lower": ("compile", ()),
    # checkpoint rail (checkpoint/, parallel/trainer.py)
    "checkpoint.capture": ("checkpoint", ("step",)),
    "checkpoint.commit": ("checkpoint", ("step", "asynchronous",
                                         "queue_s")),
    "checkpoint.serialize": ("checkpoint", ("step",)),
    "checkpoint.reshard": ("checkpoint", ("step",)),
    # fault rail (faults/)
    "faults.rollback": ("faults", ("cause",)),
    "faults.backoff": ("faults", ("attempt", "backoff_s")),
    "data.loader_seek": ("data", ("skip",)),
    "data.loader_retry": ("data", ("skip",)),
    # serving lifecycle (serving/) — request-traced spans additionally
    # carry trace_id/segment; batch-level dispatches carry slots
    "serving.enqueue": ("serving", ("id", "trace_id", "segment")),
    "serving.batch": ("serving", ("rows", "requests")),
    "serving.pad": ("serving", ("rows", "bucket")),
    "serving.exec": ("serving", ("rows", "padding")),
    "serving.reply": ("serving", ("id", "requests", "trace_id",
                                  "segment")),
    "serving.warmup": ("serving", ()),
    "serving.reload": ("serving", ("step", "arrays")),
    # the generative scheduler's worker thread (serving/generative.py):
    # serving.step > serving.admit > serving.prefill, and serving.step >
    # serving.decode | serving.draft.. serving.verify, then serving.emit.
    # A dispatch span runs from before the launch to after the host
    # sync (the same edges as GenerativeMetrics' clocks) and holds
    # serving.launch and serving.sync. With the decode loop one step
    # ahead, a serving.decode also holds the serving.launch of the step
    # after it, and that step's own span (ahead=1) opens where this one
    # closes and holds this step's serving.emit first
    "serving.step": ("serving", ()),
    "serving.admit": ("serving", ("requests",)),
    "serving.prefill": ("serving", ("bucket", "slot", "hist", "trace_id",
                                    "segment")),
    # a prompt longer than the largest bucket: serving.prefill then holds
    # one serving.prefill_chunk a run of the program, each with its
    # serving.launch and only the last with a serving.sync
    "serving.prefill_chunk": ("serving", ("index", "of", "bucket", "hist")),
    # serving.decode's one sync also brings what the program counted on
    # the device where a spec names counters (PagedGenerativeSpec.
    # program_counters: the expert layers' moe_layer_steps,
    # moe_experts_touched_sum, moe_tokens_routed_sum,
    # moe_peak_expert_tokens_sum and, for a sigmoid router with a
    # correction bias, moe_bias_moved_sum; a two-store cache's
    # kv_rows_attended_sum), packed behind the next tokens; ``turns``:
    # lanes whose tumbling window was given back at this step's boundary;
    # ``table_entries``: the table entries a lane the step was sent, over
    # the tiers on the ladder, and ``table_entries.<tier>`` each named
    # tier's own (every tier takes the rung of its own longest lane)
    "serving.decode": ("serving", ("active", "slots", "table_entries",
                                   "table_entries.<tier>", "ahead",
                                   "turns")),
    "serving.draft": ("serving", ("active", "step", "slots", "phase",
                                  "bucket", "slot")),
    "serving.verify": ("serving", ("active", "window", "slots")),
    "serving.launch": ("serving", ()),
    "serving.sync": ("serving", ()),
    "serving.emit": ("serving", ("tokens", "window")),
    # fleet tier (serving/fleet/router.py) — one span per placement
    # attempt, the segment boundary request waterfalls link on
    "fleet.attempt": ("fleet", ("trace_id", "segment", "kind",
                                "replica", "outcome")),
}


__all__ = ["Span", "Tracer", "TRACER", "SPAN_CATALOG", "get_tracer",
           "enable_tracing", "disable_tracing"]
