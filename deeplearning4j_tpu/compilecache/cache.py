"""Persistent compilation cache wiring + process-wide compile accounting.

The reference JVM stack has no analogue: DL4J pays per-op JNI dispatch
and never compiles, so a restarted server is as fast as a warm one.
Under whole-graph XLA compilation the FIRST execution of every distinct
program shape pays seconds of compiler time — a production restart
replays all of it, and a serving process compiles each batch bucket on
the first live request that needs it. JAX ships the fix (a persistent,
content-addressed on-disk executable cache) but it is opt-in and
invisible; this module makes it a wired, observable part of the runtime:

- :func:`configure_cache` applies the cache directory and admission
  knobs to the LIVE process through ``jax.config`` (the
  ``Environment`` property ``compilation_cache_dir`` routes here, so
  ``Environment.set()`` after import actually works — previously the
  property was declared startup-only and a late ``set()`` silently did
  nothing).
- :class:`CompileStats` (singleton :data:`COMPILE_STATS`) counts every
  compile in the process via ``jax.monitoring`` events and splits them
  into persistent-cache HITS (cheap deserialization) vs MISSES (real
  backend compiles), with cumulative wall time per phase. Tests assert
  against deltas of these counters, and the benchmark's
  ``setup_compile_s`` reads one;
  ``MetricsRegistry.fold_compile`` exports them as ``dl4j_compile_*``.
- Each compile phase also lands in the monitor/ tracer ring as a
  synthetic span — ``compile.trace`` (jaxpr tracing), ``compile.lower``
  (StableHLO emission), ``compile.backend`` (XLA compile OR cache
  retrieval, with a ``cache_hit`` arg) — so a Perfetto trace of a cold
  start shows exactly where the seconds went.
- The phases of a start that are not the compiler's — building a
  model's graph or spec, a server's constructor, its warm-up, what a
  first ``fit`` does before it runs — are spans opened through
  :meth:`CompileStats.span`: a tracer span and, on the same two edges,
  the span's SELF time into ``build_seconds``. ``build``, ``trace``,
  ``lower``, ``backend_compile`` and ``plan_analyze`` seconds partition
  the wall time under such a span: no second is in two of them
  (docs/cold_start.md "Where a start's seconds go").

What is cacheable: the persistent cache keys on the serialized HLO +
compile options + backend/runtime version, so entries survive process
restarts and machine reboots but NOT jax/jaxlib/libtpu upgrades (the
key changes and the entry is recompiled — stale entries are harmless
disk). Donation, sharding and remat structure are all part of the HLO,
so they cache fine. See docs/cold_start.md.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Dict, Optional

from deeplearning4j_tpu.monitor.trace import TRACER as _tracer

_COUNT_KEYS = ("backend_compiles", "cache_hits", "cache_misses",
               "precompiles")
_STAT_KEYS = _COUNT_KEYS + (
    "backend_compile_seconds", "cache_load_seconds", "trace_seconds",
    "lower_seconds", "build_seconds", "plan_analyze_seconds",
    "saved_seconds")

#: closed intervals a thread keeps for :func:`_own_seconds`; an interval
#: with more uncovered ones before it than this over-reads, as every
#: nested one did before there was a list
_CLOSED_KEPT = 4096


class CompileStats:
    """Process-wide XLA compile counters fed by ``jax.monitoring``.

    ``backend_compiles`` counts every compile request that reached the
    backend-compile layer — on a persistent-cache HIT that layer only
    deserializes, so the number of *expensive* compiles is
    ``miss_compiles()`` (= ``backend_compiles - cache_hits``; with the
    cache disabled no hit/miss events fire and every backend compile is
    a real one). ``cache_load_seconds`` is the part of
    ``backend_compile_seconds`` those hits took.

    ``build_seconds`` and ``plan_analyze_seconds`` are the self times
    of the spans opened through :meth:`span`; ``precompiles`` counts the
    programs built ahead of time (:meth:`precompile`).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.precompiles = 0
        self.backend_compile_seconds = 0.0
        self.cache_load_seconds = 0.0
        self.trace_seconds = 0.0
        self.lower_seconds = 0.0
        self.build_seconds = 0.0
        self.plan_analyze_seconds = 0.0
        self.saved_seconds = 0.0    # compile time the cache saved (jax est.)

    # -- recording (called from jax.monitoring listeners) ---------------
    def _add(self, **fields) -> None:
        with self._lock:
            for k, v in fields.items():
                setattr(self, k, getattr(self, k) + v)

    # -- the phases of a start (module docstring) -------------------------
    def span(self, name: str, cat: str = "", into: str = "build_seconds",
             **args) -> "_Phase":
        """``TRACER.span(name, cat, **args)`` whose self time is also
        counted, ring on or off: its length less the spans of this kind
        and the compile events inside it goes to the counter ``into``.
        Two clock reads; for a start's scale, not a step's."""
        return _Phase(_tracer.span(name, cat=cat, **args), into)

    def precompile(self, label: str) -> "_Phase":
        """The ``compile.precompile`` span of one program built ahead
        of time, counted in ``precompiles`` when it ends well."""
        return _Phase(_tracer.span("compile.precompile", cat="compile",
                                   target=label),
                      "build_seconds", counts="precompiles")

    def model_build(self, family: str):
        """Decorator: a ``model.build`` span around a zoo builder."""
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*a, **kw):
                with self.span("model.build", cat="model", family=family):
                    return fn(*a, **kw)
            return wrapper
        return deco

    def program_row(self, label: str, mark: Dict[str, float]) -> dict:
        """One row of a warm-up's table: what building the program
        ``label`` added to the counters since ``mark``."""
        d = self.delta(mark)
        return {"label": label, "trace_s": d["trace_seconds"],
                "lower_s": d["lower_seconds"],
                "backend_s": d["backend_compile_seconds"],
                "cache_hit": bool(d["cache_hits"]),
                "plan_analyze_s": d["plan_analyze_seconds"]}

    # -- readout ---------------------------------------------------------
    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {k: getattr(self, k) for k in _STAT_KEYS}

    # a mark IS a snapshot; the split exists so call sites read as
    # mark()/delta() bracketing, like Tracer.mark()/drain()
    mark = snapshot

    def delta(self, mark: Dict[str, float]) -> Dict[str, float]:
        """Counters accumulated since ``mark`` (a prior snapshot)."""
        now = self.snapshot()
        out = {k: now[k] - mark.get(k, 0) for k in _STAT_KEYS}
        for k in _COUNT_KEYS:
            out[k] = int(out[k])
        return out

    def miss_compiles(self) -> int:
        """Expensive (non-cache-hit) compiles so far."""
        with self._lock:
            return max(0, self.backend_compiles - self.cache_hits)

    def to_record(self) -> dict:
        """One ``{"type": "compile"}`` record in the ui/stats JSON-lines
        convention (rendered by ui/report.py, folded by
        ``MetricsRegistry.fold_compile``)."""
        snap = self.snapshot()
        snap["miss_compiles"] = max(0, snap["backend_compiles"]
                                    - snap["cache_hits"])
        return {"type": "compile", "t": time.time(), **snap}

    def publish(self, storage) -> dict:
        rec = self.to_record()
        storage.put(rec)
        return rec


#: The process-wide instance every listener records into.
COMPILE_STATS = CompileStats()

_install_lock = threading.Lock()
_installed = False
_tls = threading.local()


def _thread_list(name: str) -> list:
    got = getattr(_tls, name, None)
    if got is None:
        got = []
        setattr(_tls, name, got)
    return got


def _close(start: float, dur: float) -> float:
    """Put an interval among those this thread has closed, in place of
    the ones it covers (the last ones closed, each begun at or after
    ``start``), and return the seconds those held."""
    closed = _thread_list("closed")
    inside = 0.0
    while closed and closed[-1][0] >= start:
        inside += closed.pop()[1]
    closed.append((start, dur))
    if len(closed) > _CLOSED_KEPT:
        del closed[:_CLOSED_KEPT // 2]
    return inside


def _own_seconds(dur: float) -> float:
    """The part of an event that ends NOW on this thread, ``dur``
    seconds long, which no interval closed inside it has counted: a
    function jitted inside a traced function fires a trace event of its
    own, and the outer event holds those seconds again. What is left is
    also taken from the self time of the :class:`_Phase` open around
    it."""
    own = max(0.0, dur - _close(time.perf_counter() - dur, dur))
    phases = _thread_list("phases")
    if phases:
        phases[-1].inside += own
    return own


class _Phase:
    """A span of :meth:`CompileStats.span`: the tracer's span, and
    inside its two edges a clock of its own, so that the counter moves
    with the ring off too. ``dur`` is its length once it has closed."""

    __slots__ = ("_span", "_into", "counts", "t0", "dur", "inside")

    def __init__(self, span, into: str, counts: Optional[str] = None):
        self._span, self._into, self.counts = span, into, counts
        self.t0 = self.dur = self.inside = 0.0

    def __enter__(self) -> "_Phase":
        self._span.__enter__()
        _thread_list("phases").append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.dur = time.perf_counter() - self.t0
        phases = _thread_list("phases")
        if self in phases:
            del phases[phases.index(self):]
        fields = {self._into: max(0.0, self.dur - self.inside)}
        if self.counts is not None and exc_type is None:
            fields[self.counts] = 1
        COMPILE_STATS._add(**fields)
        if phases:
            phases[-1].inside += self.dur
        # every second of it is counted now: it stands for the
        # intervals closed inside it, should an event cover it in turn
        _close(self.t0, self.dur)
        return self._span.__exit__(exc_type, exc, tb)

    def set(self, **args) -> "_Phase":
        self._span.set(**args)
        return self


def _on_event(event: str, **kw) -> None:
    if event.endswith("/compilation_cache/cache_hits"):
        COMPILE_STATS._add(cache_hits=1)
        # the matching backend_compile duration event (which fires for
        # hits too — it wraps retrieval) marks its span via this flag;
        # compiles are synchronous on the calling thread, so
        # thread-local pairing is race-free
        _tls.pending_hit = True
    elif event.endswith("/compilation_cache/cache_misses"):
        COMPILE_STATS._add(cache_misses=1)
        # a hit whose backend_compile duration event never arrived
        # (aborted compile) must not mislabel THIS compile as a hit
        _tls.pending_hit = False


def _on_duration(event: str, duration: float, **kw) -> None:
    if event.endswith("backend_compile_duration") or \
            event.endswith("backend_compile_time_sec"):
        hit = bool(getattr(_tls, "pending_hit", False))
        _tls.pending_hit = False
        own = _own_seconds(float(duration))
        COMPILE_STATS._add(backend_compiles=1, backend_compile_seconds=own,
                           cache_load_seconds=own if hit else 0.0)
        _tracer.record_completed("compile.backend", cat="compile",
                                 dur=own, cache_hit=hit)
    elif event.endswith("jaxpr_trace_duration"):
        own = _own_seconds(float(duration))
        COMPILE_STATS._add(trace_seconds=own)
        _tracer.record_completed("compile.trace", cat="compile", dur=own)
    elif event.endswith("jaxpr_to_mlir_module_duration"):
        own = _own_seconds(float(duration))
        COMPILE_STATS._add(lower_seconds=own)
        _tracer.record_completed("compile.lower", cat="compile", dur=own)
    elif event.endswith("compile_time_saved_sec"):
        # jax reports compile_time - retrieval_time; can be negative for
        # programs that compile faster than they deserialize
        COMPILE_STATS._add(saved_seconds=float(duration))


def install_compile_watcher() -> CompileStats:
    """Register the ``jax.monitoring`` listeners feeding
    :data:`COMPILE_STATS` (idempotent; listeners are process-lifetime).
    Called automatically by cache configuration, ``precompile()`` and
    serving warmup — call it directly only to observe purely-lazy
    compilation."""
    global _installed
    with _install_lock:
        if not _installed:
            from jax import monitoring
            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
            _installed = True
    return COMPILE_STATS


def configure_cache(cache_dir: Optional[str],
                    min_entry_size: Optional[int] = None,
                    min_compile_time: Optional[float] = None) -> None:
    """Apply persistent-cache settings to the LIVE jax process.

    Which directory is decided in one place,
    ``Environment.apply_compilation_cache()``; this function only
    applies it. ``cache_dir=None``/``""`` disables the cache.
    ``min_entry_size`` (bytes; -1 = cache everything) and
    ``min_compile_time`` (seconds; 0 = cache everything) gate which
    executables are worth persisting — production defaults skip
    sub-second compiles, tests set both to the cache-everything values.
    Installs the compile watcher whenever a cache is enabled, so
    hit/miss accounting is always live alongside.
    """
    import jax
    target = cache_dir or None
    if jax.config.jax_compilation_cache_dir != target:
        jax.config.update("jax_compilation_cache_dir", target)
        # jax initializes its cache object AT MOST ONCE, on the first
        # compile — if anything compiled before this call the cache
        # latched its old state and the config update above would never
        # take effect. Reset to pristine so the next compile re-reads
        # the config. Skipped when the dir is already the live value
        # (the admission knobs are read per-put), so repeated applies —
        # every fit() and serving warmup calls this — don't tear down
        # and re-create the cache backend.
        from jax._src import compilation_cache
        compilation_cache.reset_cache()
    if min_entry_size is not None:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          int(min_entry_size))
    if min_compile_time is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time))
    if cache_dir:
        install_compile_watcher()


def cache_dir() -> Optional[str]:
    """The live process's persistent cache directory (None = disabled)."""
    import jax
    return jax.config.jax_compilation_cache_dir


__all__ = ["CompileStats", "COMPILE_STATS", "install_compile_watcher",
           "configure_cache", "cache_dir"]
