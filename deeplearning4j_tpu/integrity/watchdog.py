"""Stall watchdog: adaptive deadlines around blocking device boundaries.

The fault rail (faults/) handles failures that RAISE; a wedged
collective, a lost device or a hung host↔device transfer raises
nothing — the process just stops making progress with healthy-looking
/healthz. This module arms a daemon heartbeat thread over every
blocking device boundary the tracer already names:

====================  =====================================================
boundary              guarded call
====================  =====================================================
``window_dispatch``   the fused-window dispatch (autodiff/window.py)
``step_dispatch``     the per-step tier's train dispatch
``flush``             the listener flush's ``jax.device_get`` burst
``serving_execute``   ``ParallelInference._execute``'s graph exec
``checkpoint_capture`` the checkpoint device→host state capture
====================  =====================================================

Each boundary's deadline is ADAPTIVE: ``k ×`` the rolling p50 of its own
recent durations (``monitor.steptime.RollingPercentiles``), floored at
``floor_s``; until ``min_samples`` observations exist — and for any
guard entered with ``first=True`` (a first dispatch that will compile) —
the ``grace_s`` compile grace applies instead, so cold starts and
retraces never false-positive.

On expiry the monitor thread (NOT the wedged one):

1. captures forensics — all-thread stacks (:func:`dump_all_stacks`),
   a live HBM snapshot and the active compiled-program memory plan —
   while the boundary is still wedged;
2. publishes ``{"type": "faults", "event": "stall"}`` (flips
   ``/healthz`` to 503 — monitor/server.py treats ``stall`` as
   degrading) plus a ``{"type": "integrity"}`` forensics record;
3. marks the guard expired. If the blocked call eventually returns
   (a *recoverable* stall), the guard's exit raises a typed
   :class:`~deeplearning4j_tpu.faults.errors.TrainingStalledError`
   carrying the forensics — retryable, so ``FaultTolerantFit`` rolls
   back and retries under its normal budget. A permanent wedge never
   returns, but the record/503/stack dump are already out for the
   supervisor that will kill the process.

When no watchdog is installed, :func:`guard` returns a shared no-op
context — the boundaries pay one global read. Clean-path training
with the watchdog armed is bit-identical to unguarded (the guard never
touches the math).
"""
from __future__ import annotations

import sys
import threading
import time
import traceback
from typing import Dict, List, Optional

from deeplearning4j_tpu.monitor.steptime import RollingPercentiles


def dump_all_stacks() -> List[dict]:
    """Snapshot every live thread's Python stack: ``[{name, ident,
    daemon, stack: [frame lines]}, ...]`` — the payload of the
    TelemetryServer's ``GET /stacks`` debug route and of stall
    forensics. Pure introspection; never blocks the dumped threads."""
    names = {t.ident: t for t in threading.enumerate()}
    out = []
    for ident, frame in sys._current_frames().items():
        t = names.get(ident)
        out.append({
            "name": t.name if t is not None else f"thread-{ident}",
            "ident": ident,
            "daemon": bool(t.daemon) if t is not None else None,
            "stack": [ln.rstrip("\n") for ln in
                      traceback.format_stack(frame)],
        })
    return out


class _NullGuard:
    """Shared no-op context for the uninstalled-watchdog fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullGuard()
_ACTIVE: Optional["StallWatchdog"] = None


def guard(boundary: str, first: bool = False):
    """The boundary seam: a context manager timing this blocking call
    under the installed watchdog (or a shared no-op when none is).
    ``first=True`` marks a call expected to compile — it gets the
    compile grace instead of the adaptive deadline."""
    wd = _ACTIVE
    if wd is None:
        return _NULL
    return wd.guard(boundary, first=first)


def active() -> Optional["StallWatchdog"]:
    return _ACTIVE


class _Guard:
    __slots__ = ("wd", "boundary", "deadline_s", "start", "expired",
                 "error")

    def __init__(self, wd: "StallWatchdog", boundary: str,
                 deadline_s: float):
        self.wd = wd
        self.boundary = boundary
        self.deadline_s = deadline_s
        self.start = 0.0
        self.expired = False
        self.error = None

    def __enter__(self):
        self.start = self.wd._clock()
        self.wd._register(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        waited = self.wd._clock() - self.start
        self.wd._unregister(self, waited)
        if self.expired and self.error is None:
            # the monitor claimed this guard but its forensics dump is
            # still in flight: wait for the typed error briefly so the
            # stall surfaces here, not as a silent 503
            for _ in range(200):
                if self.error is not None:
                    break
                time.sleep(0.01)
        if self.error is not None and exc_type is None:
            # the stall healed (the call returned): surface it typed so
            # the recovery driver can roll back the possibly-suspect
            # boundary instead of training on
            raise self.error
        return False


class StallWatchdog:
    """Daemon heartbeat thread arming adaptive deadlines around
    blocking device boundaries (module docstring).

    ::

        wd = StallWatchdog(storage=storage, k=8.0, floor_s=5.0)
        with wd:                       # install() / uninstall()
            ftf.fit(it, epochs=20)
        wd.stats()                     # {"stalls": ..., "guards": ...}

    ``k``/``floor_s``/``grace_s`` tune the deadline; ``poll_s`` bounds
    detection latency; ``storage`` receives the stall records;
    ``forensics=False`` skips the HBM snapshot (stacks always dump).
    """

    def __init__(self, storage=None, k: float = 8.0, floor_s: float = 5.0,
                 grace_s: float = 120.0, poll_s: float = 0.25,
                 min_samples: int = 3, window: int = 256,
                 forensics: bool = True,
                 clock=time.monotonic):
        self.storage = storage
        self.k = float(k)
        self.floor_s = float(floor_s)
        self.grace_s = float(grace_s)
        self.poll_s = float(poll_s)
        self.min_samples = int(min_samples)
        self.forensics = bool(forensics)
        self._clock = clock
        self._window = int(window)
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._percentiles: Dict[str, RollingPercentiles] = {}
        self._entries: Dict[int, _Guard] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.stalls = 0
        self.guards = 0
        self.events: List[dict] = []

    # -- lifecycle ------------------------------------------------------
    def install(self) -> "StallWatchdog":
        """Become the process-wide watchdog (:func:`guard` routes to
        this instance) and start the monitor thread."""
        global _ACTIVE
        _ACTIVE = self
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._monitor, name="integrity-watchdog",
                daemon=True)
            self._thread.start()
        return self

    def uninstall(self) -> None:
        global _ACTIVE
        if _ACTIVE is self:
            _ACTIVE = None
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def __enter__(self) -> "StallWatchdog":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # -- deadlines ------------------------------------------------------
    def deadline_for(self, boundary: str, first: bool = False) -> float:
        """``max(floor, k × rolling-p50)`` — or the compile grace while
        the boundary has fewer than ``min_samples`` observations or the
        caller flagged a first (compiling) dispatch."""
        with self._lock:
            p = self._percentiles.get(boundary)
            n = len(p) if p is not None else 0
            p50 = p.percentile(50) if n else 0.0
        if first or n < self.min_samples:
            return max(self.grace_s, self.floor_s)
        return max(self.floor_s, self.k * p50)

    def guard(self, boundary: str, first: bool = False) -> _Guard:
        return _Guard(self, boundary, self.deadline_for(boundary, first))

    # -- guard bookkeeping ---------------------------------------------
    def _register(self, g: _Guard) -> None:
        with self._cv:
            self.guards += 1
            self._entries[id(g)] = g
            self._cv.notify_all()

    def _unregister(self, g: _Guard, waited: float) -> None:
        with self._cv:
            self._entries.pop(id(g), None)
            p = self._percentiles.get(g.boundary)
            if p is None:
                p = self._percentiles[g.boundary] = \
                    RollingPercentiles(self._window)
            p.add(waited)

    # -- the heartbeat --------------------------------------------------
    def _monitor(self) -> None:
        while not self._stop.is_set():
            with self._cv:
                if not self._entries:
                    self._cv.wait(timeout=self.poll_s)
                    continue
                now = self._clock()
                expired = [g for g in self._entries.values()
                           if not g.expired
                           and now - g.start > g.deadline_s]
                for g in expired:
                    # claimed under the lock BEFORE the (slow) forensics
                    # capture — the next poll cycle must not re-expire
                    # a guard whose dump is still being built
                    g.expired = True
            for g in expired:
                self._expire(g)
            self._stop.wait(self.poll_s)

    def _expire(self, g: _Guard) -> None:
        waited = self._clock() - g.start
        forensics = self._forensics()
        from deeplearning4j_tpu.faults.errors import TrainingStalledError
        g.error = TrainingStalledError(
            f"{g.boundary} stalled: blocked {waited:.3f}s > deadline "
            f"{g.deadline_s:.3f}s (k={self.k} × rolling-p50, floor "
            f"{self.floor_s}s) — forensics (all-thread stacks, HBM "
            f"snapshot, active memory plan) attached; "
            f"{'{'}\"type\": \"faults\", \"event\": \"stall\"{'}'} "
            f"published", boundary=g.boundary, waited_s=round(waited, 6),
            deadline_s=round(g.deadline_s, 6), forensics=forensics)
        self.stalls += 1
        rec = {"type": "faults", "event": "stall", "t": time.time(),
               "boundary": g.boundary, "waited_s": round(waited, 6),
               "deadline_s": round(g.deadline_s, 6),
               "threads": len(forensics.get("stacks", ()))}
        self.events.append(rec)
        if self.storage is not None:
            self.storage.put(rec)
            # the heavyweight forensics ride a separate integrity
            # record so the faults fold stays cheap
            self.storage.put({
                "type": "integrity", "event": "stall_forensics",
                "t": time.time(), "boundary": g.boundary,
                "waited_s": round(waited, 6),
                "stacks": forensics.get("stacks"),
                "active_program": forensics.get("active_program"),
                "hbm": {k: forensics.get("memory", {}).get(k)
                        for k in ("bytes_in_use", "peak_bytes",
                                  "bytes_limit")}})

    def _forensics(self) -> dict:
        out: dict = {"stacks": dump_all_stacks()}
        if not self.forensics:
            return out
        try:
            from deeplearning4j_tpu.monitor import memstats
            out["memory"] = memstats.memory_record(source="watchdog")
            active_plan = memstats.PLANS.active_plan()
            out["active_program"] = active_plan.label \
                if active_plan is not None else None
            if active_plan is not None:
                out["plan"] = active_plan.to_record()
        except Exception as e:      # noqa: BLE001 — forensics must not
            out["memory_error"] = repr(e)     # mask the stall itself
        return out

    def stats(self) -> dict:
        with self._lock:
            per = {b: {"n": len(p), "p50_s": round(p.percentile(50), 6)}
                   for b, p in self._percentiles.items()}
        return {"stalls": self.stalls, "guards": self.guards,
                "boundaries": per}


__all__ = ["StallWatchdog", "active", "dump_all_stacks", "guard"]
