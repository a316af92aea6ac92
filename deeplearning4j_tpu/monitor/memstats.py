"""HBM memory observability: live telemetry, compiled-program memory
plans, and OOM forensics.

The 16 GB HBM budget of gpt_medium used to be worked out **by hand**
("f32 masters + Adam m/v 6 GB + grads 2 GB + … logits 2.1 GB"), and an
OOM surfaced as a raw ``RESOURCE_EXHAUSTED`` with no breakdown.
This module makes memory a first-class observable on the same "ride
existing flush boundaries, bit-identical when on" discipline as the
rest of monitor/:

- **live telemetry** — :func:`memory_record` samples
  :func:`deeplearning4j_tpu.memory.snapshot` into a ``{"type":
  "memory"}`` record (ui/stats schema). ``MonitorListener`` publishes
  one per listener flush (the host already syncs there — no extra
  device round-trips, clean runs stay bit-identical),
  ``ParallelInference`` at serving batch boundaries,
  ``MetricsRegistry.fold_memory`` exports ``dl4j_hbm_*`` gauges, and
  ``TelemetryServer`` serves it all live at ``GET /memory``.
- **static memory & compute plans** — :func:`capture_plan` reads
  ``compiled.memory_analysis()`` (temp/argument/output/generated-code
  bytes) and ``cost_analysis()`` (flops, bytes accessed) off every
  executable built by ``SameDiff.precompile()`` /
  ``precompile_output()`` (serving warmup buckets) into the
  process-wide :data:`PLANS` registry. With plan capture **enabled**
  (:func:`enable_plan_capture` — ``MonitorListener`` arms it), lazily
  jitted train programs are promoted to AOT executables at their first
  dispatch (``lower().compile()`` instead of the jit call's internal
  compile — the SAME lowering, one compile either way, bit-identical
  outputs) so their plans are captured too. The fit tiers report the
  active program via :func:`note_dispatch`, which is what lets
  ``MonitorListener`` export a live MFU-estimate gauge mid-fit:
  plan flops-per-step ÷ measured step time ÷ :func:`peak_flops`.
- **OOM forensics** — :func:`reraise_oom` converts a backend
  ``RESOURCE_EXHAUSTED`` caught at the fit / serving exec paths into a
  structured :class:`~deeplearning4j_tpu.memory.MemoryExhaustedError`
  carrying the last device snapshot, a live-array census, and the
  active program's plan. ``FaultTolerantFit`` publishes it as a
  ``{"type": "faults", "event": "oom"}`` record and aborts — a
  rollback cannot shrink the program, so OOM is
  non-retryable-with-diagnosis (docs/fault_tolerance.md).
- **headroom guards** — :func:`projected_headroom` (bytes_limit −
  bytes_in_use, min across devices that report a limit) backs the
  serving-side refusals: ``reload_from()`` and ``warmup()`` raise
  :class:`~deeplearning4j_tpu.memory.MemoryHeadroomError` instead of
  letting a too-big swap/bucket OOM a live server.

See docs/observability.md ("Memory observability").
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from deeplearning4j_tpu import memory
from deeplearning4j_tpu.memory import (MemoryExhaustedError,
                                       MemoryHeadroomError)
from deeplearning4j_tpu.monitor.trace import TRACER as _tracer

#: memory-plan byte components, in stacked-budget-chart order
PLAN_BYTE_FIELDS = ("argument_bytes", "temp_bytes", "output_bytes",
                    "generated_code_bytes")


_graph_counter = itertools.count(1)


def graph_key(graph) -> Optional[int]:
    """Stable per-graph identity for plan attribution (assigned on
    first use, stored on the graph). The registry is process-global;
    this is what lets a listener publish only ITS model's plans when
    several models train/serve in one process."""
    if graph is None:
        return None
    gid = graph.__dict__.get("_memstats_gid")
    if gid is None:
        gid = graph.__dict__["_memstats_gid"] = next(_graph_counter)
    return gid


@dataclasses.dataclass
class MemoryPlan:
    """One compiled executable's static memory & compute plan."""
    label: str                       # "window_k8", "train_step", "output_b32"
    sig: str                         # placeholder shape signature (repr)
    steps: int = 1                   # train steps per dispatch (k)
    graph: Optional[int] = None      # graph_key() of the owning graph
    argument_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    t: float = 0.0

    @property
    def total_bytes(self) -> int:
        """Predicted peak footprint of one dispatch: arguments + temps
        + outputs + generated code (aliased/donated bytes excluded —
        they reuse argument space)."""
        return sum(int(getattr(self, f) or 0) for f in PLAN_BYTE_FIELDS) \
            - int(self.alias_bytes or 0)

    @property
    def flops_per_step(self) -> Optional[float]:
        if self.flops is None:
            return None
        return float(self.flops) / max(1, int(self.steps))

    def to_record(self) -> dict:
        """One ``{"type": "memory_plan"}`` record (ui/stats schema)."""
        rec = {"type": "memory_plan", "t": self.t or time.time(),
               "program": self.label, "sig": self.sig,
               "steps": int(self.steps),
               "total_bytes": int(self.total_bytes)}
        for f in PLAN_BYTE_FIELDS + ("alias_bytes",):
            v = getattr(self, f)
            if v is not None:
                rec[f] = int(v)
        if self.flops is not None:
            rec["flops"] = float(self.flops)
            rec["flops_per_step"] = float(self.flops_per_step)
        if self.bytes_accessed is not None:
            rec["bytes_accessed"] = float(self.bytes_accessed)
        return rec


def _analyze(compiled=None, lowered=None) -> Dict[str, Any]:
    """Read whatever analyses the stage object supports — memory from a
    ``Compiled``, cost from either — defensively: a backend without an
    analysis returns a partial plan, never an error."""
    out: Dict[str, Any] = {}
    if compiled is not None:
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                out["argument_bytes"] = int(ma.argument_size_in_bytes)
                out["temp_bytes"] = int(ma.temp_size_in_bytes)
                out["output_bytes"] = int(ma.output_size_in_bytes)
                out["generated_code_bytes"] = \
                    int(ma.generated_code_size_in_bytes)
                out["alias_bytes"] = int(ma.alias_size_in_bytes)
        except Exception:
            pass
    for stage in (compiled, lowered):
        if stage is None or "flops" in out:
            continue
        try:
            ca = stage.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if ca:
                if ca.get("flops") is not None:
                    out["flops"] = float(ca["flops"])
                if ca.get("bytes accessed") is not None:
                    out["bytes_accessed"] = float(ca["bytes accessed"])
        except Exception:
            pass
    return out


class MemoryPlans:
    """Process-wide registry of captured memory plans (the static half
    of the memory story), keyed by placeholder shape signature.

    ``note_dispatch`` is on the fit hot path: its fast path is one
    attribute check when no plans exist, one dict lookup + attribute
    store when they do — no locks, no allocation."""

    def __init__(self):
        self._lock = threading.Lock()
        self._by_sig: Dict[str, MemoryPlan] = {}
        self._order: List[str] = []          # capture order (publishing)
        self._active_sig: Optional[str] = None

    @staticmethod
    def _sig_key(sig) -> str:
        return sig if isinstance(sig, str) else repr(sig)

    def capture(self, label: str, sig, compiled=None, lowered=None,
                steps: int = 1, graph=None) -> Optional[MemoryPlan]:
        """Analyze one executable into the registry (idempotent per
        signature; re-capture refreshes). ``graph`` is the owning
        SameDiff (attribution — see :func:`graph_key`). Never raises —
        plan capture must not be able to break a compile path."""
        # compilecache imports this package's tracer: not at the top
        from deeplearning4j_tpu.compilecache.cache import COMPILE_STATS
        try:
            with COMPILE_STATS.span("compile.plan_analyze", cat="compile",
                                    into="plan_analyze_seconds",
                                    target=str(label)):
                fields = _analyze(compiled=compiled, lowered=lowered)
            if not fields:
                return None
            key = self._sig_key(sig)
            plan = MemoryPlan(label=str(label), sig=key,
                              steps=max(1, int(steps)), t=time.time(),
                              graph=graph_key(graph), **fields)
            with self._lock:
                if key not in self._by_sig:
                    self._order.append(key)
                self._by_sig[key] = plan
            return plan
        except Exception:       # noqa: BLE001 — observability-only path
            return None

    def note_dispatch(self, sig, steps: int = 1) -> None:
        """The fit tiers report the program they just dispatched; the
        MFU gauge and OOM forensics read it back as the ACTIVE plan."""
        if not self._by_sig:
            return
        key = self._sig_key(sig)
        if key in self._by_sig:
            self._active_sig = key

    def active_plan(self) -> Optional[MemoryPlan]:
        key = self._active_sig
        return self._by_sig.get(key) if key is not None else None

    def get(self, sig) -> Optional[MemoryPlan]:
        return self._by_sig.get(self._sig_key(sig))

    def find(self, label: str) -> Optional[MemoryPlan]:
        """Newest plan captured under ``label``."""
        with self._lock:
            for key in reversed(self._order):
                p = self._by_sig.get(key)
                if p is not None and p.label == label:
                    return p
        return None

    def plans(self) -> List[MemoryPlan]:
        with self._lock:
            return [self._by_sig[k] for k in self._order]

    def __len__(self) -> int:
        return len(self._by_sig)

    def reset(self) -> None:
        with self._lock:
            self._by_sig.clear()
            self._order.clear()
            self._active_sig = None


#: The process-wide plan registry.
PLANS = MemoryPlans()

_capture_enabled = False


def enable_plan_capture() -> None:
    """Arm lazy-compile plan capture: the fit tiers promote a new
    placeholder signature's first compile to an AOT ``lower().
    compile()`` (same lowering the jit call would do — ONE compile
    either way, bit-identical outputs, tested) so its memory plan is
    inspectable. ``MonitorListener`` calls this at training start;
    AOT surfaces (``precompile``/warmup) capture unconditionally."""
    global _capture_enabled
    _capture_enabled = True


def disable_plan_capture() -> None:
    global _capture_enabled
    _capture_enabled = False


def plan_capture_enabled() -> bool:
    return _capture_enabled


def capture_plan(label: str, sig, compiled=None, lowered=None,
                 steps: int = 1, graph=None) -> Optional[MemoryPlan]:
    """Module-level convenience over :data:`PLANS` (see
    :meth:`MemoryPlans.capture`)."""
    return PLANS.capture(label, sig, compiled=compiled, lowered=lowered,
                         steps=steps, graph=graph)


def note_dispatch(sig, steps: int = 1) -> None:
    PLANS.note_dispatch(sig, steps)


# ---------------------------------------------------------------------
# live telemetry
def memory_record(epoch: Optional[int] = None,
                  iteration: Optional[int] = None,
                  source: str = "flush") -> dict:
    """One ``{"type": "memory"}`` record: per-device counters, totals,
    projected headroom, and the AllocationsTracker's tagged transfer
    totals. Pure host work — reading PJRT counters never syncs the
    device, so publishing these at flush boundaries keeps clean runs
    bit-identical (tested)."""
    snap = memory.snapshot()
    devices = [dataclasses.asdict(s) for s in snap]
    limits = [s.bytes_limit for s in snap if s.bytes_limit]
    tracker = memory.AllocationsTracker.get_instance()
    rec = {"type": "memory", "t": time.time(), "source": source,
           "bytes_in_use": sum(s.bytes_in_use for s in snap),
           "peak_bytes": max((s.peak_bytes or s.bytes_in_use)
                             for s in snap) if snap else 0,
           "bytes_limit": sum(limits),
           "devices": devices,
           "tracked": tracker.totals(),
           "tracked_counts": tracker.counts()}
    head = projected_headroom(snap)
    if head is not None:
        rec["headroom"] = int(head)
    skipped = sum(s.skipped_arrays for s in snap)
    if skipped:
        rec["live_skipped"] = int(skipped)
    if epoch is not None:
        rec["epoch"] = int(epoch)
    if iteration is not None:
        rec["iteration"] = int(iteration)
    return rec


def projected_headroom(snap: Optional[List] = None) -> Optional[int]:
    """Remaining HBM: min over devices reporting a ``bytes_limit`` of
    ``limit − in_use``. None when no device reports a limit (CPU) —
    headroom guards are then no-ops rather than false refusals."""
    if snap is None:
        snap = memory.snapshot()
    rooms = [s.bytes_limit - s.bytes_in_use
             for s in snap if s.bytes_limit]
    return min(rooms) if rooms else None


def check_headroom(required_bytes: int, what: str,
                   margin: float = 1.0) -> None:
    """Raise :class:`MemoryHeadroomError` when ``required_bytes ×
    margin`` exceeds the projected headroom (no-op where no device
    reports a limit)."""
    head = projected_headroom()
    if head is None:
        return
    need = int(required_bytes * float(margin))
    if need > head:
        raise MemoryHeadroomError(
            f"{what} needs ~{need / 2**20:.1f} MiB but projected HBM "
            f"headroom is {head / 2**20:.1f} MiB — refused before the "
            f"backend OOMs (docs/observability.md)",
            required_bytes=need, headroom_bytes=head)


# ---------------------------------------------------------------------
# OOM forensics
def is_resource_exhausted(exc: BaseException) -> bool:
    """Is this the backend's allocation-failure error? XLA surfaces it
    as ``JaxRuntimeError`` with a ``RESOURCE_EXHAUSTED:`` status (the
    chaos injector raises the same type+message)."""
    if isinstance(exc, MemoryExhaustedError):
        return False                 # already converted
    if "RESOURCE_EXHAUSTED" not in str(exc):
        return False
    from jax.errors import JaxRuntimeError
    return isinstance(exc, JaxRuntimeError)


def oom_error(cause: BaseException, program: Optional[str] = None,
              step: Optional[int] = None,
              epoch: Optional[int] = None) -> MemoryExhaustedError:
    """Build the structured OOM with forensics attached: last device
    snapshot, live-array census, active program plan."""
    try:
        snap = memory.snapshot()
    except Exception:
        snap = []
    try:
        census = memory.live_census()
    except Exception:
        census = None
    plan = PLANS.active_plan()
    if plan is not None and program is None:
        program = plan.label
    return MemoryExhaustedError(
        f"device memory exhausted during "
        f"{program or 'execution'}: {cause}",
        program=program, step=step, epoch=epoch, snapshot=snap,
        census=census, plan=plan.to_record() if plan is not None else None)


def reraise_oom(exc: BaseException, program: Optional[str] = None,
                step: Optional[int] = None,
                epoch: Optional[int] = None) -> None:
    """Exec-path hook: convert a ``RESOURCE_EXHAUSTED`` into a
    :class:`MemoryExhaustedError` with forensics (raises); any other
    exception passes through untouched (returns)."""
    if is_resource_exhausted(exc):
        raise oom_error(exc, program=program, step=step,
                        epoch=epoch) from exc


# ---------------------------------------------------------------------
# lazy-compile promotion (the "SameDiff jit" plan-capture path)
def promote_dispatch(disp, args: Tuple, sig, label: str,
                     steps: int = 1, graph=None) -> bool:
    """With plan capture enabled, compile a NEW placeholder signature
    through the AOT path (``disp.lower(*args).compile()``) and install
    it in ``disp.aot`` so (a) its memory plan is captured and (b) the
    dispatch about to happen hits the prebuilt executable. This
    replaces the jit call's internal compile — same lowering, one
    compile either way. Returns True when promoted. Any failure falls
    back to the lazy jit path silently (observability must not break
    training)."""
    if not _capture_enabled:
        return False
    aot = getattr(disp, "aot", None)
    if aot is None or sig in aot:
        return False
    try:
        with _tracer.span("compile.plan_capture", cat="compile",
                          target=label):
            compiled = disp.lower(*args).compile()
        aot[sig] = compiled
        capture_plan(label, sig, compiled=compiled, steps=steps,
                     graph=graph)
        return True
    except Exception:       # noqa: BLE001 — fall back to lazy jit
        return False


# ---------------------------------------------------------------------
# MFU estimate
#: THE peak-rate table: ``device_kind`` substring (lower case, first
#: match wins) -> peak dense bf16 FLOP/s of one chip, each with its
#: source. chip_smoke.py reads it too; a kind that is not
#: listed has no peak (an error where a utilization is printed, an
#: absent gauge here) and nothing in the environment can invent one.
_PEAK_FLOPS_BY_KIND = (
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    # (393 TOP/s is its int8 rate). PJRT reports this chip as
    # "TPU v5 lite".
    ("v5 lite", 197.0e12), ("v5e", 197.0e12),
    # Google Cloud documentation, "TPU v5p": 459 TFLOP/s bf16
    ("v5p", 459.0e12), ("v5", 459.0e12),
    # Google Cloud documentation, "TPU v4": 275 TFLOP/s bf16
    ("v4", 275.0e12),
    # Google Cloud documentation, "TPU v6e": 918 TFLOP/s bf16
    ("v6", 918.0e12),
)


def peak_flops(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak bf16 FLOP/s for the MFU denominator from
    :data:`_PEAK_FLOPS_BY_KIND`, for ``device_kind`` (default: the
    first local device's). None for a kind the table does not list —
    no MFU gauge is better than a wrong one."""
    if device_kind is None:
        import jax
        device_kind = jax.local_devices()[0].device_kind
    kind = device_kind.lower()
    for sub, flops in _PEAK_FLOPS_BY_KIND:
        if sub in kind:
            return flops
    return None


def mfu_estimate(step_seconds: float) -> Optional[Tuple[float, float]]:
    """Live MFU estimate from the ACTIVE program's plan: ``(flops_per
    _step / step_seconds / peak, flops_per_step)``. None when no plan
    with flops is active, step time is unknown, or the peak is unknown
    — the gauge is simply not exported rather than exported wrong."""
    plan = PLANS.active_plan()
    if plan is None or plan.flops_per_step is None or step_seconds <= 0:
        return None
    fps = plan.flops_per_step
    peak = peak_flops()
    if peak is None or peak <= 0:
        return None
    return fps / step_seconds / peak, fps


__all__ = ["MemoryPlan", "MemoryPlans", "PLANS", "graph_key",
           "capture_plan",
           "note_dispatch", "enable_plan_capture", "disable_plan_capture",
           "plan_capture_enabled", "memory_record", "projected_headroom",
           "check_headroom", "is_resource_exhausted", "oom_error",
           "reraise_oom", "promote_dispatch", "peak_flops",
           "mfu_estimate", "MemoryExhaustedError", "MemoryHeadroomError"]
