"""Profiler session: capture + aggregate device op times.

Reference parity: ProfilerConfig/OpProfiler enable-collect-report cycle
(OpProfiler.java:41 printOutDashboard). Usage:

    with ProfilerSession() as prof:
        step(...)                 # any device work
    profile = prof.profile()
    print(profile.report(top=10))
"""
from __future__ import annotations

import glob
import os
import tempfile
import time
from typing import Dict, List, Optional

from deeplearning4j_tpu.profiler.xplane import (
    OpTime, category_times, device_op_times, load_xspace)


class OpProfile:
    """Aggregated per-op device times for one capture."""

    def __init__(self, op_times: List[OpTime]):
        self.op_times = op_times

    def top(self, n: int = 10) -> List[OpTime]:
        return self.op_times[:n]

    def by_category(self) -> Dict[str, float]:
        return category_times(self.op_times)

    def total_ms(self) -> float:
        return sum(o.total_ms for o in self.op_times)

    def report(self, top: int = 15) -> str:
        lines = [f"device op time: {self.total_ms():.2f} ms total",
                 f"{'op':<60} {'count':>6} {'ms':>9} {'%':>6}  category"]
        tot = self.total_ms() or 1.0
        for o in self.top(top):
            nm = o.name if len(o.name) <= 60 else o.name[:57] + "..."
            lines.append(f"{nm:<60} {o.count:>6} {o.total_ms:>9.2f} "
                         f"{100*o.total_ms/tot:>5.1f}%  {o.category}")
        lines.append("-- by category --")
        for cat, ms in self.by_category().items():
            lines.append(f"  {cat:<30} {ms:>9.2f} ms {100*ms/tot:>5.1f}%")
        return "\n".join(lines)


class ProfilerSession:
    """Context manager around jax.profiler.start_trace/stop_trace that
    decodes the resulting xplane artifact."""

    def __init__(self, log_dir: Optional[str] = None):
        self.log_dir = log_dir or tempfile.mkdtemp(prefix="dl4j_tpu_prof_")
        self._profile: Optional[OpProfile] = None
        # capture window in time.perf_counter terms — the clock
        # monitor/trace spans use, so correlate_spans can select the
        # spans that overlap this capture
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None

    def __enter__(self):
        import jax
        jax.profiler.start_trace(self.log_dir)
        self.t_start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        return False

    def xplane_paths(self) -> List[str]:
        return sorted(glob.glob(
            os.path.join(self.log_dir, "**", "*.xplane.pb"), recursive=True))

    def profile(self) -> OpProfile:
        if self._profile is None:
            ops: List[OpTime] = []
            for p in self.xplane_paths():
                ops.extend(device_op_times(load_xspace(p)))
            self._profile = OpProfile(sorted(ops, key=lambda o: -o.total_ps))
        return self._profile

    def correlate_spans(self, tracer=None, spans=None) -> dict:
        """Correlate this capture's DEVICE op time with the monitor
        tracer's host-side ``window``/``step`` spans.

        The xplane capture knows what the device did but not which fit
        window asked for it; the tracer knows the windows but times only
        the host. This joins them at the capture boundary: window spans
        overlapping [t_start, t_stop] share the capture's total device
        op time proportionally to their wall duration (an ESTIMATE — the
        two clocks are not event-correlated; with equal-length windows,
        which fused training produces by construction, the proportional
        split is exact up to scheduling jitter). Each correlated span
        gains a ``device_ms_est`` arg (visible in the chrome trace) and
        the summary reports device utilization over the window wall time.
        """
        if spans is None:
            if tracer is None:
                from deeplearning4j_tpu.monitor.trace import TRACER as tracer
            spans = [
                s for s in tracer.spans()
                if s.name in ("window", "step")
                and (self.t_start is None or s.t0 + s.dur >= self.t_start)
                and (self.t_stop is None or s.t0 <= self.t_stop)]
        device_ms = self.profile().total_ms()
        wall_s = sum(s.dur for s in spans)
        windows = []
        for s in spans:
            est = device_ms * (s.dur / wall_s) if wall_s > 0 else 0.0
            s.set(device_ms_est=round(est, 4))
            windows.append({
                "name": s.name, "ts": s.t0, "dur_s": round(s.dur, 9),
                "k": int(s.args.get("k", 1)),
                "iteration": s.args.get("iteration"),
                "device_ms_est": round(est, 4)})
        return {"device_total_ms": round(device_ms, 4),
                "window_wall_s": round(wall_s, 6),
                "device_utilization": round(
                    device_ms / (wall_s * 1e3), 6) if wall_s > 0 else 0.0,
                "windows": windows}
