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
    decodes the resulting xplane artifact. While it is open every
    ``monitor.trace`` span is in the capture too, as a host event of the
    same name on the capture's clock (monitor/trace.py)."""

    def __init__(self, log_dir: Optional[str] = None):
        self.log_dir = log_dir or tempfile.mkdtemp(prefix="dl4j_tpu_prof_")
        self._profile: Optional[OpProfile] = None

    def __enter__(self):
        import jax
        jax.profiler.start_trace(self.log_dir)
        return self

    def __exit__(self, exc_type, exc, tb):
        import jax
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
