"""From a profiler trace to numbers: device busy seconds, one program's
device time, the operations that took most time, and the idle gaps named
by what the host was doing in them.

Works on a plain structure so that a test can write one by hand::

    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops",
                          "events": [(name, start_s, duration_s), ...]}]},
              {"name": "/host:CPU", "lines": [...]}]

:func:`load` makes that structure from the ``.xplane.pb`` file that
``jax.profiler`` writes, with nothing but JAX.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the host annotation that the harness holds open over the traced span
WINDOW_EVENT = "bench.window"
#: idle gaps shorter than this are summed into one entry
SHORT_GAP_S = 50e-6


def load(trace_dir: str):
    """The newest ``*.xplane.pb`` under ``trace_dir`` as plain planes."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes):
    return [p for p in planes if p["name"].startswith("/device:")
            and any(l["name"] == OPS_LINE and l["events"]
                    for l in p["lines"])]


def _line(plane, name):
    for l in plane["lines"]:
        if l["name"] == name:
            return l["events"]
    return []


def union(intervals):
    """Merged, sorted ``(start, end)`` pairs."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(events, window):
    lo, hi = window
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def trace_window(planes):
    """The traced span on the trace's own clock: the host's
    ``bench.window`` annotation, so that idle time before the first
    device operation and after the last one counts. A trace without the
    annotation gives the first start to the last end of anything that
    ran on a device."""
    for p in planes:
        if p["name"].startswith("/host:"):
            for l in p["lines"]:
                for name, s, d in l["events"]:
                    if name == WINDOW_EVENT:
                        return s, s + d
    starts, ends = [], []
    for p in device_planes(planes):
        for _, s, d in _line(p, OPS_LINE):
            starts.append(s)
            ends.append(s + d)
    if not starts:
        return None
    return min(starts), max(ends)


def busy_seconds(planes):
    """Seconds in which an operation ran, averaged over the device
    planes, and the window's length. ``None`` where nothing ran."""
    window = trace_window(planes)
    devs = device_planes(planes)
    if window is None or not devs:
        return None
    busy = [sum(b - a for a, b in union(
        (a, b) for _, a, b in _clip(_line(p, OPS_LINE), window)))
        for p in devs]
    return sum(busy) / len(busy), window[1] - window[0]


def module_seconds(planes) -> dict:
    """Device seconds and runs of every XLA module (program) on the first
    device plane, by the module's name without its run counter:
    ``{"jit_decode_fn": (seconds, runs), ...}``. Only runs that lie
    wholly inside the window count: a run that an edge cuts through has
    done work that the window did not see."""
    window = trace_window(planes)
    out = {}
    for p in device_planes(planes)[:1]:
        for name, s, d in _line(p, MODULES_LINE):
            if s < window[0] or s + d > window[1]:
                continue
            k = name.split("(")[0]
            sec, n = out.get(k, (0.0, 0))
            out[k] = (sec + d, n + 1)
    return out


_HLO = re.compile(r"^%?([A-Za-z_\-]+(?:[._][A-Za-z_\-]+)*?)(?:[._]\d+)*"
                  r"\s*=\s*\(?(\w+)\[([\d,]*)\]")


def op_label(name: str) -> str:
    """``%copy.12 = f32[48,129]{...} copy(...)`` -> ``copy_f32_48_129``;
    anything else with its trailing counter dropped."""
    m = _HLO.match(name)
    if m:
        op, dt, dims = m.groups()
        label = "_".join([op, dt] + [d for d in dims.split(",") if d])
    else:
        label = re.sub(r"[._]\d+$", "", name.split(" ")[0].lstrip("%"))
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", label)[:64]


def top_device_ops(planes, n: int = 10):
    window = trace_window(planes)
    devs = device_planes(planes)
    if not devs:
        return []
    agg = {}
    for name, a, b in _clip(_line(devs[0], OPS_LINE), window):
        k = op_label(name)
        agg[k] = agg.get(k, 0.0) + (b - a)
    return [[k, v] for k, v in
            sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(planes, n: int = 10):
    """The first device's idle time inside the window, by the host event
    that covers most of each gap: ``[[host event, seconds], ...]``, the
    ``n`` largest, short gaps summed into one entry."""
    window = trace_window(planes)
    devs = device_planes(planes)
    if window is None or not devs:
        return []
    busy = union((a, b) for _, a, b in _clip(_line(devs[0], OPS_LINE),
                                             window))
    gaps, at = [], window[0]
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if window[1] > at:
        gaps.append((at, window[1]))
    host = [(s, s + d, name)
            for p in planes if p["name"].startswith("/host:")
            for l in p["lines"] for name, s, d in l["events"]
            if d > 0 and name != WINDOW_EVENT]
    hs = np.array([h[0] for h in host], np.float64)
    he = np.array([h[1] for h in host], np.float64)
    agg, short, n_short = {}, 0.0, 0
    for a, b in gaps:
        if b - a < SHORT_GAP_S:
            short += b - a
            n_short += 1
            continue
        best = "unattributed"
        if host:
            cover = np.minimum(he, b) - np.maximum(hs, a)
            top = cover.max()
            if top > 0:
                # among the events that cover as much, the innermost
                # (shortest) says most about what the host was doing
                cands = np.flatnonzero(cover >= top * 0.999)
                best = host[int(cands[np.argmin((he - hs)[cands])])][2]
        k = re.sub(r"[^A-Za-z0-9_.:\-]", "_", best)[:64]
        agg[k] = agg.get(k, 0.0) + (b - a)
    out = sorted(agg.items(), key=lambda kv: -kv[1])[:n - 1]
    if n_short:
        out.append((f"shorter_gaps_{n_short}", short))
    return [[k, v] for k, v in out]
