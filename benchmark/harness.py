"""What every run shares: finding a cell's files by the names in
``BENCHMARK.json``, the look for the chip, the profiler window, reading
metrics through their readers, and the result line.

Nothing here names a cell, a configuration or a metric: a cell is an
entry of ``workloads``; its configuration is the file that entry's
``config`` names; its traffic is ``<data>/traffic/<traffic>.json``, whose
``kind`` names a module of ``benchmark.generators``; a metric is
``<data>/metrics/<name>.json``, whose ``reader`` names a module of
``benchmark.readers``; the limits ``correct`` is held to are
``<data>/limits/<cell>.json``. ``<data>`` is the first of ``paths``.
"""
from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import time

T_PROCESS = time.monotonic()


class BenchFailure(RuntimeError):
    """The run cannot give a result: exit non-zero, print none."""


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_PROCESS:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _read(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


class Cell:
    """One entry of ``workloads`` with every file it names, read."""

    def __init__(self, root: str, name: str):
        self.root = os.path.abspath(root)
        self.bench = _read(os.path.join(self.root, "BENCHMARK.json"))
        self.data = os.path.join(self.root, self.bench["paths"][0])
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise BenchFailure(f"no workload {name!r}; there are "
                               f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in self.bench["configs"]}
        self.config = _read(os.path.join(
            self.root, conf[self.entry["config"]]["file"]))
        self.traffic = _read(os.path.join(
            self.data, "traffic", self.entry["traffic"] + ".json"))
        self.limits = _read(os.path.join(
            self.data, "limits", name + ".json"))
        self.generator = importlib.import_module(
            "benchmark.generators." + self.traffic["kind"])
        self.adapter = importlib.import_module(
            "benchmark.adapters." + self.config["family"])
        self.counts = importlib.import_module(
            "benchmark.counts." + self.config["family"])

    def metric_names(self, trace: bool):
        """The metrics this cell reports in a run of this kind."""
        group = self.bench["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if self.name in m.get("workloads", [self.name])]

    def metric_file(self, name: str) -> dict:
        return _read(os.path.join(self.data, "metrics", name + ".json"))


def peaks(kind: str) -> dict:
    table = _read(os.path.join(os.path.dirname(__file__), "peaks.json"))
    if kind not in table or not isinstance(table[kind], dict):
        raise BenchFailure(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def peaks_for(stamp: dict):
    """The peaks of the device a run is on; nothing off the chip (tests)."""
    return peaks(stamp["kind"]) if stamp["platform"] == "tpu" else None


def place_compile_cache(root: str) -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else one fixed directory in the checkout (the program applies the
    same rule). Every program is kept, however quickly it compiled."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(root, ".jax_cache"))
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_stamp(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    stamp = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if require_chip and (stamp["platform"] != "tpu" or len(devs) < chips):
        raise BenchFailure(
            f"this cell needs {chips} TPU chip(s); JAX reports "
            f"{stamp['count']} x {stamp['platform']} "
            f"({stamp['kind']}): no fallback")
    return stamp


def memory_peak() -> dict:
    """Peak and limit on the fullest chip, as PJRT counts them."""
    import jax
    peak = limit = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        if st.get("peak_bytes_in_use", 0) >= peak:
            peak = int(st.get("peak_bytes_in_use", 0))
            limit = int(st.get("bytes_limit", 0))
    return {"peak_bytes": peak, "limit_bytes": limit}


class CompileWatch:
    """``COMPILE_STATS`` around set-up and around the window."""

    def __init__(self):
        from deeplearning4j_tpu.compilecache import (COMPILE_STATS,
                                                     install_compile_watcher)
        install_compile_watcher()
        self.stats = COMPILE_STATS
        self.start = self.stats.mark()
        self.at_window = None

    def window_opens(self) -> tuple:
        """The end of set-up. Returns ``(t0, setup_s, the compile
        statistics of set-up)``."""
        self.at_window = self.stats.mark()
        done = self.stats.delta(self.start)
        t0 = time.monotonic()
        setup_s = t0 - T_PROCESS
        say(f"set-up {setup_s:.1f}s (compile "
            f"{done['backend_compile_seconds']:.1f}s, "
            f"{done['cache_hits']} cache hits, "
            f"{done['cache_misses']} misses); window opens")
        return t0, setup_s, done

    def window_closes(self) -> None:
        d = self.stats.delta(self.at_window)
        if d["backend_compiles"]:
            raise BenchFailure(
                f"{d['backend_compiles']} program(s) compiled inside the "
                f"measured window ({d['backend_compile_seconds']:.2f} s): "
                f"a shape was not warmed")


class Tracing:
    """A ``jax.profiler`` trace of part of the window, written inside
    the checkout and removed once reduced."""

    def __init__(self, root: str, enabled: bool):
        self.dir = os.path.join(root, ".bench_trace")
        self.enabled = enabled
        self.t_start = self.t_stop = self._span = None

    def start(self) -> None:
        if not self.enabled or self.t_start is not None:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        # no Python-function events: they cost the host more than the
        # server's own step does, and the TraceMe events of JAX and the
        # runtime already say what the host was doing
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        # the span as the trace's own clock sees it (reduce.trace_window)
        from benchmark.reduce import WINDOW_EVENT
        self._span = jax.profiler.TraceAnnotation(WINDOW_EVENT)
        self._span.__enter__()
        self.t_start = time.monotonic()

    def stop(self) -> None:
        if self.t_start is None or self.t_stop is not None:
            return
        import jax
        self.t_stop = time.monotonic()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self):
        """``(trace record, breakdown)`` or ``(None, None)``."""
        if not self.enabled:
            return None, None
        from benchmark import reduce
        planes = reduce.load(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        busy = reduce.busy_seconds(planes)
        if busy is None:
            raise BenchFailure("the traced window holds no device "
                               "operation")
        modules = reduce.module_seconds(planes)
        rec = {"busy_s": busy[0], "window_s": busy[1],
               "modules": {k: {"seconds": s, "runs": n}
                           for k, (s, n) in modules.items()},
               "t_start": self.t_start, "t_stop": self.t_stop}
        breakdown = {"device_ops": reduce.top_device_ops(planes),
                     "idle_gaps": reduce.idle_gaps(planes)}
        return rec, breakdown


def read_metrics(cell: Cell, record: dict, trace: bool) -> dict:
    """Each of the cell's metrics through its reader. A reader that finds
    nothing to read returns ``None`` and the metric is left out."""
    out = {}
    for m in cell.metric_names(trace):
        spec = cell.metric_file(m["name"])
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(record, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def judge(compared: dict, limits: dict) -> tuple:
    """``compared`` is ``{name: value}``; every name needs a limit, and a
    value that is missing, not a number or over its limit is a failure.
    Returns ``(correct, {name: {"value", "limit"}})``."""
    table, ok = {}, True
    for name, value in compared.items():
        if name not in limits:
            raise BenchFailure(f"no limit for compared number {name!r}")
        limit = float(limits[name])
        good = value is not None and value == value and value <= limit
        ok = ok and good
        table[name] = {"value": value, "limit": limit}
    return ok and bool(table), table


def result_line(cell: Cell, record: dict, stamp: dict, trace: bool,
                correct: bool, table: dict, breakdown) -> str:
    device = dict(stamp,
                  memory_peak_bytes=record["memory"]["peak_bytes"])
    if trace:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
    out = {"correct": bool(correct),
           "attempted": int(record["attempted"]),
           "failed": int(record["failed"]),
           "metrics": read_metrics(cell, record, trace),
           "device": device}
    if trace and breakdown:
        out["breakdown"] = breakdown
    out["compared"] = table
    for name, row in table.items():
        print(f"compared {name}: {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    return json.dumps(out)
