"""Where a cell's set-up goes, phase by phase and program by program,
as the program itself tells it (PERF.md section 5, "Set-up"): nothing is
patched. The ring is on and a ``jax.profiler`` session is open before
the cell's adapter builds its server (or its trainer, and the first
``fit`` runs); both stop where the benchmark's window would open, less
the warm-in of a serving cell, which is traffic and a fixed number of
seconds (``traffic.warm_in_s``).

Printed, as one JSON line: the seconds from the start of the process to
that point and their split (the interpreter and the imports, the
benchmark's own requests or batches, the build, the first fit); the five
counters that partition the program's part (``COMPILE_STATS``: build,
trace, lower, backend compile, plan analysis) beside the length of the
spans they were counted under, from the ring; every phase with its count,
length and self time; ``warmup_report["programs"]`` or the first
dispatch's row. The ring is written as a Chrome trace to
``chiprun_out/setup_timeline/<cell>.<tag>.json`` and the new span names
are counted on the profiler's host lines.

Run on the chip from the root of a checkout::

    PYTHONPATH=. python experiments/setup_timeline.py <cell> <seed> \\
        [tag=<word>] [cold] [off] [dry]

``cold`` gives the run an empty compile cache of its own; ``off`` leaves
the ring and the profiler off, for what the instrumentation costs (the
counters and the table of programs are there all the same); ``dry`` does
not look for the chip (a tiny root on the CPU, to try the script)."""
import json
import os
import shutil
import sys
import time

T_PROCESS = time.monotonic()

PARTITION = ("build_seconds", "trace_seconds", "lower_seconds",
             "backend_compile_seconds", "plan_analyze_seconds")
PHASES = ("model.build", "serving.build", "serving.build.params",
          "serving.build.pool", "serving.warmup", "compile.precompile",
          "compile.plan_analyze", "fit.build", "fit.dispatch")
MARKERS = ("compile.trace", "compile.lower", "compile.backend")


def is_phase(sp) -> bool:
    return sp.name in PHASES and (sp.name != "fit.dispatch"
                                  or bool(sp.args.get("first")))


def phase_table(spans) -> dict:
    """``{name: {"n", "seconds", "self_s"}}``: a phase's self time is
    its length less the phases and compile markers right under it."""
    by_sid = {sp.sid: sp for sp in spans}
    own = {sp.sid: sp.dur for sp in spans if is_phase(sp)}
    table = {}
    for sp in spans:
        if not (is_phase(sp) or sp.name in MARKERS):
            continue
        row = table.setdefault(sp.name, {"n": 0, "seconds": 0.0,
                                         "self_s": 0.0})
        row["n"] += 1
        row["seconds"] += sp.dur
        up = by_sid.get(sp.parent)
        while up is not None and not is_phase(up):
            up = by_sid.get(up.parent)
        if up is not None:
            own[up.sid] -= sp.dur
    for sid, seconds in own.items():
        table[by_sid[sid].name]["self_s"] += seconds
    for name in MARKERS:
        if name in table:
            table[name]["self_s"] = table[name]["seconds"]
    return table


def roots(spans) -> list:
    """The phases with no phase above them: what the partition is held
    against."""
    by_sid = {sp.sid: sp for sp in spans}
    out = []
    for sp in spans:
        if not is_phase(sp):
            continue
        up = by_sid.get(sp.parent)
        while up is not None and not is_phase(up):
            up = by_sid.get(up.parent)
        if up is None:
            out.append(sp)
    return out


def under(spans, tops) -> list:
    return [sp for sp in spans
            if any(sp.tid == r.tid and sp.t0 >= r.t0
                   and sp.t0 + sp.dur <= r.t0 + r.dur for r in tops)]


def profiler_names(log_dir: str) -> dict:
    """How often each phase name stands on a host line of the capture."""
    import glob

    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return {}
    seen = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in PHASES or ev.name in MARKERS:
                    seen[ev.name] = seen.get(ev.name, 0) + 1
    return seen


def main(cell_name: str, seed: int, words) -> None:
    tag = next((w[4:] for w in words if w.startswith("tag=")), "run")
    cold, instrument = "cold" in words, "off" not in words
    root = os.getcwd()
    cold_dir = os.path.join(root, ".jax_cache_cold")
    if cold:
        shutil.rmtree(cold_dir, ignore_errors=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cold_dir
    from benchmark import harness
    cell = harness.Cell(root, cell_name)
    harness.place_compile_cache(cell.root)
    stamp = harness.device_stamp(cell.chips,
                                 require_chip="dry" not in words)
    from deeplearning4j_tpu.monitor.trace import TRACER, enable_tracing
    prof_dir = os.path.join(root, ".bench_trace", "setup_timeline")
    if instrument:
        import jax
        enable_tracing(capacity=1 << 18, reset=True)
        shutil.rmtree(prof_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
    t_imports = time.monotonic()
    serving = cell.generator.MODE == "serve"
    made = cell.generator.generate(cell.traffic, cell.config, seed)
    watch = harness.CompileWatch()
    t_made = time.monotonic()
    first_fit_s = None
    if serving:
        subject = cell.adapter.build_server(cell.config,
                                            cell.traffic["server"], seed)
        t_built = time.monotonic()
        programs = subject.warmup_report["programs"]
        warmup_s = subject.warmup_report["seconds"]
    else:
        subject = cell.adapter.build_trainer(cell.config, cell.traffic,
                                             seed, *made)
        t_built = time.monotonic()
        subject.fit()
        first_fit_s = time.monotonic() - t_built
        programs, warmup_s = None, None
    t_open = time.monotonic()
    counters = watch.stats.delta(watch.start)
    if instrument:
        import jax
        jax.profiler.stop_trace()
    t_stopped = time.monotonic()
    spans = TRACER.spans()
    if serving:
        subject.shutdown(drain=False)
    else:
        subject.close()
    at_s = t_open - T_PROCESS
    counted = sum(counters[k] for k in PARTITION)
    out = {
        "cell": cell_name, "seed": seed, "tag": tag, "cold": cold,
        "instrumented": instrument, "device": stamp,
        "to_window_s": at_s,
        "split_s": {"interpreter_imports_backend": t_imports - T_PROCESS,
                    "benchmark_traffic_or_batches": t_made - t_imports,
                    "build": t_built - t_made,
                    "first_fit": first_fit_s},
        "counters": counters,
        "counted_s": counted,
        "untraced_s": at_s - counted,
        "warmup_s": warmup_s, "programs": programs,
        "profiler_stop_s": t_stopped - t_open,
    }
    if instrument:
        tops = roots(spans)
        inside = phase_table(under(spans, tops))
        whole = phase_table(spans)
        counter_of = {"compile.trace": "trace_seconds",
                      "compile.lower": "lower_seconds",
                      "compile.backend": "backend_compile_seconds"}
        # build and plan analysis as the COUNTERS read them (their own
        # clocks; every phase lies under a root), jax's events as the
        # ring's markers under the roots
        held = {"build_seconds": counters["build_seconds"],
                "plan_analyze_seconds": counters["plan_analyze_seconds"]}
        outside = {}
        for name, key in counter_of.items():
            held[key] = inside.get(name, {"seconds": 0.0})["seconds"]
            outside[key] = whole.get(name, {"seconds": 0.0})["seconds"] \
                - held[key]
        span_s = sum(sp.dur for sp in tops)
        ring_build = sum(row["self_s"] for name, row in whole.items()
                         if name not in counter_of
                         and name != "compile.plan_analyze")
        out["phases"] = whole
        out["partition"] = {
            "roots": [[sp.name, sp.dur] for sp in tops],
            "roots_s": span_s,
            "held_s": held, "held_sum_s": sum(held.values()),
            "held_over_roots": sum(held.values()) / span_s
            if span_s else None,
            "trace_s_over_roots": held["trace_seconds"] / span_s
            if span_s else None,
            "build_counter_over_ring": counters["build_seconds"]
            / ring_build if ring_build else None,
            # compiled under no phase: the benchmark's own jits (its
            # weights), a first fit's fit.stage and fit.sync
            "outside_roots_s": outside}
        first = [sp for sp in spans
                 if sp.name == "fit.dispatch" and sp.args.get("first")]
        if first:
            out["first_dispatch"] = dict(first[0].args, seconds=first[0].dur)
        fits = [sp for sp in spans if sp.name.startswith("fit")
                and sp.name not in ("fit.build", "fit.dispatch")]
        if fits:
            out["fit_spans"] = {}
            for sp in fits:
                row = out["fit_spans"].setdefault(sp.name, [0, 0.0])
                row[0] += 1
                row[1] += sp.dur
        dest = os.path.join(root, "chiprun_out", "setup_timeline")
        os.makedirs(dest, exist_ok=True)
        TRACER.write_chrome_trace(
            os.path.join(dest, f"{cell_name}.{tag}.json"))
        out["ring_spans"] = len(spans)
        out["profiler_host_events"] = profiler_names(prof_dir)
        shutil.rmtree(prof_dir, ignore_errors=True)
    if cold:
        shutil.rmtree(cold_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), sys.argv[3:])
