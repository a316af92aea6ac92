"""What a training cell's fit boundary holds (PERF.md section 5,
``medium_train`` (c)): the device's idle time between one ``fit``'s
epoch program and the next one's, SPLIT over the program's own spans
(the benchmark's ``idle_gaps`` gives a gap whole to the event that
covers most of it), each span's length on the host, the device time of
every program that ran inside the boundary, and the host events of the
runtime under ``fit.stage`` (a stage's mean; nested events each count
their own length).

The trainer is the benchmark's (``adapter.build_trainer``); after its
first ``fit`` and one more, a ``jax.profiler`` session is open over
``fits`` calls of ``fit``, with the ring on for the spans' args.
Printed as one JSON line. Run on the chip from the root of a checkout::

    PYTHONPATH=. python experiments/fit_boundary.py <cell> <seed> [fits]

A fourth word ``dry`` does not look for the chip (a tiny root on the
CPU, to try the script: no device line there, so only the host's part
is printed)."""
import json
import os
import shutil
import sys

SPANS = ("fit", "fit.stage", "fit.dispatch", "fit.sync", "fit.commit")
#: a module that runs this long is an epoch's program, not the boundary's
EPOCH_S = 0.5


def split_idle(gaps, host):
    """Idle seconds by the innermost (shortest) of ``host``'s spans that
    covers each piece of each gap; ``outside`` where none does."""
    out = {}
    for a, b in gaps:
        cuts = sorted({a, b} | {t for s, e, _ in host for t in (s, e)
                                if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            over = [(e - s, name) for s, e, name in host if s <= mid < e]
            name = min(over)[1] if over else "outside"
            out[name] = out.get(name, 0.0) + hi - lo
    return out


def main(cell_name: str, seed: int, fits: int, dry: bool) -> None:
    root = os.getcwd()
    from benchmark import harness, reduce
    cell = harness.Cell(root, cell_name)
    harness.place_compile_cache(cell.root)
    stamp = harness.device_stamp(cell.chips, require_chip=not dry)
    import jax

    from deeplearning4j_tpu.monitor.trace import TRACER, enable_tracing
    made = cell.generator.generate(cell.traffic, cell.config, seed)
    trainer = cell.adapter.build_trainer(cell.config, cell.traffic, seed,
                                         *made)
    trainer.fit()
    trainer.fit()
    prof_dir = os.path.join(root, ".bench_trace", "fit_boundary")
    shutil.rmtree(prof_dir, ignore_errors=True)
    enable_tracing(reset=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(prof_dir, profiler_options=opts)
    for _ in range(fits):
        trainer.fit()
    jax.profiler.stop_trace()
    stage_args = [sp.args for sp in TRACER.spans()
                  if sp.name == "fit.stage"]
    trainer.close()
    planes = reduce.load(prof_dir)
    shutil.rmtree(prof_dir, ignore_errors=True)
    host = [(s, s + d, name) for p in planes
            if p["name"].startswith("/host:") for l in p["lines"]
            for name, s, d in l["events"] if name in SPANS and d > 0]
    # what the host ran under fit.stage, beside the program's own spans
    stages = [(s, e) for s, e, name in host if name == "fit.stage"]
    under_stage = {}
    # (a set: the same event can stand on two of the host's planes)
    for name, s, d in {ev for p in planes if p["name"].startswith("/host:")
                       for l in p["lines"] for ev in l["events"]}:
        if name not in SPANS and any(a <= s and s + d <= b
                                     for a, b in stages):
            row = under_stage.setdefault(name[:60], [0, 0.0])
            row[0] += 1
            row[1] += d
    under_stage = {k: [c, sec * 1e3 / len(stages)] for k, (c, sec) in sorted(
        under_stage.items(), key=lambda kv: -kv[1][1])[:16]}
    if dry:
        print(json.dumps({"host_spans": len(host), "stage_args": stage_args,
                          "host_events_under_stage_ms": under_stage}),
              flush=True)
        return
    dev = reduce.device_planes(planes)[0]
    modules = reduce._line(dev, reduce.MODULES_LINE)
    epochs = sorted((s, s + d) for _, s, d in modules if d > EPOCH_S)
    # the boundaries: from one epoch program's end to the next one's start
    window = (epochs[0][1], epochs[-1][0])
    busy = reduce.union((a, b) for _, a, b in reduce._clip(
        reduce._line(dev, reduce.OPS_LINE), window))
    inside = [(a, b) for a, b in busy if b - a < EPOCH_S]
    gaps, at = [], window[0]
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    n = len(epochs) - 1
    programs = {}
    for name, s, d in modules:
        if window[0] <= s < window[1] and d <= EPOCH_S:
            row = programs.setdefault(name.split("(")[0], [0, 0.0])
            row[0] += 1
            row[1] += d
    host_ms = {}
    for s, e, name in host:
        if name == "fit" or window[0] <= s < window[1]:
            row = host_ms.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += (e - s) * 1e3
    print(json.dumps({
        "cell": cell_name, "seed": seed, "device": stamp,
        "boundaries": n,
        "boundary_ms": sum(b[0] - a[1] for a, b in
                           zip(epochs, epochs[1:])) * 1e3 / n,
        "boundary_busy_ms": sum(b - a for a, b in inside) * 1e3 / n,
        "boundary_idle_ms": sum(b - a for a, b in gaps) * 1e3 / n,
        "epoch_ms": sum(b - a for a, b in epochs[1:-1]) * 1e3
        / max(n - 1, 1),
        "idle_ms_by_span": {k: v * 1e3 / n for k, v in sorted(
            split_idle(gaps, host).items(), key=lambda kv: -kv[1])},
        "host_span_ms": {k: [c, ms / c] for k, (c, ms) in host_ms.items()},
        "boundary_programs": {k: [c, s * 1e3 / c] for k, (c, s) in sorted(
            programs.items(), key=lambda kv: -kv[1][1])},
        "stage_args": stage_args,
        "host_events_under_stage_ms": under_stage,
    }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]),
         int(sys.argv[3]) if len(sys.argv) > 3 else 3, "dry" in sys.argv)
