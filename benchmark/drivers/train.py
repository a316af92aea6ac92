"""One run of a training cell: one trainer object is built, driven
through its first ``fit`` in set-up (which compiles, and whose outcome
the reference is held against), and handed as it is to the window, which
goes on calling the same ``fit`` until its seconds are spent."""
from __future__ import annotations

import gc
import statistics
import time

from benchmark import harness
from benchmark.harness import say

#: a ``--trace 1`` window traces this many of its first fits: two, so
#: that the span holds a whole boundary between one ``fit`` and the next
TRACE_FITS = 2


def norm_gap(prog: dict, ref: dict, skip=()) -> tuple:
    """Worst leaf of ``|prog - ref|`` over the larger of the reference's
    norm of that leaf and of its median leaf. Returns ``(gap, leaf)``."""
    floor = statistics.median(ref.values())
    worst, at = 0.0, None
    for leaf, r in ref.items():
        if leaf in skip:
            continue
        g = abs(prog[leaf] - r) / max(r, floor)
        if g > worst:
            worst, at = g, leaf
    return worst, at


def still_leaves(ref_moments: dict) -> set:
    """Leaves whose gradient is nought to rounding in the reference:
    under a thousandth of the median leaf's. Adam moves them by
    round-off alone, so their change is not compared."""
    floor = 1e-3 * statistics.median(ref_moments.values())
    return {leaf for leaf, v in ref_moments.items() if v < floor}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers ``correct`` is decided on, from the program's and the
    reference's readings after the same first steps."""
    ref_loss = sum(ref["losses"]) / len(ref["losses"])
    skip = still_leaves(ref["moment_norms"])
    m_gap, m_leaf = norm_gap(prog["moment_norms"], ref["moment_norms"])
    d_gap, d_leaf = norm_gap(prog["change_norms"], ref["change_norms"],
                             skip)
    say(f"loss {prog['loss']:.6f} against {ref_loss:.6f}; moment gap "
        f"{m_gap:.4g} at {m_leaf}; change gap {d_gap:.4g} at {d_leaf}; "
        f"{len(skip)} still leaves left out")
    return {"loss_gap": abs(prog["loss"] - ref_loss) / abs(ref_loss),
            "moment_gap": m_gap, "change_gap": d_gap}


def run(cell, seed: int, seconds: float, trace: bool, stamp: dict,
        wrap_trainer=None):
    """Returns ``(record, compared, breakdown)``."""
    job, cfg, adapter = cell.traffic, cell.config, cell.adapter
    say("imports done, device found")
    ids, targets = cell.generator.generate(job, cfg, seed)
    watch = harness.CompileWatch()
    trainer = adapter.build_trainer(cfg, job, seed, ids, targets)
    say(f"trainer built (the program's graph build "
        f"{trainer.build_graph_s:.1f}s); first fit, which compiles or "
        f"loads")
    if wrap_trainer is not None:
        trainer = wrap_trainer(trainer)
    tracing = harness.Tracing(cell.root, trace)
    try:
        trainer.fit()
        say("first fit done")
        prog = trainer.readings()
        prog["loss"] = trainer.losses[0]
        t0, setup_s, setup_compile = watch.window_opens()
        fits, traced, out_s = 0, 0, 0.0
        while time.monotonic() - t0 - out_s < seconds:
            if trace and traced < TRACE_FITS:
                # a traced fit is not the trainer's own pace (the
                # profiler starts, stops and writes around it): its
                # time and its tokens are both left out of the window
                t = time.monotonic()
                tracing.start()
                trainer.fit()
                traced += 1
                if traced == TRACE_FITS:
                    tracing.stop()
                out_s += time.monotonic() - t
                continue
            trainer.fit()
            fits += 1
        t1 = time.monotonic() - out_s
        watch.window_closes()
        memory = harness.memory_peak()
        tokens_per_fit = trainer.tokens_per_fit
        steps_per_fit = trainer.steps_per_fit
    finally:
        tracing.stop()
        trainer.close()
    del trainer
    gc.collect()
    trace_rec, breakdown = tracing.reduce()
    record = {
        "cell": cell.name, "config": cfg, "traffic": job,
        "counts": cell.counts, "peaks": harness.peaks_for(stamp),
        "setup_s": setup_s, "window": (t0, t1), "window_s": t1 - t0,
        "train": {"fits": fits, "steps": fits * steps_per_fit,
                  "tokens": fits * tokens_per_fit,
                  "traced_tokens": traced * tokens_per_fit,
                  "seq_len": int(job["seq_len"])},
        "compile": setup_compile, "memory": memory,
        "attempted": fits * steps_per_fit, "failed": 0,
        "trace": trace_rec,
    }
    t_ref = time.monotonic()
    B = int(job["batch"])
    batches = [(ids[i:i + B], targets[i:i + B])
               for i in range(0, ids.shape[0], B)]
    ref = adapter.reference_training(cfg, job, seed, batches)
    say(f"reference followed {len(batches)} steps in "
        f"{time.monotonic() - t_ref:.1f}s")
    return record, compare(prog, ref), breakdown
