"""What ``glm_mixed_closed``'s ``widest_gap`` reads, token by token: one
window of the cell and then, on the rows it sampled, each served token's
gap under the float32 reference beside the MARGIN of the reference's
routers at that position (``reference.glm4_moe_lite``: by how much the
last expert chosen stands over the first one left out, the least over
the expert layers), for the program and for each control in its place
(float8 operands; the choice without the correction bias; a scale of 1
for 1.8). The readings ``CLEAR_MARGIN`` and the cell's limit are set
from (PERF.md section 2, PR 32): a position whose margin is a near-tie
may be settled the other way by bfloat16 rounding, and one other expert
moves its logits by more than a fault does.

Run on the chip from the root of a checkout: ``PYTHONPATH=. python
experiments/glm_gap_margin.py <seed> <seconds> [controls=a,b]``. Writes
``chiprun_out/gap_margin/<seed>.npz`` (``margin`` [expert layers,
tokens], ``row`` [tokens], ``gap_<name>`` [tokens]) and prints one JSON
line: for each of several margins, the widest and mean gap over the
positions at least that clear, and their share."""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.drivers import serve
from benchmark.reference import glm4_moe_lite as ref

CELL = "glm_mixed_closed"
MARGINS = (0.0, 0.001, 0.002, 0.003, 0.004, 0.006, 0.008, 0.012)


def main(seed: int, seconds: float, controls) -> None:
    cell = harness.Cell(os.getcwd(), CELL)
    harness.place_compile_cache(cell.root)
    stamp = harness.device_stamp(cell.chips, require_chip=True)
    record, rows, _ = serve.offer(cell, seed, seconds, False, stamp)
    cfg = cell.config
    seqs, spans = ref.served_rows(
        rows, int(cell.traffic["server"]["max_seq_len"]))
    xs, margins = ref.hidden(cfg, seed, seqs)
    g, w = ref.draw(cfg, seed, "norm_f"), ref.draw(cfg, seed, "head")
    eps = float(cfg["rms_norm_eps"])
    lgs = [ref._head(x[jnp.asarray(sp)], g, w, eps, "float32")
           for x, sp in zip(xs, spans)]
    del xs
    margin = np.concatenate([m[:, sp] for m, sp in zip(margins, spans)], 1)
    picks = {"program": [np.asarray(r[1], np.int32) for r in rows]}
    for name in controls:
        wrong, mode = ref.control_of(cfg, name)
        picks[name] = [np.asarray(jnp.argmax(lg, axis=-1)) for lg in
                       ref.logits(wrong, seed, seqs, spans, mode)]
    gaps = {n: np.concatenate(ref.gaps_under(lgs, p))
            for n, p in picks.items()}
    out_dir = os.path.join("chiprun_out", "gap_margin")
    os.makedirs(out_dir, exist_ok=True)
    np.savez(os.path.join(out_dir, f"{seed}.npz"), margin=margin,
             row=np.concatenate([np.full(len(sp), r) for r, sp in
                                 enumerate(spans)]),
             **{"gap_" + n: v for n, v in gaps.items()})
    least = margin.min(axis=0)
    out = {"seed": seed, "failed": record["failed"],
           "rows": [(len(p), len(t)) for p, t in rows],
           "tokens": int(least.size),
           "logit_std": float(np.mean([float(jnp.std(lg)) for lg in lgs]))}
    for name, v in gaps.items():
        out[name] = {f"{m:g}": [round(float(v[least >= m].max()), 4),
                                round(float(v[least >= m].mean()), 5),
                                round(float((least >= m).mean()), 3)]
                     for m in MARGINS if (least >= m).any()}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    named = [a[9:].split(",") for a in sys.argv[3:]
             if a.startswith("controls=")]
    main(int(sys.argv[1]), float(sys.argv[2]),
         named[0] if named else ("float8", "bias_off", "scale_off"))
