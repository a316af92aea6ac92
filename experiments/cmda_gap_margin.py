"""What ``cmda_mixed_closed``'s ``widest_gap`` reads, token by token: one
window of the cell and then, on the rows it sampled, each served token's
gap under the float32 reference beside the MARGIN of the reference's
routers at that position (``reference.cohere2_moe``: by how much the 8th
largest sigmoid stands over the 9th where one of the two experts is held
on this chip, the least over the layers; infinite where neither is), for
the program and for each control in its place (float8 operands; the
block made sequential; the shared experts summed): the readings
``CLEAR_MARGIN`` and the cell's limit are set from (PERF.md section 2).
Each is also judged through ``harness.judge`` and the cell's limits at
the reference's ``CLEAR_MARGIN``, as ``benchmark.run`` would.

Run on the chip from the root of a checkout: ``PYTHONPATH=. python
experiments/cmda_gap_margin.py <seed> <seconds> [controls=a,b]``; prints
one JSON line: for each of several margins, the widest and mean gap over
the positions at least that clear, and their share."""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.drivers import serve
from benchmark.reference import cohere2_moe as ref

CELL = "cmda_mixed_closed"
MARGINS = (0.0, 0.0005, 0.001, 0.002, 0.004, 0.008)


def main(seed: int, seconds: float, controls) -> None:
    cell = harness.Cell(os.getcwd(), CELL)
    harness.place_compile_cache(cell.root)
    stamp = harness.device_stamp(cell.chips, require_chip=True)
    record, rows, _ = serve.offer(cell, seed, seconds, False, stamp)
    cfg = cell.config
    seqs, spans = ref.served_rows(
        rows, int(cell.traffic["server"]["max_seq_len"]))
    lgs, least = ref.logits(cfg, seed, seqs, spans, margins=True)
    picks = {"program": [np.asarray(r[1], np.int32) for r in rows]}
    for name in controls:
        mode, variant = ref.control_of(name)
        picks[name] = [np.asarray(jnp.argmax(lg, axis=-1)) for lg in
                       ref.logits(cfg, seed, seqs, spans, mode, variant)]
    gaps = {n: np.concatenate(ref.gaps_under(lgs, p))
            for n, p in picks.items()}
    least = np.concatenate(least)
    out = {"seed": seed, "failed": record["failed"],
           "rows": [(len(p), len(t)) for p, t in rows],
           "tokens": int(least.size), "memory": record["memory"],
           "logit_std": float(np.mean([float(jnp.std(lg)) for lg in lgs]))}
    clear = least >= ref.CLEAR_MARGIN
    for name, v in gaps.items():
        out[name] = {f"{m:g}": [round(float(v[least >= m].max()), 4),
                                round(float(v[least >= m].mean()), 5),
                                round(float((least >= m).mean()), 3)]
                     for m in MARGINS if (least >= m).any()}
        out[name]["correct"] = harness.judge(
            {"widest_gap": float(v[clear].max()),
             "requests_failed": record["failed"]}, cell.limits)[0]
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    named = [a[9:].split(",") for a in sys.argv[3:]
             if a.startswith("controls=")]
    main(int(sys.argv[1]), float(sys.argv[2]),
         named[0] if named else ("float8", "sequential_block",
                                 "shared_summed"))
