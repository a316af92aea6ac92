"""One window of a serving cell and then, on the rows it sampled, the
reference's verdict on the program beside the controls' (float8
operands; for SmallThinker the window off and the window a block short;
any others named as ``controls=a,b,c``, such as GLM-4.7-Flash's
``bias_off`` and ``scale_off``), each through ``harness.judge`` and the
cell's limits: the readings a cell's ``widest_gap`` limit is set between
(PERF.md section 2). With ``trace`` as the last word the window is traced
instead, and the device seconds and runs of each XLA program are printed
with the breakdown (PERF.md section 5). Run on the chip from the root
of a checkout: ``PYTHONPATH=. python experiments/st_control.py <cell>
<seed> <seconds> [trace | controls=<names>]``; prints one JSON line."""
import json
import os
import sys

from benchmark import harness
from benchmark.drivers import serve


def main(cell_name: str, seed: int, seconds: float, trace: bool,
         controls=None) -> None:
    cell = harness.Cell(os.getcwd(), cell_name)
    harness.place_compile_cache(cell.root)
    stamp = harness.device_stamp(cell.chips, require_chip=True)
    record, rows, breakdown = serve.offer(cell, seed, seconds, trace, stamp)
    out = {"cell": cell_name, "seed": seed, "failed": record["failed"],
           "rows": [(len(p), len(t)) for p, t in rows],
           "memory": record["memory"]}
    if trace:
        tr = record["trace"]
        out.update(modules=tr["modules"], busy_s=tr["busy_s"],
                   window_s=tr["window_s"], breakdown=breakdown,
                   counters=record["counters"])
    else:
        # the program, then each control in its place: what
        # benchmark.run would compare, through the cell's own limits
        pad = int(cell.traffic["server"]["max_seq_len"])
        block = int(cell.traffic["server"]["block_size"])
        for control in [None] + list(controls or (
                "float8", "window_off", f"window_less_{block}")):
            got = cell.adapter.check_served(cell.config, seed, rows, pad,
                                            control=control)
            got["correct"] = harness.judge(
                {"widest_gap": got["widest_gap"],
                 "requests_failed": record["failed"]}, cell.limits)[0]
            out[control or "program"] = got
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    named = [a[9:].split(",") for a in sys.argv[4:]
             if a.startswith("controls=")]
    main(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]),
         sys.argv[4:] == ["trace"], named[0] if named else None)
