"""How wide each tier's table was sent, decode step by decode step, in
one run of a serving cell (its warm-in and its window): a tally of the
widths ``PagedGenerativeServer._decode_io`` handed the decode program,
tier by tier and as combinations, which the counters hold only as one sum
over the tiers (``decode_table_entries_sum``; PERF.md section 4). Run on
the chip from the root of a checkout: ``PYTHONPATH=. python
experiments/table_widths_sent.py <cell> <seed> <seconds>``; prints one
JSON line."""
import collections
import json
import os
import sys

from benchmark import harness
from benchmark.drivers import serve
from deeplearning4j_tpu.serving.paged import PagedGenerativeServer


def main(cell_name: str, seed: int, seconds: float) -> None:
    sent = collections.Counter()
    real = PagedGenerativeServer._decode_io

    def tally(self, lead=0):
        io = real(self, lead)
        if io is not None:
            sent[tuple((k, v.shape[1]) for k, v in sorted(io.items())
                       if k.startswith("tables"))] += 1
        return io

    PagedGenerativeServer._decode_io = tally
    cell = harness.Cell(os.getcwd(), cell_name)
    harness.place_compile_cache(cell.root)
    stamp = harness.device_stamp(cell.chips, require_chip=True)
    record, _, _ = serve.offer(cell, seed, seconds, False, stamp)
    steps = sum(sent.values())
    tiers = collections.defaultdict(collections.Counter)
    for combo, n in sent.items():
        for key, width in combo:
            tiers[key][width] += n
    c = record["counters"]
    print(json.dumps({
        "cell": cell_name, "seed": seed, "failed": record["failed"],
        "decode_io_calls": steps,
        "share_by_tier": {k: {str(w): round(n / steps, 4)
                              for w, n in sorted(t.items())}
                          for k, t in sorted(tiers.items())},
        "mean_by_tier": {k: round(sum(w * n for w, n in t.items()) / steps, 2)
                         for k, t in sorted(tiers.items())},
        "combinations": {" ".join(f"{k}={w}" for k, w in combo):
                         round(n / steps, 4)
                         for combo, n in sorted(sent.items())},
        "window_counters": {k: c[k] for k in (
            "decode_steps", "decode_table_entries_sum",
            "decode_table_capacity_sum", "kv_rows_gathered_sum",
            "kv_rows_attended_sum") if k in c}}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]))
