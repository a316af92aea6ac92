"""Run one cell of the benchmark once.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace
1`` ``breakdown``, and last ``compared``: each number ``correct`` was
decided on beside its limit. Without the chips the cell asks for it
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=ROOT,
                    help="directory of BENCHMARK.json (tests)")
    ap.add_argument("--dry", action="store_true",
                    help="tests only: do not look for the chip; the "
                         "result names the device it ran on")
    args = ap.parse_args(argv)

    from benchmark import harness
    try:
        cell = harness.Cell(args.root, args.workload)
        harness.place_compile_cache(cell.root)
        stamp = harness.device_stamp(cell.chips, require_chip=not args.dry)
        driver = importlib.import_module(
            "benchmark.drivers." + cell.generator.MODE)
        record, compared, breakdown = driver.run(
            cell, args.seed, args.seconds, bool(args.trace), stamp)
        correct, table = harness.judge(compared, cell.limits)
        line = harness.result_line(cell, record, stamp, bool(args.trace),
                                   correct, table, breakdown)
    except harness.BenchFailure as e:
        print(f"benchmark.run: {e}", file=sys.stderr)
        return 3
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
