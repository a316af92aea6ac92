"""The ladder's counters in the tiny EvaByte cell, read through
``benchmark.run``'s own harness on the CPU (the root, configuration and
traffic of ``tests/benchmarking/test_run_eva_cpu.py``, which this file
leaves as it is): ``decode_table_share.tpot`` and
``decode_rows_used_share.tpot`` are read from BOTH tiers; at a window of
96 (a ring of 24 entries, long enough to split) each tier is sent a rung
of its own and the cell stays correct."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmarking"))

from benchmark import harness  # noqa: E402
from benchmark.drivers import serve  # noqa: E402

import test_run_eva_cpu as eva_cell  # noqa: E402

CPU_STAMP, ROWS_USED = eva_cell.CPU_STAMP, eva_cell.ROWS_USED

#: the same cell at a ring long enough to split: a window of 96 is 24
#: entries (16, 24), 512 positions 32 summary entries (16, 24, 32); the
#: documents cross one and three windows
SPLIT = dict(eva_cell.TINY, window_size=96, max_position_embeddings=512)
SPLIT_MIX = dict(eva_cell.MIX,
                 documents=[{"prompt_len": 131, "output_len": 36},
                            {"prompt_len": 300, "output_len": 48}],
                 server=dict(eva_cell.MIX["server"], max_seq_len=512,
                             buckets=[16, 32]))


def write_root(root: str, tiny: dict, mix: dict) -> str:
    """The tiny cell's root with another configuration and traffic."""
    eva_cell.write_root(root)
    for rel, obj in (("configs/eva_tiny.json", tiny),
                     ("traffic/tiny_mix.json", mix)):
        with open(os.path.join(root, "bench_data", rel), "w") as fh:
            json.dump(obj, fh)
    return root


def both_tiers(c: dict, ring: int, summaries: int):
    """Every decode step's capacity is the two tiers' entries (a step in
    the air at an edge of the window is counted on one side by its launch
    and on the other by its sync)."""
    assert c["decode_table_capacity_sum"] % (ring + summaries) == 0
    assert abs(c["decode_table_capacity_sum"] // (ring + summaries)
               - c["decode_steps"]) <= 2


def test_the_tiny_cells_shares_are_read_from_both_tiers(tmp_path):
    """The ring's 8 entries are too short to split (sent whole); the
    summaries' 16 are on rungs 8 and 16."""
    cell = harness.Cell(eva_cell.write_root(str(tmp_path)), "eva_tiny_mix")
    record, _, _ = serve.offer(cell, 2**31 + 5, 3.0, False, CPU_STAMP)
    assert record["failed"] == 0
    c = record["counters"]
    assert c["compiles"] == 0
    both_tiers(c, 8, 16)
    got = harness.read_metrics(cell, record, trace=True)
    assert got["decode_table_share.tpot"]["value"] == pytest.approx(
        100.0 * c["decode_table_entries_sum"]
        / c["decode_table_capacity_sum"])
    assert 100.0 * (8 + 8) / (8 + 16) <= \
        got["decode_table_share.tpot"]["value"] <= 100
    assert got[ROWS_USED]["value"] == pytest.approx(
        100.0 * c["kv_rows_attended_sum"] / c["kv_rows_gathered_sum"])


def test_a_ring_long_enough_to_split_is_cut_and_the_cell_stays_correct(
        tmp_path, monkeypatch):
    """Window 96: the exact tier takes a rung of its own beside the
    summary tier's, every finished request is under the cell's limit
    against the reference at that window, and both shares move."""
    from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
    sent, real = set(), PagedGenerativeServer._decode_io

    def spy(self, lead=0):
        io = real(self, lead)
        if io is not None:
            sent.add((io["tables.exact"].shape[1],
                      io["tables.summary"].shape[1]))
        return io

    monkeypatch.setattr(PagedGenerativeServer, "_decode_io", spy)
    cell = harness.Cell(write_root(str(tmp_path), SPLIT, SPLIT_MIX),
                        "eva_tiny_mix")
    cell.traffic = dict(cell.traffic, check={"sample": 200})
    record, rows, _ = serve.offer(cell, 2**31 + 9, 3.0, False, CPU_STAMP)
    assert record["failed"] == 0
    c = record["counters"]
    assert c["window_turns"] > 0 and c["compiles"] == 0
    both_tiers(c, 24, 32)
    got = harness.read_metrics(cell, record, trace=True)
    # each tier on rungs of its own, and not always on the same one
    assert {we for we, _ in sent} == {16, 24}
    assert {ws for _, ws in sent} <= {16, 24, 32} and len(sent) >= 3
    assert 100.0 * (16 + 16) / 56 <= \
        got["decode_table_share.tpot"]["value"] < 100.0
    assert got["decode_table_share.tpot"]["value"] == pytest.approx(
        100.0 * c["decode_table_entries_sum"]
        / c["decode_table_capacity_sum"])
    assert got[ROWS_USED]["value"] == pytest.approx(
        100.0 * c["kv_rows_attended_sum"] / c["kv_rows_gathered_sum"])
    sound = cell.adapter.check_served(cell.config, 2**31 + 9, rows,
                                      cell.traffic["server"]["max_seq_len"])
    assert sound["tokens"] > 300
    assert harness.judge({"widest_gap": sound["widest_gap"]},
                         cell.limits)[0], sound
