"""The scheduler's metrics that read the program's counters (PR 26's
``sched_host_share.tpot``, ``decode_launch_ms``, ``queue_wait_mean_ms``
and ``decode_ahead_share.tpot`` over PR 33's counter) at a tiny size on
the CPU: the package's own metric files and ``per_layer`` entries, laid
over the tests' benchmark root, are read from the counters of a real
serving run and printed in a traced run's result line; each is absent,
not zero, where its counter is missing from the record. What the
entries say is held by ``test_cells.py``."""
import json
import os
import shutil

import pytest

from benchmark import harness
from benchmark.drivers import serve

from bench_tiny import CPU_STAMP, REPO, write_root

NEW = ("sched_host_share.tpot", "decode_launch_ms", "queue_wait_mean_ms",
       "decode_ahead_share.tpot")
#: the counter whose absence silences each metric
COUNTER = {"sched_host_share.tpot": "sched_host_ms_sum",
           "decode_launch_ms": "decode_launch_ms_sum",
           "queue_wait_mean_ms": "requests_admitted",
           "decode_ahead_share.tpot": "decode_ahead_steps"}


def _entries():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        per_layer = json.load(fh)["per_layer"]
    return [m for m in per_layer if m["name"] in NEW]


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One serving run on a root that has the package's three files and
    entries, pointed at the tiny cell."""
    root = write_root(str(tmp_path_factory.mktemp("sched_root")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    for m in _entries():
        bench["per_layer"].append(dict(m, workloads=["tiny_serve"]))
        shutil.copy(
            os.path.join(REPO, "benchmark", "metrics", m["name"] + ".json"),
            os.path.join(root, "bench_data", "metrics", m["name"] + ".json"))
    with open(path, "w") as fh:
        json.dump(bench, fh)
    cell = harness.Cell(root, "tiny_serve")
    record, compared, _ = serve.run(cell, 2**31 + 26, 1.5, False, CPU_STAMP)
    assert harness.judge(compared, cell.limits)[0], compared
    return cell, record


def test_a_traced_runs_line_prints_them_from_the_programs_counters(run):
    cell, record = run
    c = record["counters"]
    # what a --trace 1 run adds to the record, as far as the line needs it
    traced = dict(record, trace={"busy_s": 1.0, "window_s": 1.5})
    line = json.loads(harness.result_line(
        cell, traced, CPU_STAMP, True, True, {}, None))
    got = line["metrics"]
    assert set(NEW) <= set(got)
    assert {n: got[n]["unit"] for n in NEW} == {
        "sched_host_share.tpot": "%", "decode_launch_ms": "ms",
        "queue_wait_mean_ms": "ms", "decode_ahead_share.tpot": "%"}
    host, prefill, decode = (c["sched_host_ms_sum"], c["prefill_ms_sum"],
                             c["decode_ms_sum"])
    assert got["sched_host_share.tpot"]["value"] == pytest.approx(
        100 * host / (host + prefill + decode))
    assert 0 < got["sched_host_share.tpot"]["value"] < 100
    assert got["decode_launch_ms"]["value"] == pytest.approx(
        c["decode_launch_ms_sum"] / c["decode_steps"])
    # a launch is part of its step
    assert 0 < c["decode_launch_ms_sum"] <= decode
    assert got["queue_wait_mean_ms"]["value"] == pytest.approx(
        c["queue_wait_ms_sum"] / c["requests_admitted"])
    # the record's counters are the difference of two snapshots taken
    # while the worker runs, and the worker counts a request's admission
    # and then, once its first token is there, its prefill, one request
    # at a time (``_admit``): a snapshot finds at most one request
    # between the two, so the window's two counts differ by one at most
    assert abs(c["requests_admitted"] - c["prefills"]) <= 1
    assert c["requests_admitted"] > 1 and c["prefills"] > 1
    # four callers on four slots: most steps find no slot free and run
    # ahead, and a step is counted ahead with the step, never beyond
    assert 0 < c["decode_ahead_steps"] <= c["decode_steps"]
    assert got["decode_ahead_share.tpot"]["value"] == pytest.approx(
        100 * c["decode_ahead_steps"] / c["decode_steps"])
    # no request waits longer than it took to get its first token
    ttft = max(r["token_t"][0] - r["submit_t"]
               for r in record["requests"] if r["token_t"])
    assert 0 <= got["queue_wait_mean_ms"]["value"] <= ttft * 1e3


@pytest.mark.parametrize("name", NEW)
def test_a_missing_counter_leaves_the_metric_out(run, name):
    """The parent commit's program has no such counter: the line leaves
    the metric out and reads no zero."""
    cell, record = run
    counters = {k: v for k, v in record["counters"].items()
                if k != COUNTER[name]}
    got = harness.read_metrics(cell, dict(record, counters=counters),
                               trace=True)
    assert name not in got
    assert set(NEW) - {name} <= set(got)
