"""Set-up's layers (PR 37): the reader that takes accounted parts from a
whole, and the six ``compile path`` metrics that read the program's
set-up counters out of ``record["compile"]``. The package's own metric
files and ``per_layer`` entries are laid over the tests' benchmark root
and read from a real serving run and a real training run at a tiny size
on the CPU: each reads a number, and what no counter covers plus what the
counters cover is ``setup_s``. What the entries say is held here too:
one layer, one end-to-end metric, every cell listed."""
import json
import os
import shutil

import pytest

from benchmark import harness
from benchmark.drivers import serve, train
from benchmark.readers import rest, value

from bench_tiny import CPU_STAMP, REPO, write_root

NEW = ("setup_trace_lower_s", "setup_programs", "setup_build_s",
       "setup_plan_analyze_s", "setup_cache_load_s", "setup_untraced_s")
#: the counters of the program that partition what it spends in set-up
PARTITION = ("build_seconds", "trace_seconds", "lower_seconds",
             "backend_compile_seconds", "plan_analyze_seconds")
CELLS = ("tiny_serve", "tiny_fit")


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _entries():
    return [m for m in _bench()["per_layer"] if m["name"] in NEW]


# ----------------------------------------------------------------------
# the reader
RECORD = {"setup_s": 30.0, "traffic": {"warm_in_s": 6.0},
          "compile": {"build_seconds": 2.0, "trace_seconds": 1.5,
                      "none": None}}


@pytest.mark.parametrize("params, want", [
    ({"of": "setup_s"}, 30.0),
    ({"of": "setup_s", "less": ["compile.build_seconds"]}, 28.0),
    ({"of": "setup_s", "less": ["compile.build_seconds",
                                "compile.trace_seconds"],
      "less_if_there": ["traffic.warm_in_s"]}, 20.5),
    # a warm-in the traffic does not have counts nothing
    ({"of": "setup_s", "less": ["compile.build_seconds"],
      "less_if_there": ["traffic.no_such"]}, 28.0),
    # alternatives resolve as they do for ``value``
    ({"of": "no.such|setup_s", "less": ["compile.trace_seconds"]}, 28.5),
    # the whole is missing, or a part the program should have counted
    ({"of": "no_such", "less": ["compile.build_seconds"]}, None),
    ({"of": "setup_s", "less": ["compile.plan_analyze_seconds"]}, None),
    ({"of": "setup_s", "less": ["compile.none"]}, None),
])
def test_rest_is_the_whole_less_its_parts_or_nothing(params, want):
    got = rest.read(RECORD, params)
    assert got == want if want is None else got == pytest.approx(want)


# ----------------------------------------------------------------------
# the entries
@pytest.mark.parametrize("name", NEW)
def test_entry_is_of_the_compile_path_and_lists_every_cell(name):
    bench = _bench()
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert (m["layer"], m["moves"], m["source"], m["better"]) == (
        "compile path", "setup_s", "program_counter", "lower")
    assert m["unit"] == ("programs" if name == "setup_programs" else "s")
    assert m["workloads"] == [w["name"] for w in bench["workloads"]]
    # appended: behind every entry the benchmark had
    assert [e["name"] for e in bench["per_layer"][-len(NEW):]] == list(NEW)
    spec = harness._read(os.path.join(REPO, "benchmark", "metrics",
                                      name + ".json"))
    assert spec["reader"] == ("rest" if name == "setup_untraced_s"
                              else "value")


# ----------------------------------------------------------------------
# the metrics, from real runs
@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One serving run and one training run on a root that has the
    package's six files and entries, pointed at the tiny cells."""
    root = write_root(str(tmp_path_factory.mktemp("setup_root")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    for m in _entries():
        bench["per_layer"].append(dict(m, workloads=list(CELLS)))
        shutil.copy(
            os.path.join(REPO, "benchmark", "metrics", m["name"] + ".json"),
            os.path.join(root, "bench_data", "metrics", m["name"] + ".json"))
    with open(path, "w") as fh:
        json.dump(bench, fh)
    out = {}
    for name, driver in (("tiny_serve", serve), ("tiny_fit", train)):
        cell = harness.Cell(root, name)
        record, compared, _ = driver.run(cell, 2**31 + 37, 1.0, False,
                                         CPU_STAMP)
        assert harness.judge(compared, cell.limits)[0], compared
        # what a --trace 1 run adds to the record, as far as the line
        # needs it
        traced = dict(record, trace={"busy_s": 1.0, "window_s": 1.0})
        line = json.loads(harness.result_line(
            cell, traced, CPU_STAMP, True, True, {}, None))
        out[name] = (record, line["metrics"])
    return out


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("name", NEW)
def test_a_traced_runs_line_prints_the_metric(runs, cell, name):
    record, got = runs[cell]
    assert name in got
    assert got[name]["unit"] == ("programs" if name == "setup_programs"
                                 else "s")
    assert got[name]["value"] >= 0
    c = record["compile"]
    want = {"setup_trace_lower_s": c["trace_seconds"] + c["lower_seconds"],
            "setup_programs": c["backend_compiles"],
            "setup_build_s": c["build_seconds"],
            "setup_plan_analyze_s": c["plan_analyze_seconds"],
            "setup_cache_load_s": c["cache_load_seconds"]}
    if name in want:
        assert got[name]["value"] == pytest.approx(want[name])


@pytest.mark.parametrize("cell", CELLS)
def test_the_rest_and_the_counters_and_the_warm_in_are_setup_s(runs, cell):
    record, got = runs[cell]
    c = record["compile"]
    parts = sum(c[k] for k in PARTITION)
    warm_in = float(record["traffic"].get("warm_in_s", 0.0))
    assert got["setup_untraced_s"]["value"] + parts + warm_in == \
        pytest.approx(record["setup_s"], rel=0.01)
    # the program did build, trace, lower and compile in set-up, and the
    # seconds it counted are seconds set-up had
    assert c["build_seconds"] > 0 and c["trace_seconds"] > 0
    assert c["lower_seconds"] > 0 and c["backend_compile_seconds"] > 0
    assert 0 < parts < record["setup_s"]
    assert c["cache_load_seconds"] <= c["backend_compile_seconds"]


def test_serving_warms_programs_and_reads_each_ones_plan(runs):
    c = runs["tiny_serve"][0]["compile"]
    # decode and three prefill buckets, each built ahead of time, each
    # followed by one reading of its memory plan
    assert c["precompiles"] == 4 <= c["backend_compiles"]
    assert c["plan_analyze_seconds"] > 0


@pytest.mark.parametrize("name", NEW)
def test_a_record_without_the_counter_leaves_the_metric_out(runs, name):
    """The parent commit's record: ``delta`` gave it the keys it had."""
    record, _ = runs["tiny_serve"]
    old = {k: v for k, v in record["compile"].items()
           if k in ("backend_compiles", "cache_hits", "cache_misses",
                    "backend_compile_seconds", "trace_seconds",
                    "lower_seconds", "saved_seconds")}
    spec = harness._read(os.path.join(REPO, "benchmark", "metrics",
                                      name + ".json"))
    reader = rest if spec["reader"] == "rest" else value
    got = reader.read(dict(record, compile=old), spec["params"])
    if name in ("setup_trace_lower_s", "setup_programs"):
        assert got is not None       # counters the parent has
    else:
        assert got is None
