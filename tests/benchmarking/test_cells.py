"""``BENCHMARK.json`` held cell by cell, by NAME and CONTENT, wherever an
entry stands in its list.

A cell's row is a file, ``tests/benchmarking/cells/<cell>.json``: its
configuration, traffic and ``chips``, the end-to-end metrics it reports
and the per-layer metrics it MUST list. A PR that adds a cell APPENDS
its entries to ``BENCHMARK.json`` and adds its row beside the others;
it edits no file that is there. One rule (:func:`check_cell`) is run
over every row, on the file as committed and on a copy to which a fifth
cell has been appended in memory: the proof that there is room."""
import copy
import glob
import importlib
import json
import os
import re

import pytest

from benchmark import harness
from benchmark.readers import value

from bench_tiny import REPO

HERE = os.path.dirname(os.path.abspath(__file__))


def _read(*rel):
    with open(os.path.join(*rel)) as fh:
        return json.load(fh)


ROWS = {os.path.basename(p)[:-len(".json")]: _read(p)
        for p in sorted(glob.glob(os.path.join(HERE, "cells", "*.json")))}


@pytest.fixture()
def bench():
    return _read(REPO, "BENCHMARK.json")


def package_metric_file(bench, name):
    return _read(REPO, bench["paths"][0], "metrics", name + ".json")


def cells_of(metric, bench):
    """The cells a metric is read in: its list, or every cell."""
    return metric.get("workloads", [w["name"] for w in bench["workloads"]])


def reports(bench, cell):
    """The end-to-end metrics ``cell`` reports."""
    return {m["name"] for m in bench["end_to_end"]
            if cell in cells_of(m, bench)}


def check_cell(bench, name, row, metric_file=package_metric_file):
    """The one rule for "the benchmark names the cell and its metrics".
    Nothing here asks where an entry stands or who else is listed."""
    (cell,) = [w for w in bench["workloads"] if w["name"] == name]
    assert {k: cell[k] for k in ("config", "traffic", "chips")} == \
        {k: row[k] for k in ("config", "traffic", "chips")}
    assert 0 < len(cell["why"]) <= 200
    (config,) = [c for c in bench["configs"] if c["name"] == row["config"]]
    assert 0 < len(config["why"]) <= 200
    assert any(config["file"].startswith(p + "/") for p in bench["paths"])
    # the end-to-end metrics it reports: these and no others
    assert reports(bench, name) == set(row["end_to_end"])
    assert "setup_s" in row["end_to_end"] and len(row["end_to_end"]) > 1
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert row["per_layer"], "a cell reports at least one per-layer metric"
    for must in row["per_layer"]:
        assert must in per_layer, f"{must} is no per-layer entry"
        assert name in cells_of(per_layer[must], bench), \
            f"{must} does not list {name}"
    for m in bench["per_layer"]:
        # a per-layer metric lists a cell only if that cell reports the
        # end-to-end metric it moves
        if name in cells_of(m, bench):
            assert m["moves"] in reports(bench, name), \
                f"{m['name']} moves {m['moves']}, which {name} does " \
                f"not report"
        spec = metric_file(bench, m["name"])
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        assert callable(reader.read)


@pytest.mark.parametrize("name", sorted(ROWS))
def test_the_benchmark_names_the_cell_and_its_metrics(bench, name):
    check_cell(bench, name, ROWS[name])


def test_every_cell_of_the_benchmark_has_a_row(bench):
    assert {w["name"] for w in bench["workloads"]} == set(ROWS)
    assert {w["config"] for w in bench["workloads"]} == \
        {c["name"] for c in bench["configs"]}


@pytest.mark.parametrize("name", sorted(ROWS))
def test_the_harness_finds_every_file_the_cell_names(name):
    cell = harness.Cell(REPO, name)
    row = ROWS[name]
    assert cell.chips == row["chips"] and cell.config["family"]
    assert cell.generator.MODE in ("serve", "train")
    assert set(cell.limits)
    for trace, key in ((False, "end_to_end"), (True, "per_layer")):
        names = [m["name"] for m in cell.metric_names(trace)]
        assert set(row[key]) <= set(names)
        assert all(cell.metric_file(n)["reader"] for n in names)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_names_are_single_and_lists_name_cells_that_exist(bench):
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    configs = [c["name"] for c in bench["configs"]]
    for names in (cells, metrics, configs):
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = cells_of(m, bench)
        assert listed and len(listed) == len(set(listed))
        assert set(listed) <= set(cells), m["name"]
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    # metrics of one layer give the same layer, letter for letter
    assert all(len(spelt) == 1 for spelt in layers.values()), layers
    # at most a quarter of the cells, rounded down, and one always, on
    # four chips
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= \
        max(1, len(cells) // 4)


# ----------------------------------------------------------------------
# the entries whose content an issue fixed (PR 26, 28, 32, 34): the whole
# entry but its list of cells, and the whole metric file
def _entry(unit, better, layer, moves, num, den, times=None, scale=None):
    params = {"num": num, "den": den}
    if times:
        params["times"] = times
    if scale:
        params["scale"] = scale
    return ({"unit": unit, "better": better, "source": "program_counter",
             "layer": layer, "moves": moves},
            {"reader": "value", "params": params})


SCHED, EXPERTS = "serving scheduler", "config.moe_num_primary_experts|" \
    "config.n_routed_experts"
ENTRIES = {
    "sched_host_share.tpot": _entry(
        "%", "lower", SCHED, "tpot_mean_ms", ["counters.sched_host_ms_sum"],
        ["counters.sched_host_ms_sum", "counters.prefill_ms_sum",
         "counters.decode_ms_sum"], scale=100),
    "decode_launch_ms": _entry(
        "ms", "lower", SCHED, "tpot_mean_ms",
        ["counters.decode_launch_ms_sum"], ["counters.decode_steps"]),
    "queue_wait_mean_ms": _entry(
        "ms", "lower", SCHED, "ttft_p50_ms", ["counters.queue_wait_ms_sum"],
        ["counters.requests_admitted"]),
    "decode_ahead_share.tpot": _entry(
        "%", "higher", SCHED, "tpot_mean_ms",
        ["counters.decode_ahead_steps"], ["counters.decode_steps"],
        scale=100),
    # a run of the prefill program: a chat prompt's one, or one of a
    # document's 10 to 28 chunks
    "prefill_chunk_ms": _entry(
        "ms", "lower", SCHED, "tpot_mean_ms", ["counters.prefill_ms_sum"],
        ["counters.prefill_runs"]),
    "kv_window_held_share.tpot": _entry(
        "%", "higher", "KV memory tier", "tpot_mean_ms",
        ["counters.window_blocks_held_sum"],
        ["counters.window_blocks_capacity_sum"], scale=100),
    # one metric for both expert families: the number of routed experts
    # by either configuration's key
    "moe_experts_touched_share.tpot": _entry(
        "%", "lower", "expert layer", "tpot_mean_ms",
        ["counters.moe_experts_touched_sum"], ["counters.moe_layer_steps"],
        times=[EXPERTS], scale=100),
}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_an_entry_and_its_file_say_what_its_issue_asked(bench, name):
    entry, spec = ENTRIES[name]
    (got,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert {k: v for k, v in got.items()
            if k not in ("name", "workloads")} == entry
    assert package_metric_file(bench, name) == spec


@pytest.mark.parametrize("gone", ["prefill_run_ms.tpot",
                                  "moe_routed_touched_share.tpot"])
def test_a_doubled_metric_is_gone_with_its_file(bench, gone):
    assert gone not in {m["name"] for m in bench["per_layer"]}
    assert not os.path.exists(os.path.join(
        REPO, bench["paths"][0], "metrics", gone + ".json"))


@pytest.mark.parametrize("config,key", [
    ("smallthinker-21b-a3b", "moe_num_primary_experts"),
    ("glm-4.7-flash", "n_routed_experts")])
def test_either_family_names_its_routed_experts(bench, config, key):
    cfg = _read(REPO, bench["paths"][0], "configs", config + ".json")
    assert value.lookup({"config": cfg}, EXPERTS) == cfg[key] == 64
    other = ({"moe_num_primary_experts", "n_routed_experts"} - {key}).pop()
    assert other not in cfg
    rec = {"config": cfg, "counters": {"moe_experts_touched_sum": 48.0,
                                       "moe_layer_steps": 3}}
    spec = package_metric_file(bench, "moe_experts_touched_share.tpot")
    assert value.read(rec, spec["params"]) == pytest.approx(25.0)


def test_a_path_with_alternatives_reads_the_first_that_resolves():
    rec = {"a": {"x": 1, "none": None}, "b": {"y": 2}}
    assert value.lookup(rec, "a.x|b.y") == 1
    assert value.lookup(rec, "a.q|b.y") == 2
    assert value.lookup(rec, "a.none|a.q|b.y") == 2
    assert value.lookup(rec, "a.q|b.q") is None
    # a path without alternatives reads as it did
    assert value.lookup(rec, "b.y") == 2 and value.lookup(rec, "b.q") is None
    assert value.lookup(rec, "a.x.deeper") is None
    assert value.read(rec, {"num": ["a.x"], "times": ["a.q|b.q"]}) is None


# ----------------------------------------------------------------------
# the proof that there is room: a fifth cell, appended
FIFTH, FIFTH_CONFIG = "fifth_mixed_closed", "fifth-model"
FIFTH_METRIC = "kv_summary_held_share.tpot"
FIFTH_LISTS = ("sched_prefill_share.tpot", "kv_pool_held_share.tpot",
               "decode_step_ms", "serve_step_mfu", "decode_fn_roofline",
               "decode_ahead_share.tpot")
FIFTH_ROW = {"config": FIFTH_CONFIG, "traffic": "mixed_closed_32k",
             "chips": 1, "end_to_end": ["tpot_mean_ms", "setup_s"],
             "per_layer": list(FIFTH_LISTS) + [FIFTH_METRIC,
                                               "setup_compile_s"]}


def appended(bench):
    """``bench`` with a fifth configuration, a fifth cell, the cell's
    name at the END of ``tpot_mean_ms``'s list and of six per-layer
    lists, and one new entry at the END of ``per_layer``: what a
    ``model_config`` PR does, and all it may do."""
    out = copy.deepcopy(bench)
    out["configs"].append({
        "name": FIFTH_CONFIG, "source": "https://example.org/fifth",
        "file": f"{out['paths'][0]}/configs/{FIFTH_CONFIG}.json",
        "reduced": ["num_hidden_layers"], "why": "a fifth family"})
    out["workloads"].append({
        "name": FIFTH, "config": FIFTH_CONFIG,
        "traffic": FIFTH_ROW["traffic"], "chips": 1,
        "why": "a fifth cell, appended"})
    for m in out["end_to_end"] + out["per_layer"]:
        if m["name"] == "tpot_mean_ms" or m["name"] in FIFTH_LISTS:
            m["workloads"].append(FIFTH)
    out["per_layer"].append({
        "name": FIFTH_METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "KV memory tier",
        "moves": "tpot_mean_ms", "workloads": [FIFTH]})
    return out


def appended_metric_file(bench, name):
    if name == FIFTH_METRIC:
        return {"reader": "value",
                "params": {"num": ["counters.summary_rows_held_sum"],
                           "den": ["counters.summary_rows_capacity_sum"],
                           "scale": 100}}
    return package_metric_file(bench, name)


@pytest.mark.parametrize("name", sorted(ROWS) + [FIFTH])
def test_a_fifth_cell_appended_leaves_every_case_passing(bench, name):
    more = appended(bench)
    assert more["workloads"][:len(bench["workloads"])] == bench["workloads"]
    assert len(more["per_layer"]) == len(bench["per_layer"]) + 1
    check_cell(more, name, {**ROWS, FIFTH: FIFTH_ROW}[name],
               appended_metric_file)


@pytest.mark.parametrize("metric", ("tpot_mean_ms",) + FIFTH_LISTS
                         + (FIFTH_METRIC,))
def test_a_cell_taken_out_of_a_list_it_must_be_in_fails_its_case(
        bench, metric):
    more = appended(bench)
    for m in more["end_to_end"] + more["per_layer"]:
        if m["name"] == metric:
            m["workloads"].remove(FIFTH)
            if not m["workloads"]:
                more["per_layer"].remove(m)
    with pytest.raises(AssertionError):
        check_cell(more, FIFTH, FIFTH_ROW, appended_metric_file)
    # the cells that were there do not mind
    for name in ROWS:
        check_cell(more, name, ROWS[name], appended_metric_file)


@pytest.mark.parametrize("name", sorted(n for n, r in ROWS.items()
                                        if "tpot_mean_ms" in r["end_to_end"]))
def test_a_real_cell_taken_out_of_a_list_fails_its_case(bench, name):
    for m in bench["per_layer"]:
        if m["name"] == "decode_ahead_share.tpot":
            m["workloads"].remove(name)
    with pytest.raises(AssertionError, match="does not list"):
        check_cell(bench, name, ROWS[name])


@pytest.mark.parametrize("name", ["st_mixed_closed", "glm_mixed_closed",
                                  "medium_train"])
def test_a_metric_may_not_list_a_cell_that_lacks_what_it_moves(bench, name):
    """``queue_wait_mean_ms`` moves ``ttft_p50_ms``, which only
    ``xl_chat_closed`` reports: its list stays by this rule."""
    for m in bench["per_layer"]:
        if m["name"] == "queue_wait_mean_ms":
            assert m["workloads"].count("xl_chat_closed") == 1
            m["workloads"].append(name)
    with pytest.raises(AssertionError, match="does not report"):
        check_cell(bench, name, ROWS[name])

