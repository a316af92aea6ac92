"""The SmallThinker cell on the CPU at a tiny size, through
``benchmark.run`` on a root of this file's own (a tiny configuration of
the family, a ``closed_mix`` traffic file, the cell's new metric files
copied from the package, limits): a ``--dry`` run prints the result line
with the new per-layer metrics, and the controls (lower precision, the
window wrong) fail its limit at the same prompts and positions."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness
from benchmark.drivers import serve

from bench_tiny import SCHED_METRICS, check_sched_metrics

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CPU_STAMP = {"platform": "cpu", "kind": "cpu", "count": 1}

TINY = {"family": "smallthinker", "head_dim": 8, "hidden_size": 32,
        "max_position_embeddings": 128, "moe_ffn_hidden_size": 16,
        "moe_num_active_primary_experts": 2, "moe_num_primary_experts": 8,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_hidden_layers": 4,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
        "rope_layout": [0, 1, 1, 1], "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1],
        "sliding_window_size": 8, "tie_word_embeddings": False,
        "vocab_size": 61, "param_dtype": "bfloat16",
        "kv_dtype": "bfloat16"}

MIX = {"kind": "closed_mix", "clients": 3, "round": 6, "lengths_seed": 1,
       "warm_in_s": 0.2,
       "chat": {"pairs": 4,
                "prompt_len": {"dist": "lognormal", "mean": 5, "sigma": 0.4,
                               "min": 2, "max": 8},
                "output_len": {"dist": "lognormal", "mean": 8, "sigma": 0.5,
                               "min": 4, "max": 16}},
       "documents": [{"prompt_len": 21, "output_len": 12},
                     {"prompt_len": 30, "output_len": 12}],
       "server": {"max_slots": 3, "block_size": 4, "max_seq_len": 64,
                  "buckets": [4, 8]},
       "check": {"sample": 6}}

NEW_METRICS = ("kv_window_held_share.tpot", "moe_experts_touched_share.tpot",
               "prefill_chunk_ms")
#: set from readings at this size on the CPU (the program rounds operands
#: to bfloat16 there as on the chip), three seeds of some 1,900 tokens: the
#: program reads 0 to 0.0048, the float8 control 0.024 to 0.038 (the
#: bfloat16 control reads as the program does); the window off 0.19 to
#: 0.24, a window one block short 0.20 to 0.25 (two seeds)
LIMIT = 0.012


def write_root(root: str) -> str:
    data = os.path.join(root, "bench_data")
    for sub in ("configs", "traffic", "metrics", "limits"):
        os.makedirs(os.path.join(data, sub))

    def put(rel, obj):
        with open(os.path.join(data, rel), "w") as fh:
            json.dump(obj, fh)

    put("configs/st_tiny.json", TINY)
    put("traffic/tiny_mix.json", MIX)
    for name in ("tpot_mean_ms", "ttft_p50_ms", "setup_s",
                 "kv_pool_held_share.tpot", "decode_step_ms") + NEW_METRICS \
            + SCHED_METRICS:
        shutil.copy(os.path.join(REPO, "benchmark", "metrics",
                                 name + ".json"),
                    os.path.join(data, "metrics", name + ".json"))
    put("limits/st_tiny_mix.json", {"widest_gap": LIMIT,
                                    "requests_failed": 0})
    cell = ["st_tiny_mix"]
    layer = {"kv_pool_held_share.tpot": "KV memory tier",
             "kv_window_held_share.tpot": "KV memory tier",
             "moe_experts_touched_share.tpot": "expert layer",
             "prefill_chunk_ms": "serving scheduler",
             "decode_step_ms": "model step",
             **dict.fromkeys(SCHED_METRICS, "serving scheduler")}
    bench = {
        "command": ["python3", "-m", "benchmark.run"],
        "paths": ["bench_data"], "run_seconds": 1,
        "configs": [{"name": "st_tiny", "source": "test",
                     "file": "bench_data/configs/st_tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "st_tiny_mix", "config": "st_tiny",
                       "traffic": "tiny_mix", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": cell}
            for n, u in (("ttft_p50_ms", "ms"), ("tpot_mean_ms", "ms"),
                         ("setup_s", "s"))],
        "per_layer": [
            {"name": n, "unit": "ms" if n.endswith("_ms") else "%",
             "better": "lower", "source": "program_counter",
             "layer": layer[n], "moves": "tpot_mean_ms", "workloads": cell}
            for n in layer],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


@pytest.fixture(scope="module")
def st_root(tmp_path_factory):
    return write_root(str(tmp_path_factory.mktemp("st_root")))


def _run_cli(root, *args):
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
             JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--root", root, *args],
        cwd=REPO, env=e, capture_output=True, text=True, timeout=900)


def test_dry_run_of_the_cell_prints_its_end_to_end_metrics(st_root):
    p = _run_cli(st_root, "--workload", "st_tiny_mix", "--seed",
                 str(2**31 + 11), "--seconds", "3", "--trace", "0", "--dry")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_p50_ms", "tpot_mean_ms",
                                    "setup_s"}
    assert line["compared"]["widest_gap"]["limit"] == LIMIT
    assert line["compared"]["widest_gap"]["value"] <= LIMIT
    assert line["compared"]["requests_failed"]["value"] == 0


def test_a_run_reads_the_new_metrics_and_its_sample_crossed_the_window(
        st_root):
    cell = harness.Cell(st_root, "st_tiny_mix")
    record, rows, _ = serve.offer(cell, 2**31 + 5, 3.0, False, CPU_STAMP)
    assert record["failed"] == 0
    # the longest finished request leads the sample: a document, whose
    # prompt went through in chunks and past the window
    assert len(rows[0][0]) == 30 and len(rows[0][1]) == 12
    got = harness.read_metrics(cell, record, trace=True)
    assert set(NEW_METRICS) <= set(got)
    c = record["counters"]
    assert got["kv_window_held_share.tpot"]["value"] == pytest.approx(
        100.0 * c["window_blocks_held_sum"]
        / c["window_blocks_capacity_sum"])
    assert 0 < got["kv_window_held_share.tpot"]["value"] <= 100
    # at most two experts a lane of eight, at least two a layer and step
    share = got["moe_experts_touched_share.tpot"]["value"]
    assert 100 * 2 / 8 <= share <= 100 * min(8, 2 * 3) / 8
    assert c["prefill_runs"] > c["prefills"] > 0
    assert got["prefill_chunk_ms"]["value"] == pytest.approx(
        c["prefill_ms_sum"] / c["prefill_runs"])
    check_sched_metrics(got, c)


@pytest.fixture(scope="module")
def served(st_root):
    cell = harness.Cell(st_root, "st_tiny_mix")
    cell.traffic = dict(cell.traffic, check={"sample": 200})
    _, rows, _ = serve.offer(cell, 77, 3.0, False, CPU_STAMP)
    return cell, rows


def test_the_program_is_correct_on_every_finished_request(served):
    cell, rows = served
    sound = cell.adapter.check_served(cell.config, 77, rows,
                                      cell.traffic["server"]["max_seq_len"])
    assert sound["tokens"] > 500
    assert harness.judge({"widest_gap": sound["widest_gap"]},
                         cell.limits)[0], sound


@pytest.mark.parametrize("control", ["float8", "window_off",
                                     "window_less_4"])
def test_a_control_fails_the_cells_limit(served, control):
    """The reference put in the program's place, at the same prompts and
    positions, with float8 operands (one precision below the bfloat16
    the configuration states), with the window layers' mask off, or with
    a window one block short: not correct."""
    cell, rows = served
    got = cell.adapter.check_served(cell.config, 77, rows,
                                    cell.traffic["server"]["max_seq_len"],
                                    control=control)
    assert not harness.judge({"widest_gap": got["widest_gap"]},
                             cell.limits)[0], got
