"""The LFM2-8B-A1B cell on the CPU at a tiny size, through
``benchmark.run`` on a root of this file's own (a tiny configuration of
the family, a ``closed_mix`` traffic file with documents in chunks, the
metric files the cell lists copied from the package, limits): a ``--dry``
run prints the result line, a run's state counters and
``kv_rows_per_position.tpot`` read what the server counted, and the
controls (lower precision, the convolution's taps reversed, the expert
bias off, the state dropped between chunks, the q/k norms off) fail the
limit at the same prompts and positions."""
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

#: a tiny block: 8 layers, two of them attention, two dense;
#: hidden 64, 4 heads over 2 of 16; 8 experts, top-2; a vocabulary of
#: 256. The convolution's leaves are drawn with std 0.08 (the reference's
#: ``conv_std``), which gives its output at hidden 64 the size 0.02 gives
#: it at the published 2,048
TINY = {"family": "lfm2_moe", "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_experts": 8, "num_experts_per_tok": 2, "num_dense_layers": 2,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv", "full_attention", "conv"],
        "num_hidden_layers": 8, "norm_eps": 1e-5, "rope_theta": 1e6,
        "routed_scaling_factor": 1, "norm_topk_prob": True,
        "use_expert_bias": True, "conv_std": 0.08, "vocab_size": 256,
        "max_position_embeddings": 128, "param_dtype": "bfloat16",
        "kv_dtype": "bfloat16"}

MIX = {"kind": "closed_mix", "clients": 4, "round": 6, "lengths_seed": 1,
       "warm_in_s": 0.2,
       "chat": {"pairs": 4,
                "prompt_len": {"dist": "lognormal", "mean": 5, "sigma": 0.4,
                               "min": 1, "max": 8},
                "output_len": {"dist": "lognormal", "mean": 8, "sigma": 0.5,
                               "min": 4, "max": 16}},
       "documents": [{"prompt_len": 21, "output_len": 12},
                     {"prompt_len": 30, "output_len": 12}],
       "server": {"max_slots": 4, "block_size": 4, "max_seq_len": 64,
                  "buckets": [4, 8]},
       "check": {"sample": 6}}

#: the package's metrics the cell lists, copied from their files
LISTED = ("tpot_mean_ms", "setup_s", "kv_pool_held_share.tpot",
          "decode_step_ms", "decode_table_share.tpot",
          "kv_rows_per_position.tpot", "prefill_chunk_ms")
#: set from readings at this size on the CPU (the program rounds operands
#: to bfloat16 there as on the chip), over the positions where every
#: router of the reference chose by ``ref.CLEAR_MARGIN`` (27-37% of
#: 1,800 tokens a run judged). The PROGRAM on three seeds: 0.0010 to
#: 0.0017. The CONTROLS on the same three: the bias off 0.034 to 0.052
#: (the nearest), the state dropped between chunks 0.09 to 0.32, float8
#: 0.16 to 0.19, the q/k norms off 0.27 to 0.34, the taps reversed 0.83
#: to 1.0. 0.008 leaves the program 4.7 times of room and lies 4.2 times
#: under the nearest control
LIMIT = 0.008


def write_root(root: str) -> str:
    data = os.path.join(root, "bench_data")
    for sub in ("configs", "traffic", "metrics", "limits"):
        os.makedirs(os.path.join(data, sub))

    def put(rel, obj):
        with open(os.path.join(data, rel), "w") as fh:
            json.dump(obj, fh)

    put("configs/lfm2_tiny.json", TINY)
    put("traffic/tiny_mix.json", MIX)
    for name in LISTED + SCHED_METRICS:
        shutil.copy(os.path.join(REPO, "benchmark", "metrics",
                                 name + ".json"),
                    os.path.join(data, "metrics", name + ".json"))
    put("limits/lfm2_tiny_mix.json", {"widest_gap": LIMIT,
                                      "requests_failed": 0})
    cell = ["lfm2_tiny_mix"]
    layer = {"kv_pool_held_share.tpot": "KV memory tier",
             "decode_table_share.tpot": "KV memory tier",
             "kv_rows_per_position.tpot": "KV memory tier",
             "decode_step_ms": "model step",
             "prefill_chunk_ms": "serving scheduler",
             **dict.fromkeys(SCHED_METRICS, "serving scheduler")}
    bench = {
        "command": ["python3", "-m", "benchmark.run"],
        "paths": ["bench_data"], "run_seconds": 1,
        "configs": [{"name": "lfm2_tiny", "source": "test",
                     "file": "bench_data/configs/lfm2_tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "lfm2_tiny_mix", "config": "lfm2_tiny",
                       "traffic": "tiny_mix", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": cell}
            for n, u in (("tpot_mean_ms", "ms"), ("setup_s", "s"))],
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
def lfm2_root(tmp_path_factory):
    return write_root(str(tmp_path_factory.mktemp("lfm2_root")))


def _run_cli(root, *args):
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
             JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--root", root, *args],
        cwd=REPO, env=e, capture_output=True, text=True, timeout=900)


def test_dry_run_of_the_cell_prints_its_end_to_end_metrics(lfm2_root):
    p = _run_cli(lfm2_root, "--workload", "lfm2_tiny_mix", "--seed",
                 str(2**31 + 15), "--seconds", "3", "--trace", "0", "--dry")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"tpot_mean_ms", "setup_s"}
    assert line["compared"]["widest_gap"]["limit"] == LIMIT
    assert line["compared"]["widest_gap"]["value"] <= LIMIT
    assert line["compared"]["requests_failed"]["value"] == 0


def test_a_run_reads_the_state_through_the_counters_and_its_metric(
        lfm2_root):
    """A window's record: every admission started a lane's state from
    zeros, a document's later chunks carried theirs, and
    ``kv_rows_per_position.tpot`` reads the pool's rows and the state's
    (2 rows a lane and conv layer at this size: 2 x 64 numbers against
    K and V of 2 x 16 each a row) over a row a position on all 8
    layers."""
    cell = harness.Cell(lfm2_root, "lfm2_tiny_mix")
    record, rows, _ = serve.offer(cell, 2**31 + 5, 3.0, False, CPU_STAMP)
    assert record["failed"] == 0
    # the longest finished request leads the sample: a document, whose
    # prompt went through in chunks of 8
    assert len(rows[0][0]) == 30 and len(rows[0][1]) == 12
    got = harness.read_metrics(cell, record, trace=True)
    c = record["counters"]
    # a prompt's runs are counted as each is launched, the prompt and its
    # runs once the last is done: a window's edge may fall between (one
    # prompt, its first run and at most 3 more of a document of 30 in 8s)
    assert c["prefills"] > 0 and c["prefill_runs"] > c["prefills"]
    assert abs(c["state_lanes_started"] - c["prefills"]) <= 1
    assert abs(c["state_runs_carried"]
               - (c["prefill_runs"] - c["prefills"])) <= 4
    assert got["kv_rows_per_position.tpot"]["value"] == pytest.approx(
        100.0 * c["kv_rows_held_sum"] / c["kv_positions_sum"])
    # 2 of 8 layers cache a row a position, the 6 others hold 4 rows a
    # lane whatever its length: over a quarter, under a quarter and 3/p
    assert 25 < got["kv_rows_per_position.tpot"]["value"] < 100
    assert c["moe_layer_steps"] == 6 * c["decode_steps"]
    assert c["moe_tokens_routed_sum"] == 2 * 6 * c["slots_active_sum"]
    assert 0 < got["decode_table_share.tpot"]["value"] <= 100
    check_sched_metrics(got, c)


def test_the_parent_of_the_family_fails_at_once_without_its_module(
        lfm2_root, tmp_path):
    """A checkout without the family's adapter (as the program before it
    had) refuses the cell at once, exit 3 and no result line: it does
    not hang."""
    root = str(tmp_path / "bare")
    shutil.copytree(lfm2_root, root)
    path = os.path.join(root, "bench_data", "configs", "lfm2_tiny.json")
    with open(path) as fh:
        cfg = json.load(fh)
    cfg["family"] = "no_such_family"
    with open(path, "w") as fh:
        json.dump(cfg, fh)
    p = _run_cli(root, "--workload", "lfm2_tiny_mix", "--seed", "3",
                 "--seconds", "1", "--trace", "0", "--dry")
    assert p.returncode != 0 and not p.stdout.strip()


@pytest.fixture(scope="module")
def served(lfm2_root):
    cell = harness.Cell(lfm2_root, "lfm2_tiny_mix")
    cell.traffic = dict(cell.traffic, check={"sample": 200})
    _, rows, _ = serve.offer(cell, 77, 3.0, False, CPU_STAMP)
    return cell, rows


def test_the_program_is_correct_on_every_finished_request(served):
    """Every finished request of a window, the documents' chunks among
    them, against the reference over the clear
    positions (:data:`LIMIT` says what it reads)."""
    cell, rows = served
    sound = cell.adapter.check_served(cell.config, 77, rows,
                                      cell.traffic["server"]["max_seq_len"])
    assert sound["tokens"] > 300
    assert harness.judge({"widest_gap": sound["widest_gap"]},
                         cell.limits)[0], sound


@pytest.mark.parametrize("control", ["float8", "conv_taps_reversed",
                                     "bias_off", "state_dropped_8",
                                     "qk_norm_off"])
def test_a_control_fails_the_cells_limit(served, control):
    """The reference put in the program's place, at the same prompts and
    positions, with float8 operands (one precision below the bfloat16 the
    configuration states), with the convolution's taps reversed, with the
    choice by the bare sigmoids, with every chunk after a prompt's first
    (of the largest bucket, 8) starting its convolutions from zeros, or
    with q and k rotated unnormed: not correct."""
    cell, rows = served
    got = cell.adapter.check_served(cell.config, 77, rows,
                                    cell.traffic["server"]["max_seq_len"],
                                    control=control)
    assert not harness.judge({"widest_gap": got["widest_gap"]},
                             cell.limits)[0], got
