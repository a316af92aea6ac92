"""The GLM-4.7-Flash cell on the CPU at a tiny size, through
``benchmark.run`` on a root of this file's own (a tiny configuration of
the family, a ``closed_mix`` traffic file, the metric files the two
expert cells share copied from the package, limits): a ``--dry`` run
prints the result line, the experts-touched share reads the program's
counters over this family's key for the number of routed experts, and
the controls (lower precision, the router's two new rules wrong) fail a
tight limit at the same prompts and positions."""
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

TINY = {"family": "glm4_moe_lite", "attention_bias": False,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
        "max_position_embeddings": 128, "moe_intermediate_size": 48,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 4, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
        "first_k_dense_replace": 1, "num_hidden_layers": 3,
        "num_key_value_heads": 4, "num_nextn_predict_layers": 0,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-5,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 24,
        "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
        "v_head_dim": 16, "vocab_size": 61, "param_dtype": "bfloat16",
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

#: one metric and one file for both expert families since PR 34 (they
#: were ``moe_routed_touched_share.tpot`` and ``prefill_run_ms.tpot``)
NEW_METRIC = "moe_experts_touched_share.tpot"
RUN_METRIC = "prefill_chunk_ms"
#: set from readings at this size on the CPU (the program rounds operands
#: to bfloat16 there as on the chip), over the positions where the
#: reference's two routers chose by a clear margin (``ref.CLEAR_MARGIN``:
#: 64-89% of 1,600-3,800 tokens). The PROGRAM over seven seeds: 0.0001 to
#: 0.0011 (0.0008 to 0.0181 with the near-ties in, where bfloat16 rounding
#: chose the other expert on three of the seeds). The CONTROLS on the same
#: seven seeds: float8 0.0234 to 0.0468, the selection without the bias
#: 0.100 to 0.137, a scale of 1 for 1.8 0.0317 to 0.0640: four times of
#: room on both sides
LIMIT = 0.005


def write_root(root: str) -> str:
    data = os.path.join(root, "bench_data")
    for sub in ("configs", "traffic", "metrics", "limits"):
        os.makedirs(os.path.join(data, sub))

    def put(rel, obj):
        with open(os.path.join(data, rel), "w") as fh:
            json.dump(obj, fh)

    put("configs/glm_tiny.json", TINY)
    put("traffic/tiny_mix.json", MIX)
    for name in ("tpot_mean_ms", "setup_s", "kv_pool_held_share.tpot",
                 "decode_step_ms", "decode_table_share.tpot", NEW_METRIC,
                 RUN_METRIC) + SCHED_METRICS:
        shutil.copy(os.path.join(REPO, "benchmark", "metrics",
                                 name + ".json"),
                    os.path.join(data, "metrics", name + ".json"))
    put("limits/glm_tiny_mix.json", {"widest_gap": LIMIT,
                                     "requests_failed": 0})
    cell = ["glm_tiny_mix"]
    layer = {"kv_pool_held_share.tpot": "KV memory tier",
             "decode_table_share.tpot": "KV memory tier",
             NEW_METRIC: "expert layer", "decode_step_ms": "model step",
             RUN_METRIC: "serving scheduler",
             **dict.fromkeys(SCHED_METRICS, "serving scheduler")}
    bench = {
        "command": ["python3", "-m", "benchmark.run"],
        "paths": ["bench_data"], "run_seconds": 1,
        "configs": [{"name": "glm_tiny", "source": "test",
                     "file": "bench_data/configs/glm_tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "glm_tiny_mix", "config": "glm_tiny",
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
def glm_root(tmp_path_factory):
    return write_root(str(tmp_path_factory.mktemp("glm_root")))


def _run_cli(root, *args):
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
             JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--root", root, *args],
        cwd=REPO, env=e, capture_output=True, text=True, timeout=900)


def test_dry_run_of_the_cell_prints_its_end_to_end_metrics(glm_root):
    p = _run_cli(glm_root, "--workload", "glm_tiny_mix", "--seed",
                 str(2**31 + 15), "--seconds", "3", "--trace", "0", "--dry")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"tpot_mean_ms", "setup_s"}
    assert line["compared"]["widest_gap"]["limit"] == LIMIT
    assert line["compared"]["widest_gap"]["value"] <= LIMIT
    assert line["compared"]["requests_failed"]["value"] == 0


def test_a_run_reads_the_new_metric_and_its_sample_leads_with_a_document(
        glm_root):
    cell = harness.Cell(glm_root, "glm_tiny_mix")
    record, rows, _ = serve.offer(cell, 2**31 + 5, 3.0, False, CPU_STAMP)
    assert record["failed"] == 0
    # the longest finished request leads the sample: a document, whose
    # prompt went through in chunks and was decoded in the absorbed form
    assert len(rows[0][0]) == 30 and len(rows[0][1]) == 12
    got = harness.read_metrics(cell, record, trace=True)
    c = record["counters"]
    assert got[NEW_METRIC]["value"] == pytest.approx(
        100.0 * c["moe_experts_touched_sum"]
        / (c["moe_layer_steps"] * TINY["n_routed_experts"]))
    # at most two experts a lane of eight, at least two a layer and step
    assert 100 * 2 / 8 <= got[NEW_METRIC]["value"] <= 100 * 6 / 8
    # two expert layers of the three run a step; the bias steers
    assert c["moe_layer_steps"] == 2 * c["decode_steps"]
    assert 0 < c["moe_bias_moved_sum"] <= c["moe_tokens_routed_sum"]
    assert c["prefill_runs"] > c["prefills"] > 0
    assert got[RUN_METRIC]["value"] == pytest.approx(
        c["prefill_ms_sum"] / c["prefill_runs"])
    assert 0 < got["decode_table_share.tpot"]["value"] <= 100
    check_sched_metrics(got, c)


@pytest.fixture(scope="module")
def served(glm_root):
    cell = harness.Cell(glm_root, "glm_tiny_mix")
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


@pytest.mark.parametrize("control", ["float8", "bias_off", "scale_off"])
def test_a_control_fails_the_cells_limit(served, control):
    """The reference put in the program's place, at the same prompts and
    positions, with float8 operands (one precision below the bfloat16 the
    configuration states), with the experts chosen without the
    correction bias, or with the chosen weights summing to 1 and not to
    1.8: not correct."""
    cell, rows = served
    got = cell.adapter.check_served(cell.config, 77, rows,
                                    cell.traffic["server"]["max_seq_len"],
                                    control=control)
    assert not harness.judge({"widest_gap": got["widest_gap"]},
                             cell.limits)[0], got
