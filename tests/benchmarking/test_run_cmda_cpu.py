"""The Command A+ cell on the CPU at a tiny size, through
``benchmark.run`` on a root of this file's own (a tiny configuration of
the family at one chip's share of a layer, a ``closed_mix`` traffic file,
the metric files the cell lists copied from the package, limits): a
``--dry`` run prints the result line, a metric of this root's own reads
the rows a held expert's grouped product multiplies per read of its
weights from the program's counters (``moe_rows_per_expert.tpot``, which
the package's ``BENCHMARK.json`` does not list yet: PERF.md section 7),
and the controls (lower precision, the block made sequential, the shared
experts summed, rotate-half, the window off) fail the limit at the same
prompts and positions."""
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

#: wide enough (hidden 128, a vocabulary of 512) that the largest logits
#: of a position often stand close, so that a control's error parts its
#: greedy tokens from the reference's; a rotary theta of 10, so that at
#: these 64 positions every pair of a head turns and rotate-half is a
#: different rotation, not nearly the same one
TINY = {"family": "cohere2_moe", "hidden_size": 128, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 32, "num_experts": 4, "router_experts": 16,
        "first_expert": 0, "num_experts_per_tok": 4,
        "num_shared_experts": 4, "sliding_window": 8,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "num_hidden_layers": 4, "layer_norm_eps": 1e-5,
        "rope_theta": 10, "logit_scale": 1, "vocab_size": 512,
        "max_position_embeddings": 128, "use_parallel_block": True,
        "position_embedding_type": "rope_gptj", "rotary_pct": 1,
        "use_qk_norm": False, "first_k_dense_replace": 0,
        "shared_expert_combination_strategy": "average",
        "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
        "tie_word_embeddings": True, "param_dtype": "bfloat16",
        "kv_dtype": "bfloat16"}

MIX = {"kind": "closed_mix", "clients": 4, "round": 6, "lengths_seed": 1,
       "warm_in_s": 0.2,
       "chat": {"pairs": 4,
                "prompt_len": {"dist": "lognormal", "mean": 5, "sigma": 0.4,
                               "min": 2, "max": 8},
                "output_len": {"dist": "lognormal", "mean": 8, "sigma": 0.5,
                               "min": 4, "max": 16}},
       "documents": [{"prompt_len": 21, "output_len": 12},
                     {"prompt_len": 30, "output_len": 12}],
       "server": {"max_slots": 4, "block_size": 4, "max_seq_len": 64,
                  "buckets": [4, 8]},
       "check": {"sample": 6}}

#: a metric of this root's own: the held pairs over the held experts with
#: a token, from the two counters the family's decode program adds
NEW_METRIC = "moe_rows_per_expert.tpot"
NEW_SPEC = {"reader": "value",
            "params": {"num": ["counters.moe_held_pairs_sum"],
                       "den": ["counters.moe_experts_touched_sum"]}}
#: the package's metrics the cell lists, copied from their files
LISTED = ("tpot_mean_ms", "setup_s", "kv_pool_held_share.tpot",
          "decode_step_ms", "decode_table_share.tpot",
          "kv_window_held_share.tpot", "prefill_chunk_ms")
#: set from readings at this size on the CPU (the program rounds operands
#: to bfloat16 there as on the chip), over the positions where no router
#: of the reference stood at a near-tie of a held expert
#: (``ref.CLEAR_MARGIN``: 55-60% of 1,800-1,900 tokens judged). The
#: PROGRAM over seven seeds: 0 to 0.0014. The CONTROLS on the same seven:
#: rotate-half 0.0023 to 0.0100, the sequential block 0.020 to 0.064,
#: float8 0.045 to 0.062, the shared experts summed 0.10 to 0.22, the
#: window off 0.11 to 0.96. Rotate-half is the nearest, and on this
#: file's seed (77) it reads 0.0068 against the program's 0
LIMIT = 0.002


def write_root(root: str) -> str:
    data = os.path.join(root, "bench_data")
    for sub in ("configs", "traffic", "metrics", "limits"):
        os.makedirs(os.path.join(data, sub))

    def put(rel, obj):
        with open(os.path.join(data, rel), "w") as fh:
            json.dump(obj, fh)

    put("configs/cmda_tiny.json", TINY)
    put("traffic/tiny_mix.json", MIX)
    for name in LISTED + SCHED_METRICS:
        shutil.copy(os.path.join(REPO, "benchmark", "metrics",
                                 name + ".json"),
                    os.path.join(data, "metrics", name + ".json"))
    put(f"metrics/{NEW_METRIC}.json", NEW_SPEC)
    put("limits/cmda_tiny_mix.json", {"widest_gap": LIMIT,
                                      "requests_failed": 0})
    cell = ["cmda_tiny_mix"]
    layer = {"kv_pool_held_share.tpot": "KV memory tier",
             "decode_table_share.tpot": "KV memory tier",
             "kv_window_held_share.tpot": "KV memory tier",
             NEW_METRIC: "expert layer", "decode_step_ms": "model step",
             "prefill_chunk_ms": "serving scheduler",
             **dict.fromkeys(SCHED_METRICS, "serving scheduler")}
    bench = {
        "command": ["python3", "-m", "benchmark.run"],
        "paths": ["bench_data"], "run_seconds": 1,
        "configs": [{"name": "cmda_tiny", "source": "test",
                     "file": "bench_data/configs/cmda_tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "cmda_tiny_mix", "config": "cmda_tiny",
                       "traffic": "tiny_mix", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": cell}
            for n, u in (("tpot_mean_ms", "ms"), ("setup_s", "s"))],
        "per_layer": [
            {"name": n, "unit": "rows" if n == NEW_METRIC
             else "ms" if n.endswith("_ms") else "%",
             "better": "lower", "source": "program_counter",
             "layer": layer[n], "moves": "tpot_mean_ms", "workloads": cell}
            for n in layer],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


@pytest.fixture(scope="module")
def cmda_root(tmp_path_factory):
    return write_root(str(tmp_path_factory.mktemp("cmda_root")))


def _run_cli(root, *args):
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
             JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--root", root, *args],
        cwd=REPO, env=e, capture_output=True, text=True, timeout=900)


def test_dry_run_of_the_cell_prints_its_end_to_end_metrics(cmda_root):
    p = _run_cli(cmda_root, "--workload", "cmda_tiny_mix", "--seed",
                 str(2**31 + 15), "--seconds", "3", "--trace", "0", "--dry")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"tpot_mean_ms", "setup_s"}
    assert line["compared"]["widest_gap"]["limit"] == LIMIT
    assert line["compared"]["widest_gap"]["value"] <= LIMIT
    assert line["compared"]["requests_failed"]["value"] == 0


def test_a_run_reads_the_new_metric_and_its_sample_leads_with_a_document(
        cmda_root):
    cell = harness.Cell(cmda_root, "cmda_tiny_mix")
    record, rows, _ = serve.offer(cell, 2**31 + 5, 3.0, False, CPU_STAMP)
    assert record["failed"] == 0
    # the longest finished request leads the sample: a document, whose
    # prompt went through in chunks past the window
    assert len(rows[0][0]) == 30 and len(rows[0][1]) == 12
    got = harness.read_metrics(cell, record, trace=True)
    c = record["counters"]
    assert got[NEW_METRIC]["value"] == pytest.approx(
        c["moe_held_pairs_sum"] / c["moe_experts_touched_sum"])
    # a held expert with a token multiplies at least one row, at most a
    # row for each of the 4 lanes
    assert 1 <= got[NEW_METRIC]["value"] <= MIX["server"]["max_slots"]
    # four layers a step, four of 16 experts a token: a quarter of the
    # pairs land on the 4 held here, near enough
    assert c["moe_layer_steps"] == 4 * c["decode_steps"]
    assert c["moe_tokens_routed_sum"] == 4 * 4 * c["slots_active_sum"]
    assert 0.1 < c["moe_held_pairs_sum"] / c["moe_tokens_routed_sum"] < 0.5
    assert c["prefill_runs"] > c["prefills"] > 0
    assert 0 < got["kv_window_held_share.tpot"]["value"] <= 100
    assert 0 < got["decode_table_share.tpot"]["value"] <= 100
    check_sched_metrics(got, c)


def test_the_parent_of_the_family_fails_at_once_without_its_module(
        cmda_root, tmp_path):
    """A checkout without the family's adapter (as the program before it
    had) refuses the cell at once, exit 3 and no result line: it does
    not hang."""
    root = str(tmp_path / "bare")
    shutil.copytree(cmda_root, root)
    with open(os.path.join(root, "bench_data", "configs",
                           "cmda_tiny.json")) as fh:
        cfg = json.load(fh)
    cfg["family"] = "no_such_family"
    with open(os.path.join(root, "bench_data", "configs",
                           "cmda_tiny.json"), "w") as fh:
        json.dump(cfg, fh)
    p = _run_cli(root, "--workload", "cmda_tiny_mix", "--seed", "3",
                 "--seconds", "1", "--trace", "0", "--dry")
    assert p.returncode != 0 and not p.stdout.strip()


@pytest.fixture(scope="module")
def served(cmda_root):
    cell = harness.Cell(cmda_root, "cmda_tiny_mix")
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


@pytest.mark.parametrize("control", ["float8", "sequential_block",
                                     "shared_summed", "rope_half",
                                     "window_off"])
def test_a_control_fails_the_cells_limit(served, control):
    """The reference put in the program's place, at the same prompts and
    positions, with float8 operands (one precision below the bfloat16 the
    configuration states), with the block made sequential, with the
    shared experts summed, with rotate-half for GPT-J's pairs, or with
    the window off: not correct."""
    cell, rows = served
    got = cell.adapter.check_served(cell.config, 77, rows,
                                    cell.traffic["server"]["max_seq_len"],
                                    control=control)
    assert not harness.judge({"widest_gap": got["widest_gap"]},
                             cell.limits)[0], got
