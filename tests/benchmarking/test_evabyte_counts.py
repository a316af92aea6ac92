"""The EvaByte cell's yardstick, with no program in it: the counts of
``benchmark/counts/evabyte.py`` worked by hand at two contexts, the
committed configuration against the catalog's published keys, the
traffic file's pairs, and the files the cell names (its entries in
``BENCHMARK.json`` are held by ``test_cells.py``, by name)."""
import json
import os

import numpy as np
import pytest

from benchmark.counts import evabyte as counts
from benchmark.generators import closed_mix

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(*rel):
    with open(os.path.join(REPO, "benchmark", *rel)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cfg():
    return _load("configs", "evabyte-6.5b.json")


# the published widths, by hand (ISSUE 35's arithmetic)
H, F, A, D, V, P, L, W, C = 4096, 11008, 32, 128, 320, 8, 12, 2048, 16
LAYER_MM = 4 * H * H + 3 * H * F                       # q, k, v, o; the MLP
LAYER_ALL = LAYER_MM + 2 * H + 2 * A * D               # norms, phi, mu
KV = 2 * H * 2                              # a K row and a V row, bfloat16


def test_the_configuration_keeps_every_published_key(cfg):
    catalog = {"attention_bias": False, "attention_class": "eva",
               "chunk_size": 16, "fp32_ln": False, "fp32_logits": True,
               "fp32_skip_add": True, "hidden_act": "silu",
               "hidden_size": 4096, "init_cutoff_factor": None,
               "init_fn": "v2", "init_std": 0.01275,
               "intermediate_size": 11008, "lazy_init": True,
               "max_position_embeddings": 32768, "max_seq_length": 32768,
               "mixedp_attn": True, "model_type": "evabyte",
               "norm_add_unit_offset": True, "num_attention_heads": 32,
               "num_chunks": None, "num_key_value_heads": 32,
               "num_pred_heads": 8, "rms_norm_eps": 1e-05,
               "rope_scaling": None, "rope_theta": 100000,
               "tie_word_embeddings": False, "vocab_size": 320,
               "window_size": 2048}
    for key, value in catalog.items():
        assert key in cfg and cfg[key] == value, key
    # the one cut: depth (the period is one layer), never under 8
    assert cfg["num_hidden_layers"] in (10, 11, 12)
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 32
    assert "pipeline" in cfg["deployment"]
    for said in ("phi scale", "mu", "rotation", "head", "dtype", "weights"):
        assert said in cfg["assumed"]
    assert (cfg["param_dtype"], cfg["kv_dtype"]) == ("bfloat16", "bfloat16")
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == "evabyte-6.5b"][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/evabyte-6.5b.json"


def test_the_parameters_are_the_issues_count(cfg):
    assert LAYER_ALL == 202_391_552
    n = cfg["num_hidden_layers"]
    assert counts.param_count(cfg) \
        == V * H + H * P * V + H + n * LAYER_ALL
    assert counts.matmul_params(cfg) == n * LAYER_MM + H * V
    if n == 12:
        assert counts.param_count(cfg) == 2_440_499_200      # 4.88 GB


@pytest.mark.parametrize("context, exact, far", [
    (1, 1, 0), (100, 100, 0), (2048, 2048, 0), (2049, 1, 128),
    (21248, 768, 1280), (32768, 2048, 1920)])
def test_a_query_attends_to_its_window_and_the_summaries_before_it(
        cfg, context, exact, far):
    assert counts.rows_attended(cfg, context) == (exact, far)


def test_decode_counts_by_hand_at_two_contexts(cfg):
    """Context 100 (inside the first window, mid-chunk) and context
    21,248 (the last byte of a 20,480-byte document's 768: ten windows
    turned, 768 exact rows, 1,280 summaries, and its chunk ends)."""
    n = cfg["num_hidden_layers"]
    mm = n * LAYER_MM + H * V
    near = 2.0 * mm + n * 4.0 * H * 100
    far = 2.0 * mm + n * (4.0 * H * (768 + 1280) + 6.0 * H * C)
    assert counts.decode_flops(cfg, [100]) == near
    assert counts.decode_flops(cfg, [21248]) == far
    assert counts.decode_flops(cfg, [100, 21248]) == near + far
    # a step reads the layers, the last norm and head 0's columns, never
    # the embedding's table nor heads 1 to 7
    weights = 2 * (n * LAYER_ALL + H + H * V)
    assert counts.decode_bytes(cfg, 1, [100]) \
        == weights + n * KV * (100 + 1)
    assert counts.decode_bytes(cfg, 1, [21248]) \
        == weights + n * KV * (768 + 1280 + 1 + 1)
    assert counts.decode_bytes(cfg, 3, [100, 21248]) \
        == 3 * weights + n * KV * (101 + 2050)
    # full attention would read ten times the rows at that context
    assert 21248 / (768 + 1280) > 10


def test_prefill_counts_by_hand_at_two_lengths(cfg):
    """300 bytes (one run, no window turned) and 8,192 (four whole
    windows in 16 runs: each window's queries see 128 more summaries)."""
    n = cfg["num_hidden_layers"]
    body = n * LAYER_MM
    short = 2.0 * body * 300 + 2.0 * H * V + n * (
        4.0 * H * (300 * 301 / 2) + 6.0 * H * C * 18)
    pairs = 4 * 2048 * 2049 / 2 + 128 * 2048 * (0 + 1 + 2 + 3)
    long = 2.0 * body * 8192 + 2.0 * H * V + n * (
        4.0 * H * pairs + 6.0 * H * C * 512)
    assert counts.prefill_flops(cfg, [300]) == short
    assert counts.prefill_flops(cfg, [8192]) == long
    weights = 2 * (n * LAYER_ALL + H + H * V)
    assert counts.prefill_bytes(cfg, 17, [300, 8192]) \
        == 17 * weights + 2.0 * n * KV * (300 + 18 + 8192 + 512)
    assert counts.prefill_bytes(cfg, 0, []) == 0


def test_the_mix_is_fourteen_chat_pairs_in_bytes_and_two_documents():
    traffic = _load("traffic", "mixed_closed_bytes.json")
    pairs = closed_mix.round_pairs(traffic)
    assert len(pairs) == 16
    assert pairs[14:] == [(8192, 768), (20480, 768)]
    chat = np.asarray(pairs[:14])
    assert 16 <= chat[:, 0].min() and chat[:, 0].max() <= 256
    assert 16 <= chat[:, 1].min() and chat[:, 1].max() <= 1024
    # the source's means at 4.4 bytes a token, within the clips' reach
    assert abs(chat[:, 0].mean() - 85) < 6
    assert abs(chat[:, 1].mean() - 257) < 30
    # documents are whole windows: 4 and 10 turned before the first byte
    assert [p % 2048 for p, _ in pairs[14:]] == [0, 0]
    assert traffic["server"] == {"max_slots": 8, "block_size": 16,
                                 "max_seq_len": 32768,
                                 "buckets": [64, 128, 256, 512]}
    assert traffic["clients"] == 8 and traffic["warm_in_s"] == 20.0
    assert traffic["check"]["sample"] == 8
    cfg = _load("configs", "evabyte-6.5b.json")
    reqs = closed_mix.generate(traffic, cfg, 2**31 + 7)
    assert len(reqs) == 64 * 16
    assert max(int(r["prompt"].max()) for r in reqs[:64]) < 320
    again = closed_mix.generate(traffic, cfg, 2**31 + 7)
    assert all(np.array_equal(a["prompt"], b["prompt"])
               for a, b in zip(reqs[:32], again[:32]))


@pytest.mark.parametrize("name, num, den", [
    ("kv_rows_per_position.tpot", "kv_rows_held_sum", "kv_positions_sum"),
    ("decode_rows_used_share.tpot", "kv_rows_attended_sum",
     "kv_rows_gathered_sum")])
def test_the_two_metrics_read_the_rows_by_kind(name, num, den):
    spec = _load("metrics", name + ".json")
    assert spec == {"reader": "value", "params": {
        "num": ["counters." + num], "den": ["counters." + den],
        "scale": 100}}
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [m for m in bench["per_layer"] if m["name"] == name][0]
    assert entry["workloads"] == ["eva_mixed_closed"]
    assert (entry["layer"], entry["moves"], entry["unit"]) \
        == ("KV memory tier", "tpot_mean_ms", "%")


def test_the_cells_limits_and_reference_are_the_familys(cfg):
    limits = _load("limits", "eva_mixed_closed.json")
    assert set(limits) == {"widest_gap", "requests_failed"}
    assert limits["requests_failed"] == 0 and 0 < limits["widest_gap"] < 1
    # the reference imports nothing of the program under test
    with open(os.path.join(REPO, "benchmark", "reference",
                           "evabyte.py")) as fh:
        text = fh.read()
    assert "deeplearning4j_tpu" not in text
    from benchmark.reference import evabyte as ref
    assert ref.kind_shape(cfg, "head") == (H, P * V)
    assert ref.kind_shape(cfg, "phi") == (A, D)
    assert set(ref.VARIANTS) == {"eva", "summaries_off", "window_slides"}
    for name in ("float8", "summaries_off", "window_slides"):
        assert ref.control_of(name)[0] in ("float8", "float32")
    with pytest.raises(ValueError):
        ref.control_of("bfloat16")
