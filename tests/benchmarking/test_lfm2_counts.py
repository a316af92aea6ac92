"""The LFM2-8B-A1B cell's yardstick, with no program in it: the
configuration file against the catalog's published keys, the counts of
``benchmark/counts/lfm2_moe.py`` worked by hand, and the committed files
of the cell ``lfm2_mixed_closed`` (its entries in ``BENCHMARK.json`` are
held by ``test_cells.py``, by name). The experts a run touches against
the reference's own router are held in ``tests/test_lfm2_moe.py``."""
import json
import os

import numpy as np
import pytest

from benchmark.counts import lfm2_moe as counts
from benchmark.generators import closed_mix
from benchmark.reference import lfm2_moe as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "lfm2_mixed_closed", "lfm2-8b-a1b"


def _load(*rel):
    with open(os.path.join(REPO, "benchmark", *rel)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cfg():
    return _load("configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


# the published widths, by hand
H, V, E, K, F, I, L, LC = 2048, 65536, 32, 4, 1792, 7168, 16, 3
A, KV, D = 32, 8, 64
CONV = 3 * H * H + LC * H + H * H            # in, taps, out
ATTN = H * A * D + 2 * H * KV * D + A * D * H + 2 * D
DENSE = 3 * H * I
EXPERT, ROUTER = 3 * H * F, H * E
KINDS = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 3 \
    + ["full_attention", "conv"]
CATALOG_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
                 "full_attention", "conv", "conv", "conv", "full_attention",
                 "conv", "conv", "conv", "full_attention", "conv", "conv",
                 "conv", "full_attention", "conv", "conv", "full_attention",
                 "conv", "conv"]


def test_the_configuration_keeps_every_published_key(cfg, bench):
    """The catalog's ``config`` of the model (the model-configs guide's
    ``architectures.jsonl``, from the published ``config.json``), key by
    key; the two keys that differ are the two in ``reduced``."""
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "layer_types": CATALOG_TYPES,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    differ = sorted(k for k, v in catalog.items() if cfg[k] != v)
    assert differ == ["layer_types", "num_hidden_layers"]
    assert sorted(cfg["reduced"]) == differ
    # depth: the first 16 layers, both dense ones, 14 >= 4 after them, 4
    # attention layers of 16 as the published 6 of 24
    assert cfg["num_hidden_layers"] == 16
    assert cfg["layer_types"] == CATALOG_TYPES[:16] == KINDS
    assert cfg["layer_types"].count("full_attention") * 24 == 6 * 16
    assert cfg["published"] == {"num_hidden_layers": 24,
                                "layer_types": CATALOG_TYPES}
    assert cfg["family"] == "lfm2_moe"
    assert cfg["param_dtype"] == cfg["kv_dtype"] == "bfloat16"
    assert set(cfg["assumed"]) >= {"head", "router", "norms", "state",
                                   "dtype", "weights"}
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert sorted(entry["reduced"]) == differ
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_parameters_by_hand(cfg):
    assert (CONV, ATTN, DENSE) == (16_783_360, 10_485_888, 44_040_192)
    moe = ROUTER + E + E * EXPERT
    layers = sum(2 * H + (ATTN if k == "full_attention" else CONV)
                 + (DENSE if i < 2 else moe) for i, k in enumerate(KINDS))
    assert counts.param_count(cfg) == layers + V * H + H \
        == 5_399_129_024                               # 10.80 GB in bf16
    # a token multiplies the projections, the taps and gates, a dense
    # layer or the router and four experts, and the tied head
    weights = 12 * 4 * H * H + 4 * (ATTN - 2 * D) + 2 * DENSE \
        + 14 * (ROUTER + K * EXPERT) + V * H
    assert counts.matmul_flops(cfg) == pytest.approx(
        2.0 * weights + 12 * (2 * LC * H + 2 * H))
    drawn = sum(int(np.prod(ref.kind_shape(cfg, k)))
                for k in ref.TOP_KINDS) + sum(
        int(np.prod(ref.kind_shape(cfg, k)))
        for i in range(L) for k in ref.layer_kinds(cfg, i))
    assert drawn == counts.param_count(cfg)


def test_one_decode_step_by_hand(cfg):
    """Four lanes: a pair costs 4 x 32 x 64 on each of the 4 attention
    layers; a step reads every weight outside the experts, the experts 4
    tokens touch, each live K and V of 1,024 numbers of 2 bytes an
    attention layer, the new ones, and each lane's state (2 x 2,048 of 2
    bytes on 12 conv layers) once in and once out."""
    ctx = [4000, 30, 60, 900]
    assert counts.decode_flops(cfg, ctx) == pytest.approx(
        counts.matmul_flops(cfg) * 4 + 4 * A * D * 4 * sum(ctx))
    fixed = counts.param_count(cfg) - 14 * E * EXPERT
    kv = 2 * KV * D * 2 * 4
    state = 2 * H * 2 * 12
    assert counts.decode_bytes(cfg, 1, ctx) == pytest.approx(
        2 * (fixed + 14 * counts.experts_touched(cfg, 4) * EXPERT)
        + kv * (sum(ctx) + 4) + 2 * state * 4)
    assert counts.decode_bytes(cfg, 0, []) == 0.0


def test_one_chunked_prompt_by_hand(cfg):
    """4,096 tokens in 8 runs of 512: the head once, n (n + 1) / 2 pairs
    an attention layer; every run reads the non-routed weights, the
    experts 512 tokens touch and the state once in and once out; K and V
    written once and read once."""
    n = 4096
    head = 2.0 * V * H
    assert counts.prefill_flops(cfg, [n]) == pytest.approx(
        (counts.matmul_flops(cfg) - head) * n + head
        + 4 * A * D * 4 * n * (n + 1) / 2)
    fixed = counts.param_count(cfg) - 14 * E * EXPERT
    per_run = 2 * (fixed + 14 * counts.experts_touched(cfg, 512) * EXPERT)
    kv = 2 * KV * D * 2 * 4
    assert counts.prefill_bytes(cfg, 8, [n]) == pytest.approx(
        8 * per_run + 2 * kv * n + 8 * 2 * (2 * H * 2 * 12))
    assert counts.prefill_bytes(cfg, 0, []) == 0.0
    # 512 tokens touch nearly all 32 experts (one the bias pushes down
    # may have none), one token its 4
    assert 30 < counts.experts_touched(cfg, 512) < E
    assert counts.experts_touched(cfg, 1) == pytest.approx(K)


# ----------------------------------------------------------------------
# the traffic and the entries
@pytest.fixture(scope="module")
def mix():
    return _load("traffic", "mixed_closed_sharegpt_64.json")


def test_the_traffic_file_holds_the_cells_parameters(mix, cfg):
    sharegpt = _load("traffic", "mixed_closed_sharegpt.json")
    assert mix["kind"] == "closed_mix" and mix["clients"] == 64
    assert mix["round"] == 16 and mix["warm_in_s"] == 16
    assert mix["chat"] == sharegpt["chat"] and mix["chat"]["pairs"] == 14
    assert mix["lengths_seed"] == sharegpt["lengths_seed"]
    assert mix["documents"] == [{"prompt_len": 2048, "output_len": 384},
                                {"prompt_len": 4096, "output_len": 384}]
    assert mix["server"] == {"max_slots": 64, "block_size": 16,
                             "max_seq_len": 4608,
                             "buckets": [16, 32, 64, 512]}
    assert mix["check"] == {"sample": 8}
    assert "arXiv:2309.06180" in mix["source"]
    assert set(mix["assumed"]) >= {"documents", "tokens", "clients"}
    # whole chunks: 4 and 8 runs, 3 and 7 of them carrying the state; the
    # longest request inside the server
    for d in mix["documents"]:
        assert d["prompt_len"] % 512 == 0
        assert d["prompt_len"] + d["output_len"] <= 4608
    pairs = sorted(closed_mix.round_pairs(mix))
    assert max(p + o for p, o in pairs) <= 4608
    reqs = closed_mix.generate(mix, cfg, 2**31 + 7)
    assert len(reqs) == 16 * 64
    ids = np.concatenate([r["prompt"] for r in reqs[:64]])
    assert ids.min() >= 0 and 65000 < ids.max() < 65536
    limits = _load("limits", CELL + ".json")
    assert set(limits) == {"widest_gap", "requests_failed"}
    assert limits["requests_failed"] == 0
