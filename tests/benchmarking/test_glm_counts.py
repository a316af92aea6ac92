"""The GLM-4.7-Flash cell's yardstick, with no program in it: the
configuration file against the catalog's published keys, the counts of
``benchmark/counts/glm4_moe_lite.py`` worked by hand, the experts a run
touches against the reference's own router, and the committed files of
the cell ``glm_mixed_closed`` (its entries in ``BENCHMARK.json`` are
held by ``test_cells.py``, by name)."""
import json
import os

import numpy as np
import pytest

from benchmark.counts import glm4_moe_lite as counts
from benchmark.generators import closed_mix
from benchmark.reference import glm4_moe_lite as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONFIG = "glm_mixed_closed", "glm-4.7-flash"


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


# the published widths, by hand (ISSUE 32's arithmetic)
H, V, E, K, F, I, L = 2048, 154880, 64, 4, 1536, 10240, 8
A, QR, C, DN, DR, DV = 20, 768, 512, 192, 64, 256
ATTN = H * QR + QR * A * (DN + DR) + H * (C + DR) \
    + C * A * (DN + DV) + A * DV * H
EXPERT, ROUTER = 3 * H * F, H * E
NORMS = 2 * H + QR + C
MOE_LAYERS = L - 1


def test_the_configuration_keeps_every_published_key(cfg, bench):
    """The catalog's ``config`` of the model (the model-configs guide's
    ``architectures.jsonl``, from the published ``config.json``), key by
    key; the two keys that differ are the two in ``reduced``."""
    catalog = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    differ = sorted(k for k, v in catalog.items() if cfg[k] != v)
    assert differ == ["num_hidden_layers", "num_nextn_predict_layers"]
    assert sorted(cfg["reduced"]) == differ
    # depth: the dense layer and 7 expert layers, never fewer than 4;
    # the next-token module is not served
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] in \
        (7, 6, 5, 4)
    assert cfg["num_nextn_predict_layers"] == 0
    assert cfg["published"] == {
        "num_hidden_layers": 47, "num_nextn_predict_layers": 1,
        "n_routed_experts": 64, "vocab_size": 154880}
    assert cfg["family"] == "glm4_moe_lite"
    assert cfg["param_dtype"] == cfg["kv_dtype"] == "bfloat16"
    assert set(cfg["assumed"]) >= {"dtype", "rotary", "router", "bias",
                                   "weights"}
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert sorted(entry["reduced"]) == differ
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_parameters_by_hand(cfg):
    assert ATTN == 21_757_952                       # the issue's figure
    expert_layer = ATTN + NORMS + ROUTER + E + EXPERT + E * EXPERT
    dense_layer = ATTN + NORMS + 3 * H * I
    assert expert_layer == 635_311_424 and dense_layer == 84_677_888
    assert counts.param_count(cfg) == dense_layer \
        + MOE_LAYERS * expert_layer + 2 * V * H + H
    assert counts.param_count(cfg) == 5_166_248_384     # 10.33 GB in bf16
    # a token multiplies four routed experts and the shared one
    assert counts.matmul_params(cfg) == L * ATTN + 3 * H * I \
        + MOE_LAYERS * (ROUTER + (K + 1) * EXPERT) + V * H
    # the reference draws exactly these leaves
    drawn = sum(int(np.prod(ref.kind_shape(cfg, k)))
                for k in ref.TOP_KINDS) + sum(
        int(np.prod(ref.kind_shape(cfg, k)))
        for i in range(L) for k in ref.layer_kinds(cfg, i))
    assert drawn == counts.param_count(cfg)


def test_one_decode_step_by_hand(cfg):
    """Eight lanes, four with a document's context and four a chat's: a
    pair costs the published form's 2 x 20 x (256 + 256), the smaller of
    the two; a live position's row is 576 numbers of 2 bytes a layer."""
    ctx = [9000, 14500, 8300, 14400, 30, 60, 90, 120]
    pair = 2 * A * min((DN + DR) + DV, (C + DR) + C)
    assert pair == 2 * 20 * 512
    assert counts.decode_flops(cfg, ctx) == pytest.approx(
        2.0 * counts.matmul_params(cfg) * 8 + pair * L * sum(ctx))
    touched = counts.experts_touched(cfg, 8)
    fixed = L * (ATTN + NORMS) + 3 * H * I \
        + MOE_LAYERS * (ROUTER + E + EXPERT) + H + V * H
    # ISSUE 32's 1.24 GB of non-routed weights a step
    assert 2 * fixed == pytest.approx(1.24e9, rel=0.01)
    rows = (C + DR) * 2 * L * (sum(ctx) + 8)
    assert counts.decode_bytes(cfg, 1, ctx) == pytest.approx(
        2 * (fixed + MOE_LAYERS * touched * EXPERT) + rows)
    # two steps read the weights twice, for four tokens each
    assert counts.decode_bytes(cfg, 2, ctx) == pytest.approx(
        2 * 2 * (fixed + MOE_LAYERS * counts.experts_touched(cfg, 4)
                 * EXPERT) + rows)
    assert counts.decode_bytes(cfg, 0, []) == 0.0
    # the absorbed form's count a pair is the larger and never taken
    assert 2 * A * ((C + DR) + C) == 43_520 > pair


def test_one_chunked_prompt_by_hand(cfg):
    """14,336 tokens in 28 runs of 512: the head once, n (n + 1) / 2
    pairs a layer; every run reads the non-routed weights and the
    experts 512 tokens touch; the rows written once and read once."""
    n = 14336
    body = counts.matmul_params(cfg) - V * H
    assert counts.prefill_flops(cfg, [n]) == pytest.approx(
        2.0 * body * n + 2.0 * V * H
        + 2 * A * 512 * L * n * (n + 1) / 2)
    fixed = L * (ATTN + NORMS) + 3 * H * I \
        + MOE_LAYERS * (ROUTER + E + EXPERT) + H + V * H
    per_run = 2 * (fixed + MOE_LAYERS * counts.experts_touched(cfg, 512)
                   * EXPERT)
    rows = (C + DR) * 2 * L * n
    assert counts.prefill_bytes(cfg, 28, [n]) == pytest.approx(
        28 * per_run + 2 * rows)
    assert counts.prefill_bytes(cfg, 0, []) == 0.0


def test_the_experts_a_run_touches_against_the_references_router(cfg):
    """The published rule with the seeded bias routes UNEVENLY: an even
    choice would touch ``E (1 - (60/64)^n)`` = 25.8 experts at 8 tokens,
    the reference's own router (its weights and bias at the published
    width, unit-RMS rows as a norm hands them over; 12 draws) touches
    some 20.5. The counts take the rule's expectation under the draw's
    statistics, held here within 5% of the reference's figure; with the
    bias off both give the even figure."""
    import jax
    import jax.numpy as jnp
    assert (counts.WEIGHT_STD, counts.BIAS_STD) == (ref.STD, ref.BIAS_STD)
    assert cfg["router_bias_std"] == ref.BIAS_STD
    even = {n: E * (1 - (1 - K / E) ** n) for n in (8, 32, 512)}
    assert even[8] == pytest.approx(25.81, abs=0.01)
    seen = {n: [] for n in even}
    bare = []
    for seed in (1, 2**31 + 3, 77):
        for layer in (2, 3, 4, 5):
            router = ref.draw(cfg, seed, "router", layer)
            bias = ref.draw(cfg, seed, "router_bias", layer)
            m = jax.random.normal(jax.random.PRNGKey(seed + layer),
                                  (2048, H), jnp.float32)
            m = m / jnp.sqrt(jnp.mean(m * m, axis=-1, keepdims=True))
            chosen = np.asarray(ref.route(m, router, bias, K, 1.8)[0])
            for n in even:
                seen[n] += [len(np.unique(chosen[i:i + n]))
                            for i in range(0, 2048, n)]
            plain = np.asarray(ref.route(m, router, jnp.zeros_like(bias),
                                         K, 1.8)[0])
            bare += [len(np.unique(plain[i:i + 8]))
                     for i in range(0, 2048, 8)]
    for n in even:
        assert counts.experts_touched(cfg, n) == pytest.approx(
            np.mean(seen[n]), rel=0.05)
    # more than 5% under the even formula: why the counts do not use it
    assert np.mean(seen[8]) < 0.9 * even[8]
    assert np.mean(bare) == pytest.approx(even[8], rel=0.02)
    assert counts.experts_touched(dict(cfg, router_bias_std=0.0), 8) == \
        pytest.approx(even[8], rel=0.02)
    assert counts.experts_touched(cfg, 1) == pytest.approx(K)


# ----------------------------------------------------------------------
# the traffic and the entries
@pytest.fixture(scope="module")
def mix():
    return _load("traffic", "mixed_closed_16k.json")


def test_the_traffic_file_holds_the_parameters_the_issue_names(mix, cfg):
    st = _load("traffic", "mixed_closed.json")
    assert mix["kind"] == "closed_mix" and mix["clients"] == 8
    assert mix["round"] == 16 and mix["warm_in_s"] == 16
    assert mix["lengths_seed"] == 20260929
    assert mix["chat"] == st["chat"] and mix["chat"]["pairs"] == 14
    assert mix["documents"] == [{"prompt_len": 8192, "output_len": 384},
                                {"prompt_len": 14336, "output_len": 384}]
    assert mix["server"] == {"max_slots": 8, "block_size": 16,
                             "max_seq_len": 16384,
                             "buckets": [16, 32, 64, 512]}
    assert mix["check"] == {"sample": 8}
    assert "arXiv:2309.06180" in mix["source"]
    assert set(mix["assumed"]) >= {"documents", "tokens"}
    # whole chunks, inside the server, past the first two table rungs
    for d in mix["documents"]:
        assert d["prompt_len"] % 512 == 0
        assert d["prompt_len"] + d["output_len"] < 16384
    # a round's pairs past the chunk are the two documents
    pairs = sorted(closed_mix.round_pairs(mix))
    assert [p for p in pairs if p[0] > 512] == [(8192, 384), (14336, 384)]
    reqs = closed_mix.generate(mix, cfg, 2**31 + 7)
    assert len(reqs) == 16 * 64
    assert max(int(r["prompt"].max()) for r in reqs[:64]) > 150000
    limits = _load("limits", CELL + ".json")
    assert set(limits) == {"widest_gap", "requests_failed"}
    assert limits["requests_failed"] == 0

