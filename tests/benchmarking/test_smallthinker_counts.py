"""The SmallThinker cell's yardstick, with no program in it: the counts
of ``benchmark/counts/smallthinker.py`` worked by hand, the formula for
the experts a run touches against the reference's own router, the
``closed_mix`` generator as a function of the seed, and the committed
files of the cell (its entries in ``BENCHMARK.json`` are held by
``test_cells.py``, by name)."""
import json
import os

import numpy as np
import pytest

from benchmark.counts import smallthinker as counts
from benchmark.generators import closed_mix
from benchmark.reference import smallthinker as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(*rel):
    with open(os.path.join(REPO, "benchmark", *rel)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cfg():
    return _load("configs", "smallthinker-21b-a3b.json")


# the published widths, by hand (ISSUE 28's arithmetic)
H, V, E, K, F, L = 2560, 151936, 64, 6, 768, 12
ATTN = 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560       # q, k, v, o
ROUTER, EXPERT = 2560 * 64, 3 * 2560 * 768
GLOBAL, WINDOWED, W = 3, 9, 4096


def test_the_configuration_keeps_every_published_width(cfg):
    catalog = {"head_dim": 128, "hidden_size": 2560,
               "max_position_embeddings": 16384,
               "model_name": "smallthinker_21b_instruct",
               "moe_ffn_hidden_size": 768,
               "moe_num_active_primary_experts": 6,
               "moe_num_primary_experts": 64,
               "moe_primary_router_apply_softmax": True,
               "norm_topk_prob": True, "num_attention_heads": 28,
               "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
               "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
               "rope_theta": 1500000,
               "sliding_window_layout": [0, 1, 1, 1] * 13,
               "sliding_window_size": 4096, "tie_word_embeddings": False,
               "vocab_size": 151936}
    for key, value in catalog.items():
        assert cfg[key] == value, key
    # the one cut: depth, in whole periods, no fewer than two
    assert cfg["num_hidden_layers"] in (8, 12)
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"]
             if c["name"] == "smallthinker-21b-a3b"][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    assert cfg["param_dtype"] == cfg["kv_dtype"] == "bfloat16"


def test_parameters_by_hand(cfg):
    layer = ATTN + ROUTER + 2 * H + E * EXPERT
    assert layer == 398_627_840                     # the issue's 398.63M
    assert counts.param_count(cfg) == L * layer + 2 * V * H + H
    assert counts.param_count(cfg) == 5_561_448_960     # 11.12 GB in bf16
    assert counts.matmul_params(cfg) == \
        L * (ATTN + ROUTER + K * EXPERT) + V * H


def test_one_decode_step_by_hand(cfg):
    """Eight lanes, four with a document's context and four a chat's:
    six experts a token, a window layer attends to 4096 at the most."""
    ctx = [6000, 7000, 5500, 7500, 30, 60, 90, 120]
    attended = sum(GLOBAL * c + WINDOWED * min(c, W) for c in ctx)
    assert counts.decode_flops(cfg, ctx) == pytest.approx(
        2.0 * (L * (ATTN + ROUTER + K * EXPERT) + V * H) * 8
        + 4.0 * 3584 * attended)
    touched = E * (1 - (58 / 64) ** 8)
    assert touched == pytest.approx(34.88, abs=0.01)    # the issue's 35
    weights = 2 * (L * (ATTN + ROUTER + 2 * H) + H + V * H
                   + L * touched * EXPERT)
    # 4.9 GB of the experts' 9.1 GB, as the issue has it
    assert 2 * L * touched * EXPERT == pytest.approx(4.94e9, rel=0.01)
    kv = 2 * 512 * 2 * (attended + L * 8)
    assert counts.decode_bytes(cfg, 1, ctx) == pytest.approx(weights + kv)
    # two steps read the weights twice, for four tokens each
    two = counts.decode_bytes(cfg, 2, ctx)
    assert two == pytest.approx(
        2 * 2 * (L * (ATTN + ROUTER + 2 * H) + H + V * H
                 + L * E * (1 - (58 / 64) ** 4) * EXPERT) + kv)
    assert counts.decode_bytes(cfg, 0, []) == 0.0


def test_one_chunked_prompt_by_hand(cfg):
    """7,168 tokens in 14 runs of 512: the head once, global layers over
    n (n + 1) / 2 pairs, window layers over 4096 a query past the
    window; every run reads the non-expert weights and, at 512 tokens,
    all 64 experts."""
    n = 7168
    body = L * (ATTN + ROUTER + K * EXPERT)
    pairs_g = n * (n + 1) / 2
    pairs_w = W * (W + 1) / 2 + (n - W) * W
    assert counts.prefill_flops(cfg, [n]) == pytest.approx(
        2.0 * body * n + 2.0 * V * H
        + 4.0 * 3584 * (GLOBAL * pairs_g + WINDOWED * pairs_w))
    touched = E * (1 - (58 / 64) ** 512)
    assert touched == pytest.approx(64.0)
    per_run = 2 * (L * (ATTN + ROUTER + 2 * H) + H + V * H
                   + L * touched * EXPERT)
    kv = 2 * 512 * 2 * L * n
    assert counts.prefill_bytes(cfg, 14, [n]) == pytest.approx(
        14 * per_run + 2 * kv)
    # a chat prompt of 19 tokens alone: one run, 54 experts a layer
    assert counts.experts_touched(cfg, 19) == pytest.approx(54.14, abs=0.01)
    # a window layer never counts more than the window
    short = counts.prefill_flops(cfg, [100])
    assert short == pytest.approx(2.0 * body * 100 + 2.0 * V * H
                                  + 4.0 * 3584 * L * 100 * 101 / 2)


def test_the_experts_a_run_touches_against_the_references_router():
    """The formula assumes that seeded weights route near evenly. The
    reference's own router at a small size (64 experts, six a token, two
    layers, 512 tokens): the distinct experts of every run of eight
    tokens, within 5% of it."""
    small = {"head_dim": 16, "hidden_size": 64,
             "moe_ffn_hidden_size": 32,
             "moe_num_active_primary_experts": 6,
             "moe_num_primary_experts": 64, "num_attention_heads": 4,
             "num_hidden_layers": 2, "num_key_value_heads": 2,
             "rms_norm_eps": 1e-6, "rope_layout": [0, 1],
             "rope_theta": 1500000, "sliding_window_layout": [0, 1],
             "sliding_window_size": 64, "vocab_size": 997}
    seed = 2**31 + 3
    toks = np.random.default_rng(seed).integers(0, 997, 512)
    _, routes = ref.hidden(small, seed, [toks.astype(np.int32)],
                           routes=True)
    chosen = routes[0]
    assert chosen.shape == (2, 512, 6)
    for n in (8, 64):
        seen = [len(np.unique(chosen[layer, i:i + n]))
                for layer in range(2) for i in range(0, 512, n)]
        assert np.mean(seen) == pytest.approx(
            counts.experts_touched(small, n), rel=0.05)


# ----------------------------------------------------------------------
# the traffic
@pytest.fixture(scope="module")
def mix():
    return _load("traffic", "mixed_closed.json")


def _pairs(reqs):
    return [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]


def test_the_mix_is_a_function_of_the_seed(mix, cfg):
    a = closed_mix.generate(mix, cfg, 2**31 + 7)
    b = closed_mix.generate(mix, cfg, 2**31 + 7)
    c = closed_mix.generate(mix, cfg, 5)
    assert all((x["prompt"] == y["prompt"]).all()
               and x["max_new_tokens"] == y["max_new_tokens"]
               for x, y in zip(a, b))
    assert _pairs(a) != _pairs(c)
    assert max(int(r["prompt"].max()) for r in a[:64]) > 150000


def test_every_round_is_fourteen_chat_pairs_and_the_two_documents(mix, cfg):
    n = mix["round"]
    a = _pairs(closed_mix.generate(mix, cfg, 1))
    b = _pairs(closed_mix.generate(mix, cfg, 2))
    assert len(a) == n * closed_mix.ROUNDS == 16 * 64
    first = sorted(a[:n])
    for reqs in (a, b):
        assert all(sorted(reqs[i:i + n]) == first
                   for i in range(0, len(reqs), n))
    assert a[:n] != b[:n]
    # a round's pairs past the chunk are the two documents
    assert [p for p in first if p[0] > 512] == [(5120, 384), (7168, 384)]
    chat = [p for p in first if p[0] <= 512]
    assert len(chat) == 14
    prompts, outputs = zip(*chat)
    # chat_closed.json's own distributions: the source's means
    assert np.mean(prompts) == pytest.approx(19.31, rel=0.02)
    assert np.mean(outputs) == pytest.approx(58.45, rel=0.02)
    assert 4 < min(prompts) and max(prompts) < 64
    assert 4 < min(outputs) and max(outputs) < 256
    with pytest.raises(ValueError):
        closed_mix.round_pairs(dict(mix, round=15))


def test_the_cells_files_hold_the_parameters_the_issue_names(mix):
    chat = _load("traffic", "chat_closed.json")
    assert mix["kind"] == "closed_mix" and mix["clients"] == 8
    assert mix["round"] == 16 and mix["warm_in_s"] == 12
    assert mix["chat"] == {"pairs": 14, "prompt_len": chat["prompt_len"],
                           "output_len": chat["output_len"]}
    assert mix["lengths_seed"] == chat["lengths_seed"]
    assert mix["documents"] == [{"prompt_len": 5120, "output_len": 384},
                                {"prompt_len": 7168, "output_len": 384}]
    assert mix["server"] == {"max_slots": 8, "block_size": 16,
                             "max_seq_len": 8192,
                             "buckets": [16, 32, 64, 512]}
    assert mix["check"] == {"sample": 8}
    assert "arXiv:2309.06180" in mix["source"]
    assert set(mix["assumed"]) >= {"documents", "tokens"}
    # the documents cross the window inside the server, in whole chunks
    for d in mix["documents"]:
        assert d["prompt_len"] > 4096 and d["prompt_len"] % 512 == 0
        assert d["prompt_len"] + d["output_len"] < 8192
    limits = _load("limits", "st_mixed_closed.json")
    assert set(limits) == {"widest_gap", "requests_failed"}
    assert limits["requests_failed"] == 0

