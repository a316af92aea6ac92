"""Operations and bytes the GLM-4.7-Flash ALGORITHM (``glm4_moe_lite``)
needs, from a configuration file's shapes and dtypes; ``counts/gpt2.py``'s
signatures. Never what a program moves, and no counter of the program
enters:

- a token multiplies the attention projections (the query's two, the
  latent's two and the output's), in an expert layer the router, the
  FOUR routed experts and the shared one, in a dense layer its three
  matrices, and the head;
- a (query, context position) pair of a layer costs the SMALLER of the
  two forms of latent attention: the published one, ``2 heads (qk + v)``
  with the context expanded elsewhere, against the absorbed one's ``2
  heads ((latent + rope) + latent)``: no share passes 100% by the
  count's choice of form;
- a run of a program reads every weight outside the routed experts once
  (the embedding's rows excepted) and, in each expert layer, the experts
  that have a token: :func:`experts_touched` for a run of ``n`` tokens,
  ``n`` the mean over the span's runs. The seeded router routes UNEVENLY:
  its correction bias (std 0.1 against sigmoids that spread by 0.2) makes
  some experts likelier than others, so eight tokens touch 20 experts
  and not the ``E (1 - (1 - k/E)^8)`` = 25.8 of an even choice. The
  figure is the expectation of the published rule under the seeded
  draw's own statistics, which
  ``tests/benchmarking/test_glm_counts.py`` holds against the
  reference's router;
- a live position's latent row (``kv_lora_rank + qk_rope_head_dim``
  numbers in ``kv_dtype``) is read once a layer and decode step, and a
  new token's written once; a prompt's rows are written once and read
  once (prefill: the least any chunking can do).

Imports nothing of the program under test."""
from __future__ import annotations

import functools

import numpy as np

from benchmark.counts.gpt2 import item_bytes

#: the seeded draw's spreads (``reference/glm4_moe_lite.py`` ``STD`` and,
#: where the file names none, ``BIAS_STD``)
WEIGHT_STD, BIAS_STD = 0.02, 0.1


def _sizes(cfg: dict):
    L, dense = int(cfg["num_hidden_layers"]), int(
        cfg["first_k_dense_replace"])
    A = int(cfg["num_attention_heads"])
    C, DR = int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"])
    DN = int(cfg["qk_nope_head_dim"])
    DQ = DN + DR
    DV = int(cfg["v_head_dim"])
    return {"V": int(cfg["vocab_size"]), "H": int(cfg["hidden_size"]),
            "L": L, "dense": dense, "moe": L - dense, "A": A,
            "QR": int(cfg["q_lora_rank"]), "C": C, "ROW": C + DR,
            "DN": DN, "DQ": DQ, "DV": DV, "I": int(cfg["intermediate_size"]),
            "F": int(cfg["moe_intermediate_size"]),
            "E": int(cfg["n_routed_experts"]),
            "K": int(cfg["num_experts_per_tok"]),
            "S": int(cfg["n_shared_experts"]),
            # FLOPs of one query against one context position, a layer
            "pair": 2.0 * A * min(DQ + DV, (C + DR) + C)}


def _attention(z) -> int:
    """A layer's attention projections (its two inner norms apart)."""
    H, A = z["H"], z["A"]
    return H * z["QR"] + z["QR"] * A * z["DQ"] + H * z["ROW"] \
        + z["C"] * A * (z["DN"] + z["DV"]) \
        + A * z["DV"] * H


def _expert(z) -> int:
    return 3 * z["H"] * z["F"]


def _norms(z) -> int:
    """A layer's four gains: two on the stream, two inside attention."""
    return 2 * z["H"] + z["QR"] + z["C"]


def _moe_fixed(z) -> int:
    """An expert layer's weights outside its routed experts."""
    return _attention(z) + _norms(z) + z["H"] * z["E"] + z["E"] \
        + z["S"] * _expert(z)


def _dense_layer(z) -> int:
    return _attention(z) + _norms(z) + 3 * z["H"] * z["I"]


def param_count(cfg: dict) -> int:
    """Every stored parameter (embedding and untied head both)."""
    z = _sizes(cfg)
    return 2 * z["V"] * z["H"] + z["H"] + z["dense"] * _dense_layer(z) \
        + z["moe"] * (_moe_fixed(z) + z["E"] * _expert(z))


def matmul_params(cfg: dict) -> int:
    """Weights that multiply every token: each layer's attention
    projections, a dense layer's three matrices, an expert layer's
    router, routed experts per token and shared expert, and the head."""
    z = _sizes(cfg)
    return z["L"] * _attention(z) + z["dense"] * 3 * z["H"] * z["I"] \
        + z["moe"] * (z["H"] * z["E"] + (z["K"] + z["S"]) * _expert(z)) \
        + z["V"] * z["H"]


@functools.lru_cache(maxsize=None)
def _choice_shares(E: int, K: int, logit_std: float, bias_std: float,
                   layers: int = 32, tokens: int = 4096):
    """How often each expert of a layer is among a token's K, ``[layers,
    E]``, under the published rule (the K largest of ``sigmoid(logit) +
    bias``) with logits normal of ``logit_std`` a token and expert and a
    bias normal of ``bias_std`` an expert: the seeded draw's statistics,
    sampled once from a generator of this module's own."""
    rng = np.random.default_rng(20261002)
    out = np.zeros((layers, E))
    for i in range(layers):
        score = 1.0 / (1.0 + np.exp(-rng.normal(0.0, logit_std,
                                                (tokens, E)))) \
            + rng.normal(0.0, bias_std, E)[None]
        chosen = np.argpartition(-score, K - 1, axis=1)[:, :K]
        out[i] = np.bincount(chosen.reshape(-1), minlength=E) / tokens
    return out


def experts_touched(cfg: dict, tokens: float) -> float:
    """Distinct experts of one layer with a token, expected over a run
    of ``tokens`` tokens and over the seeded draws of a layer's router
    and bias (a unit-RMS input on weights of ``WEIGHT_STD`` gives logits
    of ``WEIGHT_STD sqrt(hidden)``)."""
    z = _sizes(cfg)
    p = _choice_shares(
        z["E"], z["K"], WEIGHT_STD * float(np.sqrt(z["H"])),
        float(cfg.get("router_bias_std", BIAS_STD)))
    return float(np.mean(np.sum(1.0 - (1.0 - p) ** float(tokens), axis=1)))


def _run_weight_bytes(cfg: dict, runs: int, tokens: float) -> float:
    """``runs`` runs of a program over ``tokens`` tokens in all."""
    if not runs:
        return 0.0
    z = _sizes(cfg)
    fixed = param_count(cfg) - z["V"] * z["H"] \
        - z["moe"] * z["E"] * _expert(z)     # no embedding, no routed expert
    return float(runs) * item_bytes(cfg, "param_dtype") * (
        fixed + z["moe"] * experts_touched(cfg, tokens / runs) * _expert(z))


def decode_flops(cfg: dict, contexts) -> float:
    """One new token per entry of ``contexts`` (the positions it attends
    to, itself included)."""
    z = _sizes(cfg)
    return 2.0 * matmul_params(cfg) * len(contexts) \
        + z["pair"] * z["L"] * float(sum(contexts))


def decode_bytes(cfg: dict, steps: int, contexts) -> float:
    """``steps`` decode steps that between them produced one token per
    entry of ``contexts``: the weights a step reads, each token's live
    latent rows once a layer, its new row once."""
    z = _sizes(cfg)
    row = z["ROW"] * item_bytes(cfg, "kv_dtype")
    return _run_weight_bytes(cfg, steps, len(contexts)) + row * z["L"] * (
        float(sum(contexts)) + len(contexts))


def prefill_flops(cfg: dict, lengths) -> float:
    """One prompt per entry of ``lengths`` (real tokens): every weight a
    token multiplies except the head, which only the last position
    needs; causal attention over n (n + 1) / 2 pairs a layer."""
    z = _sizes(cfg)
    body = matmul_params(cfg) - z["V"] * z["H"]
    return sum(2.0 * body * n + 2.0 * z["V"] * z["H"]
               + z["pair"] * z["L"] * (n * (n + 1) / 2.0) for n in lengths)


def prefill_bytes(cfg: dict, runs: int, lengths) -> float:
    """``runs`` runs of the prefill program (a chunk is a run) that
    between them took one prompt per entry of ``lengths``."""
    z = _sizes(cfg)
    row = z["ROW"] * item_bytes(cfg, "kv_dtype") * z["L"]
    return _run_weight_bytes(cfg, runs, float(sum(lengths))) \
        + 2.0 * row * float(sum(lengths))
