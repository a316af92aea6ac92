"""Operations and bytes the LFM2-8B-A1B ALGORITHM (``lfm2_moe``) needs, from
a configuration file's shapes and dtypes; ``counts/gpt2.py``'s signatures.
Never what a program moves, and no counter of the program enters:

- a token multiplies, on a conv layer, the convolution's two projections
  (``3 hidden`` and ``hidden`` columns), its ``conv_L_cache`` taps a
  channel and its two gates (``B * xt``, ``C * z``); on an attention
  layer the four projections; on a dense layer its three matrices, on an
  expert layer the router and the FOUR experts it chooses; and the head,
  which is the embedding;
- a (query, context position) pair of an attention layer costs ``4 heads
  head_dim``; a conv layer has no pair;
- a run of a program reads every weight outside the routed experts once
  (the embedding as the head) and, in each expert layer, the experts
  that have a token: :func:`experts_touched` for a run of ``n`` tokens,
  ``n`` the mean over the span's runs. The seeded router routes
  unevenly (its expert bias, std 0.1 against sigmoids that spread by
  0.2), so the figure is the expectation of the published rule under the
  seeded draw's own statistics, which ``tests/benchmarking/
  test_lfm2_counts.py`` holds against the reference's router;
- a live position's K and V are read once an attention layer and decode
  step, and a new token's written once; a prompt's are written once and
  read once (prefill: the least any chunking can do);
- a lane's state (``conv_L_cache - 1`` rows of ``hidden``, in
  ``kv_dtype``) is read once and written once a conv layer and decode
  step, and a prefill run reads and writes it once a conv layer.

Imports nothing of the program under test."""
from __future__ import annotations

import functools

import numpy as np

from benchmark.counts.gpt2 import item_bytes

#: the seeded draw's spreads (``reference/lfm2_moe.py`` ``STD`` and,
#: where the file names none, ``BIAS_STD``)
WEIGHT_STD, BIAS_STD = 0.02, 0.1


def _sizes(cfg: dict):
    L = int(cfg["num_hidden_layers"])
    H, A = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    D = H // A
    attn = sum(t == "full_attention" for t in cfg["layer_types"][:L])
    dense = int(cfg["num_dense_layers"])
    return {"V": int(cfg["vocab_size"]), "H": H, "L": L, "A": A, "D": D,
            "AD": A * D, "KD": int(cfg["num_key_value_heads"]) * D,
            "LC": int(cfg["conv_L_cache"]), "attn": attn, "conv": L - attn,
            "dense": dense, "moe": L - dense,
            "I": int(cfg["intermediate_size"]),
            "F": int(cfg["moe_intermediate_size"]),
            "E": int(cfg["num_experts"]),
            "K": int(cfg["num_experts_per_tok"])}


def _conv_mixer(z) -> int:
    """A conv layer's projections and taps."""
    return 4 * z["H"] * z["H"] + z["LC"] * z["H"]


def _attn_mixer(z) -> int:
    """An attention layer's projections and its two per-head gains."""
    return z["H"] * (z["AD"] + 2 * z["KD"]) + z["AD"] * z["H"] + 2 * z["D"]


def _expert(z) -> int:
    return 3 * z["H"] * z["F"]


def param_count(cfg: dict) -> int:
    """Every stored parameter: the embedding (the head too), the final
    gain, and each layer's two gains, mixer and feed-forward (a dense
    layer's three matrices, or the router, the expert bias and every
    expert)."""
    z = _sizes(cfg)
    return z["V"] * z["H"] + z["H"] + z["L"] * 2 * z["H"] \
        + z["conv"] * _conv_mixer(z) + z["attn"] * _attn_mixer(z) \
        + z["dense"] * 3 * z["H"] * z["I"] \
        + z["moe"] * (z["H"] * z["E"] + z["E"] + z["E"] * _expert(z))


def matmul_flops(cfg: dict) -> float:
    """FLOPs of one token outside attention's pairs: every product a
    token multiplies at 2 a weight (the conv's projections, attention's,
    a dense layer's, an expert layer's router and its K experts, the
    head), a conv layer's taps at 2 a tap and channel and its two gates at
    1 a channel each."""
    z = _sizes(cfg)
    H = z["H"]
    weights = z["conv"] * 4 * H * H \
        + z["attn"] * (H * (z["AD"] + 2 * z["KD"]) + z["AD"] * H) \
        + z["dense"] * 3 * H * z["I"] \
        + z["moe"] * (H * z["E"] + z["K"] * _expert(z)) + z["V"] * H
    return 2.0 * weights + z["conv"] * (2.0 * z["LC"] * H + 2.0 * H)


@functools.lru_cache(maxsize=None)
def _choice_shares(E: int, K: int, logit_std: float, bias_std: float,
                   layers: int = 32, tokens: int = 4096):
    """How often each expert of a layer is among a token's K, ``[layers,
    E]``, under the published rule (the K largest of ``sigmoid(logit) +
    bias``) with logits normal of ``logit_std`` a token and expert and a
    bias normal of ``bias_std`` an expert: the seeded draw's statistics,
    sampled once from a generator of this module's own."""
    rng = np.random.default_rng(20261016)
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
    fixed = param_count(cfg) - z["moe"] * z["E"] * _expert(z)
    return float(runs) * item_bytes(cfg, "param_dtype") * (
        fixed + z["moe"] * experts_touched(cfg, tokens / runs) * _expert(z))


def _state_bytes(z, cfg: dict) -> float:
    """One lane's state over the conv layers, once."""
    return (z["LC"] - 1) * z["H"] * item_bytes(cfg, "kv_dtype") * z["conv"]


def decode_flops(cfg: dict, contexts) -> float:
    """One new token per entry of ``contexts`` (the positions it attends
    to, itself included)."""
    z = _sizes(cfg)
    return matmul_flops(cfg) * len(contexts) \
        + 4.0 * z["AD"] * z["attn"] * float(sum(contexts))


def decode_bytes(cfg: dict, steps: int, contexts) -> float:
    """``steps`` decode steps that between them produced one token per
    entry of ``contexts``: the weights a step reads, each token's live K
    and V once an attention layer, its new K and V once, and its lane's
    state read and written once a conv layer."""
    z = _sizes(cfg)
    kv = 2.0 * z["KD"] * item_bytes(cfg, "kv_dtype") * z["attn"]
    return _run_weight_bytes(cfg, steps, len(contexts)) + kv * (
        float(sum(contexts)) + len(contexts)) \
        + 2.0 * _state_bytes(z, cfg) * len(contexts)


def prefill_flops(cfg: dict, lengths) -> float:
    """One prompt per entry of ``lengths`` (real tokens): every product a
    token multiplies except the head, which only the last position
    needs; causal attention over n (n + 1) / 2 pairs an attention
    layer."""
    z = _sizes(cfg)
    head = 2.0 * z["V"] * z["H"]
    return sum((matmul_flops(cfg) - head) * n + head
               + 4.0 * z["AD"] * z["attn"] * (n * (n + 1) / 2.0)
               for n in lengths)


def prefill_bytes(cfg: dict, runs: int, lengths) -> float:
    """``runs`` runs of the prefill program (a chunk is a run) that
    between them took one prompt per entry of ``lengths``: the weights a
    run reads, the prompts' K and V written once and read once, and each
    run's state read and written once."""
    z = _sizes(cfg)
    kv = 2.0 * z["KD"] * item_bytes(cfg, "kv_dtype") * z["attn"]
    return _run_weight_bytes(cfg, runs, float(sum(lengths))) \
        + 2.0 * kv * float(sum(lengths)) \
        + 2.0 * _state_bytes(z, cfg) * runs
