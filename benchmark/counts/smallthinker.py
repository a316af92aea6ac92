"""Operations and bytes the SmallThinker ALGORITHM needs, from a
configuration file's shapes and dtypes; ``counts/gpt2.py``'s signatures.
Never what a program moves, and no counter of the program enters:

- a token multiplies by the SIX routed experts' weights, never by 64;
- a window layer attends to ``min(context, window)`` positions;
- a run of a program reads every non-expert weight once and, in each
  layer, the experts that have a token: ``E (1 - (1 - k/E)^n)`` of them
  for a run of ``n`` tokens (:func:`experts_touched`), on the assumption
  that seeded weights route near evenly, which
  ``tests/benchmarking/test_smallthinker_counts.py`` holds against the
  reference's own router; ``n`` is the mean over the span's runs;
- K and V are read over the live positions of each tier (decode), and a
  prompt's are written once and read once (prefill: the least any
  chunking can do).

Imports nothing of the program under test."""
from __future__ import annotations

from benchmark.counts.gpt2 import item_bytes


def _sizes(cfg: dict):
    L = int(cfg["num_hidden_layers"])
    return {"V": int(cfg["vocab_size"]), "H": int(cfg["hidden_size"]),
            "L": L, "AD": int(cfg["num_attention_heads"])
            * int(cfg["head_dim"]),
            "KD": int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]),
            "F": int(cfg["moe_ffn_hidden_size"]),
            "E": int(cfg["moe_num_primary_experts"]),
            "K": int(cfg["moe_num_active_primary_experts"]),
            "W": int(cfg["sliding_window_size"]),
            "windows": sum(int(w) for w in
                           cfg["sliding_window_layout"][:L])}


def _layer_dense(z) -> int:
    """A layer's weights outside its experts that multiply a token."""
    return z["H"] * (z["AD"] + 2 * z["KD"] + z["E"]) + z["AD"] * z["H"]


def _expert(z) -> int:
    return 3 * z["H"] * z["F"]


def param_count(cfg: dict) -> int:
    """Every stored parameter (embedding and untied head both)."""
    z = _sizes(cfg)
    return 2 * z["V"] * z["H"] + z["H"] + z["L"] * (
        _layer_dense(z) + 2 * z["H"] + z["E"] * _expert(z))


def matmul_params(cfg: dict) -> int:
    """Weights that multiply every token: a layer's projections, its
    router and its six routed experts, and the head."""
    z = _sizes(cfg)
    return z["L"] * (_layer_dense(z) + z["K"] * _expert(z)) \
        + z["V"] * z["H"]


def experts_touched(cfg: dict, tokens: float) -> float:
    """Distinct experts of one layer with a token, expected over a run
    of ``tokens`` tokens each choosing k of E evenly."""
    z = _sizes(cfg)
    return z["E"] * (1.0 - (1.0 - z["K"] / z["E"]) ** float(tokens))


def _run_weight_bytes(cfg: dict, runs: int, tokens: float) -> float:
    """``runs`` runs of a program over ``tokens`` tokens in all."""
    if not runs:
        return 0.0
    z = _sizes(cfg)
    fixed = param_count(cfg) - z["V"] * z["H"] \
        - z["L"] * z["E"] * _expert(z)           # no embedding, no expert
    return float(runs) * item_bytes(cfg, "param_dtype") * (
        fixed + z["L"] * experts_touched(cfg, tokens / runs) * _expert(z))


def _attended(z, context: float) -> float:
    """Positions one query with ``context`` positions before and at it
    attends to, summed over the layers."""
    return (z["L"] - z["windows"]) * context \
        + z["windows"] * min(context, z["W"])


def decode_flops(cfg: dict, contexts) -> float:
    """One new token per entry of ``contexts`` (the positions it attends
    to, itself included)."""
    z = _sizes(cfg)
    return 2.0 * matmul_params(cfg) * len(contexts) \
        + 4.0 * z["AD"] * sum(_attended(z, c) for c in contexts)


def decode_bytes(cfg: dict, steps: int, contexts) -> float:
    """``steps`` decode steps that between them produced one token per
    entry of ``contexts``: the weights a step reads, each token's live K
    and V of each tier once, its new K and V once."""
    z = _sizes(cfg)
    kv = 2.0 * z["KD"] * item_bytes(cfg, "kv_dtype")
    return _run_weight_bytes(cfg, steps, len(contexts)) + kv * sum(
        _attended(z, c) + z["L"] for c in contexts)


def _pairs(n: float, cap: float) -> float:
    """Query-key pairs of a causal prompt of ``n`` whose queries see at
    most ``cap`` positions."""
    if n <= cap:
        return n * (n + 1) / 2.0
    return cap * (cap + 1) / 2.0 + (n - cap) * cap


def prefill_flops(cfg: dict, lengths) -> float:
    """One prompt per entry of ``lengths`` (real tokens): every routed
    weight per token except the head, which only the last position
    needs; causal attention, windowed on the window layers."""
    z = _sizes(cfg)
    body = matmul_params(cfg) - z["V"] * z["H"]
    return sum(2.0 * body * n + 2.0 * z["V"] * z["H"] + 4.0 * z["AD"] * (
        (z["L"] - z["windows"]) * _pairs(n, n)
        + z["windows"] * _pairs(n, z["W"])) for n in lengths)


def prefill_bytes(cfg: dict, runs: int, lengths) -> float:
    """``runs`` runs of the prefill program (a chunk is a run) that
    between them took one prompt per entry of ``lengths``."""
    z = _sizes(cfg)
    kv = 2.0 * z["KD"] * item_bytes(cfg, "kv_dtype") * z["L"]
    return _run_weight_bytes(cfg, runs, float(sum(lengths))) \
        + 2.0 * kv * float(sum(lengths))
