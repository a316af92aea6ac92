"""Operations and bytes the Command A+ ALGORITHM (``cohere2_moe``) needs at
one chip's share of a layer, from a configuration file's shapes and
dtypes; ``counts/gpt2.py``'s signatures. Never what a program moves, and
no counter of the program enters:

- a token multiplies every layer's attention projections, its router
  (over all ``router_experts``), its shared experts and, of the
  ``num_experts_per_tok`` experts it chooses, those HELD here:
  ``num_experts_per_tok * num_experts / router_experts`` of them in
  expectation (one at the cell's 8 of 128 with 16 held); and the head,
  which is the embedding;
- a window layer attends to ``min(context, sliding_window)`` positions,
  a global layer to all of them;
- a run of a program reads every weight outside the routed experts once
  (the embedding as the head) and, in each layer, the HELD experts that
  have a token: :func:`experts_touched` for a run of ``n`` tokens, ``n``
  the mean over the span's runs, on the assumption that the seeded
  sigmoid router (no correction bias) chooses near evenly over all its
  experts, which ``tests/test_cohere2_moe.py`` holds against the
  reference's own router;
- K and V are read over the positions each layer attends to (decode),
  and a prompt's are written once and read once (prefill: the least any
  chunking can do).

Imports nothing of the program under test."""
from __future__ import annotations

from benchmark.counts.gpt2 import item_bytes


def _sizes(cfg: dict):
    L = int(cfg["num_hidden_layers"])
    E = int(cfg["num_experts"])
    return {"V": int(cfg["vocab_size"]), "H": int(cfg["hidden_size"]),
            "L": L, "AD": int(cfg["num_attention_heads"])
            * int(cfg["head_dim"]),
            "KD": int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]),
            "F": int(cfg["intermediate_size"]), "E": E,
            "ER": int(cfg.get("router_experts", E)),
            "K": int(cfg["num_experts_per_tok"]),
            "S": int(cfg["num_shared_experts"]),
            "W": int(cfg["sliding_window"]),
            "windows": sum(t == "sliding_attention"
                           for t in cfg["layer_types"][:L])}


def _expert(z) -> int:
    return 3 * z["H"] * z["F"]


def _layer_fixed(z) -> int:
    """A layer's weights outside its routed experts that multiply a
    token: attention, router, shared experts."""
    return z["H"] * (z["AD"] + 2 * z["KD"] + z["ER"]) + z["AD"] * z["H"] \
        + z["S"] * _expert(z)


def held_per_token(cfg: dict) -> float:
    """Routed experts a token multiplies here, in expectation."""
    z = _sizes(cfg)
    return z["K"] * z["E"] / z["ER"]


def param_count(cfg: dict) -> int:
    """Every stored parameter: the embedding (the head too), the final
    gain, and each layer's gain, attention, router, shared and held
    experts."""
    z = _sizes(cfg)
    return z["V"] * z["H"] + z["H"] + z["L"] * (
        z["H"] + _layer_fixed(z) + z["E"] * _expert(z))


def matmul_params(cfg: dict) -> float:
    """Weights that multiply every token (the held routed experts in
    expectation), and the head."""
    z = _sizes(cfg)
    return z["L"] * (_layer_fixed(z) + held_per_token(cfg) * _expert(z)) \
        + z["V"] * z["H"]


def experts_touched(cfg: dict, tokens: float) -> float:
    """Distinct HELD experts of one layer with a token, expected over a
    run of ``tokens`` tokens each choosing k of the router's experts
    evenly."""
    z = _sizes(cfg)
    return z["E"] * (1.0 - (1.0 - z["K"] / z["ER"]) ** float(tokens))


def _run_weight_bytes(cfg: dict, runs: int, tokens: float) -> float:
    """``runs`` runs of a program over ``tokens`` tokens in all."""
    if not runs:
        return 0.0
    z = _sizes(cfg)
    fixed = param_count(cfg) - z["L"] * z["E"] * _expert(z)
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
    entry of ``contexts``: the weights a step reads, each token's K and V
    at the positions it attends to, its new K and V once."""
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
    """One prompt per entry of ``lengths`` (real tokens): every weight a
    token multiplies except the head, which only the last position
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
