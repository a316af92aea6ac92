"""Operations and bytes the GPT-2 ALGORITHM needs, from a configuration
file's shapes and dtypes. Never what a program moves: a program that
copies its whole KV pool every step has the same count as one that does
not, so its share of the roofline can rise toward 100 and not past it.
Imports nothing of the program under test."""
from __future__ import annotations

_ITEM = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
         "float8": 1}


def _sizes(cfg: dict):
    H = int(cfg["n_embd"])
    return (int(cfg["vocab_size"]), int(cfg["n_positions"]), H,
            int(cfg["n_layer"]), int(cfg.get("n_inner") or 4 * H))


def param_count(cfg: dict) -> int:
    """Every stored parameter (tied head counted once)."""
    V, P, H, L, I = _sizes(cfg)
    per_layer = (H * 3 * H + 3 * H) + (H * H + H) + (H * I + I) \
        + (I * H + H) + 4 * H
    return V * H + P * H + L * per_layer + 2 * H


def matmul_params(cfg: dict) -> int:
    """Weights that multiply every token: the blocks' four matrices and
    the tied head. Embedding look-ups and biases do no multiplications
    worth counting."""
    V, _, H, L, I = _sizes(cfg)
    return L * (3 * H * H + H * H + 2 * H * I) + V * H


def item_bytes(cfg: dict, key: str) -> int:
    return _ITEM[str(cfg[key])]


def decode_flops(cfg: dict, contexts) -> float:
    """One new token per entry of ``contexts`` (the positions it attends
    to, itself included): 2 FLOPs per matmul weight, and per layer a
    score and a weighted sum over the live positions (4 H each)."""
    _, _, H, L, _ = _sizes(cfg)
    n = len(contexts)
    return 2.0 * matmul_params(cfg) * n + 4.0 * L * H * float(sum(contexts))


def decode_bytes(cfg: dict, steps: int, contexts) -> float:
    """``steps`` decode steps that between them produced one token per
    entry of ``contexts``: all weights once a step at the file's
    parameter dtype, each token's live K and V once, its new K and V
    once, at the file's KV dtype."""
    _, _, H, L, _ = _sizes(cfg)
    w = param_count(cfg) * item_bytes(cfg, "param_dtype")
    kv = item_bytes(cfg, "kv_dtype")
    return float(steps) * w + 2.0 * L * H * kv * (
        float(sum(contexts)) + len(contexts))


def prefill_flops(cfg: dict, lengths) -> float:
    """One prompt per entry of ``lengths`` (real tokens, not a bucket's
    padding): every matmul weight per token except the head, which only
    the last position needs; causal attention over n(n+1)/2 pairs."""
    V, _, H, L, _ = _sizes(cfg)
    body = matmul_params(cfg) - V * H
    return sum(2.0 * body * n + 2.0 * V * H
               + 4.0 * L * H * (n * (n + 1) / 2.0) for n in lengths)


def prefill_bytes(cfg: dict, runs: int, lengths) -> float:
    """``runs`` prefills that between them took one prompt per entry of
    ``lengths``: all weights once a run, each prompt's K and V written
    once."""
    _, _, H, L, _ = _sizes(cfg)
    w = param_count(cfg) * item_bytes(cfg, "param_dtype")
    kv = item_bytes(cfg, "kv_dtype")
    return float(runs) * w + 2.0 * L * H * kv * float(sum(lengths))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """6 N + 12 L H S: forward and backward over the matmul weights and
    full attention; recomputation under remat is not counted."""
    _, _, H, L, _ = _sizes(cfg)
    return 6.0 * matmul_params(cfg) + 12.0 * L * H * float(seq_len)
