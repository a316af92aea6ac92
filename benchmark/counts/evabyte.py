"""Operations and bytes the EvaByte ALGORITHM needs, from a
configuration file's shapes and dtypes; ``counts/gpt2.py``'s signatures.
Never what a program moves, and no counter of the program enters:

- a token at position ``p`` (``contexts`` gives ``p + 1``, the positions
  up to and with its own) attends in every layer to the ``p - W (p // W)
  + 1`` exact rows of its own window and to the ``(W / c) (p // W)``
  summary rows of the windows before it, never to ``p + 1`` rows and
  never to a table's width;
- a run of a program reads every weight that multiplies a token once,
  the head's first ``vocab`` columns alone (a server samples head 0);
- decode reads each attended row's K and V once and writes its new K
  and V and, where the token ends a chunk, the chunk's summary K and V,
  for which the chunk's ``c`` keys are multiplied by ``phi``;
- a prompt's rows and summaries are written once and each is read once
  (prefill: the least any chunking can do).

Imports nothing of the program under test."""
from __future__ import annotations

from benchmark.counts.gpt2 import item_bytes


def _sizes(cfg: dict):
    H, A = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"V": int(cfg["vocab_size"]), "H": H,
            "L": int(cfg["num_hidden_layers"]), "AD": H,
            "A": A, "D": H // A, "F": int(cfg["intermediate_size"]),
            "P": int(cfg["num_pred_heads"]),
            "c": int(cfg["chunk_size"]), "W": int(cfg["window_size"])}


def _layer(z) -> int:
    """A layer's weights that multiply a token."""
    return 4 * z["H"] * z["AD"] + 3 * z["H"] * z["F"]


def param_count(cfg: dict) -> int:
    """Every stored parameter (embedding, the whole untied head, norms,
    ``phi`` and ``mu``)."""
    z = _sizes(cfg)
    return z["V"] * z["H"] + z["H"] * z["P"] * z["V"] + z["H"] + z["L"] * (
        _layer(z) + 2 * z["H"] + 2 * z["A"] * z["D"])


def matmul_params(cfg: dict) -> int:
    """Weights that multiply every served token: the layers' and head
    0's columns."""
    z = _sizes(cfg)
    return z["L"] * _layer(z) + z["H"] * z["V"]


def rows_attended(cfg: dict, context: int) -> tuple:
    """``(exact rows, summary rows)`` a layer of the query that is the
    last of ``context`` positions."""
    z = _sizes(cfg)
    p = int(context) - 1
    turns = p // z["W"]
    return p - z["W"] * turns + 1, (z["W"] // z["c"]) * turns


def _run_weight_bytes(cfg: dict, runs: int) -> float:
    """``runs`` runs of a program: every weight but the embedding's table
    and the columns of heads 1 and up."""
    z = _sizes(cfg)
    return float(runs) * item_bytes(cfg, "param_dtype") * (
        param_count(cfg) - z["V"] * z["H"] - z["H"] * (z["P"] - 1) * z["V"])


def _ends_chunk(z, context: int) -> bool:
    return int(context) % z["c"] == 0


def decode_flops(cfg: dict, contexts) -> float:
    """One new token per entry of ``contexts`` (the positions up to and
    with its own)."""
    z = _sizes(cfg)
    rows = sum(sum(rows_attended(cfg, n)) for n in contexts)
    chunks = sum(_ends_chunk(z, n) for n in contexts)
    # a row: q.k and weight x v; a chunk: phi.k, and its keys and values
    # weighed
    return 2.0 * matmul_params(cfg) * len(contexts) \
        + z["L"] * (4.0 * z["AD"] * rows + 6.0 * z["AD"] * z["c"] * chunks)


def decode_bytes(cfg: dict, steps: int, contexts) -> float:
    """``steps`` decode steps that between them produced one token per
    entry of ``contexts``: the weights a step reads, each token's
    attended rows once, its new rows once, a finished chunk's rows and
    its summary once."""
    z = _sizes(cfg)
    kv = 2.0 * z["AD"] * item_bytes(cfg, "kv_dtype")
    rows = sum(sum(rows_attended(cfg, n)) + 1 for n in contexts)
    chunks = sum(_ends_chunk(z, n) for n in contexts)
    return _run_weight_bytes(cfg, steps) + z["L"] * kv * (rows + chunks)


def _prompt_rows(z, n: int) -> tuple:
    """``(query-exact pairs, query-summary pairs)`` a layer of a causal
    prompt of ``n``: whole windows and the tail."""
    W, per = z["W"], z["W"] // z["c"]
    full, tail = divmod(int(n), W)
    exact = full * W * (W + 1) / 2.0 + tail * (tail + 1) / 2.0
    far = per * (W * full * (full - 1) / 2.0 + tail * full)
    return exact, far


def prefill_flops(cfg: dict, lengths) -> float:
    """One prompt per entry of ``lengths`` (real tokens): every weight
    per token except the head, which only the last position needs."""
    z = _sizes(cfg)
    body = matmul_params(cfg) - z["H"] * z["V"]
    total = 0.0
    for n in lengths:
        exact, far = _prompt_rows(z, n)
        total += 2.0 * body * n + 2.0 * z["H"] * z["V"] + z["L"] * (
            4.0 * z["AD"] * (exact + far)
            + 6.0 * z["AD"] * z["c"] * (int(n) // z["c"]))
    return total


def prefill_bytes(cfg: dict, runs: int, lengths) -> float:
    """``runs`` runs of the prefill program (a chunk is a run) that
    between them took one prompt per entry of ``lengths``."""
    z = _sizes(cfg)
    kv = 2.0 * z["AD"] * item_bytes(cfg, "kv_dtype") * z["L"]
    rows = sum(int(n) + int(n) // z["c"] for n in lengths)
    return _run_weight_bytes(cfg, runs) + 2.0 * kv * rows
