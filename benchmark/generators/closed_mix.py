"""Closed-loop traffic of two kinds of request in one queue: chat pairs
from length distributions, as ``closed_loop`` draws them, and a few
fixed (prompt, output) pairs beside them (long documents).

One ROUND is ``round`` pairs: ``chat.pairs`` at the evenly spaced
quantiles of the chat distributions (``closed_loop.round_lengths``,
paired once from ``lengths_seed``) and every entry of ``documents``.
The list is ``ROUNDS`` rounds, each the same pairs in an order of its own
drawn from ``--seed``, with fresh token ids: what a window serves is the
same mix whatever the seed and however fast the program is.
"""
from __future__ import annotations

import numpy as np

from benchmark.generators.closed_loop import ROUNDS, round_lengths

MODE = "serve"


def round_pairs(params: dict):
    """The (prompt, output) lengths of one round, chat first."""
    chat = params["chat"]
    n = int(chat["pairs"])
    prompts = round_lengths(chat["prompt_len"], n)
    outputs = round_lengths(chat["output_len"], n)
    outputs = outputs[np.random.default_rng(
        int(params["lengths_seed"])).permutation(n)]
    pairs = [(int(p), int(o)) for p, o in zip(prompts, outputs)]
    pairs += [(int(d["prompt_len"]), int(d["output_len"]))
              for d in params["documents"]]
    if len(pairs) != int(params["round"]):
        raise ValueError(f"a round of {params['round']} pairs, "
                         f"{len(pairs)} given")
    return pairs


def generate(params: dict, cfg: dict, seed: int):
    """The requests of one run, in the order the clients take them:
    ``[{"prompt": int32[n], "max_new_tokens": m}, ...]``."""
    pairs = round_pairs(params)
    rng = np.random.default_rng(int(seed))
    vocab = int(cfg["vocab_size"])
    reqs = []
    for _ in range(ROUNDS):
        for i in rng.permutation(len(pairs)):
            n, m = pairs[i]
            reqs.append({"prompt": rng.integers(0, vocab, n)
                         .astype(np.int32), "max_new_tokens": m})
    return reqs
