"""Closed-loop request traffic: ``clients`` callers, each sending its
next request the moment its last one completes.

The traffic file gives each length as a lognormal with the mean its
``source`` reports. One ROUND is ``round`` (prompt, output) pairs: the
lengths at the evenly spaced quantiles of each distribution, scaled so
that the round's mean is the source's, paired once from
``lengths_seed``. The list is ``ROUNDS`` rounds, each the same pairs in
an order of its own drawn from ``--seed``, with fresh token ids. Any
stretch of the list is whole rounds and part of one, so what a window
serves is the same mix whatever the seed and however fast the program
is; a program that outruns the list goes round it again, which is more
of the same rounds.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

MODE = "serve"
#: rounds in a run's list
ROUNDS = 64


def round_lengths(spec: dict, n: int):
    """``n`` lengths at the ``(i + 1/2) / n`` quantiles of a lognormal
    with ``sigma``, scaled to the mean ``mean`` and held to
    ``[min, max]``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(float(spec["sigma"]) * z)
    x *= float(spec["mean"]) / x.mean()
    return np.clip(np.rint(x).astype(np.int64),
                   int(spec["min"]), int(spec["max"]))


def generate(params: dict, cfg: dict, seed: int):
    """The requests of one run, in the order the clients take them:
    ``[{"prompt": int32[n], "max_new_tokens": m}, ...]``."""
    n = int(params["round"])
    prompts = round_lengths(params["prompt_len"], n)
    outputs = round_lengths(params["output_len"], n)
    outputs = outputs[np.random.default_rng(
        int(params["lengths_seed"])).permutation(n)]
    rng = np.random.default_rng(int(seed))
    vocab = int(cfg["vocab_size"])
    reqs = []
    for _ in range(ROUNDS):
        for i in rng.permutation(n):
            reqs.append({
                "prompt": rng.integers(0, vocab, int(prompts[i]))
                .astype(np.int32),
                "max_new_tokens": int(outputs[i])})
    return reqs
