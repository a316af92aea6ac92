"""A training job's batches: ``steps_per_fit`` batches of ``batch`` rows
of ``seq_len + 1`` token ids, uniform over the vocabulary, every row
different; inputs are a row's first ``seq_len`` ids and targets the ids
that follow them."""
from __future__ import annotations

import numpy as np

MODE = "train"


def generate(params: dict, cfg: dict, seed: int):
    """``(ids, targets)``, each int32 ``[steps_per_fit * batch, seq_len]``."""
    n = int(params["steps_per_fit"]) * int(params["batch"])
    rng = np.random.default_rng(int(seed))
    rows = rng.integers(0, int(cfg["vocab_size"]),
                        (n, int(params["seq_len"]) + 1)).astype(np.int32)
    return rows[:, :-1].copy(), rows[:, 1:].copy()
