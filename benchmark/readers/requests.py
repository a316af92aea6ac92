"""Metrics of the request samples: every request's submit time and the
time of each of its tokens, taken on the server's own thread. A tail is
the tail of ALL requests of the window and a mean is over ALL gaps,
finished requests or not."""
from __future__ import annotations

import numpy as np


def _in(window, t) -> bool:
    return window[0] <= t <= window[1]


def ttft_ms(record):
    w = record["window"]
    return [(r["token_t"][0] - r["submit_t"]) * 1e3
            for r in record["requests"]
            if r["token_t"] and _in(w, r["token_t"][0])]


def gaps_ms(record):
    """Every gap between consecutive tokens of one request whose later
    token fell in the window."""
    w = record["window"]
    return [(b - a) * 1e3 for r in record["requests"]
            for a, b in zip(r["token_t"], r["token_t"][1:]) if _in(w, b)]


def read(record, params):
    if "requests" not in record:
        return None
    values = ttft_ms(record) if params["stat"] == "ttft_ms" \
        else gaps_ms(record)
    if not values:
        return None
    if params["reduce"] == "mean":
        return float(np.mean(values))
    return float(np.percentile(values, float(params["q"])))
