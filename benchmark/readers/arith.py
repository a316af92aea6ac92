"""Shares of the chip's peak, all from the device trace: the operations
and bytes the ALGORITHM needs (``benchmark.counts.<family>``, from the
configuration file) for the work of the traced span, over the device
time the trace gives it, over the peak of ``peaks.json``.

- ``bound: mfu``: the FLOPs of ALL the span's work over the seconds in
  which any operation ran on the device (``busy_s``): the whole step,
  whatever programs it is made of. Host idle time is not in it; the
  device's idle share is a metric of its own.
- ``bound: roofline``: ``max(FLOPs/peak, bytes/bandwidth)`` of one kind
  of work over the device time of the named XLA program's runs that lie
  wholly inside the span.

Serving work is counted from the request samples, by the token times
that fall in the span; the program's counters enter nothing here. The
span's edges cut through a step, so the tokens counted and the device
time can differ by one step's work: a hundredth of a span of a hundred
steps.
"""
from __future__ import annotations


def _decode_contexts(record, span):
    """Positions attended by each token a decode step produced in
    ``span``: token j >= 1 of a request attends its prompt and the j
    tokens before it."""
    return [r["prompt_len"] + j for r in record["requests"]
            for j, t in enumerate(r["token_t"])
            if j >= 1 and span[0] <= t <= span[1]]


def _prefill_lengths(record, span):
    return [r["prompt_len"] for r in record["requests"]
            if r["token_t"] and span[0] <= r["token_t"][0] <= span[1]]


def read(record, params):
    peaks, tr = record.get("peaks"), record.get("trace")
    if not peaks or not tr:
        return None
    counts, cfg = record["counts"], record["config"]
    work, bound = params["work"], params["bound"]
    flops = nbytes = 0.0
    if work == "train":
        t = record["train"]
        flops = counts.train_flops_per_token(cfg, t["seq_len"]) \
            * t["traced_tokens"]
    else:
        span = (tr["t_start"], tr["t_stop"])
        mod = tr["modules"].get(params.get("program"))
        if bound == "roofline" and not mod:
            return None
        # a program's weights are read once every time it runs
        runs = mod["runs"] if mod else 0
        if work in ("decode", "serve"):
            ctx = _decode_contexts(record, span)
            flops += counts.decode_flops(cfg, ctx)
            nbytes += counts.decode_bytes(cfg, runs, ctx)
        if work in ("prefill", "serve"):
            lens = _prefill_lengths(record, span)
            flops += counts.prefill_flops(cfg, lens)
            nbytes += counts.prefill_bytes(cfg, runs, lens)
    if not flops:
        return None
    if bound == "mfu":
        return 100.0 * flops / tr["busy_s"] / peaks["flops_per_s"]
    least = max(flops / peaks["flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / mod["seconds"]
