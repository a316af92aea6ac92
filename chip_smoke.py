"""chip_smoke.py — does the system still start on the chip?

Drives the two paths users pay for once, through the public entry
points, at the full width of ``zoo.gpt.GPT_MEDIUM`` (vocab 32768, hidden
1536, 16 layers, 12 heads, ffn 6144, ~510M parameters; random weights
from a seed, no network):

- *train leg*: ``build_gpt`` -> ``TrainingConfig(Adam(1e-4), bf16
  mixed)`` -> ``SameDiff.fit`` over a ``DeviceCachedIterator`` on the
  default listener-free tier, batch 16 x seq 512;
- *serve leg*, from the same graph: ``gpt_paged_spec`` ->
  ``PagedGenerativeServer(max_slots=8, block_size=16, max_seq_len=1024,
  warmup=True)`` answering six seeded requests through ``submit()``.

It refuses any backend but ``tpu`` (it never selects a platform itself),
checks what comes out (finite falling losses from ln(vocab), exact token
budgets, tokens in range, populated latency histograms, a prefix-cache
hit, ZERO programs compiled after warmup, the one-chip train step's
attention on the tiled kernel) and ends its stdout with two
JSON lines: the readings (per-leg wall and compile seconds, losses,
tokens, ``"claim": null``), then, LAST, the verdict the driver parses,
which holds exactly ``{"ok": true, "device": {"platform", "kind",
"count"}}`` as JAX reports the device. Any failed check or exception is
a non-zero exit with no verdict. One process holds the chip: nothing
here starts a child.

    python chip_smoke.py              # one chip (the driver's call)
    python chip_smoke.py --four-chip  # on a four-chip host, same process:
                                      # serve untrained and trained
                                      # weights at tp=1 and tp=2, train on
                                      # a 2x2 mesh; placement + agreement

The readings are smoke readings, not benchmark numbers (ROADMAP S1).
The legs are functions of the config and sizes so that
tests/test_chip_smoke.py drives the same code at GPT_TINY on the CPU;
this ``__main__`` path has no CPU or tiny-size switch.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time

import numpy as np

#: seconds to wait for ONE streamed token before calling the run hung
TOKEN_TIMEOUT_S = 300.0

#: two greedy streams over the same weights may part only where the
#: model itself is undecided: the top logit leads both candidates by
#: less than this share of the logits' standard deviation. Among 32768
#: Gaussian logits the second trails the first by about 0.2 std and a
#: token picked at random by about 4, so 0.05 passes a tie and nothing
#: else; how far two program shapes move a logit on the v5e is not
#: measured yet (PERF.md §7)
TIE_GAP_STD = 0.05

#: the sizes ``__main__`` runs GPT_MEDIUM at (ISSUE 21): six train steps
#: a fit, two fits; six requests, the last two sharing a 256-token prefix
TRAIN_KW = dict(batch=16, seq_len=512, steps=2, epochs=3)
SERVE_KW = dict(max_slots=8, block_size=16, max_seq_len=1024,
                prompt_lens=(16, 48, 200, 700, 300, 380),
                shared_prefix_len=256, max_new_tokens=32)

_T0 = time.perf_counter()


class SmokeFailure(RuntimeError):
    """A check on what the chip produced did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}",
          flush=True)


def device_stamp() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def hbm_peak_bytes() -> int:
    """Highest per-device allocator watermark so far (PJRT
    ``peak_bytes_in_use``; the live-array census where the backend has
    no counters, i.e. the CPU test)."""
    from deeplearning4j_tpu import memory
    return max((s.peak_bytes or s.bytes_in_use) for s in memory.snapshot())


def shard_devices(arr) -> set:
    """The distinct devices that hold a shard of ``arr``."""
    return {s.device for s in arr.addressable_shards}


def equal_prefix(a, b) -> int:
    """How many leading tokens two streams share."""
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


def divergence_gap(sd, cfg, prompt, a, b, max_seq_len: int):
    """Where two greedy streams for ``prompt`` over ``sd``'s weights
    part, and how undecided the model is there: the unbatched dense
    prefill (``GenerativeSpec.prefill``, the program ``greedy_decode``
    runs) scores the context both streams share, and the gap from its
    top logit down to the lower of the two candidates is given in units
    of the logits' standard deviation. ``None`` for identical streams.
    Token streams are chaotic after one flip, so this — not a count of
    equal tokens — says whether a difference is a near-tie or a defect."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ndarray.dtype import DataType
    from deeplearning4j_tpu.serving.batching import BucketSpec, pow2_buckets
    from deeplearning4j_tpu.zoo.gpt import gpt_generative_spec

    k = equal_prefix(a, b)
    if k == len(a) == len(b):
        return None
    spec = gpt_generative_spec(sd, cfg)
    context = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(a[:k], np.int32)])
    buckets = BucketSpec(pow2_buckets(max_seq_len,
                                      n_buckets=max_seq_len.bit_length()))
    padded = np.zeros(buckets.bucket_for(context.size), np.int32)
    padded[:context.size] = context
    slab = jnp.zeros(spec.kv_shape(1, max_seq_len),
                     DataType.from_any(spec.kv_dtype).jnp)
    logits = jax.jit(spec.prefill)(
        dict(spec.params()), slab, slab,
        {"tokens": padded, "length": np.int32(context.size),
         "slot": np.int32(0)})[3]
    logits = np.asarray(logits, np.float64).reshape(-1)
    gap = logits.max() - min(logits[a[k]], logits[b[k]])
    return {"at": k, "tokens": [int(a[k]), int(b[k])],
            "gap_over_std": round(float(gap / logits.std()), 5)}


# ----------------------------------------------------------------------
# train leg
def train_leg(cfg, batch: int, seq_len: int, steps: int, epochs: int,
              sharding=None, seed: int = 0):
    """``SameDiff.fit`` twice for ``epochs`` passes over ``steps``
    seeded batches: the first call compiles, the second is timed and
    must compile nothing. Returns
    ``(sd, report)``; raises :class:`SmokeFailure` when a loss is not
    finite, the first is not near ln(vocab), or the last is not below
    the first."""
    import jax

    from deeplearning4j_tpu.autodiff import MixedPrecision, TrainingConfig
    from deeplearning4j_tpu.compilecache import COMPILE_STATS
    from deeplearning4j_tpu.dataset import DeviceCachedIterator
    from deeplearning4j_tpu.learning.updaters import Adam
    from deeplearning4j_tpu.zoo.gpt import build_gpt

    t_leg = time.perf_counter()
    sd = build_gpt(cfg, batch=batch, seq_len=seq_len, seed=seed)
    sd.training_config = TrainingConfig(
        updater=Adam(1e-4),
        data_set_feature_mapping=["input_ids"],
        data_set_label_mapping=["targets"],
        mixed_precision=MixedPrecision(),
        sharding=sharding)
    rng = np.random.default_rng(seed)
    n = batch * steps
    ids = rng.integers(0, cfg.vocab_size, (n, seq_len)).astype(np.int32)
    tgt = rng.integers(0, cfg.vocab_size, (n, seq_len)).astype(np.int32)
    it = DeviceCachedIterator([ids], [tgt], batch_size=batch)
    say(f"train: graph built ({len(sd.trainable_params())} parameter "
        f"arrays), fitting {epochs} x {steps} steps (compiles)")

    mark = COMPILE_STATS.mark()
    t0 = time.perf_counter()
    first = sd.fit(it, epochs=epochs)
    jax.block_until_ready(list(sd.trainable_params().values()))
    first_fit_s = time.perf_counter() - t0
    compiles = COMPILE_STATS.delta(mark)
    tier = (sd.last_fit_stats or {}).get("tier")
    say(f"train: first fit {first_fit_s:.1f}s on tier {tier!r}, losses "
        f"{[round(l, 4) for l in first.loss_curve.losses]}")

    mark = COMPILE_STATS.mark()
    t0 = time.perf_counter()
    timed = sd.fit(it, epochs=epochs)
    jax.block_until_ready(list(sd.trainable_params().values()))
    timed_s = time.perf_counter() - t0
    timed_compiles = COMPILE_STATS.delta(mark)["backend_compiles"]

    losses = [float(l) for l in
              first.loss_curve.losses + timed.loss_curve.losses]
    ln_v = math.log(cfg.vocab_size)
    check(all(math.isfinite(l) for l in losses),
          f"train: non-finite loss in {losses}")
    check(abs(losses[0] - ln_v) < 1.0,
          f"train: first loss {losses[0]:.4f} is not near "
          f"ln(vocab)={ln_v:.4f}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall: {losses}")
    check(bool(tier), "train: last_fit_stats names no tier")
    check(timed_compiles == 0,
          f"train: the second fit of the same shapes compiled "
          f"{timed_compiles} program(s)")
    # which path each block's attention took when the step was traced
    # (monitor/attention.py): a step on one chip has to take the tiled
    # kernel, a step under a mesh cannot
    sites = sd.attention_sites
    check(sites is not None and sites.kernel + sites.plain == cfg.num_layers,
          f"train: the traced step counted attention sites "
          f"{sites and sites.to_json()}, the model has {cfg.num_layers}")
    say(f"train: attention sites on the tiled kernel {sites.kernel}, plain "
        f"{sites.plain} (first reason: {sites.first_reason})")
    check(sites.kernel > 0 or sharding is not None
          or jax.default_backend() != "tpu",
          f"train: the unsharded step took no attention site on the tiled "
          f"kernel: {sites.to_json()}")
    check(sites.kernel == 0 or sharding is None,
          f"train: a step under a mesh took the tiled kernel, which "
          f"cannot be partitioned: {sites.to_json()}")
    report = {
        "wall_s": round(time.perf_counter() - t_leg, 2),
        "tier": tier,
        "batch": batch, "seq_len": seq_len,
        "steps": 2 * epochs * steps,
        "losses_per_epoch": [round(l, 5) for l in losses],
        "first_loss": round(losses[0], 5),
        "last_loss": round(losses[-1], 5),
        "first_fit_s": round(first_fit_s, 2),
        "step_time_ms": round(1000.0 * timed_s / (epochs * steps), 2),
        "compile_s": round(compiles["backend_compile_seconds"], 2),
        "cache_hits": compiles["cache_hits"],
        "cache_misses": compiles["cache_misses"],
        "hbm_peak_bytes": hbm_peak_bytes(),
        "attention_sites": sites.to_json(),
    }
    say(f"train: step {report['step_time_ms']} ms, HBM peak "
        f"{report['hbm_peak_bytes'] / 2**30:.2f} GiB")
    return sd, report


# ----------------------------------------------------------------------
# serve leg
def make_requests(vocab_size: int, prompt_lens, shared_prefix_len: int,
                  max_new_tokens: int, seed: int = 0):
    """Seeded requests: one prompt per entry of ``prompt_lens``; the
    LAST TWO start with the same ``shared_prefix_len`` tokens; the
    second request samples (temperature 0.8, its own seed), the others
    decode greedily."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab_size, shared_prefix_len).astype(np.int32)
    reqs = []
    for i, n in enumerate(prompt_lens):
        prompt = rng.integers(0, vocab_size, n).astype(np.int32)
        if i >= len(prompt_lens) - 2:
            check(n > shared_prefix_len,
                  f"request {i}: prompt of {n} tokens cannot carry the "
                  f"{shared_prefix_len}-token shared prefix")
            prompt[:shared_prefix_len] = shared
        kw = {"temperature": 0.8, "seed": 1234 + i} if i == 1 else {}
        reqs.append({"prompt": prompt, "max_new_tokens": max_new_tokens,
                     **kw})
    return reqs


def serve_leg(sd, cfg, max_slots: int, block_size: int, max_seq_len: int,
              prompt_lens, shared_prefix_len: int, max_new_tokens: int,
              tp: int = 1, seed: int = 0):
    """Serve seeded requests from ``sd``'s weights through
    ``PagedGenerativeServer.submit`` and check everything that came
    back. Returns the report; raises :class:`SmokeFailure` on a wrong
    token count, an out-of-range token, an empty latency histogram, a
    missed prefix hit or ANY compile after warmup."""
    from deeplearning4j_tpu.compilecache import COMPILE_STATS
    from deeplearning4j_tpu.serving.generative import greedy_decode
    from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
    from deeplearning4j_tpu.zoo.gpt import (gpt_generative_spec,
                                            gpt_paged_spec)

    t_leg = time.perf_counter()
    reqs = make_requests(cfg.vocab_size, prompt_lens, shared_prefix_len,
                         max_new_tokens, seed=seed)
    say(f"serve: warming PagedGenerativeServer (tp={tp}, {max_slots} "
        f"slots, block {block_size}, max_seq_len {max_seq_len})")
    mark = COMPILE_STATS.mark()
    srv = PagedGenerativeServer(gpt_paged_spec(sd, cfg),
                                max_slots=max_slots, block_size=block_size,
                                max_seq_len=max_seq_len, tp=tp, warmup=True)
    try:
        warm = COMPILE_STATS.delta(mark)
        warmup_s = time.perf_counter() - t_leg
        say(f"serve: warmup {warmup_s:.1f}s, "
            f"{srv.metrics.counters['warmup_compiles']} programs "
            f"(cache hits {warm['cache_hits']}, misses "
            f"{warm['cache_misses']})")
        kv_devices = len(set().union(
            *(shard_devices(leaf) for leaf in srv._kc + srv._vc)))

        mark = COMPILE_STATS.mark()
        t0 = time.perf_counter()
        handles = [srv.submit(**r) for r in reqs]
        outs = [list(h.tokens(timeout=TOKEN_TIMEOUT_S)) for h in handles]
        traffic_s = time.perf_counter() - t0
        traffic = COMPILE_STATS.delta(mark)
        counters = dict(srv.metrics.counters)
        lat = srv.metrics.to_record()["latency_ms"]
    finally:
        srv.shutdown(drain=False)

    for i, (r, out) in enumerate(zip(reqs, outs)):
        check(len(out) == r["max_new_tokens"],
              f"serve: request {i} delivered {len(out)} tokens, budget "
              f"{r['max_new_tokens']}")
        check(all(0 <= t < cfg.vocab_size for t in out),
              f"serve: request {i} produced a token outside "
              f"[0, {cfg.vocab_size})")
    delivered = sum(len(o) for o in outs)
    check(lat["ttft"]["count"] == len(reqs),
          f"serve: TTFT histogram holds {lat['ttft']['count']} samples "
          f"for {len(reqs)} requests")
    check(lat["intertoken"]["count"] == delivered - len(reqs),
          f"serve: inter-token histogram holds "
          f"{lat['intertoken']['count']} samples for {delivered} tokens")
    want_blocks = shared_prefix_len // block_size
    check(counters["prefix_hits"] >= 1
          and counters["prefix_blocks_hit"] >= want_blocks,
          f"serve: the shared {shared_prefix_len}-token prefix did not "
          f"hit the cache (hits {counters['prefix_hits']}, blocks "
          f"{counters['prefix_blocks_hit']}, want >= {want_blocks})")
    check(counters["compiles"] == 0 and traffic["backend_compiles"] == 0,
          f"serve: compiled under traffic after warmup — server counted "
          f"{counters['compiles']} new shapes, the compile watcher "
          f"{traffic['backend_compiles']} backend compiles")
    say(f"serve: {delivered} tokens in {traffic_s:.2f}s, TTFT p50 "
        f"{lat['ttft']['p50']:.1f} ms, 0 compiles under traffic")

    # the README's bit-identity claim against the chip's default matmul
    # precision: reported, not gated (first greedy request, unbatched
    # dense-slab reference; compiles its own two programs, which is why
    # it runs after the zero-compile window closed)
    ref = greedy_decode(gpt_generative_spec(sd, cfg), reqs[0]["prompt"],
                        max_new_tokens, max_seq_len=max_seq_len)
    agree = equal_prefix(ref, outs[0])
    parted = divergence_gap(sd, cfg, reqs[0]["prompt"], ref, outs[0],
                            max_seq_len)
    say(f"serve: greedy tokens vs greedy_decode reference: the first "
        f"{agree} of {len(ref)} equal"
        + (f"; they part at {parted}" if parted else ""))
    return {
        "wall_s": round(time.perf_counter() - t_leg, 2),
        "tp": tp,
        "kv_slab_devices": kv_devices,
        "warmup_s": round(warmup_s, 2),
        "warmup_programs": counters["warmup_compiles"],
        "compile_s": round(warm["backend_compile_seconds"], 2),
        "cache_hits": warm["cache_hits"],
        "cache_misses": warm["cache_misses"],
        "requests": len(reqs),
        "prompt_lens": [int(n) for n in prompt_lens],
        "tokens_delivered": delivered,
        "traffic_s": round(traffic_s, 3),
        # the server's histograms are log-bucketed (21% wide): a p50 is
        # the upper edge of the median's bucket, the mean is exact
        "ttft_p50_ms": round(lat["ttft"]["p50"], 2),
        "ttft_mean_ms": round(lat["ttft"]["mean"], 2),
        "intertoken_p50_ms": round(lat["intertoken"]["p50"], 2),
        "intertoken_mean_ms": round(lat["intertoken"]["mean"], 2),
        "prefix_blocks_hit": counters["prefix_blocks_hit"],
        "compiles_after_warmup": counters["compiles"]
        + traffic["backend_compiles"],
        "greedy_matches_reference": agree == len(ref),
        "greedy_tokens_equal": f"{agree}/{len(ref)}",
        "reference_tokens": [int(t) for t in ref],
        "reference_divergence": parted,
        "tokens": [[int(t) for t in out] for out in outs],
        "sampled_requests": [i for i, r in enumerate(reqs)
                             if r.get("temperature")],
        "hbm_peak_bytes": hbm_peak_bytes(),
    }


# ----------------------------------------------------------------------
# four chips, one process
def same_greedy_streams(sd, cfg, serve_kw: dict, s1: dict, s2: dict,
                        what: str) -> dict:
    """Gate on two serve reports over ``sd``'s weights (tp=1 and tp=2):
    every greedy stream of ``s2`` equals its twin in ``s1`` token for
    token, or parts from it at a near-tie of the model's own logits
    (:func:`divergence_gap` under ``TIE_GAP_STD``). Identity is not
    owed: tp=2 sums the row-parallel matmuls in another order, and on
    the v5e two of five twins parted with untrained weights at depth 4
    (PR 21; flipped near-ties are the suspect, PERF.md §6). A layout
    that garbles a head parts everywhere, far from a tie. Sampled streams are reported only: a draw moves with the
    last bits of the logits. Returns, per request, the equal leading
    tokens and where the twins parted."""
    reqs = make_requests(cfg.vocab_size, serve_kw["prompt_lens"],
                         serve_kw["shared_prefix_len"],
                         serve_kw["max_new_tokens"])
    out, bad = {}, {}
    for i, (a, b) in enumerate(zip(s1["tokens"], s2["tokens"])):
        out[i] = {"equal": equal_prefix(a, b), "parted": None}
        if i in s1["sampled_requests"]:
            continue
        parted = out[i]["parted"] = divergence_gap(
            sd, cfg, reqs[i]["prompt"], a, b, serve_kw["max_seq_len"])
        if parted and parted["gap_over_std"] > TIE_GAP_STD:
            bad[i] = parted
    say(f"four-chip: {what}: tp=2 against tp=1 per request: {out}")
    check(not bad,
          f"four-chip: {what}: tp=2 greedy streams leave their tp=1 twins "
          f"on the same weights away from any tie (logit gap over "
          f"{TIE_GAP_STD} std): {bad}")
    return out


def four_chip(cfg, train_kw: dict, serve_kw: dict) -> dict:
    """Both legs again on a four-chip host, in this one process. One set
    of weights is served at tp=1 and at tp=2 (two layouts — greedy
    streams must be identical or part at a near-tie), twice: first the
    seeded UNTRAINED weights, whose streams differ prompt by prompt
    (gated, so a server that ignored its prompt fails), then the
    one-chip train leg's, which a dozen steps have driven to
    near-constant output. Then the train leg on a 2x2 data x model mesh
    (losses must match the one-chip leg). Checks from
    ``addressable_shards`` that nothing collapsed onto device 0."""
    import jax

    from deeplearning4j_tpu.parallel import ShardingSpec
    from deeplearning4j_tpu.zoo.gpt import build_gpt

    check(len(jax.devices()) >= 4,
          f"--four-chip needs 4 devices, JAX reports {len(jax.devices())}")
    sd0 = build_gpt(cfg, batch=train_kw["batch"],
                    seq_len=train_kw["seq_len"], seed=0)
    u1 = serve_leg(sd0, cfg, tp=1, **serve_kw)
    u2 = serve_leg(sd0, cfg, tp=2, **serve_kw)
    u_agree = same_greedy_streams(sd0, cfg, serve_kw, u1, u2,
                                  "untrained weights")
    del sd0
    gc.collect()
    greedy = [i for i in range(len(u1["tokens"]))
              if i not in u1["sampled_requests"]]
    check(len({tuple(u1["tokens"][i]) for i in greedy}) == len(greedy),
          f"four-chip: untrained weights answered different prompts with "
          f"the same greedy stream: {[u1['tokens'][i] for i in greedy]}")

    sd1, t1 = train_leg(cfg, **train_kw)
    s1 = serve_leg(sd1, cfg, tp=1, **serve_kw)
    s2 = serve_leg(sd1, cfg, tp=2, **serve_kw)
    s_agree = same_greedy_streams(sd1, cfg, serve_kw, s1, s2,
                                  "trained weights")
    del sd1
    gc.collect()        # device 0 gets its 6 GB of weights and Adam back
    for s in (u2, s2):
        check(s["kv_slab_devices"] == 2,
              f"four-chip: KV slabs on {s['kv_slab_devices']} devices, "
              f"want 2")

    spec = ShardingSpec(axes={"data": 2, "model": 2}, preset="transformer")
    sd4, t4 = train_leg(cfg, sharding=spec, **train_kw)
    placed = {n: len(shard_devices(a))
              for n, a in sd4.trainable_params().items()}
    check(all(k == 4 for k in placed.values()),
          f"four-chip: parameters not on 4 devices: "
          f"{ {n: k for n, k in placed.items() if k != 4} }")
    qkv = sd4.trainable_params()["h0/attn/qkv/kernel"]
    check(qkv.addressable_shards[0].data.shape[1] * 2 == qkv.shape[1],
          "four-chip: h0/attn/qkv/kernel is not split over the model axis")
    check(np.allclose(t4["losses_per_epoch"], t1["losses_per_epoch"],
                      rtol=2e-2),
          f"four-chip: sharded losses {t4['losses_per_epoch']} != "
          f"one-chip losses {t1['losses_per_epoch']}")
    return {"one_chip": {"train": t1, "serve": s1, "serve_untrained": u1},
            "four_chip": {"train_2x2": t4, "serve_tp2": s2,
                          "serve_tp2_untrained": u2,
                          "param_arrays_on_4_devices": len(placed),
                          "tp2_vs_tp1": s_agree,
                          "tp2_vs_tp1_untrained": u_agree}}


# ----------------------------------------------------------------------
def finish(stamp: dict, cfg, result: dict) -> None:
    """The end of a run in which every check held: the readings on one
    JSON line, then, as the LAST line of stdout, the verdict with
    exactly the keys the driver's contract names and no others."""
    from deeplearning4j_tpu.compilecache import COMPILE_STATS, cache_dir

    totals = COMPILE_STATS.snapshot()
    print(json.dumps({
        "device": stamp,
        "model": {"name": "gpt_medium", **dataclasses.asdict(cfg)},
        "wall_s": round(time.perf_counter() - _T0, 2),
        "compile": {"backend_compiles": int(totals["backend_compiles"]),
                    "cache_hits": int(totals["cache_hits"]),
                    "cache_misses": int(totals["cache_misses"]),
                    "backend_compile_s":
                        round(totals["backend_compile_seconds"], 2),
                    "cache_dir": cache_dir()},
        **result, "claim": None}))
    print(json.dumps({"ok": True, "device": stamp}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="also run both legs sharded over a four-chip "
                         "host (2x2 mesh, tp=2) and compare")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    stamp = device_stamp()
    say(f"jax {jax.__version__}, backend {backend}, device_kind "
        f"{stamp['kind']!r}, {stamp['count']} device(s)")
    if backend != "tpu":
        print(f"chip_smoke: refusing to run on backend {backend!r}: this "
              f"smoke measures the TPU and has no CPU fallback",
              file=sys.stderr)
        return 2

    from deeplearning4j_tpu import memory
    from deeplearning4j_tpu.compilecache import (cache_dir,
                                                 install_compile_watcher)
    from deeplearning4j_tpu.environment import environment
    from deeplearning4j_tpu.monitor import memstats
    from deeplearning4j_tpu.zoo.gpt import GPT_MEDIUM

    for row in memory.snapshot():
        check(row.source == "pjrt" and row.bytes_limit > 0,
              f"memory.snapshot() row {row} does not come from PJRT "
              f"counters with a bytes_limit")
    check(memstats.peak_flops() is not None,
          f"device kind {stamp['kind']!r} is not in the peak-rate table "
          f"(monitor/memstats.py)")
    environment().apply_compilation_cache()
    install_compile_watcher()
    say(f"compile cache at {cache_dir()}")

    cfg = GPT_MEDIUM
    if args.four_chip:
        result = four_chip(cfg, TRAIN_KW, SERVE_KW)
    else:
        sd, train = train_leg(cfg, **TRAIN_KW)
        result = {"train": train,
                  "serve": serve_leg(sd, cfg, **SERVE_KW)}
    finish(stamp, cfg, result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
