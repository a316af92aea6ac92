"""The experts at a decode step's size on the chip, both ways, at each
MoE family's decode call: what ``parallel.moe.expert_stream_ffn``, its
tile and ``STREAM_TOKENS`` rest on (PERF.md section 6).

For each family (``st``: SmallThinker-21B-A3B, 8 lanes x 6 of 64;
``glm``: GLM-4.7-Flash, 8 x 4 of 64; ``cmda``: Command A+, 32 x 8 of
128 with 16 held; ``lfm2``: LFM2-8B-A1B, 64 x 4 of 32), the widths of
its file in ``benchmark/configs/`` in bfloat16, three layers' routings
drawn as the seeded router draws them (``benchmark.counts.*``: SmallThinker
and Command A+ choose evenly, GLM-4.7-Flash and LFM2-8B-A1B by
``sigmoid + bias`` of the seeded spreads):

- ``sorted``: the layer as it ran before (pairs sorted by expert,
  ``jax.lax.ragged_dot`` for gate, up and down), whole;
- ``stream_t<tile>``: the layer streamed (``expert_stream_ffn``) reading
  ``<tile>`` columns of an expert's width a grid step (the module's
  ``STREAM_TILE`` set to it, the VMEM it may fill unbounded), ``stream``
  at the tile the module chooses;
- ``gate`` and ``gate_pad<a>``: ``ragged_dot``'s gate product alone, on
  the sorted rows and on the same rows with every group padded to a
  multiple of ``a``: if ``ragged_dot`` reads a group again for every row
  tile the group straddles, the padded products read less per row.

Each reading is ms a call (the best of three rounds of twenty) and the
rate against the bytes of the experts that have a token (a product
alone: its one matrix of them), as a share of the v5e's 819 GB/s
(``benchmark/peaks.json``). Run on the chip from the root of a checkout:
``PYTHONPATH=. python experiments/expert_stream.py [family ...]``;
prints a line a reading and one JSON line of them all."""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.counts import glm4_moe_lite, lfm2_moe
from deeplearning4j_tpu.parallel import moe

HBM = 819e9
#: family: (config file, lanes, width key, routed-experts key, k key,
#: activation, the module of the seeded router's spreads or None for an
#: even choice)
FAMILIES = {
    "st": ("smallthinker-21b-a3b", 8, "moe_ffn_hidden_size",
           "moe_num_primary_experts", "moe_num_active_primary_experts",
           jax.nn.relu, None),
    "glm": ("glm-4.7-flash", 8, "moe_intermediate_size",
            "n_routed_experts", "num_experts_per_tok", jax.nn.silu,
            glm4_moe_lite),
    "cmda": ("command-a-plus-05-2026", 32, "intermediate_size",
             "router_experts", "num_experts_per_tok", jax.nn.silu, None),
    "lfm2": ("lfm2-8b-a1b", 64, "moe_intermediate_size", "num_experts",
             "num_experts_per_tok", jax.nn.silu, lfm2_moe),
}
OUT = {}


def bench(name, fn, args, nbytes):
    try:
        f = jax.jit(fn)
        jax.block_until_ready(f(*args))
        best = 1e9
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(20):
                r = f(*args)
            jax.block_until_ready(r)
            best = min(best, (time.perf_counter() - t) / 20)
        OUT[name] = {"ms": round(best * 1e3, 4),
                     "hbm_share": round(nbytes / best / HBM, 4)}
    except Exception as e:                      # a tiling the kernel refuses
        OUT[name] = "ERR " + repr(e)[:300]
    print(name, OUT[name], flush=True)


def route(rng, n, E, k, spreads, H):
    """``[n, k]`` distinct experts a token, as the family's seeded router
    spreads them."""
    if spreads is None:
        return np.argsort(rng.random((n, E)), axis=1)[:, :k]
    score = 1.0 / (1.0 + np.exp(-rng.normal(
        0.0, spreads.WEIGHT_STD * np.sqrt(H), (n, E)))) \
        + rng.normal(0.0, spreads.BIAS_STD, E)[None]
    return np.argsort(-score, axis=1)[:, :k]


def family(tag, rng, key):
    name, n, fk, ek, kk, act, spreads = FAMILIES[tag]
    with open(f"benchmark/configs/{name}.json") as fh:
        cfg = json.load(fh)
    H, F, E, K = (int(cfg[c]) for c in ("hidden_size", fk, ek, kk))
    held = int(cfg["num_experts"]) if tag == "cmda" else E
    ks = jax.random.split(key, 4)
    g, u = (jax.random.normal(k_, (held, H, F), jnp.bfloat16) * 0.02
            for k_ in ks[:2])
    d = jax.random.normal(ks[2], (held, F, H), jnp.bfloat16) * 0.02
    x = jax.random.normal(ks[3], (n, H), jnp.float32)
    expert = 3 * H * F * 2
    for layer in range(3):
        idx = route(rng, n, E, K, spreads, H)
        mine = idx < held                       # experts 0 .. held - 1
        flat = jnp.asarray(np.where(mine, idx, held).reshape(-1), jnp.int32)
        w = jnp.asarray(np.where(mine, rng.random((n, K)), 0.0), jnp.float32)
        tokens = np.bincount(np.asarray(flat), minlength=held + 1)[:held]
        touched = int((tokens > 0).sum())
        pre = f"{tag}_L{layer}_touched{touched}_rows{int(tokens.sum())}"
        OUT[pre] = {"touched": touched, "pairs": int(tokens.sum()),
                    "expert_bytes": expert}
        tk = jnp.asarray(tokens, jnp.int32)
        args = (x, flat, w, tk, g, u, d)
        bench(pre + "_sorted",
              lambda *a: moe._sorted_grouped(*a, act), args,
              touched * expert)
        chosen = (moe.STREAM_TILE, moe.STREAM_TILE_BYTES)
        for t in (128, 256, 512, 768, 896, 1024, None):
            if t is not None and F % t:
                continue
            moe.STREAM_TILE, moe.STREAM_TILE_BYTES = \
                chosen if t is None else (t, 1 << 40)
            bench(pre + (f"_stream_t{t}" if t else "_stream"),
                  lambda *a: moe._streamed(*a, act), args, touched * expert)
        moe.STREAM_TILE, moe.STREAM_TILE_BYTES = chosen
        order = np.argsort(np.asarray(flat), kind="stable")
        rows = x[jnp.asarray(order) // K].astype(jnp.bfloat16)
        bench(pre + "_gate", lambda r, s, g: jax.lax.ragged_dot(
            r, g, s, preferred_element_type=jnp.float32),
            (rows, tk, g), touched * expert // 3)
        for al in (8, 16, 128):
            pad = (-(-tokens // al) * al).astype(np.int32)
            m = int(pad.sum()) + n * K
            # what the rows hold does not enter the time
            padded = jnp.zeros((m, H), jnp.bfloat16)
            bench(pre + f"_gate_pad{al}_M{m}",
                  lambda r, s, g: jax.lax.ragged_dot(
                      r, g, s, preferred_element_type=jnp.float32),
                  (padded, jnp.asarray(pad), g), touched * expert // 3)
    del g, u, d


if __name__ == "__main__":
    print("device", jax.devices()[0].device_kind, flush=True)
    for i, tag in enumerate(sys.argv[1:] or FAMILIES):
        family(tag, np.random.default_rng(44 + i), jax.random.PRNGKey(i))
    print(json.dumps(OUT))
