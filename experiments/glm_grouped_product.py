"""The experts' grouped product at a prefill chunk's size on the chip:
2,048 sorted rows over 64 groups of 2,048 x 1,536 (and 1,536 x 2,048)
bfloat16, the rows spread over the groups as GLM-4.7-Flash's seeded
router spreads them (``benchmark.counts.glm4_moe_lite._choice_shares``).
What ``parallel.moe.tiled_grouped_dot`` and its tile constants rest on
(PERF.md section 6, PR 32):

- ``ragged``: ``jax.lax.ragged_dot`` as it is, with a bfloat16 result,
  with gate and up fused into one product, with every group's rows
  padded to a multiple of 8, 16, 32 or 128; JAX's ``megablox.gmm`` kernel
  under five tilings; a dense batched product of 64 x 256 rows as a
  yardstick;
- ``tilings``: ``gmm`` under eight tilings for both shapes.

Run on the chip from the root of a checkout: ``PYTHONPATH=. python
experiments/glm_grouped_product.py ragged|tilings``; prints a line a
reading (ms a product, the best of three rounds of twenty) and one JSON
line of them all."""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm

from benchmark.counts import glm4_moe_lite as counts

E, M = 64, 2048
OUT = {}


def bench(name, fn, *args):
    try:
        f = jax.jit(fn)
        r = f(*args)
        jax.block_until_ready(r)
        best = 1e9
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(20):
                r = f(*args)
            jax.block_until_ready(r)
            best = min(best, (time.perf_counter() - t) / 20)
        OUT[name] = round(best * 1e3, 3)
    except Exception as e:                      # a tiling the kernel refuses
        OUT[name] = "ERR " + repr(e)[:200]
    print(name, OUT[name], flush=True)


def operands(K, N, key):
    w = (jax.random.normal(key, (E, K, N), jnp.float32) * 0.02
         ).astype(jnp.bfloat16)
    x = jax.random.normal(key, (M, K), jnp.float32).astype(jnp.bfloat16)
    return x, w


def ragged_dot(x, w, g, out=jnp.float32):
    return jax.lax.ragged_dot(x, w, g, preferred_element_type=out)


def ragged(shares, rng, key):
    K, N = 2048, 1536
    x, w = operands(K, N, key)
    _, w2 = operands(K, 2 * N, key)
    for layer in (0, 5):
        sizes = rng.multinomial(M, shares[layer] / shares[layer].sum()
                                ).astype(np.int32)
        gs = jnp.asarray(sizes)
        tag = f"L{layer}_max{sizes.max()}_nz{(sizes > 0).sum()}"
        bench(tag + "_ragged", ragged_dot, x, w, gs)
        bench(tag + "_ragged_bf16out",
              lambda x, w, g: ragged_dot(x, w, g, jnp.bfloat16), x, w, gs)
        bench(tag + "_ragged_fused2N", ragged_dot, x, w2, gs)
        for al in (8, 16, 32, 128):
            padded = (-(-sizes // al) * al).astype(np.int32)
            rows = M + E * al
            # what the rows hold does not enter the time
            xp = jnp.zeros((rows, K), jnp.bfloat16).at[:M].set(x)
            bench(tag + f"_ragged_align{al}_M{rows}", ragged_dot, xp, w,
                  jnp.asarray(padded))
        for tiling in ((128, 128, 128), (128, 512, 512), (256, 1024, 512),
                       (512, 1024, 768), (128, 2048, 512)):
            bench(tag + f"_gmm_{tiling}",
                  lambda x, w, g, t=tiling: gmm(x, w, g, jnp.float32, t),
                  x, w, gs)
        xb = jnp.zeros((E, 256, K), jnp.bfloat16)
        bench(tag + "_batched_dense_cap256",
              lambda xb, w: jnp.einsum("ecd,edf->ecf", xb, w,
                                       preferred_element_type=jnp.float32),
              xb, w)


def tilings(shares, rng, key):
    sizes = jnp.asarray(rng.multinomial(M, shares[0] / shares[0].sum()
                                        ).astype(np.int32))
    for K, N in ((2048, 1536), (1536, 2048)):
        x, w = operands(K, N, key)
        for t in ((128, K, 512), (128, K, 256), (128, K, N),
                  (128, K, 768 if N == 1536 else 1024), (256, K, 512),
                  (64, K, 512), (128, K // 2, 512), (512, K, 512)):
            bench(f"{K}x{N}_{t}",
                  lambda x, w, g, t=t: gmm(x, w, g, jnp.float32, t),
                  x, w, sizes)


if __name__ == "__main__":
    shares = counts._choice_shares(64, 4, 0.02 * np.sqrt(2048), 0.1)
    {"ragged": ragged, "tilings": tilings}[sys.argv[1]](
        shares, np.random.default_rng(0), jax.random.PRNGKey(0))
    print(json.dumps(OUT))
