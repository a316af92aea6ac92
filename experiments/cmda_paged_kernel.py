"""Command A+'s decode attention alone, at the cell's shapes, on the chip:
the repo's paged decode kernel (``zoo.paged_attend.paged_decode``) at
several blocks of pages against JAX's shipped ragged paged-attention
kernel over the same leaf (which the chip's compiler first copies into
the kernel's own layout, whole) and against the plain path both replace
(gather the table, scores, softmax, weighted sums), over one global
table of 672 entries and one window ring of 257, 32 lanes of 128 query
heads over 8 K/V heads of 128, blocks of 16 rows in bf16, the pool sized
as the cell's server sizes it. The lanes stand where the cell's mix puts
them: two documents (10,200 and 5,400 positions) and 30 chat lanes
spread over 20-1,500. Prints one JSON line: each way's milliseconds a
call (the median of 30 after a warm call) and its largest gap from a
float32 reference computed from the same rows, in deviations of that
reference. Run on the chip from the root of a checkout: ``PYTHONPATH=.
python experiments/cmda_paged_kernel.py``."""
import json
import statistics
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.zoo import paged_attend

S, A, KV, D, BS, W = 32, 128, 8, 128, 16, 4096
GLOBAL, RING = 672, 257


def lanes(rng):
    pos = np.concatenate([[10200, 5400], rng.integers(20, 1500, S - 2)])
    return pos.astype(np.int32)


def tables(rng, pos, num_blocks, entries, ring):
    """Every lane's blocks, distinct over the pool, where the server puts
    them: block ``u`` in entry ``u`` (``u % entries`` in a ring; a ring
    holds only what the window still reads)."""
    free = rng.permutation(np.arange(1, num_blocks))
    t = np.zeros((S, entries), np.int32)
    at = 0
    for r, p in enumerate(pos):
        last = p // BS
        lo = max(0, p - W + 1) // BS if ring else 0
        for u in range(lo, last + 1):
            t[r, u % entries if ring else u] = free[at]
            at += 1
    return t


def plain(q, leaf, pages, rows, window):
    """The reference: every page gathered, float32 scores and sums."""
    kv = leaf[pages].reshape(S, -1, KV, 2, D).astype(jnp.float32)
    k, v = kv[:, :, :, 0], kv[:, :, :, 1]
    qg = q.reshape(S, KV, A // KV, D).astype(jnp.float32)
    s = jnp.einsum("rhgd,rthd->rhgt", qg, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(D)
    j = jnp.arange(k.shape[1])
    see = j[None] < rows[:, None]
    if window:
        see &= j[None] > rows[:, None] - 1 - window
    s = jnp.where(see[:, None, None], s, -1e30)
    o = jnp.einsum("rhgt,rthd->rhgd", jax.nn.softmax(s, axis=-1), v,
                   precision=jax.lax.Precision.HIGHEST)
    return o.reshape(S, A, D)


def gathered(q, leaf, table, pos, window):
    """The parent's decode read: the whole table gathered, K and V
    split, bf16 products into float32, masks by position."""
    E = table.shape[1]
    kv = leaf[table].reshape(S, E * BS, KV, 2, D)
    k, v = kv[:, :, :, 0], kv[:, :, :, 1]
    qg = q.reshape(S, KV, A // KV, D)
    s = jnp.einsum("rhgd,rthd->rhgt", qg, k,
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    e = jnp.arange(E)
    last = (pos // BS)[:, None]
    u = last - jnp.mod(last - e[None], E)
    kpos = (u[:, :, None] * BS + jnp.arange(BS)).reshape(S, -1)
    see = (kpos >= 0) & (kpos <= pos[:, None])
    if window:
        see &= kpos > pos[:, None] - window
    s = jnp.where(see[:, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("rhgt,rthd->rhgd", w, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(S, A, D).astype(q.dtype)


def paged_attend_shipped(q, leaf, pages, rows, window):
    """JAX's shipped kernel over the same pages: its ``kv_pages`` is the
    leaf as ``[pages, 16, 16, 128]``."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import \
        ragged_paged_attention
    return ragged_paged_attention(
        q, leaf.reshape(leaf.shape[0], BS, 2 * KV, D), rows, pages,
        jnp.arange(S + 1, dtype=jnp.int32), jnp.full((1,), S, jnp.int32),
        sm_scale=float(1 / np.sqrt(D)), sliding_window=window,
        num_kv_pages_per_block=16, num_queries_per_block=1)


def timed(fn, *args, n=30):
    jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append(1e3 * (time.perf_counter() - t))
    return statistics.median(ms)


def main() -> None:
    if jax.default_backend() != "tpu":
        sys.exit("the kernel is a TPU program: run this on the chip")
    rng = np.random.default_rng(20261015)
    pos = lanes(rng)
    out = {"device": jax.devices()[0].device_kind,
           "held_pages": {}, "ms": {}, "gap": {}}
    key = jax.random.key(7)
    q = jax.random.normal(key, (S, A, D), jnp.bfloat16)
    active = jnp.ones(S, bool)
    for name, entries, window in (("global", GLOBAL, None),
                                  ("window", RING, W)):
        nb = S * (GLOBAL if window is None else 289) + 1
        leaf = jax.random.normal(jax.random.fold_in(key, entries),
                                 (nb, BS, 2 * KV * D), jnp.bfloat16)
        table = jnp.asarray(tables(rng, pos, nb, entries, window))
        posj = jnp.asarray(pos)
        wb = table[jnp.arange(S), (posj // BS) % entries]
        pages, rows = jax.jit(paged_attend.decode_pages,
                              static_argnums=(4, 5))(
            table, posj, active, wb, BS, window is not None)
        out["held_pages"][name] = int(jnp.sum(-(-rows // BS)))
        want = jax.jit(plain, static_argnums=(4,))(q, leaf, pages, rows,
                                                   window)
        ways = {"gathered": jax.jit(
            lambda q, lf, t, p, w=window: gathered(q, lf, t, p, w))}
        ways["shipped"] = jax.jit(
            lambda q, lf, pg, r, w=window: paged_attend_shipped(
                q, lf, pg, r, w))
        reach = jnp.asarray([window or 2 ** 30], jnp.int32)
        for bkv in (8, 16, 32, 64):
            ways[f"repo_{bkv}"] = jax.jit(
                lambda q, lf, pg, r, bkv=bkv, reach=reach:
                paged_attend.paged_kernel()(
                    q, lf, pg, r, reach, scale=float(1 / np.sqrt(D)),
                    per_block=bkv))
        for way, fn in ways.items():
            args = (q, leaf, table, posj) if way == "gathered" \
                else (q, leaf, pages, rows)
            try:
                got = fn(*args)
                out["gap"].setdefault(way, {})[name] = float(
                    jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                    / jnp.std(want))
                out["ms"].setdefault(way, {})[name] = timed(fn, *args)
            except Exception as e:          # a tuning the chip refuses
                out["ms"].setdefault(way, {})[name] = \
                    f"{type(e).__name__}: {str(e)[:200]}"
        del leaf
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
