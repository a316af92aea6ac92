"""LFM2-8B-A1B's decode attention alone, at the cell's shapes, on the
chip: the repo's paged decode kernel (``zoo.paged_attend.paged_decode``:
a head of 64 read as one 128-lane column ``[k_h | v_h]``, the group of 4
query heads padded to 16 rows) at several blocks of pages, against the
plain read it replaces (the table read 32 entries a span, as many spans
as the longest lane fills, each span gathered and split head-major into
a running softmax: ``zoo.lfm2_moe``'s ``_attend`` at one query a lane),
over one table of 288 entries, 64 lanes of 32 query heads over 8 K/V
heads of 64, blocks of 16 rows in bf16, the pool sized as the cell's
server sizes it. The lanes stand where the cell's mix puts them: two
documents (4,480 and 2,430 positions) and 62 chat lanes spread over
20-1,500. Prints one JSON line: each way's milliseconds a call (the
median of 30 after a warm call), its largest gap from a float32
reference computed from the same rows, in deviations of that reference,
and the pages the lanes hold. Run on the chip from the root of a
checkout: ``PYTHONPATH=. python experiments/lfm2_paged_kernel.py``."""
import json
import sys

import numpy as np

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.zoo import paged_attend
from experiments.cmda_paged_kernel import timed

S, A, KV, D, BS, E, SPAN = 64, 32, 8, 64, 16, 288, 32


def lanes(rng):
    pos = np.concatenate([[4480, 2430], rng.integers(20, 1500, S - 2)])
    return pos.astype(np.int32)


def table_of(rng, pos, num_blocks):
    """Every lane's blocks, distinct over the pool, block ``u`` in entry
    ``u``."""
    free = rng.permutation(np.arange(1, num_blocks))
    t = np.zeros((S, E), np.int32)
    at = 0
    for r, p in enumerate(pos):
        n = p // BS + 1
        t[r, :n] = free[at:at + n]
        at += n
    return t


def plain(q, leaf, pages, rows):
    """The reference: every page gathered, float32 scores and sums."""
    kv = leaf[pages].reshape(S, -1, KV, 2, D).astype(jnp.float32)
    k, v = kv[:, :, :, 0], kv[:, :, :, 1]
    qg = q.reshape(S, KV, A // KV, D).astype(jnp.float32)
    s = jnp.einsum("rhgd,rthd->rhgt", qg, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(D)
    see = jnp.arange(k.shape[1])[None] < rows[:, None]
    s = jnp.where(see[:, None, None], s, -1e30)
    o = jnp.einsum("rhgt,rthd->rhgd", jax.nn.softmax(s, axis=-1), v,
                   precision=jax.lax.Precision.HIGHEST)
    return o.reshape(S, A, D)


def spans(q, leaf, table, pos):
    """The parent's decode read: 32 entries a span, each gathered and
    split head-major, bf16 products into float32, into a running
    softmax over as many spans as the longest lane fills (the fresh row
    is among the cached ones here)."""
    qg = q.reshape(S, KV, A // KV, 1, D)
    neg = jnp.float32(paged_attend.NEG)
    scale = np.float32(1 / np.sqrt(D))

    def over(i, carry):
        part = jax.lax.dynamic_slice_in_dim(table, i * SPAN, SPAN, axis=1)
        rows = leaf[part].reshape(S, SPAN * BS, KV, 2, D)
        k, v = rows[:, :, :, 0], rows[:, :, :, 1]
        held = i * SPAN * BS + jnp.arange(SPAN * BS) <= pos[:, None]
        return paged_attend.softmax_merge(
            carry, jnp.einsum("rhgqd,rthd->rhgqt", qg, k,
                              preferred_element_type=jnp.float32) * scale,
            held[:, None, None, None, :],
            lambda e: jnp.einsum("rhgqt,rthd->rhgqd", e.astype(q.dtype), v,
                                 preferred_element_type=jnp.float32), neg)

    carry = (jnp.full((S, KV, A // KV, 1), neg),
             jnp.zeros((S, KV, A // KV, 1), jnp.float32),
             jnp.zeros((S, KV, A // KV, 1, D), jnp.float32))
    _, total, o = paged_attend.over_spans(over, carry, E // SPAN, SPAN * BS,
                                          pos + 1, cap=E * BS)
    return (o / total[..., None]).reshape(S, A, D).astype(q.dtype)


def main() -> None:
    if jax.default_backend() != "tpu":
        sys.exit("the kernel is a TPU program: run this on the chip")
    rng = np.random.default_rng(20261017)
    pos = lanes(rng)
    key = jax.random.key(7)
    q = jax.random.normal(key, (S, A, D), jnp.bfloat16)
    nb = S * E + 1
    leaf = jax.random.normal(jax.random.fold_in(key, 1),
                             (nb, BS, 2 * KV * D), jnp.bfloat16)
    table = jnp.asarray(table_of(rng, pos, nb))
    posj = jnp.asarray(pos)
    wb = table[jnp.arange(S), posj // BS]
    pages, rows = jax.jit(paged_attend.decode_pages, static_argnums=(4, 5))(
        table, posj, jnp.ones(S, bool), wb, BS, False)
    out = {"device": jax.devices()[0].device_kind,
           "held_pages": int(jnp.sum(-(-rows // BS))),
           "held_bytes": int(jnp.sum(-(-rows // BS))) * BS * 2 * KV * D * 2,
           "ms": {}, "gap": {}}
    want = jax.jit(plain)(q, leaf, pages, rows)
    ways = {"spans": (jax.jit(spans), (q, leaf, table, posj))}
    reach = jnp.asarray([2 ** 30], jnp.int32)
    for bkv in (8, 16, 32, 64):
        ways[f"repo_{bkv}"] = (jax.jit(
            lambda q, lf, pg, r, bkv=bkv: paged_attend.paged_kernel()(
                q, lf, pg, r, reach, scale=float(1 / np.sqrt(D)),
                per_block=bkv)), (q, leaf, pages, rows))
    for way, (fn, args) in ways.items():
        try:
            got = fn(*args)
            out["gap"][way] = float(
                jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                / jnp.std(want))
            out["ms"][way] = timed(fn, *args)
        except Exception as e:              # a tuning the chip refuses
            out["ms"][way] = f"{type(e).__name__}: {str(e)[:200]}"
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
