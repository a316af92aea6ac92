"""``zoo.paged_attend.PagedLeaf`` and the span-loop attention over it,
held to a plain numpy gather by table: a table that keeps every block
and a window tier's ring that has turned, spans that tile the table and
a span that does not, the null block behind a short request's unused
entries, and the two ways a fresh row is written."""
import numpy as np
import pytest

from deeplearning4j_tpu.zoo import paged_attend

BS, E, WIDTH = 4, 6, 8
#: cached positions a request holds: none, less than a block, a table
#: that is not full (its last entries point at the null block), and, for
#: the ring, one that has turned twice
HISTS = (0, 3, 13, 53)


def _pool(ring: bool, seed: int = 0):
    """A leaf of random rows, every block of it distinct, and a table
    ``[R, E]`` by hand: request ``r``'s block ``u`` is one block of its
    own, in entry ``u`` (or ``u % E`` for a ring, the last E blocks up to
    the one of position ``hist - 1``), every other entry the null block
    0. Returns the leaf, the table, ``hist`` and what each entry holds
    ``{(r, e): u}``."""
    rng = np.random.default_rng(seed)
    hists = [h for h in HISTS if ring or h <= E * BS]
    table = np.zeros((len(hists), E), np.int32)
    holds, nxt = {}, 1
    for r, h in enumerate(hists):
        if not h:
            continue
        last = (h - 1) // BS
        for u in range(max(0, last - E + 1) if ring else 0, last + 1):
            e = u % E if ring else u
            table[r, e], holds[r, e] = nxt, u
            nxt += 1
    leaf = rng.standard_normal((nxt, BS, WIDTH)).astype(np.float32)
    return leaf, table, np.asarray(hists, np.int32), holds


def _cached(table, hist, holds, leaf):
    """Each request's cached rows by position, ``{(r, pos): row}``, from
    the entries by hand."""
    out = {}
    for (r, e), u in holds.items():
        for j in range(BS):
            if u * BS + j < hist[r]:
                out[r, u * BS + j] = leaf[table[r, e], j]
    return out


def _spans(span: int):
    """``(first, counted_from)`` of each span the attention reads, as
    ``paged_attend.attend`` walks the table."""
    if E % span:
        return [(min(i * span, E - span), i * span)
                for i in range(-(-E // span))]
    return [(i * span, None) for i in range(E // span)]


@pytest.mark.parametrize("ring", [False, True], ids=["every_block", "ring"])
@pytest.mark.parametrize("span", [3, 4, 6])
def test_a_leaf_reads_what_a_gather_by_table_reads(ring, span):
    """Over the spans of a table, each span's rows are the leaf's rows of
    its entries' blocks (the null block's rows behind an unused entry),
    and each cached position of every request is held exactly once, at
    its own position, with its own row; nothing else is held. The table
    is 6 entries: spans of 3 and 6 tile it, a span of 4 does not (its
    last span is clamped and reads two entries again)."""
    import jax.numpy as jnp
    leaf, table, hist, holds = _pool(ring)
    cache = paged_attend.PagedLeaf(jnp.asarray(leaf), jnp.asarray(table),
                                   None, None, jnp.asarray(hist), BS,
                                   ring=ring)
    want = _cached(table, hist, holds, leaf)
    got = {}
    for first, counted in _spans(span):
        rows, pos, held = (np.asarray(a) for a in cache.read(
            jnp.int32(first), span,
            None if counted is None else jnp.int32(counted)))
        R = table.shape[0]
        assert rows.shape == (R, span * BS, WIDTH)
        gathered = leaf[table[:, first:first + span]].reshape(R, -1, WIDTH)
        np.testing.assert_array_equal(rows, gathered)
        pos = np.broadcast_to(pos, held.shape)
        for r, t in zip(*np.nonzero(held)):
            assert (r, pos[r, t]) not in got, "a position read twice"
            got[r, pos[r, t]] = rows[r, t]
    assert sorted(got) == sorted(want)
    for key, row in want.items():
        np.testing.assert_array_equal(got[key], row)
    # a short request's unused entries point at the null block
    assert (table[1, 1:] == 0).all()


@pytest.mark.parametrize("kind", ["interleaved", "row"])
def test_a_leaf_writes_where_a_scatter_writes(kind):
    """The fresh rows land at ``(write_block, write_off)``: K and V
    interleaved head by head (``[k0, v0, k1, v1]``) or a row as it is
    handed over, in the leaf's dtype; no other row moves."""
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    leaf = rng.standard_normal((5, BS, WIDTH)).astype(np.float32)
    wb = np.array([1, 3, 0], np.int32)
    wo = np.array([2, 0, 3], np.int32)
    cache = paged_attend.PagedLeaf(jnp.asarray(leaf, jnp.bfloat16), None,
                                   jnp.asarray(wb), jnp.asarray(wo), None,
                                   BS)
    want = np.array(jnp.asarray(leaf, jnp.bfloat16).astype(jnp.float32))
    if kind == "interleaved":
        k = rng.standard_normal((3, 2, WIDTH // 4)).astype(np.float32)
        v = rng.standard_normal((3, 2, WIDTH // 4)).astype(np.float32)
        cache.write(jnp.asarray(k), jnp.asarray(v))
        rows = np.concatenate([k[:, 0], v[:, 0], k[:, 1], v[:, 1]], axis=1)
    else:
        rows = rng.standard_normal((3, WIDTH)).astype(np.float32)
        cache.write_rows(jnp.asarray(rows))
    want[wb, wo] = np.asarray(jnp.asarray(rows, jnp.bfloat16)
                              .astype(jnp.float32))
    assert cache.leaf.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(cache.leaf.astype(jnp.float32)), want)


@pytest.mark.parametrize("ring,window", [(False, None), (True, 9)],
                         ids=["every_block", "ring_window"])
@pytest.mark.parametrize("span", [0, 3, 4])
def test_attend_is_one_softmax_over_the_cached_and_fresh_rows(ring, window,
                                                              span):
    """A run of 3 queries a request over an interleaved leaf of 2 K/V
    heads (4 query heads): the span loop gives, whatever the span, what
    one softmax gives over the request's cached rows (by the numpy
    gather) and its fresh rows up to each query (a padded row sees only
    itself), within the window where there is one."""
    import jax.numpy as jnp
    KV, G, D, Q = 2, 2, WIDTH // 4, 3
    leaf, table, hist, holds = _pool(ring, seed=2)
    R = table.shape[0]
    rng = np.random.default_rng(3)
    q = rng.standard_normal((R, Q, KV * G, D)).astype(np.float32)
    k = rng.standard_normal((R, Q, KV, D)).astype(np.float32)
    v = rng.standard_normal((R, Q, KV, D)).astype(np.float32)
    qpos = hist[:, None] + np.arange(Q, dtype=np.int32)[None]
    valid = np.arange(Q)[None] < np.array([3, 2, 3, 1][:R])[:, None]
    # the fresh rows are written afterwards, to the null block here
    null = jnp.zeros((R * Q,), jnp.int32)
    cache = paged_attend.PagedLeaf(jnp.asarray(leaf), jnp.asarray(table),
                                   null, null, jnp.asarray(hist), BS,
                                   ring=ring)
    got = np.asarray(paged_attend.attend(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(qpos),
        jnp.asarray(valid), cache, span, window))
    cached = _cached(table, hist, holds, leaf)
    scale = 1.0 / np.sqrt(D)
    for r in range(R):
        for i in range(Q):
            keys = [(p, row.reshape(KV, 2, D)[:, 0], row.reshape(
                KV, 2, D)[:, 1]) for (rr, p), row in cached.items()
                if rr == r]
            keys += [(qpos[r, j], k[r, j], v[r, j]) for j in range(Q)
                     if qpos[r, j] <= qpos[r, i] and (valid[r, j] or j == i)]
            if window:
                keys = [t for t in keys if t[0] > qpos[r, i] - window]
            K = np.stack([t[1] for t in keys])          # [T, KV, D]
            V = np.stack([t[2] for t in keys])
            for h in range(KV * G):
                s = K[:, h // G] @ q[r, i, h] * scale
                w = np.exp(s - s.max())
                want = (w / w.sum()) @ V[:, h // G]
                np.testing.assert_allclose(
                    got[r, i, h * D:(h + 1) * D], want, rtol=2e-5,
                    atol=2e-6)
