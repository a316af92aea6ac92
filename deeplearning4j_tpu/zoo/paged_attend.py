"""The paged attention of the serving zoo's blocks, written once.
:class:`PagedLeaf` is what a layer sees of one leaf of the paged pool. A
grouped-query layer (Command A+, LFM2-8B-A1B) caches ONE row a token, its
K and V heads interleaved (``[k0, v0, k1, v1, ...]``, each ``head_dim``
wide), and reads it through :func:`attend`, a span of table entries at a
time merged into a running softmax (:func:`softmax_merge`) so that no
array of scores is as long as the context, or, one query a lane on one
TPU, through :func:`attend_pages`, each lane's own pages IN PLACE
(:func:`paged_decode`, a Pallas kernel); :func:`decode_reads_pages`
decides which. GLM-4.7-Flash's latent attention reads its leaf through
the same :class:`PagedLeaf` and merges its own rows the same way.

The kernel reads the leaf as ONE array a layer ``[num_blocks,
block_size, 2 * kv_heads * head_dim]``. It has the contract of JAX's
ragged paged-attention kernel
(``jax.experimental.pallas.ops.tpu.ragged_paged_attention``) at one
query a lane: the same pages, rows, scale and window give the same
result as its reference over ``leaf.reshape(num_blocks, block_size, 2 *
kv_heads, head_dim)``. It does not call that kernel: its ``kv_pages``
operand ``[pages, block_size, 2 * kv_heads, head_dim]`` is tiled by
(heads, head_dim) on the TPU, the pool's leaf by (rows, lanes), so XLA
would copy the whole pool into the kernel's layout at every step (1.54 s
of 8 in `cmda_mixed_closed` on a TPU v5e, PERF.md section 6). This one
fetches a page ``[block_size, 2 * kv_heads * head_dim]`` as the pool
holds it and takes each head's K and V as 128-lane columns of it: a head
of 128 or more as two columns of its own, a head of 64 as ONE column
``[k_h | v_h]``, scored by the query widened with zeros over V
(``[q_h | 0] . [k_h | v_h] = q_h . k_h``) and weighed whole, its V half
kept. A group of query heads that does not fill whole sublane tiles (16
rows in bf16, 8 in float32) is padded with zero rows, which are dropped
from the result. The kernel chooses this layout from the shapes it is
handed; a head of 128 and a group of 16 in bf16 take none of it.
:func:`decode_pages` lays a tier's table out in the order the kernel
reads it, and :func:`kernel_refusal` says why a program cannot take the
kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.monitor import attention
from deeplearning4j_tpu.zoo.blocks import ein

#: a score no row that is seen ever has
NEG = -1e30

#: pages of a lane the decode kernel fetches and scores at a time
#: (:func:`paged_decode`), chosen on a TPU v5e at Command A+'s decode
#: shapes (32 lanes, 128 query heads over 8 K/V heads of 128, blocks of 16
#: rows in bf16; 2,506 pages of a 672-entry table and 2,044 of a
#: 257-entry ring a call, experiments/cmda_paged_kernel.py): 8, 16, 32
#: and 64 read 1.02 / 1.01 / 0.90 / 0.97 ms a call over the table and
#: 0.94 / 0.94 / 0.86 / 0.89 over the ring
KV_PAGES_PER_BLOCK = 32


def softmax_merge(carry, s, seen, weighted, neg):
    """One more set of scores ``s [..., T]`` into the running softmax
    ``carry = (top, total, out)``: the largest score so far ``[...]``,
    the sum of the weights under it ``[...]`` and the weighted rows
    ``[..., W]``. ``seen`` (broadcast against ``s``) says which scores
    count, and a score not seen stands at ``neg`` (the caller's float32
    :data:`NEG`, made once outside any loop over spans); ``weighted(e)``
    is the product of the span's weights ``e [..., T]`` (float32, 0 where
    not seen) with its rows."""
    top, total, o = carry
    new = jnp.maximum(top, jnp.max(jnp.where(seen, s, neg), axis=-1))
    e = jnp.where(seen, jnp.exp(s - new[..., None]), jnp.float32(0.0))
    keep = jnp.exp(top - new)
    return (new, total * keep + jnp.sum(e, axis=-1),
            o * keep[..., None] + weighted(e))


def spans_held(spans: int, span_rows: int, hist, cap=None):
    """How many spans :func:`over_spans` reads: 1 where one span is the
    whole table, else as many as the longest request's ``hist`` rows (at
    most ``cap``) fill spans of ``span_rows``."""
    if spans == 1:
        return 1
    held_rows = jnp.max(hist)
    if cap is not None:
        held_rows = jnp.minimum(held_rows, cap)
    return (held_rows + span_rows - 1) // span_rows


def over_spans(over, carry, spans: int, span_rows: int, hist, cap=None):
    """``over(i, carry)`` for each span ``i`` that holds a cached row
    (:func:`spans_held`; a loop whose length the device reads off
    ``hist`` where the table is more than one span)."""
    if spans == 1:
        return over(0, carry)
    return jax.lax.fori_loop(0, spans_held(spans, span_rows, hist, cap),
                             over, carry)


class PagedLeaf:
    """What a layer sees of one leaf of the paged pool: ``leaf
    [num_blocks, block_size, width]``, its tier's ``table [R, entries]``
    (R requests in the program), ``hist [R]``, how many positions each
    request has cached, and where the N fresh rows (request-major) go:
    ``(write_block [N], write_off [N])``. The table of a tier with a
    window is a ``ring``: entry ``e`` of its E holds block ``u = e (mod
    E)``, the one such ``u`` among the last E blocks up to the block of
    position ``hist - 1`` (the block written there last). Any other table
    holds block ``e`` in entry ``e``, at any width that holds every block
    a request has cached."""

    def __init__(self, leaf, table, write_block, write_off, hist,
                 block_size: int, ring: bool = False):
        self.leaf, self.table, self.hist = leaf, table, hist
        self.write_block, self.write_off = write_block, write_off
        self.BS, self.ring = int(block_size), bool(ring)

    def read(self, first, entries: int, counted_from=None):
        """The rows of ``entries`` entries of every request's table from
        entry ``first`` (which may be traced): ``[R, T, width]``, the
        position of each row (``[R, T]``; ``[1, T]`` where the table is
        no ring) and whether it is one of the request's cached positions
        ``[R, T]`` (an entry below ``counted_from``, which an earlier span
        read, counts as none)."""
        R, E = self.table.shape
        n, BS = int(entries), self.BS
        part = jax.lax.dynamic_slice_in_dim(self.table, first, n, axis=1)
        if not self.ring:
            rows = self.leaf[part].reshape(R, n * BS, -1)
            pos = (first * BS + jnp.arange(n * BS, dtype=jnp.int32))[None]
            held = pos < self.hist[:, None]
            if counted_from is not None:
                held = held & (pos >= counted_from * BS)
            return rows, pos, held
        e = first + jnp.arange(n, dtype=jnp.int32)
        last = jnp.floor_divide(self.hist - 1, BS)[:, None]         # [R, 1]
        u = last - jnp.mod(last - e[None], E)                       # [R, n]
        pos = (u[:, :, None] * BS
               + jnp.arange(BS, dtype=jnp.int32)[None, None])
        held = (u >= 0)[:, :, None] & (pos < self.hist[:, None, None])
        if counted_from is not None:
            held = held & (e >= counted_from)[None, :, None]
        rows = self.leaf[part].reshape(R, n * BS, -1)
        return rows, pos.reshape(R, -1), held.reshape(R, -1)

    def write_rows(self, rows):
        """The fresh rows ``[N, width]``, in place."""
        self.leaf = self.leaf.at[(self.write_block, self.write_off)].set(
            rows.astype(self.leaf.dtype))

    def write(self, k, v):
        """The fresh rows ``k, v [N, kv_heads, head_dim]``, interleaved,
        in place."""
        self.write_rows(jnp.stack([k, v], axis=2).reshape(k.shape[0], -1))


def attend(q, k, v, qpos, valid, cache: PagedLeaf, span: int,
           window: Optional[int] = None):
    """Grouped-query attention of a run over an interleaved leaf: ``q [R,
    Q, heads, D]``, ``k, v [R, Q, kv_heads, D]`` (the fresh rows, written
    to ``cache`` afterwards), ``qpos``/``valid`` ``[R, Q]``. Each query
    reads its request's cached rows and the fresh rows up to itself, one
    softmax over both (scores ``/ sqrt(D)``), and with a ``window`` only
    the rows ``j > qpos - window``. The cached rows are read ``span``
    table entries at a time (0: the whole table in one read), as many
    spans as the longest request's rows fill, each merged into a running
    softmax; where the spans do not tile the table, the last ends at the
    table's end and an entry an earlier span read is not counted again.
    Fresh rows are attended to as they will lie in the cache (rounded to
    its dtype). Returns ``[R, Q, heads * D]``."""
    R, Q = qpos.shape
    A, (KV, D) = q.shape[2], k.shape[2:]
    G, BS = A // KV, cache.BS
    scale = np.float32(1.0 / np.sqrt(D))
    dt = cache.leaf.dtype
    qg = q.reshape(R, Q, KV, G, D).astype(dt)
    E = cache.table.shape[1]
    span = min(int(span), E) if span else E
    neg = jnp.float32(NEG)

    def near(kpos):
        """Which keys at ``kpos [R, T]`` each query's window holds ``[R,
        Q, T]``."""
        return kpos[:, None, :] > qpos[:, :, None] - window

    def over_cached(i, carry):
        if E % span:
            rows, cpos, held = cache.read(jnp.minimum(i * span, E - span),
                                          span, i * span)
        else:
            rows, cpos, held = cache.read(i * span, span)
        rows = rows.reshape(R, rows.shape[1], KV, 2, D)
        Kc, Vc = rows[:, :, :, 0], rows[:, :, :, 1]
        if window:
            held = held[:, None, :] & near(cpos)                # [R, Q, T]
        # seen: [R, 1, 1, Q, T] in a window, else [R, 1, 1, 1, T]
        return softmax_merge(
            carry, ein("rqhgd,rthd->rhgqt", qg, Kc) * scale,
            held[:, None, None] if window else held[:, None, None, None, :],
            lambda e: ein("rhgqt,rthd->rhgqd", e.astype(dt), Vc), neg)

    carry = (jnp.full((R, KV, G, Q), neg),
             jnp.zeros((R, KV, G, Q), jnp.float32),
             jnp.zeros((R, KV, G, Q, D), jnp.float32))
    # as many spans as the longest request's cached rows fill (an idle
    # lane's position counts no further than its table)
    carry = over_spans(over_cached, carry, -(-E // span), span * BS,
                       cache.hist, cap=E * BS)
    # a row sees itself whether or not it is valid, so that an idle lane
    # or a padded row has a finite result (it lands in the null block,
    # which every table's unused entries point at)
    see_f = (qpos[:, None, :] <= qpos[:, :, None]) \
        & (valid[:, None, :] | jnp.eye(Q, dtype=bool)[None])
    if window:
        see_f = see_f & near(qpos)
    kf, vf = k.astype(dt), v.astype(dt)
    _, total, o = softmax_merge(
        carry, ein("rqhgd,rphd->rhgqp", qg, kf) * scale,
        see_f[:, None, None],
        lambda e: ein("rhgqp,rphd->rhgqd", e.astype(dt), vf), neg)
    o = o / total[..., None]                               # [R, KV, G, Q, D]
    o = jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(R, Q, A * D)
    cache.write(k.reshape(R * Q, KV, D), v.reshape(R * Q, KV, D))
    return o


def kernel_refusal(dtype, heads: int, kv_heads: int,
                   head_dim: int) -> Optional[str]:
    """Why a decode program whose leaf is in ``dtype``, with ``heads``
    query heads over ``kv_heads`` K/V heads of ``head_dim``, cannot read
    its pages through :func:`paged_decode`; None where it can. The kernel
    is a TPU program over bf16 or float32 rows; it takes a head's K and V
    as whole 128-lane columns, two of them or, for a head of 64, one
    (:func:`_layout`), and any whole group of query heads."""
    backend = jax.default_backend()
    if backend != "tpu":
        return f"backend {backend}"
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"dtype {dt.name}"
    if head_dim % 128 and head_dim != 64:
        return f"head size {head_dim}"
    if heads % kv_heads:
        return f"heads {heads} over {kv_heads}"
    return None


def _layout(dtype, group: int, head_dim: int):
    """How :func:`paged_decode`'s kernel lays out a group of ``group``
    query heads of ``head_dim``: ``(rows, lanes)``, the group padded to
    whole sublane tiles of ``dtype`` and a query widened to the column
    its head's K is read from (a head of 64 shares its 128 lanes with its
    V)."""
    tile = 8 * 4 // jnp.dtype(dtype).itemsize
    return -(-group // tile) * tile, 128 if head_dim == 64 else head_dim


def window_start(last, entries: int):
    """The first block a ring of ``entries`` entries is read from for a
    query in block ``last``: the oldest block the ring can hold beside
    it (0 before the ring has turned)."""
    return jnp.maximum(last - (int(entries) - 1), 0)


def decode_pages(table, positions, active, write_block, block_size: int,
                 ring: bool):
    """A decode step's tier as :func:`paged_decode` reads it: ``(pages
    [R, E], rows [R])``, each lane's blocks in the order of its
    positions and how many rows of them its query reads (its own fresh
    row, which the step has written, the last). ``table [R, E]``: a tier
    that keeps every block holds block ``u`` in entry ``u``, read as it
    is; a ``ring`` holds it in entry ``u % E`` and is read from
    :func:`window_start` on, so that every position of the lane moves
    down by the same whole blocks (which leaves the causal and the
    window's mask as they were: rotation was applied when a row was
    written) and the kernel reads at most E pages. The fresh row's block
    is ``write_block [R]``, which a ring's entry may not hold yet. An
    idle lane reads one row of its table's first entry (the null
    block)."""
    R, E = table.shape
    BS = int(block_size)
    j = jnp.arange(E, dtype=jnp.int32)
    last = jnp.where(active, positions // BS, 0).astype(jnp.int32)
    first = window_start(last, E) if ring else jnp.zeros_like(last)
    pages = table
    if ring:
        pages = jnp.take_along_axis(
            table, jnp.mod(first[:, None] + j[None], E), axis=1)
    pages = jnp.where(j[None] == (last - first)[:, None],
                      write_block[:, None], pages).astype(jnp.int32)
    rows = jnp.where(active, positions + 1 - BS * first, 1)
    return pages, rows.astype(jnp.int32)


def _lanes(x, width: int):
    """``x [n, 128]`` (every lane alike) as ``[n, width]``."""
    return x if width == x.shape[1] else jnp.concatenate(
        [x] * (width // x.shape[1]), axis=1)


def _decode_kernel(pages_ref, rows_ref, reach_ref, q_ref, kv_hbm, o_ref,
                   buf, sem, m_ref, l_ref, acc_ref, *, scale: float,
                   kv_heads: int, per_block: int):
    """One lane (grid step ``s``): its query heads ``q_ref [1, KV * G,
    C]`` (:func:`_layout`: ``G`` rows a group, ``C`` lanes a query) over
    its ``rows_ref[s]`` rows (and, of them, those within ``reach_ref[0]``
    of its own), fetched ``per_block`` pages at a time from ``kv_hbm
    [num_blocks, BS, 2 * kv_heads * D]`` through its pages
    ``pages_ref[s]`` into one of two VMEM buffers while the other is
    scored; a running softmax a K/V head (``m_ref``, ``l_ref`` ``[KV,
    G, 128]``, every lane alike; ``acc_ref [KV * G, C]`` the weighted
    rows, unnormalised). Where ``C`` is twice the head size, a head's K
    and V are one column ``[k_h | v_h]`` and the weighted rows' V half is
    the result."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s = pl.program_id(0)
    _, BS, width = kv_hbm.shape
    _, A, C = q_ref.shape
    G, T = A // kv_heads, per_block * BS
    D = width // (2 * kv_heads)
    rows = rows_ref[s]
    n_pages = (rows + BS - 1) // BS
    n_blocks = (n_pages + per_block - 1) // per_block

    def each_page(blk, slot, act):
        def one(i, carry):
            act(pltpu.make_async_copy(
                kv_hbm.at[pages_ref[s, blk * per_block + i]],
                buf.at[slot, i], sem.at[slot]))
            return carry
        jax.lax.fori_loop(
            0, jnp.minimum(per_block, n_pages - blk * per_block), one, 0)

    @pl.when(s == 0)
    def _():
        # a page the last block of a lane does not fetch is scored as 0
        # weight: what the buffer holds there must be finite
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    each_page(0, 0, lambda c: c.start())

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            each_page(blk + 1, 1 - slot, lambda c: c.start())

        each_page(blk, slot, lambda c: c.wait())
        kv = buf[slot].reshape(T, width)
        pos = blk * T + jax.lax.broadcasted_iota(jnp.int32, (G, T), 1)
        seen = jnp.logical_and(pos < rows, pos > rows - 1 - reach_ref[0])
        for h in range(kv_heads):
            if C == D:
                k = kv[:, 2 * h * D:(2 * h + 1) * D]
                v = kv[:, (2 * h + 1) * D:(2 * h + 2) * D]
            else:
                k = v = kv[:, h * C:(h + 1) * C]
            q = q_ref[0, h * G:(h + 1) * G, :]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            sc = jnp.where(seen, sc, NEG)
            top = jnp.max(sc, axis=1, keepdims=True)
            p = jnp.where(seen, jnp.exp(sc - top), 0.0)
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_next = jnp.maximum(m_prev, top)
            alpha, beta = jnp.exp(m_prev - m_next), jnp.exp(top - m_next)
            m_ref[h] = m_next
            l_ref[h] = alpha * l_prev + beta * jnp.sum(p, axis=1,
                                                      keepdims=True)
            pv = jnp.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
            rows_h = slice(h * G, (h + 1) * G)
            acc_ref[rows_h, :] = _lanes(alpha, C) * acc_ref[rows_h, :] \
                + _lanes(beta, C) * pv
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    for h in range(kv_heads):
        rows_h = slice(h * G, (h + 1) * G)
        o_ref[0, rows_h, :] = (acc_ref[rows_h, :]
                               / _lanes(l_ref[h], C)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "per_block"))
def _paged_decode_kernel(q, leaf, pages, rows, reach, *, scale: float,
                         per_block: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    R, A, D = q.shape
    _, BS, width = leaf.shape
    kv_heads = width // (2 * D)
    G = A // kv_heads
    Gp, C = _layout(q.dtype, G, D)
    laid = q
    if (Gp, C) != (G, D):
        # zero rows up to whole sublane tiles, zero lanes over V
        laid = jnp.pad(q.reshape(R, kv_heads, G, D),
                       ((0, 0), (0, 0), (0, Gp - G), (0, C - D))
                       ).reshape(R, kv_heads * Gp, C)
    lane = pl.BlockSpec((1, kv_heads * Gp, C), lambda s, *_: (s, 0, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, kv_heads=kv_heads,
                          per_block=per_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(R,),
            in_specs=[lane, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=lane,
            scratch_shapes=[
                pltpu.VMEM((2, per_block, BS, width), leaf.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((kv_heads, Gp, 128), jnp.float32),
                pltpu.VMEM((kv_heads, Gp, 128), jnp.float32),
                pltpu.VMEM((kv_heads * Gp, C), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct(laid.shape, q.dtype),
        name="paged_decode_attention",
    )(pages, rows, reach, laid, leaf)
    if laid is q:
        return out
    # the group's own rows, and of a [k_h | v_h] column its V half
    return out.reshape(R, kv_heads, Gp, C)[:, :, :G, C - D:].reshape(
        R, A, D)


def paged_kernel():
    """The decode kernel :func:`paged_decode` calls: ``fn(q, leaf, pages,
    rows, reach, *, scale, per_block)`` with ``reach [1]`` the rows a
    query reads back to, itself included (a test puts the shipped
    kernel's reference in its place)."""
    return _paged_decode_kernel


#: ``reach`` of a query that reads every row before it
_EVERY_ROW = 2 ** 30


def paged_decode(q, leaf, pages, rows, scale: float,
                 window: Optional[int] = None, width: int = 0):
    """One query a lane over its own pages, read in place: ``q [R,
    heads, head_dim]``, ``leaf [num_blocks, block_size, 2 * kv_heads *
    head_dim]`` with K and V interleaved head by head, ``(pages, rows)``
    from :func:`decode_pages`; a query at row ``rows - 1`` reads the rows
    ``j <= rows - 1`` and, with a ``window``, ``j > rows - 1 - window``.
    ``pages`` is padded to ``width`` entries where it is narrower (the
    widest table of the program's tiers), and the window is handed over
    as a number, so that the kernel is traced once for every layer and
    table width of a program. Returns ``[R, heads, head_dim]`` in ``q``'s
    dtype (the softmax and the sums in float32)."""
    if pages.shape[1] < width:
        pages = jnp.pad(pages, ((0, 0), (0, width - pages.shape[1])))
    reach = jnp.full((1,), _EVERY_ROW if window is None else window,
                     jnp.int32)
    return paged_kernel()(q, leaf, pages, rows, reach, scale=float(scale),
                          per_block=min(KV_PAGES_PER_BLOCK, pages.shape[1]))


def attend_pages(q, k, v, qpos, valid, cache: PagedLeaf, width: int,
                 window: Optional[int] = None):
    """One query a request, ``q [R, 1, heads, D]``, over its own pages of
    an interleaved leaf, read in place through :func:`paged_decode` once
    the fresh rows ``k, v [R, 1, kv_heads, D]`` are written (``width``
    the widest table of the program's tiers). Returns ``([R, 1, heads *
    D], the pages the requests with a valid row read)``."""
    R, _, A, D = q.shape
    cache.write(k.reshape(R, *k.shape[2:]), v.reshape(R, *v.shape[2:]))
    pages, rows = decode_pages(
        cache.table, qpos[:, 0], valid[:, 0], cache.write_block, cache.BS,
        ring=cache.ring)
    o = paged_decode(q[:, 0].astype(cache.leaf.dtype), cache.leaf, pages,
                     rows, np.float32(1.0 / np.sqrt(D)), window, width=width)
    read = jnp.sum(jnp.where(valid[:, 0], -(-rows // cache.BS), 0),
                   dtype=jnp.int32)
    return o.reshape(R, 1, A * D), read


def decode_reads_pages(dtype, heads: int, kv_heads: int, head_dim: int,
                       layers: int) -> bool:
    """Whether a decode program whose ``layers`` attention layers have
    leaves in ``dtype`` and ``heads`` query heads over ``kv_heads`` K/V
    heads of ``head_dim`` reads its pages through :func:`attend_pages`
    (else :func:`attend`), decided once a traced program from what it can
    see (:func:`kernel_refusal`); the program's sites are noted in
    ``monitor.attention``, which ``last_decode_program()`` then gives."""
    why = kernel_refusal(dtype, heads, kv_heads, head_dim)
    sites = attention.open_decode_program()
    for _ in range(layers):
        sites.note(why)
    return why is None


__all__ = ["NEG", "softmax_merge", "spans_held", "over_spans", "PagedLeaf",
           "attend", "attend_pages", "decode_reads_pages",
           "kernel_refusal", "window_start", "decode_pages", "paged_kernel",
           "paged_decode", "KV_PAGES_PER_BLOCK"]
