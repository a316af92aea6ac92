"""The running softmax the paged programs read a long context through: the
cached rows come a span of table entries at a time, and each span's
scores are merged into the softmax so far, so that no array of scores
is as long as the context. GLM-4.7-Flash's latent attention and Command
A+'s grouped-query attention both attend this way; each gives the
product of the weights with its own rows (``weighted``).

A decode step of heads over K and V rows may instead read each lane's
own pages IN PLACE (:func:`paged_decode`, a Pallas kernel for the TPU):
the leaf is then ONE array a layer ``[num_blocks, block_size, 2 *
kv_heads * head_dim]`` whose row interleaves the heads' K and V (``[k0,
v0, k1, v1, ...]``, each ``head_dim`` wide). The kernel has the contract
of JAX's ragged paged-attention kernel
(``jax.experimental.pallas.ops.tpu.ragged_paged_attention``) at one
query a lane: the same pages, rows, scale and window give the same
result as its reference over ``leaf.reshape(num_blocks, block_size, 2 *
kv_heads, head_dim)``. It does not call that kernel: its ``kv_pages``
operand ``[pages, block_size, 2 * kv_heads, head_dim]`` is tiled by
(heads, head_dim) on the TPU, the pool's leaf by (rows, lanes), so XLA
would copy the whole pool into the kernel's layout at every step (1.54 s
of 8 in `cmda_mixed_closed` on a TPU v5e, PERF.md section 6). This one
fetches a page ``[block_size, 2 * kv_heads * head_dim]`` as the pool
holds it and takes each head's K and V as 128-lane columns of it.
:func:`decode_pages` lays a tier's table out in the order the kernel
reads it, and :func:`kernel_refusal` says why a program cannot take the
kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

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


def over_spans(over, carry, spans: int, span_rows: int, hist, cap=None):
    """``over(i, carry)`` for each span ``i`` that holds a cached row:
    once where one span is the whole table, else as many times as the
    longest request's ``hist`` rows (at most ``cap``) fill spans of
    ``span_rows`` (a loop whose length the device reads off ``hist``)."""
    if spans == 1:
        return over(0, carry)
    held_rows = jnp.max(hist)
    if cap is not None:
        held_rows = jnp.minimum(held_rows, cap)
    return jax.lax.fori_loop(0, (held_rows + span_rows - 1) // span_rows,
                             over, carry)


def kernel_refusal(dtype, heads: int, kv_heads: int,
                   head_dim: int) -> Optional[str]:
    """Why a decode program whose leaf is in ``dtype``, with ``heads``
    query heads over ``kv_heads`` K/V heads of ``head_dim``, cannot read
    its pages through :func:`paged_decode`; None where it can. The kernel
    is a TPU program over bf16 or float32 rows, takes a head's K and V as
    whole 128-lane columns and a group of query heads as whole sublane
    rows."""
    backend = jax.default_backend()
    if backend != "tpu":
        return f"backend {backend}"
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"dtype {dt.name}"
    if head_dim % 128:
        return f"head size {head_dim}"
    if heads % kv_heads or (heads // kv_heads) % (8 * 4 // dt.itemsize):
        return f"heads {heads} over {kv_heads}"
    return None


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
    """One lane (grid step ``s``): its query heads ``q_ref [1, A, D]``
    over its ``rows_ref[s]`` rows (and, of them, those within
    ``reach_ref[0]`` of its own), fetched ``per_block`` pages at a time
    from ``kv_hbm [num_blocks, BS, 2 * kv_heads * D]`` through its pages
    ``pages_ref[s]`` into one of two VMEM buffers while the other is
    scored; a running softmax a K/V head (``m_ref``, ``l_ref`` ``[KV,
    G, 128]``, every lane alike; ``acc_ref [A, D]`` the weighted rows,
    unnormalised)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s = pl.program_id(0)
    _, BS, width = kv_hbm.shape
    _, A, D = q_ref.shape
    G, T = A // kv_heads, per_block * BS
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
            k = kv[:, 2 * h * D:(2 * h + 1) * D]
            v = kv[:, (2 * h + 1) * D:(2 * h + 2) * D]
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
            acc_ref[rows_h, :] = _lanes(alpha, D) * acc_ref[rows_h, :] \
                + _lanes(beta, D) * pv
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    for h in range(kv_heads):
        rows_h = slice(h * G, (h + 1) * G)
        o_ref[0, rows_h, :] = (acc_ref[rows_h, :]
                               / _lanes(l_ref[h], D)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "per_block"))
def _paged_decode_kernel(q, leaf, pages, rows, reach, *, scale: float,
                         per_block: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    R, A, D = q.shape
    _, BS, width = leaf.shape
    kv_heads = width // (2 * D)
    lane = pl.BlockSpec((1, A, D), lambda s, *_: (s, 0, 0))
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, kv_heads=kv_heads,
                          per_block=per_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(R,),
            in_specs=[lane, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=lane,
            scratch_shapes=[
                pltpu.VMEM((2, per_block, BS, width), leaf.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((kv_heads, A // kv_heads, 128), jnp.float32),
                pltpu.VMEM((kv_heads, A // kv_heads, 128), jnp.float32),
                pltpu.VMEM((A, D), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="paged_decode_attention",
    )(pages, rows, reach, q, leaf)


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


__all__ = ["NEG", "softmax_merge", "over_spans", "kernel_refusal",
           "window_start", "decode_pages", "paged_kernel", "paged_decode",
           "KV_PAGES_PER_BLOCK"]
