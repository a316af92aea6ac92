"""The running softmax the paged programs read a long context through: the
cached rows come a span of table entries at a time, and each span's
scores are merged into the softmax so far, so that no array of scores
is as long as the context. GLM-4.7-Flash's latent attention and Command
A+'s grouped-query attention both attend this way; each gives the
product of the weights with its own rows (``weighted``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: a score no row that is seen ever has
NEG = -1e30


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


__all__ = ["NEG", "softmax_merge", "over_spans"]
