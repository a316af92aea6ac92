"""The arithmetic the serving zoo's blocks are built from (SmallThinker,
GLM-4.7-Flash, EvaByte, Command A+, LFM2-8B-A1B), each piece once.

A product takes its operands in the dtype of the parameters (bfloat16 as
published) and accumulates in float32 (:func:`mm`, :func:`ein`); norms
and rotations are float32. Rotary embedding comes in the two conventions
the families publish, both of ``(x, angles)``: rotate-half
(:func:`rotate_half`) and GPT-J's interleaved pairs
(:func:`rotate_pairs`). Each function emits the operations its families'
blocks emitted when they were written out in each, in the same order, so
that moving a family onto them leaves its lowered programs as they were
(``experiments/lowered_text.py``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def mm(x, w):
    """``x @ w``, ``x`` taken in ``w``'s dtype, accumulated in float32."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def ein(eq, a, b):
    """``einsum(eq, a, b)`` accumulated in float32."""
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def swiglu(x, gate, up, down):
    """The gated feed-forward ``(silu(x @ gate) * (x @ up)) @ down``."""
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def rms_norm(x, g, eps):
    """``g * x / sqrt(mean(x^2) + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * g.astype(jnp.float32)


def layer_norm(x, g, eps):
    """Mean and variance over the last axis, a gain and no bias, in
    float32."""
    x = x.astype(jnp.float32)
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def rope_freqs(theta, dim: int):
    """``theta^(-2i / dim)`` for the ``dim / 2`` pairs of a rotated
    width, float32: made once a program, outside its trace, so that
    every rotation of the program reads the one constant."""
    return jnp.asarray(
        theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim),
        jnp.float32)


def rope_angles(pos, freqs):
    """The angles of positions ``pos [...]`` for rows ``[..., heads,
    dim]``: ``[..., 1, dim / 2]``, ``pos * freqs`` (:func:`rope_freqs`)."""
    return pos[..., None, None].astype(jnp.float32) * freqs


def rotate_half(x, ang):
    """``x [..., D]`` float32 rotated by ``ang [..., D / 2]``
    (broadcast): pair ``i`` is ``(x_i, x_{i + D / 2})``."""
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def rotate_pairs(x, ang):
    """``x [..., D]`` float32 rotated by ``ang [..., D / 2]``
    (broadcast), GPT-J's way: pair ``i`` is ``(x_{2i}, x_{2i + 1})``."""
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pair = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x1, x2 = pair[..., 0], pair[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def layer_params(p, i: int) -> dict:
    """Layer ``i``'s parameters under their names within the layer:
    ``h<i>/attn/q`` as ``/attn/q``."""
    sc = f"h{i}"
    return {n[len(sc):]: a for n, a in p.items() if n.startswith(sc + "/")}


def pull_params(params, want):
    """``(pull, got)``: the callable that gives ``params`` (a dict by
    name, or a callable that gives one: ``update_model`` calls it again)
    and the dict it gives now, refused where its names or shapes are not
    the configuration's ``want`` (name -> shape)."""
    pull = params if callable(params) else (lambda: params)
    got = pull()
    if set(got) != set(want):
        raise ValueError(f"parameter names differ: "
                         f"{sorted(set(got) ^ set(want))[:4]}")
    for n, shape in want.items():
        if tuple(np.shape(got[n])) != shape:
            raise ValueError(f"{n}: shape {tuple(np.shape(got[n]))}, "
                             f"the configuration gives {shape}")
    return pull, got


def global_and_window_tiers(window_layout, window: int):
    """A pool's tiers for layers of global and window attention
    (``window_layout[i]`` 1 for a window layer): the global layers, then
    the window layers (a tier with no layer is left out)."""
    from deeplearning4j_tpu.serving.paged import KVTier
    glob = tuple(i for i, w in enumerate(window_layout) if not w)
    win = tuple(i for i, w in enumerate(window_layout) if w)
    tiers = []
    if glob:
        tiers.append(KVTier("global", glob, None))
    if win:
        tiers.append(KVTier("window", win, window))
    return tuple(tiers)


def tier_tables(tiers, io, table_key: str, lift, block_size: int,
                max_blocks_per_req: int) -> dict:
    """Each layer's tier's ``(table, write_block)`` from a program's
    ``io`` (``table_key`` ``table`` for a prefill run, lifted to one
    request, ``tables`` for a decode step), by layer. A ring is addressed
    ``u % entries``, exactly its own width; a table that keeps every
    block may come cut to the blocks its lanes hold."""
    per_tier = {}
    for t in tiers:
        table = lift(io[t.key(table_key)])
        entries = t.table_blocks(block_size, max_blocks_per_req)
        if table.shape[1] > entries or (
                t.window is not None and table.shape[1] != entries):
            raise ValueError(
                f"{t.key(table_key)} has {table.shape[1]} entries, the "
                f"tier's table {entries}")
        per_tier[t.name] = (table, io[t.key("write_block")])
    return {i: per_tier[t.name] for t in tiers for i in t.layers}


__all__ = ["mm", "ein", "swiglu", "rms_norm", "layer_norm", "rope_freqs",
           "rope_angles", "rotate_half", "rotate_pairs", "layer_params",
           "pull_params", "global_and_window_tiers", "tier_tables"]
