"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Reference parity: NONE — the reference predates long-context training
(SURVEY.md §5: attention exists only as single-device fused ops,
libnd4j generic/nn/multi_head_dot_product_attention.cpp). This is a new
first-class capability, designed TPU-first:

- **Ring attention**: shard the sequence over the 'seq' mesh axis; each
  step computes one (q-block × kv-block) tile and rotates the kv shard to
  the next neighbor with lax.ppermute — a pure ICI-neighbor transfer that
  overlaps with the tile matmul — while a flash-style running
  (max, denom, accum) makes the softmax exact across blocks
  (Liu et al. 2023 blockwise formulation).
- **Ulysses attention**: all_to_all swaps the sequence shard for a head
  shard, runs full-sequence attention on head-local data, and swaps back
  — better when heads ≥ devices and ICI all-to-all is cheap (within a
  v5e slice it is).

Both are exact: outputs match single-device softmax attention to
numerical tolerance (tested on the CPU mesh).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.parallel import collectives
from deeplearning4j_tpu.parallel.mesh import SEQ_AXIS, DeviceMesh


def _shard_map_norep(**kw):
    """shard_map as a decorator, with the replication check off."""
    return lambda f: shard_map(f, check_vma=False, **kw)


def _block_attn(q, k, v, m, l, o, scale, mask=None):
    """One blockwise-softmax accumulation step (flash-attention update).

    q: (B, Tq, H, D); k/v: (B, Tk, H, D); m/l: (B, H, Tq); o like q.
    """
    # float32 accumulation regardless of input dtype (bf16 running sums
    # lose ~1e-2 relative accuracy over long sequences; standard flash
    # practice is f32 m/l/o with a cast at the end)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) → nan
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
    p = jnp.exp(s - safe_m[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    # corr is (B, H, Tq); o is (B, Tq, H, D)
    o_new = o * jnp.moveaxis(corr, 1, 2)[..., None] + \
        jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def ring_attention(q, k, v, mesh: DeviceMesh, causal: bool = False,
                   axis_name: str = SEQ_AXIS):
    """Exact attention with the sequence sharded over ``axis_name``.

    q/k/v: (batch, seq, heads, head_dim), seq sharded over the mesh axis.
    Returns same-shaped output, seq-sharded.
    """
    n = mesh.axis_size(axis_name)
    scale = 1.0 / (q.shape[-1] ** 0.5)

    spec = P(None, axis_name, None, None)

    @_shard_map_norep(mesh=mesh.mesh, in_specs=(spec, spec, spec),
                      out_specs=spec)
    def _ring(q_blk, k_blk, v_blk):
        b, tq, h, d = q_blk.shape
        tk = k_blk.shape[1]
        my = lax.axis_index(axis_name)
        q_pos = my * tq + jnp.arange(tq)                    # global q positions

        def step(i, carry):
            m, l, o, k_cur, v_cur = carry
            src = (my - i) % n                              # kv block owner
            mask = None
            if causal:
                k_pos = src * tk + jnp.arange(tk)
                mask = q_pos[:, None] >= k_pos[None, :]     # (Tq, Tk)
                mask = mask[None, None, :, :]               # (1,1,Tq,Tk)
            m, l, o = _block_attn(q_blk, k_cur, v_cur, m, l, o, scale, mask)
            k_nxt = collectives.ring_permute(k_cur, axis_name)
            v_nxt = collectives.ring_permute(v_cur, axis_name)
            return m, l, o, k_nxt, v_nxt

        m0 = jnp.full((b, h, tq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, tq), jnp.float32)
        o0 = jnp.zeros(q_blk.shape, jnp.float32)
        m, l, o, _, _ = lax.fori_loop(0, n, step, (m0, l0, o0, k_blk, v_blk))
        denom = jnp.moveaxis(l, 1, 2)[..., None]            # (B, Tq, H, 1)
        return (o / jnp.maximum(denom, 1e-30)).astype(q_blk.dtype)

    return _ring(q, k, v)


def ulysses_attention(q, k, v, mesh: DeviceMesh, causal: bool = False,
                      axis_name: str = SEQ_AXIS):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style): swap the
    seq shard for a head shard, attend over the full sequence locally,
    swap back. Heads must divide the axis size."""
    n = mesh.axis_size(axis_name)
    h = q.shape[2]
    if h % n != 0:
        raise ValueError(f"heads ({h}) must be divisible by mesh axis ({n})")
    scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(None, axis_name, None, None)

    @_shard_map_norep(mesh=mesh.mesh, in_specs=(spec, spec, spec),
                      out_specs=spec)
    def _ulysses(q_blk, k_blk, v_blk):
        # (B, T/n, H, D) --a2a--> (B, T, H/n, D)
        def seq_to_head(x):
            return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

        def head_to_seq(x):
            return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

        qf, kf, vf = seq_to_head(q_blk), seq_to_head(k_blk), seq_to_head(v_blk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            t = qf.shape[1]
            mask = jnp.tril(jnp.ones((t, t), bool))
            s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        of = jnp.einsum("bhqk,bkhd->bqhd", p, vf,
                        preferred_element_type=jnp.float32)
        # cast BEFORE the return all_to_all so bf16 (not f32) rides the ICI
        return head_to_seq(of.astype(q_blk.dtype))

    return _ulysses(q, k, v)
