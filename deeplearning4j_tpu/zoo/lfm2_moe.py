"""LFM2-8B-A1B's block (LiquidAI, ``model_type`` ``lfm2_moe``): gated
short-convolution layers beside grouped-query attention layers, each with
a dense or a sigmoid-routed expert feed-forward, on the paged serving
path with a per-lane state store for the convolutions.

The block, written ONCE (:func:`lfm2_moe_paged_decode_fns` and
:func:`lfm2_moe_forward` derive prefill, decode and a whole-sequence
forward from it), with ``x`` the stream ``[tokens, hidden]``, no bias on
any projection and every norm an RMSNorm ``g * x / sqrt(mean(x^2) +
norm_eps)``:

- ``h = x + mix(rmsnorm(x; g_op))``; ``y = h + ffn(rmsnorm(h; g_ffn))``.
- ``mix`` on a ``conv`` layer: ``[B | C | xt] = u @ W_in`` (in that
  order), ``v = B * xt``, ``z_t = sum_k w[:, k] * v_{t - (L - 1) + k}``
  over ``k < L`` (``conv_L_cache``: a depthwise causal convolution, ``v``
  zero before the sequence), ``out = (C * z) @ W_out``. What a lane keeps
  of it is the ``v`` of its last ``L - 1`` positions, whatever the length
  of its context: a ``serving.paged.StateLeaf`` of ``(L - 1, hidden)`` a
  conv layer, which the server holds per slot.
- ``mix`` on a ``full_attention`` layer: ``num_heads`` query heads over
  ``num_kv_heads`` K/V heads of ``hidden / num_heads``; q and k each pass
  an RMSNorm over a head's numbers (a gain a head size) BEFORE they are
  rotated (rotate-half, ``rope_theta``, position = index in the
  sequence); causal over the whole context, scores ``/ sqrt(head_dim)``,
  softmax in float32.
- ``ffn``: the first ``num_dense_layers`` layers ``W2(silu(u W1) * u
  W3)``; every later one ``s = sigmoid(u @ Wr)`` over ``num_experts``,
  the ``num_experts_per_tok`` largest of ``s + b`` chosen (``b`` the expert
  bias), weights ``routed_scaling_factor * s_e / (sum of the chosen s +
  1e-6)`` (``parallel.moe.sigmoid_bias_route``), ``dropless_topk_ffn``
  over SwiGLU experts.
- after the last layer ``rmsnorm(x; g_f) @ E^T``: the head is the
  embedding (tied).

The stream, the norms, the router's product (float32 at the highest
precision) and the softmax are float32; every other product takes its
operands in the dtype the parameters are handed over in (bfloat16 as
published) and accumulates in float32. K and V are cached in that dtype as
ONE row a token and attention layer, the heads' K and V interleaved
(``[k0, v0, k1, v1, ...]``, each ``head_dim`` wide): the pool's layers are
the attention layers alone, in order, on one tier that keeps every block.
The convolution's ``v`` is rounded to the state's dtype where it is made,
so that a run that reads its first taps from the store computes what one
run over the whole prompt would. The attention is the zoo's one
grouped-query attention over a ``zoo.paged_attend.PagedLeaf``: a prefill
run reads its cached rows :data:`READ_SPAN` table entries at a time into
a running softmax (``paged_attend.attend``). A decode step on one TPU
reads each lane's own pages in place (``paged_attend.attend_pages``),
whose kernel takes a head of 64 as one 128-lane column ``[k_h | v_h]``
and pads a group of 4 query heads to whole sublane tiles; where
``paged_attend.kernel_refusal`` names a reason (the CPU) it reads the
table it was handed the same way as a prefill run, span by span
(``monitor.attention.last_decode_program()`` says which way the program
went).

There is no training graph for this block; :func:`lfm2_moe_paged_spec`
serves parameters handed over by name.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

from deeplearning4j_tpu.compilecache.cache import COMPILE_STATS
from deeplearning4j_tpu.parallel.moe import (dropless_topk_ffn,
                                             sigmoid_bias_route,
                                             streams_experts)
from deeplearning4j_tpu.zoo import paged_attend
from deeplearning4j_tpu.zoo.blocks import (ein, layer_params, mm, pull_params,
                                           rms_norm, rope_angles, rope_freqs,
                                           rotate_half, swiglu)


class Lfm2MoeUnsupportedError(ValueError):
    """A published key asks for what this block does not compute;
    ``key`` names it."""

    def __init__(self, key: str, value, why: str):
        super().__init__(f"{key}={value!r} is not computed: {why}")
        self.key = key


#: (key, default, the values computed, why another is refused)
_COMPUTED = (
    ("conv_bias", False, (False,),
     "the convolution and its projections have no bias"),
    ("use_expert_bias", True, (True,), "the choice reads the expert bias"),
    ("norm_topk_prob", True, (True,), "the chosen weights are renormalised"),
    ("tie_word_embeddings", True, (True,), "the head is the embedding"),
)

_LAYER_KINDS = {"conv": 0, "full_attention": 1}

#: the divisor's epsilon in the chosen weights (the family's code)
ROUTER_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    conv_width: int
    dense_width: int
    expert_width: int
    num_experts: int
    experts_per_token: int
    num_dense_layers: int
    attention_layout: Tuple[int, ...]
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    routed_scale: float = 1.0
    max_seq_len: int = 128000

    @classmethod
    def from_dict(cls, d: dict) -> "Lfm2MoeConfig":
        """From the keys of the model's published ``config.json``;
        ``layer_types`` may run past ``num_hidden_layers``: the first
        layers count. What the block does not compute is refused,
        :class:`Lfm2MoeUnsupportedError` naming the key."""
        for key, default, ok, why in _COMPUTED:
            got = d.get(key, default)
            if got not in ok:
                raise Lfm2MoeUnsupportedError(key, got, why)
        L = int(d["num_hidden_layers"])
        kinds = list(d["layer_types"][:L])
        for k in kinds:
            if k not in _LAYER_KINDS:
                raise Lfm2MoeUnsupportedError(
                    "layer_types", k, f"a layer is one of "
                    f"{sorted(_LAYER_KINDS)}")
        E, K = int(d["num_experts"]), int(d["num_experts_per_tok"])
        if not 0 < K <= E:
            raise Lfm2MoeUnsupportedError(
                "num_experts_per_tok", K,
                f"a token chooses among the {E} experts")
        return cls(
            vocab_size=int(d["vocab_size"]),
            hidden_size=int(d["hidden_size"]), num_layers=L,
            num_heads=int(d["num_attention_heads"]),
            num_kv_heads=int(d["num_key_value_heads"]),
            conv_width=int(d["conv_L_cache"]),
            dense_width=int(d["intermediate_size"]),
            expert_width=int(d["moe_intermediate_size"]),
            num_experts=E, experts_per_token=K,
            num_dense_layers=int(d["num_dense_layers"]),
            attention_layout=tuple(_LAYER_KINDS[k] for k in kinds),
            rope_theta=float(d["rope_theta"]),
            norm_eps=float(d["norm_eps"]),
            routed_scale=float(d.get("routed_scaling_factor", 1.0)),
            max_seq_len=int(d["max_position_embeddings"]))

    def __post_init__(self):
        if len(self.attention_layout) != self.num_layers:
            raise ValueError("a layer type is needed for every layer")
        if self.hidden_size % self.num_heads or \
                self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("the hidden size must divide into heads, the "
                             "query heads over the K/V heads, a head into "
                             "halves")
        if not self.attention_layers:
            raise ValueError("the pool caches the attention layers: there "
                             "is none")
        if self.num_dense_layers >= self.num_layers:
            raise ValueError("the decode program counts its expert layers: "
                             "there is none")
        if self.conv_width < 2:
            raise ValueError("a convolution of one tap keeps no state")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        """The model's attention layers, in order: pool layer ``j`` is
        the ``j``-th of them."""
        return tuple(i for i, a in enumerate(self.attention_layout) if a)

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        """The model's conv layers, in order: state array ``j``."""
        return tuple(i for i, a in enumerate(self.attention_layout)
                     if not a)

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers


#: what the decode program counts a step: over its expert layers,
#: GLM-4.7-Flash's five under their names (layers run, experts with a
#: token of an active lane, (token, expert) pairs, the fullest expert's
#: tokens, the choices the expert bias moved against the largest bare
#: sigmoids), and over its attention layers the pages the active lanes
#: read, as Command A+ counts them (the kernel: each lane's own, from the
#: rows it was handed; the plain path: every entry of the spans it
#: gathered), and the expert layers whose experts were streamed
#: (``parallel.moe.streams_experts``); docs/serving.md says what an
#: operator reads from each
PROGRAM_COUNTERS = ("moe_layer_steps", "moe_experts_touched_sum",
                    "moe_tokens_routed_sum", "moe_peak_expert_tokens_sum",
                    "moe_bias_moved_sum", "kv_pages_read_sum",
                    "moe_stream_layer_steps")

#: table entries a prefill run, and a decode step that does not take the
#: kernel, reads its cached rows through at a time, where they divide the
#: table it is handed (else the whole table in one read): the scores of a
#: 512-token chunk over 32 heads and a span of 512 positions are 34 MB in
#: float32, and a decode step of 64 lanes gathers 67 MB a span where the
#: whole table of 288 entries would be 604 MB a layer (3.7 GB of
#: temporaries in all, past a v5e's memory beside the cell's 13.2 GB of
#: weights and pool, by a compile for the chip)
READ_SPAN = 32


def lfm2_moe_param_shapes(cfg: Lfm2MoeConfig) -> Dict[str, tuple]:
    """Every parameter by name with its shape; a product's weight is
    ``[in, out]``, an expert's carries the expert first, ``conv/in``'s
    columns are ``[B | C | xt]`` and ``conv/w`` is ``[hidden, L]`` (tap
    ``k`` reads ``v`` of position ``t - (L - 1) + k``). There is no head:
    the embedding is it."""
    H, E, F = cfg.hidden_size, cfg.num_experts, cfg.expert_width
    AD, KD, D = cfg.num_heads * cfg.head_dim, \
        cfg.num_kv_heads * cfg.head_dim, cfg.head_dim
    out = {"embed": (cfg.vocab_size, H)}
    for i in range(cfg.num_layers):
        out.update({f"h{i}/norm_op": (H,), f"h{i}/norm_ffn": (H,)})
        if cfg.attention_layout[i]:
            out.update({f"h{i}/attn/q": (H, AD), f"h{i}/attn/k": (H, KD),
                        f"h{i}/attn/v": (H, KD), f"h{i}/attn/o": (AD, H),
                        f"h{i}/attn/q_norm": (D,),
                        f"h{i}/attn/k_norm": (D,)})
        else:
            out.update({f"h{i}/conv/in": (H, 3 * H),
                        f"h{i}/conv/w": (H, cfg.conv_width),
                        f"h{i}/conv/out": (H, H)})
        if cfg.is_dense(i):
            out.update({f"h{i}/mlp/gate": (H, cfg.dense_width),
                        f"h{i}/mlp/up": (H, cfg.dense_width),
                        f"h{i}/mlp/down": (cfg.dense_width, H)})
        else:
            out.update({f"h{i}/router": (H, E), f"h{i}/router_bias": (E,),
                        f"h{i}/experts/gate": (E, H, F),
                        f"h{i}/experts/up": (E, H, F),
                        f"h{i}/experts/down": (E, F, H)})
    out["norm_f"] = (H,)
    return out


def lfm2_moe_param_names(cfg: Lfm2MoeConfig):
    return list(lfm2_moe_param_shapes(cfg))


class _ConvState:
    """What a conv layer sees of the state store: its array ``[rows,
    L - 1, hidden]``, the row each of R requests reads (``read_rows
    [R]``; zeros where ``start [R]``: the run starts the request) and
    writes (``write_rows [R]``), and how many of a request's fresh rows
    are real (``length [R]``: the state after the run is what its last
    real positions leave, never a bucket's padding)."""

    def __init__(self, store, read_rows, write_rows, start, length):
        self.store, self.read_rows, self.write_rows = \
            store, read_rows, write_rows
        self.start, self.length = start, length

    def extend(self, v):
        """``v [R, Q, hidden]`` behind what each request keeps: ``[R, L
        - 1 + Q, hidden]``, the row ``j`` holding position ``hist - (L -
        1) + j`` of the request."""
        import jax.numpy as jnp
        kept = self.store[self.read_rows]
        kept = jnp.where(self.start[:, None, None], jnp.zeros_like(kept),
                         kept)
        return jnp.concatenate([kept, v.astype(kept.dtype)], axis=1)

    def write(self, ext):
        """Keep the last ``L - 1`` real rows of ``ext`` (:meth:`extend`)
        for each request, in place."""
        import jax.numpy as jnp
        keep = self.store.shape[1]
        at = self.length[:, None] + jnp.arange(keep, dtype=jnp.int32)[None]
        last = jnp.take_along_axis(ext, at[:, :, None], axis=1)
        self.store = self.store.at[self.write_rows].set(last)


def _programs(cfg: Lfm2MoeConfig, block_size: int,
              max_blocks_per_req: int) -> dict:
    """``prefill_fn``, ``decode_fn`` and ``forward``, all over one
    block function (:func:`lfm2_moe_paged_decode_fns` says what the
    first two take)."""
    import jax
    import jax.numpy as jnp

    H, L = cfg.hidden_size, cfg.num_layers
    A, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    K, LC = cfg.experts_per_token, cfg.conv_width
    BS = int(block_size)
    eps, inv_freq = cfg.norm_eps, rope_freqs(cfg.rope_theta, D)
    n_moe = L - cfg.num_dense_layers

    def _attention(lp, u, qpos, valid, cache, span, paged):
        """The attention mix on ``u [R, Q, H]`` and the pages the
        requests with a valid row read: their own through the kernel,
        every entry of the spans gathered on the plain path."""
        R, Q, _ = u.shape
        q = mm(u, lp["/attn/q"]).reshape(R, Q, A, D)
        k = mm(u, lp["/attn/k"]).reshape(R, Q, KV, D)
        v = mm(u, lp["/attn/v"]).reshape(R, Q, KV, D)
        # the per-head norms, then the rotation
        q = rotate_half(rms_norm(q, lp["/attn/q_norm"], eps),
                        rope_angles(qpos, inv_freq))
        k = rotate_half(rms_norm(k, lp["/attn/k_norm"], eps),
                        rope_angles(qpos, inv_freq))
        if paged:
            # one query a request, over its own pages in place
            att, read = paged_attend.attend_pages(
                q, k, v, qpos, valid, cache, int(max_blocks_per_req))
        else:
            att = paged_attend.attend(q, k, v, qpos, valid, cache, span)
            E = cache.table.shape[1]
            read = jnp.sum(jnp.any(valid, axis=1), dtype=jnp.int32) * span \
                * paged_attend.spans_held(E // span, span * BS, cache.hist,
                                          cap=E * BS)
        return mm(att, lp["/attn/o"]), read

    def _conv(lp, u, state):
        """The gated short convolution on ``u [R, Q, H]``, its first taps
        from the state (:class:`_ConvState`), which it leaves holding the
        run's last ``L - 1`` real ``v``."""
        Q = u.shape[1]
        bcx = mm(u, lp["/conv/in"])
        b, c, xt = bcx[..., :H], bcx[..., H:2 * H], bcx[..., 2 * H:]
        ext = state.extend(b * xt)             # [R, L - 1 + Q, H]
        w = lp["/conv/w"].astype(jnp.float32)
        z = sum(w[:, j] * ext[:, j:j + Q].astype(jnp.float32)
                for j in range(LC))
        state.write(ext)
        return mm(c * z, lp["/conv/out"])

    def _block(lp, x, qpos, valid, store, table, wb, hist, write_off, rows,
               conv, dense, span, paged):
        """One layer on the stream ``x [R, Q, H]`` (R requests, Q fresh
        rows each): ``lp`` its parameters under their names within the
        layer, ``store`` its state array (``conv``; ``rows`` says which
        rows, :class:`_ConvState`) or its pool leaf. Returns the stream,
        what its router did (the tokens each expert served ``[E]`` and,
        behind them, the choices the bias moved; nothing for a dense
        layer), the pages its attention read (nothing for a conv layer)
        and the store. Jitted on its own (``conv``, ``dense``,
        ``span`` and ``paged`` static), so that a program's trace and
        lowering hold each kind of layer once and call it."""
        R, Q, _ = x.shape
        u = rms_norm(x, lp["/norm_op"], eps)
        read = None
        if conv:
            cache = _ConvState(store, *rows)
            x = x + _conv(lp, u, cache)
            store = cache.store
        else:
            cache = paged_attend.PagedLeaf(store, table, wb, write_off, hist,
                                           BS)
            att, read = _attention(lp, u, qpos, valid, cache, span, paged)
            x = x + att
            store = cache.leaf
        m = rms_norm(x, lp["/norm_ffn"], eps)
        if dense:
            return x + swiglu(m, lp["/mlp/gate"], lp["/mlp/up"],
                              lp["/mlp/down"]), None, read, store
        flat, ok = m.reshape(R * Q, H), valid.reshape(R * Q)
        idx, wts, moved = sigmoid_bias_route(
            flat, lp["/router"], lp["/router_bias"], K, cfg.routed_scale,
            eps=ROUTER_EPS)
        # a decode step's lanes stream the experts (parallel.moe
        # .streams_experts); a prefill chunk takes ragged_dot: the tiled
        # kernel (parallel.moe.tiled_grouped_dot) wants whole tiles of 512
        # output columns, and an expert of 1,792 is none
        y, served = dropless_topk_ffn(
            flat, idx, wts, lp["/experts/gate"], lp["/experts/up"],
            lp["/experts/down"], valid=ok, activation=jax.nn.silu)
        did = jnp.concatenate(
            [served, jnp.sum(jnp.where(ok, moved, 0), dtype=jnp.int32)[None]])
        return x + y.reshape(R, Q, H), did, read, store

    block = jax.jit(_block,
                    static_argnames=("conv", "dense", "span", "paged"))

    def _stack(p, tokens, qpos, valid, kc, state, table, wb, hist,
               write_off, rows, span, paged=False):
        """Every layer over the stream; ``kc`` is one pool leaf an
        attention layer, ``state`` one array a conv layer. Returns the
        stream (before the last norm), what each expert layer's router
        did ``[expert layers, E + 1]``, the pages the attention layers
        read, summed, the leaves and the state."""
        kc, st = list(kc), list(state)
        x = jnp.take(p["embed"], tokens, axis=0).astype(jnp.float32)
        did, read = [], jnp.int32(0)
        for i in range(L):
            conv = not cfg.attention_layout[i]
            store = st if conv else kc
            j = (cfg.conv_layers if conv else cfg.attention_layers).index(i)
            x, d, r, store[j] = block(
                layer_params(p, i), x, qpos, valid, store[j], table, wb,
                hist, write_off, rows, conv=conv, dense=cfg.is_dense(i),
                span=span, paged=paged)
            if d is not None:
                did.append(d)
            if r is not None:
                read = read + r
        return x, jnp.stack(did), read, tuple(kc), tuple(st)

    def _head(p, x):
        """The last norm and the tied head on the stream ``x [..., H]``."""
        emb = p["embed"]
        return ein("...h,vh->...v",
                   rms_norm(x, p["norm_f"], eps).astype(emb.dtype), emb)

    def prefill_fn(params, kc, vc, io, state):
        tokens, length, hist = io["tokens"], io["length"], io["hist"]
        table, slot = io["table"], io["slot"]
        Lb = tokens.shape[0]
        g = hist + jnp.arange(Lb, dtype=jnp.int32)
        valid = jnp.arange(Lb) < length
        # a row lands in the block its position names; padding in the
        # null block
        wb = jnp.where(valid, table[g // BS], 0)
        entries = table.shape[0]
        span = READ_SPAN if entries % READ_SPAN == 0 else entries
        rows = (slot[None], slot[None], (hist == 0)[None], length[None])
        x, _, _, kc, conv = _stack(
            params, tokens[None], g[None], valid[None], kc, state[0],
            table[None], wb, hist[None], g % BS, rows, span)
        h_last = jax.lax.dynamic_slice_in_dim(
            x[0], jnp.maximum(length - 1, 0), 1, axis=0)
        logits = _head(params, h_last)[0]
        return kc, vc, jnp.argmax(logits).astype(jnp.int32), logits, \
            (conv,)

    def decode_fn(params, kc, vc, io, state):
        tokens, pos, active = io["tokens"], io["positions"], io["active"]
        S = tokens.shape[0]
        lanes = jnp.arange(S, dtype=jnp.int32)
        paged = paged_attend.decode_reads_pages(
            kc[0].dtype, A, KV, D, len(cfg.attention_layers))
        # an idle lane's state is written to the spare row behind the
        # slots', which nothing reads
        rows = (lanes, jnp.where(active, lanes, S), pos == 0,
                jnp.ones(S, jnp.int32))
        width = io["tables"].shape[1]
        x, did, read, kc, conv = _stack(
            params, tokens[:, None], pos[:, None], active[:, None], kc,
            state[0], io["tables"], io["write_block"], pos, io["write_off"],
            rows, READ_SPAN if width % READ_SPAN == 0 else width,
            paged=paged)
        logits = _head(params, x[:, 0])
        served = did[:, :-1]
        counted = jnp.stack([                  # PROGRAM_COUNTERS' order
            jnp.int32(n_moe), jnp.sum(served > 0, dtype=jnp.int32),
            jnp.sum(served, dtype=jnp.int32),
            jnp.sum(jnp.max(served, axis=1), dtype=jnp.int32),
            jnp.sum(did[:, -1], dtype=jnp.int32), read,
            jnp.int32(n_moe if streams_experts(S) else 0)])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return kc, vc, jnp.concatenate([nxt, counted]), logits, (conv,)

    def forward(params, tokens):
        """Logits ``[T, vocab]`` at every position of ``tokens [T]``, the
        stream before the last norm ``[T, H]`` and what each expert
        layer's router did: the block over a request with nothing cached
        and a state of zeros, every row fresh (its K, V and state go to
        stores of their own and are dropped)."""
        T = tokens.shape[0]
        dt = params["embed"].dtype
        leaf = jnp.zeros((1, BS, 2 * KV * D), dt)
        store = jnp.zeros((2, LC - 1, H), dt)
        g = jnp.arange(T, dtype=jnp.int32)
        zero = jnp.zeros((1,), jnp.int32)
        rows = (zero, zero, jnp.ones((1,), bool), jnp.full((1,), T))
        x, did, _, _, _ = _stack(
            params, tokens[None], g[None], jnp.ones((1, T), bool),
            (leaf,) * len(cfg.attention_layers),
            (store,) * len(cfg.conv_layers), jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((T,), jnp.int32), zero, g % BS, rows, 1)
        return _head(params, x[0]), x[0], did

    return {"prefill_fn": prefill_fn, "decode_fn": decode_fn,
            "forward": forward}


@COMPILE_STATS.model_build("lfm2_moe")
def lfm2_moe_paged_decode_fns(cfg: Lfm2MoeConfig, block_size: int,
                              max_blocks_per_req: int):
    """``(prefill_fn, decode_fn)``, both ``fn(params, kc, vc, io, state)
    -> (kc, vc, next, logits, state)``: ``kc`` a tuple of one leaf an
    ATTENTION layer ``[num_blocks, block_size, 2 * kv_heads * head_dim]``
    (K and V interleaved head by head), ``vc`` ``()`` (the pool's second
    side, empty for a spec of one leaf), ``state`` a tuple of ONE tuple of
    an array a CONV layer ``[max_slots + 1, L - 1, hidden]``, all three
    donated and returned:

    - ``prefill_fn``: ``io = {"tokens": [Lb] (a run of the prompt, padded
      to its bucket), "length": () real tokens of the run, "hist": ()
      positions cached before it (earlier runs of the same prompt),
      "table": [entries], "slot": ()}``; the cached rows are read
      :data:`READ_SPAN` entries at a time and no further than they go;
      the convolutions read the slot's state, or zeros where ``hist`` is
      0, and leave in it the ``v`` of the run's last real positions;
      returns ``(kc, vc, next token, logits [vocab], state)`` from
      position ``hist + length - 1``.
    - ``decode_fn``: ``io = {"tokens", "positions", "active": [S],
      "tables": [S, E] (any ``E <= max_blocks_per_req`` that holds every
      active lane's blocks), "write_block": [S], "write_off": [S]}``;
      on one TPU each lane's attention reads its own pages through
      ``paged_attend.paged_decode``, elsewhere the table it was handed,
      :data:`READ_SPAN` entries at a time; returns ``(kc, vc, next,
      logits [S, vocab], state)``, ``next [S + 6]``: behind the S next
      tokens come the step's :data:`PROGRAM_COUNTERS` (idle lanes route
      and read nothing, and their state rows stay as they were)."""
    fns = _programs(cfg, block_size, max_blocks_per_req)
    return fns["prefill_fn"], fns["decode_fn"]


def lfm2_moe_forward(cfg: Lfm2MoeConfig, params, tokens):
    """The block over a whole sequence ``tokens [T]`` with no cache:
    ``(logits [T, vocab], the stream before the last norm [T, hidden],
    what each expert layer's router did [expert layers, E + 1])`` (the
    tokens each expert served, then the choices the bias moved)."""
    import jax
    return jax.jit(_programs(cfg, 16, 1)["forward"])(params, tokens)


@COMPILE_STATS.model_build("lfm2_moe")
def lfm2_moe_paged_spec(cfg: Lfm2MoeConfig, params):
    """A :class:`~deeplearning4j_tpu.serving.paged.PagedGenerativeSpec`
    over ``params`` (a dict by :func:`lfm2_moe_param_names`, or a
    callable that gives one: ``update_model`` calls it again). K and V are
    cached in the dtype of the parameters, as ONE leaf an attention layer
    (``kv``: the heads' K and V interleaved), on one tier of the attention
    layers alone; the conv layers keep their last ``L - 1`` inputs a lane
    in a state leaf (``conv``) of the same dtype. The prefix cache,
    ``tp > 1`` and a draft are refused (a state cannot be brought back)."""
    from deeplearning4j_tpu.serving.paged import (KVLeaf,
                                                  PagedGenerativeSpec,
                                                  StateLeaf)
    pull, got = pull_params(params, lfm2_moe_param_shapes(cfg))
    return PagedGenerativeSpec(
        params=pull,
        make_fns=lambda bs, maxb: lfm2_moe_paged_decode_fns(cfg, bs, maxb),
        kv_shape=lambda nb, bs: (len(cfg.attention_layers), int(nb),
                                 cfg.num_kv_heads, int(bs), cfg.head_dim),
        vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        num_heads=cfg.num_kv_heads,
        kv_dtype=np.dtype(got["embed"].dtype).name,
        program_counters=PROGRAM_COUNTERS,
        kv_leaves=(KVLeaf("kv", 2 * cfg.num_kv_heads * cfg.head_dim),),
        state_leaves=(StateLeaf("conv", cfg.conv_layers,
                                (cfg.conv_width - 1, cfg.hidden_size)),))


__all__ = ["Lfm2MoeConfig", "Lfm2MoeUnsupportedError", "PROGRAM_COUNTERS",
           "READ_SPAN", "ROUTER_EPS", "lfm2_moe_param_shapes",
           "lfm2_moe_param_names", "lfm2_moe_paged_decode_fns",
           "lfm2_moe_forward", "lfm2_moe_paged_spec"]
