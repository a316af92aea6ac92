"""Command A+'s block (CohereLabs, ``model_type`` ``cohere2_moe``): a
PARALLEL block of window and global attention beside a sigmoid-routed
expert layer with shared experts, on the paged serving path, at one
chip's share of an expert-parallel layer.

The block, written ONCE (:func:`cohere2_moe_paged_decode_fns` and
:func:`cohere2_moe_forward` derive prefill, decode and a whole-sequence
forward from it), with ``x`` the stream ``[tokens, hidden]`` and no bias
on any projection:

- ``n = layernorm(x; g)``: mean and variance, a gain and no bias. BOTH
  branches read this ``n``, and both add to the stream: ``x = x +
  attn(n) + moe(n)`` (``use_parallel_block``).
- ``attn``: grouped queries (``num_heads`` query heads over
  ``num_kv_heads`` K/V heads), no qk-norm. On a WINDOW layer q and k are
  rotated GPT-J style (``rope_gptj``: the interleaved pairs ``(2i, 2i +
  1)`` of a head, position = index in the sequence) and position ``p``
  reads ``p - window < j <= p``; a GLOBAL layer has no positional term
  and reads every ``j <= p``. Scores ``/ sqrt(head_dim)``, softmax in
  float32.
- ``moe``: ``s = sigmoid(n @ Wr)`` over ``router_experts`` experts, the
  ``experts_per_token`` largest of ``s`` chosen, their weights ``s_e /
  sum of the chosen s`` (``parallel.moe.sigmoid_bias_route`` without a
  bias); ``dropless_topk_ffn`` with SiLU experts computes the part the
  experts HELD here give (``num_experts`` from ``first_expert``: a chip's
  share of a layer whose experts are divided over chips; what the absent
  experts would add is no part of this program); plus the
  ``num_shared_experts`` shared experts AVERAGED, as one SwiGLU product
  ``num_shared_experts`` experts wide whose down-projection is scaled by
  ``1 / num_shared_experts``.
- after the last layer ``logit_scale * layernorm(x; gf) @ E^T``: the head
  is the embedding (tied), over the vocabulary held here.

The stream, the norms, the router's product (float32 at the highest
precision) and the softmax are float32; every other product takes its
operands in the dtype the parameters are handed over in (bfloat16 as
published) and accumulates in float32, and K and V are cached in that
dtype. A layer caches ONE row a token, its K and V heads interleaved
(``[k0, v0, k1, v1, ...]``, each ``head_dim`` wide: one
``serving.paged.KVLeaf`` without heads), and the rows of window and
global layers live in two tiers of the paged pool
(``serving.paged.KVTier``), as SmallThinker's do. The attention is the
zoo's one grouped-query attention over a ``zoo.paged_attend.PagedLeaf``.
The decode program writes a step's fresh rows first and then reads each
lane's own pages in place through a Pallas kernel with the contract of
JAX's ragged paged-attention kernel (``paged_attend.attend_pages``),
where the chip can take it (``paged_attend.decode_reads_pages``;
``monitor.attention.last_decode_program()`` says which way the program
went). Elsewhere, and in the prefill program, the layer's rows are read
a span of table entries at a time into a running softmax
(``paged_attend.attend``), so that a prefill chunk of 512 queries over
128 heads never holds the scores of a whole context; fresh rows are
there attended to as they will lie in the cache (rounded to its dtype)
and written afterwards.

There is no training graph for this block; :func:`cohere2_moe_paged_spec`
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
from deeplearning4j_tpu.zoo.blocks import (ein, global_and_window_tiers,
                                           layer_norm, layer_params, mm,
                                           pull_params, rope_angles,
                                           rope_freqs, rotate_pairs, swiglu,
                                           tier_tables)


class Cohere2MoeUnsupportedError(ValueError):
    """A published key asks for what this block does not compute;
    ``key`` names it."""

    def __init__(self, key: str, value, why: str):
        super().__init__(f"{key}={value!r} is not computed: {why}")
        self.key = key


#: (key, default, the values computed, why another is refused)
_COMPUTED = (
    ("use_parallel_block", True, (True,),
     "the block is parallel: attention and experts read one norm"),
    ("position_embedding_type", "rope_gptj", ("rope_gptj",),
     "window layers rotate GPT-J's interleaved pairs"),
    ("rotary_pct", 1, (1,), "the whole head is rotated"),
    ("use_qk_norm", False, (False,), "there is no norm on q and k"),
    ("first_k_dense_replace", 0, (0,), "there is no leading dense layer"),
    ("shared_expert_combination_strategy", "average", ("average",),
     "the shared experts are averaged"),
    ("expert_selection_fn", "sigmoid", ("sigmoid",),
     "experts are chosen by their sigmoids"),
    ("norm_topk_prob", True, (True,),
     "the chosen weights are renormalised"),
    ("hidden_act", "silu", ("silu",), "experts are SwiGLU"),
    ("use_gated_activation", True, (True,), "experts are gated"),
    ("attention_bias", False, (False,), "no projection has a bias"),
    ("tie_word_embeddings", True, (True,), "the head is the embedding"),
)

_LAYER_KINDS = {"sliding_attention": 1, "full_attention": 0}


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    expert_width: int
    num_experts: int
    router_experts: int
    experts_per_token: int
    num_shared_experts: int
    window: int
    window_layout: Tuple[int, ...]
    first_expert: int = 0
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_seq_len: int = 200000

    @classmethod
    def from_dict(cls, d: dict) -> "Cohere2MoeConfig":
        """From the keys of the model's published ``config.json`` and
        three of the deployment's: ``num_experts`` is the experts HELD
        here, ``router_experts`` the router's width (``num_experts``
        where absent: every expert held) and ``first_expert`` the first
        held (0 where absent). ``layer_types`` may run past
        ``num_hidden_layers``: the first layers count. What the block
        does not compute is refused, :class:`Cohere2MoeUnsupportedError`
        naming the key."""
        for key, default, ok, why in _COMPUTED:
            got = d.get(key, default)
            if got not in ok:
                raise Cohere2MoeUnsupportedError(key, got, why)
        rope = d.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default" \
                or d.get("rope_scaling") is not None:
            raise Cohere2MoeUnsupportedError(
                "rope_parameters", rope, "rotation has no length scale")
        L = int(d["num_hidden_layers"])
        kinds = list(d["layer_types"][:L])
        for k in kinds:
            if k not in _LAYER_KINDS:
                raise Cohere2MoeUnsupportedError(
                    "layer_types", k, f"a layer is one of "
                    f"{sorted(_LAYER_KINDS)}")
        held = int(d["num_experts"])
        return cls(
            vocab_size=int(d["vocab_size"]),
            hidden_size=int(d["hidden_size"]), num_layers=L,
            num_heads=int(d["num_attention_heads"]),
            num_kv_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]),
            expert_width=int(d["intermediate_size"]),
            num_experts=held,
            router_experts=int(d.get("router_experts", held)),
            experts_per_token=int(d["num_experts_per_tok"]),
            num_shared_experts=int(d["num_shared_experts"]),
            window=int(d["sliding_window"]),
            window_layout=tuple(_LAYER_KINDS[k] for k in kinds),
            first_expert=int(d.get("first_expert", 0)),
            rope_theta=float(d.get("rope_theta",
                                   rope.get("rope_theta", 50000.0))),
            norm_eps=float(d["layer_norm_eps"]),
            logit_scale=float(d.get("logit_scale", 1.0)),
            max_seq_len=int(d["max_position_embeddings"]))

    def __post_init__(self):
        if len(self.window_layout) != self.num_layers:
            raise ValueError("a layer type is needed for every layer")
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("query heads must divide over the K/V heads, "
                             "and a head into pairs")
        if not (0 <= self.first_expert and self.first_expert
                + self.num_experts <= self.router_experts):
            raise ValueError(
                f"experts {self.first_expert} to "
                f"{self.first_expert + self.num_experts - 1} held of the "
                f"router's {self.router_experts}")
        if not 0 < self.experts_per_token <= self.router_experts:
            raise ValueError("experts_per_token must lie within the router")
        if self.num_shared_experts < 1:
            raise ValueError("the shared experts are averaged: there must "
                             "be one")

    def kv_tiers(self):
        """The pool's tiers: the global layers, then the window layers
        (a tier with no layer is left out)."""
        return global_and_window_tiers(self.window_layout, self.window)


#: what the decode program counts a step, summed over its layers:
#: SmallThinker's four under their names (layers run, HELD experts with a
#: token of an active lane, the (token, expert) pairs the routers chose
#: over ALL the router's experts, the fullest held expert's tokens), the
#: pairs whose expert is held here, the pages the active lanes'
#: attention read (the kernel: each lane's own, from the rows it was
#: handed; the plain path: every entry of the table it gathered) and the
#: layers whose held experts were streamed
#: (``parallel.moe.streams_experts``); ``block_size`` x the pages over
#: the server's ``kv_rows_gathered_sum`` is the share of what the tables
#: sent that was read (docs/serving.md says what an operator reads from
#: each)
PROGRAM_COUNTERS = ("moe_layer_steps", "moe_experts_touched_sum",
                    "moe_tokens_routed_sum", "moe_peak_expert_tokens_sum",
                    "moe_held_pairs_sum", "kv_pages_read_sum",
                    "moe_stream_layer_steps")

#: table entries a prefill run reads its cached rows through at a time,
#: as GLM-4.7-Flash's (``glm_moe_lite.PREFILL_SPAN``): the scores of a
#: 512-token chunk over 128 heads and one span of 2,048 positions are
#: 0.5 GB in float32, those over a whole 10,752-position table 2.8 GB
PREFILL_SPAN = 128


def cohere2_moe_param_shapes(cfg: Cohere2MoeConfig) -> Dict[str, tuple]:
    """Every parameter by name with its shape; a product's weight is
    ``[in, out]``, a routed expert's carries the expert first (the held
    ones, from ``first_expert``), the shared experts lie side by side
    along their width. There is no head: the embedding is it."""
    H, F, E = cfg.hidden_size, cfg.expert_width, cfg.num_experts
    A, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    S = cfg.num_shared_experts * F
    out = {"embed": (cfg.vocab_size, H)}
    for i in range(cfg.num_layers):
        out.update({
            f"h{i}/norm": (H,), f"h{i}/router": (H, cfg.router_experts),
            f"h{i}/attn/q": (H, A * D), f"h{i}/attn/k": (H, K * D),
            f"h{i}/attn/v": (H, K * D), f"h{i}/attn/o": (A * D, H),
            f"h{i}/experts/gate": (E, H, F), f"h{i}/experts/up": (E, H, F),
            f"h{i}/experts/down": (E, F, H),
            f"h{i}/shared/gate": (H, S), f"h{i}/shared/up": (H, S),
            f"h{i}/shared/down": (S, H)})
    out["norm_f"] = (H,)
    return out


def cohere2_moe_param_names(cfg: Cohere2MoeConfig):
    return list(cohere2_moe_param_shapes(cfg))


def _programs(cfg: Cohere2MoeConfig, block_size: int,
              max_blocks_per_req: int) -> dict:
    """``prefill_fn``, ``decode_fn`` and ``forward``, all over one
    block function (:func:`cohere2_moe_paged_decode_fns` says what the
    first two take)."""
    import jax
    import jax.numpy as jnp

    H, L = cfg.hidden_size, cfg.num_layers
    A, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    K = cfg.experts_per_token
    BS, W = int(block_size), cfg.window
    inv_freq = rope_freqs(cfg.rope_theta, D)
    shared_scale = np.float32(1.0 / cfg.num_shared_experts)
    widest = max(t.table_blocks(BS, max_blocks_per_req)
                 for t in cfg.kv_tiers())

    def _block(lp, x, qpos, valid, kv, table, wb, hist, write_off,
               window, span, paged):
        """One layer on the stream ``x [R, Q, H]`` (R requests, Q fresh
        rows each): ``lp`` its parameters under their names within the
        layer, ``kv`` its leaf, ``(table, wb)`` its tier's; ``paged``
        (one query a request) reads the pages through the kernel.
        Returns the stream, what its router did (the tokens each held
        expert served ``[held]`` and, behind them, the pairs the router
        chose over all its experts), the pages its attention read (the
        requests with a valid row: their own through the kernel, the
        table's width each on the plain path) and the leaf. Jitted on its
        own (``window``, ``span`` and ``paged`` static), so that a
        program's trace and lowering hold each kind of layer once and
        call it: the decode program is built once a table width."""
        cache = paged_attend.PagedLeaf(kv, table, wb, write_off, hist, BS,
                                       ring=window)
        R, Q, _ = x.shape
        n = layer_norm(x, lp["/norm"], cfg.norm_eps)
        flat, ok = n.reshape(R * Q, H), valid.reshape(R * Q)
        # ATTENTION, on n
        q = mm(n, lp["/attn/q"]).reshape(R, Q, A, D)
        k = mm(n, lp["/attn/k"]).reshape(R, Q, KV, D)
        v = mm(n, lp["/attn/v"]).reshape(R, Q, KV, D)
        if window:
            q = rotate_pairs(q, rope_angles(qpos, inv_freq))
            k = rotate_pairs(k, rope_angles(qpos, inv_freq))
        if paged:
            att, read = paged_attend.attend_pages(
                q, k, v, qpos, valid, cache, widest, W if window else None)
        else:
            att = paged_attend.attend(q, k, v, qpos, valid, cache, span,
                                      W if window else None)
            read = jnp.sum(jnp.any(valid, axis=1), dtype=jnp.int32) \
                * table.shape[1]
        # the EXPERTS, on the same n: the router over all its experts,
        # the held experts' part, the shared experts' mean. A decode
        # step's lanes stream the held experts (parallel.moe
        # .streams_experts); a prefill chunk's grouped products are
        # ragged_dot's: the held pairs are about k / router_experts of a
        # run's rows, and ragged_dot multiplies those alone, where the
        # tiled kernel (parallel.moe.tiled_grouped_dot) would multiply
        # every row, the pairs routed elsewhere riding behind the last
        # held expert, and at the published widths XLA copies each held
        # expert stack into the kernel's layout first (a 512-token chunk
        # then needs 18.4 GiB of a v5e's 15.75, by a compile for the
        # chip; PERF.md §4)
        idx, wts, _ = sigmoid_bias_route(flat, lp["/router"], None, K)
        y, served = dropless_topk_ffn(
            flat, idx, wts, lp["/experts/gate"], lp["/experts/up"],
            lp["/experts/down"], first_expert=cfg.first_expert, valid=ok,
            activation=jax.nn.silu)
        y = y + swiglu(flat, lp["/shared/gate"], lp["/shared/up"],
                       lp["/shared/down"]) * shared_scale
        did = jnp.concatenate(
            [served, (jnp.sum(ok, dtype=jnp.int32) * K)[None]])
        x = x + mm(att, lp["/attn/o"]) + y.reshape(R, Q, H)
        return x, did, read, cache.leaf

    block = jax.jit(_block, static_argnames=("window", "span", "paged"))

    def _stack(p, tokens, qpos, valid, kc, tiers, hist, write_off, span,
               paged=False):
        """Every layer over the stream; ``kc`` is one leaf a layer,
        ``tiers`` maps a layer to its tier's ``(table, write_block)``.
        Returns the stream (before the last norm), what each layer's
        router did ``[L, held + 1]``, the pages the layers' attention
        read, summed, and the leaves."""
        kc = list(kc)
        x = jnp.take(p["embed"], tokens, axis=0).astype(jnp.float32)
        did, read = [], jnp.int32(0)
        for i in range(L):
            x, d, r, kc[i] = block(
                layer_params(p, i), x, qpos, valid, kc[i], *tiers[i], hist,
                write_off,
                window=bool(cfg.window_layout[i]), span=span, paged=paged)
            did.append(d)
            read = read + r
        return x, jnp.stack(did), read, tuple(kc)

    def _head(p, x):
        """The last norm and the tied head on the stream ``x [..., H]``."""
        emb = p["embed"]
        h = layer_norm(x, p["norm_f"], cfg.norm_eps).astype(emb.dtype)
        return ein("...h,vh->...v", h, emb) * np.float32(cfg.logit_scale)

    def _tiers(io, table_key, lift):
        return tier_tables(cfg.kv_tiers(), io, table_key, lift, BS,
                           max_blocks_per_req)

    def prefill_fn(params, kc, vc, io):
        tokens, length, hist = io["tokens"], io["length"], io["hist"]
        Lb = tokens.shape[0]
        g = hist + jnp.arange(Lb, dtype=jnp.int32)
        valid = jnp.arange(Lb) < length
        x, _, _, kc = _stack(
            params, tokens[None], g[None], valid[None], kc,
            _tiers(io, "table", lambda t: t[None]), hist[None], g % BS,
            PREFILL_SPAN)
        h_last = jax.lax.dynamic_slice_in_dim(
            x[0], jnp.maximum(length - 1, 0), 1, axis=0)
        logits = _head(params, h_last)[0]
        return kc, vc, jnp.argmax(logits).astype(jnp.int32), logits

    def decode_fn(params, kc, vc, io):
        tokens, pos, active = io["tokens"], io["positions"], io["active"]
        x, did, read, kc = _stack(
            params, tokens[:, None], pos[:, None], active[:, None], kc,
            _tiers(io, "tables", lambda t: t), pos, io["write_off"], 0,
            paged=paged_attend.decode_reads_pages(kc[0].dtype, A, KV, D, L))
        logits = _head(params, x[:, 0])
        served = did[:, :-1]
        counted = jnp.stack([                  # PROGRAM_COUNTERS' order
            jnp.int32(L), jnp.sum(served > 0, dtype=jnp.int32),
            jnp.sum(did[:, -1], dtype=jnp.int32),
            jnp.sum(jnp.max(served, axis=1), dtype=jnp.int32),
            jnp.sum(served, dtype=jnp.int32), read,
            jnp.int32(L if streams_experts(tokens.shape[0]) else 0)])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return kc, vc, jnp.concatenate([nxt, counted]), logits

    def forward(params, tokens):
        """Logits ``[T, vocab]`` at every position of ``tokens [T]``, the
        stream before the last norm ``[T, H]`` and what each layer's
        router did: the block over a request with nothing cached, every
        row fresh (its K and V go to a block of its own and are
        dropped)."""
        T = tokens.shape[0]
        leaf = jnp.zeros((1, BS, 2 * KV * D), params["embed"].dtype)
        none = (jnp.zeros((1, 1), jnp.int32), jnp.zeros((T,), jnp.int32))
        g = jnp.arange(T, dtype=jnp.int32)
        x, did, _, _ = _stack(params, tokens[None], g[None],
                              jnp.ones((1, T), bool), (leaf,) * L,
                              dict.fromkeys(range(L), none),
                              jnp.zeros((1,), jnp.int32), g % BS, 0)
        return _head(params, x[0]), x[0], did

    return {"prefill_fn": prefill_fn, "decode_fn": decode_fn,
            "forward": forward}


@COMPILE_STATS.model_build("cohere2_moe")
def cohere2_moe_paged_decode_fns(cfg: Cohere2MoeConfig, block_size: int,
                                 max_blocks_per_req: int):
    """``(prefill_fn, decode_fn)`` over the two-tier paged pool, both
    ``fn(params, kc, vc, io)`` with ``kc`` a tuple of one leaf a layer
    ``[num_blocks, block_size, 2 * kv_heads * head_dim]`` (K and V
    interleaved head by head), donated and returned, and ``vc`` ``()``
    (the pool's second side, empty for a spec of one leaf). With ``<t>``
    a tier's name (``global``, ``window``;
    :meth:`Cohere2MoeConfig.kv_tiers`):

    - ``prefill_fn``: ``io = {"tokens": [Lb] (a run of the prompt,
      padded to its bucket), "length": () real tokens of the run,
      "hist": () positions cached before it (earlier runs of the same
      prompt), "table.<t>": [entries_t], "write_block.<t>": [Lb] the
      block each fresh row lands in (the null block for padding)}``;
      the cached rows are read :data:`PREFILL_SPAN` entries at a time and
      no further than they go; returns ``(kc, vc, next token, logits
      [vocab])`` from position ``hist + length - 1``.
    - ``decode_fn``: ``io = {"tokens", "positions", "active": [S],
      "tables.<t>": [S, E_t] (a window tier's ring: its ``entries_t``;
      the global tier: any ``E_t <= entries_t`` that holds every active
      lane's blocks, read whole), "write_block.<t>": [S], "write_off":
      [S]}``; returns ``(kc, vc, next [S + 6], logits [S, vocab])``:
      behind the S next tokens come the step's :data:`PROGRAM_COUNTERS`
      (idle lanes route and read nothing). Each layer writes the step's
      fresh rows and then reads every active lane's own pages through
      the decode kernel (the global table as handed; the ring from the
      oldest block its window can see), or, where
      ``paged_attend.kernel_refusal`` names a reason, gathers the whole
      table as the prefill program does.
    """
    fns = _programs(cfg, block_size, max_blocks_per_req)
    return fns["prefill_fn"], fns["decode_fn"]


def cohere2_moe_forward(cfg: Cohere2MoeConfig, params, tokens):
    """The block over a whole sequence ``tokens [T]`` with no cache:
    ``(logits [T, vocab], the stream before the last norm [T, hidden],
    what each layer's router did [L, held + 1])`` (the tokens each held
    expert served, then the pairs chosen)."""
    import jax
    return jax.jit(_programs(cfg, 16, 1)["forward"])(params, tokens)


@COMPILE_STATS.model_build("cohere2_moe")
def cohere2_moe_paged_spec(cfg: Cohere2MoeConfig, params):
    """A :class:`~deeplearning4j_tpu.serving.paged.PagedGenerativeSpec`
    over ``params`` (a dict by :func:`cohere2_moe_param_names`, or a
    callable that gives one: ``update_model`` calls it again). K and V
    are cached in the dtype of the parameters, as ONE leaf a layer
    (``kv``: the heads' K and V interleaved, ``2 x kv_heads x head_dim``
    wide, the bytes of a K leaf and a V leaf), on the two tiers of
    :meth:`Cohere2MoeConfig.kv_tiers`. A leaf without heads: ``tp > 1``,
    int8 rows and a dense draft are refused
    (``serving.paged.KVLeafUnsupportedError``)."""
    from deeplearning4j_tpu.serving.paged import KVLeaf, PagedGenerativeSpec
    pull, got = pull_params(params, cohere2_moe_param_shapes(cfg))
    return PagedGenerativeSpec(
        params=pull,
        make_fns=lambda bs, maxb: cohere2_moe_paged_decode_fns(cfg, bs, maxb),
        kv_shape=lambda nb, bs: (cfg.num_layers, int(nb), cfg.num_kv_heads,
                                 int(bs), cfg.head_dim),
        vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        num_heads=cfg.num_kv_heads,
        kv_dtype=np.dtype(got["embed"].dtype).name,
        kv_tiers=cfg.kv_tiers(), program_counters=PROGRAM_COUNTERS,
        kv_leaves=(KVLeaf("kv", 2 * cfg.num_kv_heads * cfg.head_dim),))


__all__ = ["Cohere2MoeConfig", "Cohere2MoeUnsupportedError",
           "PROGRAM_COUNTERS", "PREFILL_SPAN", "cohere2_moe_param_shapes",
           "cohere2_moe_param_names", "cohere2_moe_paged_decode_fns",
           "cohere2_moe_forward", "cohere2_moe_paged_spec"]
