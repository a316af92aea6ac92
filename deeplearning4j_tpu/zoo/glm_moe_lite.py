"""GLM-4.7-Flash's block (zai-org, ``model_type`` ``glm4_moe_lite``): a
decoder of LATENT attention over a dense first layer and sigmoid-routed
expert layers with a shared expert, on the paged serving path.

The block, written ONCE (:func:`glm_moe_lite_paged_decode_fns` derives
prefill and decode from it), with ``x`` the stream ``[tokens, hidden]``
and no bias on any projection:

- ``a = rmsnorm(x; g1)``. Queries through a bottleneck with a norm:
  ``q = rmsnorm(a @ Wqa; gq) @ Wqb``, each head split into ``q_nope`` and
  ``q_rope``. K and V from ONE latent row a token: ``[c, kr] = a @ Wkva``,
  ``c = rmsnorm(c; gkv)`` (``kv_lora_rank`` wide), ``kr`` one rotary key
  (``qk_rope_head_dim`` wide) shared by every head. ``q_rope`` and ``kr``
  are rotated (rotate-half, position = index in the sequence). THE CACHE
  HOLDS ``[c, kr]`` after the norm and the rotation, one row a token:
  the pool's one leaf a layer (``serving.paged.KVLeaf``), no V. (A row
  wider than the TPU's 128 lanes is laid out in whole lane tiles:
  :attr:`GlmMoeLiteConfig.leaf_width`.)
- the published form expands the context through ``Wkvb``: ``[k_nope_h,
  v_h] = c @ Wkvb`` a head, ``k_h = [k_nope_h, kr]``, scores ``q_h . k_h
  / sqrt(qk_nope + qk_rope)``, softmax in float32, ``o = concat_h(p_h @
  v_h)``. The ABSORBED form gives the same numbers with nothing of the
  context multiplied by ``Wkvb``: with ``Wkvb`` split by head into
  ``Wuk_h`` and ``Wuv_h``, ``score_h = ((q_nope_h @ Wuk_h^T) . c +
  q_rope_h . kr) * scale`` and ``o_h = (p_h @ C) @ Wuv_h``: every head
  reads the same latent rows. BOTH programs attend absorbed (``_attend``):
  decode by design, and a prefill run because it measured faster on the
  chip at every size the cell serves (a 512-token chunk behind 8,192
  rows 59 against 67 ms, a chat prompt 8 against 20: the published form
  expands the whole table's rows whatever the run holds; PERF.md section
  6). The published form is the reference's (``benchmark/reference/
  glm4_moe_lite.py``), which the tests hold this one to.
  ``x = x + o @ Wo``.
- ``m = rmsnorm(x; g2)``. The first ``first_dense_layers`` layers: ``x +=
  (silu(m @ G) * (m @ U)) @ D``. The others: ``parallel.moe.
  sigmoid_bias_route`` (the experts with the largest ``sigmoid(m @ Wr) +
  b``; weights the chosen sigmoids without ``b``, over their sum, times
  ``routed_scale``), ``dropless_topk_ffn`` with SiLU experts, no token
  dropped, plus the shared expert's dense product, which every token
  passes.
- after the last layer ``rmsnorm(x; gf) @ Wh``, an untied head, logits in
  float32.

The stream, the norms, the router and the softmax are float32; every
other product takes its operands in the dtype the parameters are handed
over in (bfloat16 as published) and accumulates in float32, and the
latent rows are cached in that dtype. Fresh rows are attended to as they
will lie in the cache (rounded to its dtype) and written afterwards.

The next-token-prediction module of the published model
(``num_nextn_predict_layers``) is no part of its logits and is not
served (ROADMAP R3). There is no training graph for this block;
:func:`glm_moe_lite_paged_spec` serves parameters handed over by name.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from deeplearning4j_tpu.compilecache.cache import COMPILE_STATS
from deeplearning4j_tpu.parallel.moe import (GROUPED_TILE_COLS,
                                             GROUPED_TILE_ROWS,
                                             dropless_topk_ffn,
                                             sigmoid_bias_route,
                                             tiled_grouped_dot)
from deeplearning4j_tpu.zoo.paged_attend import (NEG, over_spans,
                                                 softmax_merge)


@dataclasses.dataclass(frozen=True)
class GlmMoeLiteConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    dense_width: int
    expert_width: int
    num_experts: int
    experts_per_token: int
    num_shared_experts: int = 1
    first_dense_layers: int = 1
    routed_scale: float = 1.0
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    max_seq_len: int = 16384

    @classmethod
    def from_dict(cls, d: dict) -> "GlmMoeLiteConfig":
        """From the keys of the model's published ``config.json``; what
        the block does not compute is refused by name."""
        refused = {
            "hidden_act": d.get("hidden_act", "silu") != "silu",
            "attention_bias": bool(d.get("attention_bias", False)),
            "tie_word_embeddings": bool(d.get("tie_word_embeddings", False)),
            "rope_scaling": d.get("rope_scaling") is not None,
            "partial_rotary_factor": d.get("partial_rotary_factor", 1) != 1,
            "n_group/topk_group": (d.get("n_group", 1),
                                   d.get("topk_group", 1)) != (1, 1),
            "topk_method": d.get("topk_method", "noaux_tc") != "noaux_tc",
            "norm_topk_prob": not d.get("norm_topk_prob", True),
            "num_key_value_heads": int(d.get(
                "num_key_value_heads", d["num_attention_heads"]))
            != int(d["num_attention_heads"]),
            # no part of the model's own logits; as a draft it needs the
            # target's hidden state (ROADMAP R3)
            "num_nextn_predict_layers": bool(
                d.get("num_nextn_predict_layers", 0))}
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(f"not computed by this block: {bad}")
        return cls(
            vocab_size=int(d["vocab_size"]),
            hidden_size=int(d["hidden_size"]),
            num_layers=int(d["num_hidden_layers"]),
            num_heads=int(d["num_attention_heads"]),
            q_lora_rank=int(d["q_lora_rank"]),
            kv_lora_rank=int(d["kv_lora_rank"]),
            qk_nope_head_dim=int(d["qk_nope_head_dim"]),
            qk_rope_head_dim=int(d["qk_rope_head_dim"]),
            v_head_dim=int(d["v_head_dim"]),
            dense_width=int(d["intermediate_size"]),
            expert_width=int(d["moe_intermediate_size"]),
            num_experts=int(d["n_routed_experts"]),
            experts_per_token=int(d["num_experts_per_tok"]),
            num_shared_experts=int(d.get("n_shared_experts", 0)),
            first_dense_layers=int(d.get("first_k_dense_replace", 0)),
            routed_scale=float(d.get("routed_scaling_factor", 1.0)),
            rope_theta=float(d["rope_theta"]),
            rms_eps=float(d["rms_norm_eps"]),
            max_seq_len=int(d["max_position_embeddings"]))

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary width must be even")
        if not 0 <= self.first_dense_layers <= self.num_layers:
            raise ValueError("first_dense_layers must lie within the depth")

    @property
    def row_width(self) -> int:
        """Numbers a token's cached row holds: the latent and the one
        rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def leaf_width(self) -> int:
        """Numbers a row of the pool's leaf holds: :attr:`row_width`, and
        from one lane tile on a whole number of tiles (576 -> 640, the
        rest zeros). The TPU stores a row in tiles of 128 lanes whatever
        is declared, so the padding costs no memory a narrower leaf would
        save; but a leaf ``[blocks, block, 576]`` gets the compiler's
        default layout with its BLOCKS along the lanes, and every program
        then copies the whole pool to row-major and back, each layer
        (measured: 151 MB twice a layer and program, 28% of the device's
        time; PERF.md section 6), where a declared multiple of 128 is
        written in place and read where it lies."""
        w = self.row_width
        return w if w < 128 else -(-w // 128) * 128

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_dense_layers


#: table entries a prefill run reads its cached rows through at a time
#: (128 blocks of 16 are 2,048 positions: a product large enough to fill
#: the MXU, a step fine enough that a run reads little past ``hist``)
PREFILL_SPAN = 128

#: tokens of a run from which the experts' grouped products are the tiled
#: kernel (``parallel.moe.tiled_grouped_dot``): a prefill CHUNK, the one
#: long run the paged path has. Measured at 512 tokens (2,048 sorted rows:
#: 0.66 ms a product against ``ragged_dot``'s 1.35, a chunk 41 -> 28.6 ms)
#: and at a decode step's 8 (32 rows, where ``ragged_dot`` reads only the
#: experts that have a token); a chat prompt's 16 to 64 tokens stay with
#: ``ragged_dot`` and nothing between was measured (PERF.md section 6)
TILED_RUN_TOKENS = 512

#: what the decode program counts a step, summed over its expert layers:
#: SmallThinker's four under their names (layers run, experts with a token
#: of an active lane, (token, expert) pairs, the fullest expert's tokens)
#: and the (token, expert) choices the correction bias changed against
#: the largest bare sigmoids (docs/serving.md says what an operator reads
#: from each)
PROGRAM_COUNTERS = ("moe_layer_steps", "moe_experts_touched_sum",
                    "moe_tokens_routed_sum", "moe_peak_expert_tokens_sum",
                    "moe_bias_moved_sum")


def glm_moe_lite_param_shapes(cfg: GlmMoeLiteConfig) -> Dict[str, tuple]:
    """Every parameter by name with its shape; a product's weight is
    ``[in, out]``, an expert's carries the expert first. ``attn/q_b``'s
    columns are a head's ``[nope | rope]``, ``attn/kv_a``'s ``[latent |
    rotary key]``, ``attn/kv_b``'s a head's ``[k_nope | v]``, head after
    head, as the published projections lay them out."""
    H, A, E = cfg.hidden_size, cfg.num_heads, cfg.num_experts
    F, S = cfg.expert_width, cfg.num_shared_experts * cfg.expert_width
    DQ = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    out = {"embed": (cfg.vocab_size, H)}
    for i in range(cfg.num_layers):
        out.update({
            f"h{i}/norm_1": (H,),
            f"h{i}/attn/q_a": (H, cfg.q_lora_rank),
            f"h{i}/attn/q_norm": (cfg.q_lora_rank,),
            f"h{i}/attn/q_b": (cfg.q_lora_rank, A * DQ),
            f"h{i}/attn/kv_a": (H, cfg.row_width),
            f"h{i}/attn/kv_norm": (cfg.kv_lora_rank,),
            f"h{i}/attn/kv_b": (cfg.kv_lora_rank,
                                A * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            f"h{i}/attn/o": (A * cfg.v_head_dim, H),
            f"h{i}/norm_2": (H,)})
        if cfg.is_dense(i):
            out.update({f"h{i}/mlp/gate": (H, cfg.dense_width),
                        f"h{i}/mlp/up": (H, cfg.dense_width),
                        f"h{i}/mlp/down": (cfg.dense_width, H)})
            continue
        out.update({
            f"h{i}/router": (H, E), f"h{i}/router_bias": (E,),
            f"h{i}/experts/gate": (E, H, F), f"h{i}/experts/up": (E, H, F),
            f"h{i}/experts/down": (E, F, H)})
        if S:
            out.update({f"h{i}/shared/gate": (H, S),
                        f"h{i}/shared/up": (H, S),
                        f"h{i}/shared/down": (S, H)})
    out["norm_f"] = (H,)
    out["lm_head"] = (H, cfg.vocab_size)
    return out


def glm_moe_lite_param_names(cfg: GlmMoeLiteConfig):
    return list(glm_moe_lite_param_shapes(cfg))


class _LatentCache:
    """What a block sees of the paged pool: its layer's one leaf
    ``[num_blocks, block_size, leaf_width]``, the requests' ``table [R,
    entries]`` (entry ``e`` holds block ``e``; any width that holds every
    block a request has cached), ``hist`` [R], how many positions each
    request has cached, and where the N fresh rows (request-major) go:
    ``(write_block [N], write_off [N])``."""

    def __init__(self, leaf, table, write_block, write_off, hist,
                 block_size):
        self.leaf, self.table, self.hist = leaf, table, hist
        self.write_block, self.write_off = write_block, write_off
        self.BS = int(block_size)

    def read(self, first, entries: int):
        """The cached rows of every request through ``entries`` entries
        of its table from entry ``first`` (which may be traced), ``[R, T,
        leaf_width]``, and which of them hold a position of the request
        ``[R, T]``."""
        import jax
        import jax.numpy as jnp
        R, E = self.table.shape[0], int(entries)
        part = jax.lax.dynamic_slice_in_dim(self.table, first, E, axis=1)
        rows = self.leaf[part].reshape(R, E * self.BS, -1)
        pos = first * self.BS + jnp.arange(E * self.BS, dtype=jnp.int32)
        return rows, pos[None] < self.hist[:, None]

    def write(self, rows):
        """The fresh rows ``[N, leaf_width]``, in place."""
        self.leaf = self.leaf.at[(self.write_block, self.write_off)].set(
            rows.astype(self.leaf.dtype))


@COMPILE_STATS.model_build("glm_moe_lite")
def glm_moe_lite_paged_decode_fns(cfg: GlmMoeLiteConfig, block_size: int,
                                  max_blocks_per_req: int):
    """``(prefill_fn, decode_fn)`` over the paged pool, both ``fn(params,
    kc, vc, io)`` with ``kc`` a tuple of one latent leaf a layer, donated
    and returned, and ``vc`` the empty tuple a pool of one leaf has on its
    second side (``serving.paged.PagedGenerativeSpec.kv_leaves``):

    - ``prefill_fn``: ``io = {"tokens": [Lb] (a run of the prompt, padded
      to its bucket), "length": () real tokens of the run, "hist": ()
      positions cached before it (earlier runs of the same prompt, or a
      prefix the cache held), "table": [entries]}`` (the whole table; the
      program reads the ``hist`` cached rows :data:`PREFILL_SPAN` entries
      at a time and no further than they go); returns ``(kc, vc,
      next token, logits [vocab])`` from position ``hist + length - 1``.
    - ``decode_fn``: ``io = {"tokens", "positions", "active": [S],
      "tables": [S, E] (any ``E <= max_blocks_per_req`` that holds every
      active lane's blocks), "write_block": [S], "write_off": [S]}``;
      returns ``(kc, vc, next [S + 5], logits [S, vocab])``: behind the S
      next tokens come the step's :data:`PROGRAM_COUNTERS`.

    Both attend in the ABSORBED form: no array of the context's length
    has a per-head K or V width."""
    import jax
    import jax.numpy as jnp

    H, L, A = cfg.hidden_size, cfg.num_layers, cfg.num_heads
    C, DN, DR, DV = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    BS, W = int(block_size), cfg.leaf_width
    PAD = W - cfg.row_width       # zero columns behind [c, kr]
    scale = np.float32(1.0 / np.sqrt(DN + DR))
    inv_freq = jnp.asarray(
        cfg.rope_theta ** (-np.arange(0, DR, 2, dtype=np.float64) / DR),
        jnp.float32)

    def _rmsnorm(x, g):
        x = x.astype(jnp.float32)
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + cfg.rms_eps) * g.astype(jnp.float32)

    def _mm(x, w):
        return jnp.matmul(x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    def _ein(eq, a, b):
        return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)

    def _rope(x, ang):
        # x [..., DR] float32, ang [..., DR / 2]: rotate-half over DR
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin

    def _attend(q_nope, q_rope, fresh, w_kvb, qpos, valid, cache, span):
        """q_nope [R, Q, A, DN], q_rope [R, Q, A, DR] (rotated), fresh
        [R, Q, leaf_width] (the fresh latent rows, in the cache's dtype):
        each query over its request's cached rows and the fresh rows up
        to itself, one softmax over both, in the absorbed form: the
        queries are carried into the latent space and every head reads
        the same rows, whole (the columns of the weighted sum behind the
        latent are dropped: cheaper than a copy of the context without
        them). The cached rows are read ``span`` table entries at a time,
        as many spans as hold ``hist`` rows (a loop whose length the
        device reads off ``hist``), each merged into a running softmax; a
        ``span`` of the whole table is one read. Returns ``[R, Q, A *
        DV]``."""
        R, Q = qpos.shape
        dt = cache.leaf.dtype
        w3 = w_kvb.reshape(C, A, DN + DV)
        q_lat = _ein("rqad,cad->rqac", q_nope.astype(dt), w3[..., :DN])
        qf = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros(q_rope.shape[:-1] + (PAD,))],
            axis=-1).astype(dt)
        neg = jnp.float32(NEG)

        def merge(carry, s, seen, rows):
            """One more set of scores ``s [R, A, Q, T]`` over ``rows [R,
            T, W]`` into the running softmax (``paged_attend``)."""
            return softmax_merge(
                carry, s, seen,
                lambda e: _ein("raqt,rtw->raqw", e.astype(dt), rows), neg)

        def over_cached(i, carry):
            rows, held = cache.read(i * span, span)
            return merge(carry, _ein("rqaw,rtw->raqt", qf, rows) * scale,
                         held[:, None, None, :], rows)

        carry = (jnp.full((R, A, Q), neg), jnp.zeros((R, A, Q), jnp.float32),
                 jnp.zeros((R, A, Q, W), jnp.float32))
        carry = over_spans(over_cached, carry, cache.table.shape[1] // span,
                           span * BS, cache.hist)
        # a row sees itself whether or not it is valid, so that an idle
        # lane or a padded row has a finite result (it lands in the null
        # block, which every table's unused entries point at)
        see_f = (qpos[:, None, :] <= qpos[:, :, None]) \
            & (valid[:, None, :] | jnp.eye(Q, dtype=bool)[None])
        _, total, o_lat = merge(
            carry, _ein("rqaw,rpw->raqp", qf, fresh) * scale,
            see_f[:, None], fresh)
        o_lat = o_lat[..., :C] / total[..., None]
        out = _ein("raqc,cav->rqav", o_lat.astype(dt), w3[..., DN:])
        return out.reshape(R, Q, A * DV)

    def chunk_tiles(tokens: int) -> bool:
        """A run long enough for the tiled grouped product, at widths
        that fill its tiles (the published ones do)."""
        return tokens >= TILED_RUN_TOKENS and not (
            tokens * cfg.experts_per_token % GROUPED_TILE_ROWS
            or H % GROUPED_TILE_COLS or cfg.expert_width % GROUPED_TILE_COLS)

    def _gated(m, gate, up, down):
        return _mm(jax.nn.silu(_mm(m, gate)) * _mm(m, up), down)

    def _block(lp, x, qpos, valid, leaf, table, wb, hist, write_off, dense,
               span):
        """One layer on the stream ``x [R, Q, H]`` (R requests, Q fresh
        rows each): ``lp`` its parameters under their names within the
        layer, ``leaf`` its latent leaf. Returns the stream, what its
        router did (the tokens each expert served ``[E]`` and, behind
        them, the choices the bias moved; nothing for a dense layer) and
        the leaf. Jitted on its own (``dense`` and ``span`` static),
        so that a program's trace and lowering hold each kind of layer once
        and call it: the decode program is built once a table width."""
        cache = _LatentCache(leaf, table, wb, write_off, hist, BS)
        R, Q, _ = x.shape
        a = _rmsnorm(x, lp["/norm_1"])
        q = _mm(_rmsnorm(_mm(a, lp["/attn/q_a"]), lp["/attn/q_norm"]),
                lp["/attn/q_b"]).reshape(R, Q, A, DN + DR)
        ckr = _mm(a, lp["/attn/kv_a"])                      # [R, Q, C + DR]
        ang = qpos[..., None].astype(jnp.float32) * inv_freq
        fresh = jnp.concatenate(
            [_rmsnorm(ckr[..., :C], lp["/attn/kv_norm"]),
             _rope(ckr[..., C:], ang), jnp.zeros((R, Q, PAD))],
            axis=-1).astype(leaf.dtype)
        att = _attend(q[..., :DN], _rope(q[..., DN:], ang[:, :, None]),
                      fresh, lp["/attn/kv_b"], qpos, valid, cache, span)
        cache.write(fresh.reshape(R * Q, W))
        x = x + _mm(att, lp["/attn/o"])
        m = _rmsnorm(x, lp["/norm_2"])
        if dense:
            y = _gated(m, lp["/mlp/gate"], lp["/mlp/up"], lp["/mlp/down"])
            return x + y, None, cache.leaf
        flat, ok = m.reshape(R * Q, H), valid.reshape(R * Q)
        idx, wts, moved = sigmoid_bias_route(
            flat, lp["/router"], lp["/router_bias"], cfg.experts_per_token,
            cfg.routed_scale)
        y, served = dropless_topk_ffn(
            flat, idx, wts, lp["/experts/gate"], lp["/experts/up"],
            lp["/experts/down"], valid=ok, activation=jax.nn.silu,
            grouped=tiled_grouped_dot if chunk_tiles(R * Q) else None)
        if "/shared/gate" in lp:
            # the shared expert: every token passes it, added once
            y = y + _gated(flat, lp["/shared/gate"], lp["/shared/up"],
                           lp["/shared/down"])
        did = jnp.concatenate(
            [served, jnp.sum(jnp.where(ok, moved, 0), dtype=jnp.int32)[None]])
        return x + y.reshape(R, Q, H), did, cache.leaf

    block = jax.jit(_block, static_argnames=("dense", "span"))

    def _stack(p, tokens, qpos, valid, kc, table, wb, hist, write_off,
               span):
        """Every layer over the stream; the cached rows are read ``span``
        table entries at a time (:func:`_attend`). Returns the stream after the
        last norm, what each expert layer's router did ``[expert layers,
        E + 1]``, and the leaves."""
        kc = list(kc)
        if table.shape[1] > int(max_blocks_per_req):
            raise ValueError(f"a table of {table.shape[1]} entries, the "
                             f"longest request holds {max_blocks_per_req}")
        x = jnp.take(p["embed"], tokens, axis=0).astype(jnp.float32)
        did = []
        for i in range(L):
            sc = f"h{i}"
            lp = {n[len(sc):]: a for n, a in p.items()
                  if n.startswith(sc + "/")}
            x, d, kc[i] = block(lp, x, qpos, valid, kc[i], table, wb, hist,
                                write_off, dense=cfg.is_dense(i),
                                span=span)
            if d is not None:
                did.append(d)
        return _rmsnorm(x, p["norm_f"]), jnp.stack(did), tuple(kc)

    def prefill_fn(params, kc, vc, io):
        tokens, length, hist = io["tokens"], io["length"], io["hist"]
        table = io["table"]
        Lb = tokens.shape[0]
        g = hist + jnp.arange(Lb, dtype=jnp.int32)
        valid = jnp.arange(Lb) < length
        # a row lands in the block its position names; padding in the
        # null block
        wb = jnp.where(valid, table[g // BS], 0)
        # the server hands a prefill the whole table; the run reads its
        # cached rows a span of entries at a time, as many spans as hold
        # them (a 512-token chunk behind 2,048 rows attends to 2,048
        # cached positions, not to max_seq_len's 16,384)
        entries = table.shape[0]
        span = PREFILL_SPAN if entries % PREFILL_SPAN == 0 else entries
        x, _, kc = _stack(params, tokens[None], g[None], valid[None], kc,
                          table[None], wb, hist[None], g % BS, span)
        h_last = jax.lax.dynamic_slice_in_dim(
            x[0], jnp.maximum(length - 1, 0), 1, axis=0)
        logits = _mm(h_last, params["lm_head"])[0]
        return kc, vc, jnp.argmax(logits).astype(jnp.int32), logits

    def decode_fn(params, kc, vc, io):
        tokens, pos, active = io["tokens"], io["positions"], io["active"]
        x, did, kc = _stack(params, tokens[:, None], pos[:, None],
                            active[:, None], kc, io["tables"],
                            io["write_block"], pos, io["write_off"],
                            io["tables"].shape[1])
        logits = _mm(x[:, 0], params["lm_head"])
        served = did[:, :-1]
        counted = jnp.stack([                  # PROGRAM_COUNTERS' order
            jnp.int32(did.shape[0]), jnp.sum(served > 0, dtype=jnp.int32),
            jnp.sum(served, dtype=jnp.int32),
            jnp.sum(jnp.max(served, axis=1), dtype=jnp.int32),
            jnp.sum(did[:, -1], dtype=jnp.int32)])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return kc, vc, jnp.concatenate([nxt, counted]), logits

    return prefill_fn, decode_fn


@COMPILE_STATS.model_build("glm_moe_lite")
def glm_moe_lite_paged_spec(cfg: GlmMoeLiteConfig, params):
    """A :class:`~deeplearning4j_tpu.serving.paged.PagedGenerativeSpec`
    over ``params`` (a dict by :func:`glm_moe_lite_param_names`, or a
    callable that gives one: ``update_model`` calls it again). The pool
    has ONE leaf a layer, the latent row, cached in the dtype of the
    parameters; one tier that keeps every block, so the table-width
    ladder, chunked prefill and the prefix cache serve it as they are."""
    from deeplearning4j_tpu.serving.paged import KVLeaf, PagedGenerativeSpec
    if cfg.first_dense_layers >= cfg.num_layers:
        raise ValueError("the decode program counts its expert layers: "
                         "there is none")
    pull = params if callable(params) else (lambda: params)
    got, want = pull(), glm_moe_lite_param_shapes(cfg)
    if set(got) != set(want):
        raise ValueError(f"parameter names differ: "
                         f"{sorted(set(got) ^ set(want))[:4]}")
    for n, shape in want.items():
        if tuple(np.shape(got[n])) != shape:
            raise ValueError(f"{n}: shape {tuple(np.shape(got[n]))}, "
                             f"the configuration gives {shape}")
    return PagedGenerativeSpec(
        params=pull,
        make_fns=lambda bs, maxb: glm_moe_lite_paged_decode_fns(cfg, bs, maxb),
        kv_shape=lambda nb, bs: (cfg.num_layers, int(nb), 1, int(bs),
                                 cfg.leaf_width),
        vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        num_heads=cfg.num_heads,
        kv_dtype=np.dtype(got["embed"].dtype).name,
        program_counters=PROGRAM_COUNTERS,
        kv_leaves=(KVLeaf("latent", cfg.leaf_width,
                          filled=cfg.row_width),))


__all__ = ["GlmMoeLiteConfig", "PROGRAM_COUNTERS",
           "glm_moe_lite_param_shapes", "glm_moe_lite_param_names",
           "glm_moe_lite_paged_decode_fns", "glm_moe_lite_paged_spec"]
