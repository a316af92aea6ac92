"""SmallThinker (PowerInfer, 2025): a decoder of window and global
attention layers over a dropless top-k expert layer, on the paged
serving path.

The block, written ONCE (:func:`smallthinker_paged_decode_fns` derives
prefill and decode from it), with ``x`` the stream ``[tokens, hidden]``:

- ``a = rmsnorm(x; g1)``; the ROUTER reads ``a``, the input of the
  attention block, not of the experts: ``parallel.moe.topk_route`` (the
  k largest of ``a @ Wr`` in float32, weights a softmax over those k);
- attention over ``a``: grouped queries (``num_heads`` query heads over
  ``num_kv_heads`` K/V heads), no biases; where ``rope_layout[i]`` is 1
  q and k are rotated (rotate-half over the whole head, position = index
  in the sequence), else the layer has no positional term at all; where
  ``window_layout[i]`` is 1 position ``p`` reads ``p - window < j <= p``
  only; scores ``/ sqrt(head_dim)``, softmax in float32;
- ``m = rmsnorm(x; g2)``; ``x += sum over the k experts e of w_e *
  ((relu(m @ G_e) * (m @ U_e)) @ D_e)``: ``parallel.moe.
  dropless_topk_ffn``, no token dropped;
- after the last layer ``rmsnorm(x; gf) @ Wh``, an untied head, logits
  in float32.

The stream, the norms, the router and the softmax are float32; every
other product takes its operands in the dtype the parameters are handed
over in (bfloat16 as published) and accumulates in float32, and K and V
are cached in that dtype.

The K/V a layer caches live in one of TWO TIERS of the paged pool
(``serving.paged.KVTier``): global layers keep every block of a request,
window layers a ring of the blocks one window can touch. The block sees
neither: it is given a cache that knows, for its layer, which rows its
requests have cached and at which positions, and where the fresh rows
go. Fresh rows are attended to as they come out of the projections and
written afterwards, so a chunk of a prompt reads the ring as the chunk
before it left it.

There is no training graph for this block (``SameDiff.fit`` cannot run
it yet); :func:`smallthinker_paged_spec` serves parameters handed over
by name.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

from deeplearning4j_tpu.compilecache.cache import COMPILE_STATS
from deeplearning4j_tpu.parallel.moe import dropless_topk_ffn, topk_route


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    expert_width: int
    num_experts: int
    experts_per_token: int
    window: int
    window_layout: Tuple[int, ...]
    rope_layout: Tuple[int, ...]
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    max_seq_len: int = 16384

    @classmethod
    def from_dict(cls, d: dict) -> "SmallThinkerConfig":
        """From the keys of the model's published ``config.json``. The
        layouts may run past ``num_hidden_layers`` (a configuration cut
        in depth keeps the published lists): the first layers count."""
        if not d.get("moe_primary_router_apply_softmax", True):
            raise ValueError("only the softmax router is computed")
        if d.get("tie_word_embeddings", False):
            raise ValueError("the head is untied")
        if d.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is not computed")
        L = int(d["num_hidden_layers"])
        return cls(
            vocab_size=int(d["vocab_size"]),
            hidden_size=int(d["hidden_size"]), num_layers=L,
            num_heads=int(d["num_attention_heads"]),
            num_kv_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]),
            expert_width=int(d["moe_ffn_hidden_size"]),
            num_experts=int(d["moe_num_primary_experts"]),
            experts_per_token=int(d["moe_num_active_primary_experts"]),
            window=int(d["sliding_window_size"]),
            window_layout=tuple(int(v) for v in
                                d["sliding_window_layout"][:L]),
            rope_layout=tuple(int(v) for v in d["rope_layout"][:L]),
            rope_theta=float(d["rope_theta"]),
            rms_eps=float(d["rms_norm_eps"]),
            max_seq_len=int(d["max_position_embeddings"]))

    def __post_init__(self):
        if len(self.window_layout) != self.num_layers \
                or len(self.rope_layout) != self.num_layers:
            raise ValueError("a layout entry is needed for every layer")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("query heads must divide over the K/V heads")

    def kv_tiers(self):
        """The pool's tiers: the global layers, then the window layers
        (a tier with no layer is left out)."""
        from deeplearning4j_tpu.serving.paged import KVTier
        glob = tuple(i for i, w in enumerate(self.window_layout) if not w)
        win = tuple(i for i, w in enumerate(self.window_layout) if w)
        tiers = []
        if glob:
            tiers.append(KVTier("global", glob, None))
        if win:
            tiers.append(KVTier("window", win, self.window))
        return tuple(tiers)


#: what the decode program counts a step, summed over its layers: layers
#: run, experts with a token of an active lane, (token, expert) pairs,
#: and the fullest expert's tokens (docs/serving.md says what an
#: operator reads from each)
PROGRAM_COUNTERS = ("moe_layer_steps", "moe_experts_touched_sum",
                    "moe_tokens_routed_sum", "moe_peak_expert_tokens_sum")


def smallthinker_param_shapes(cfg: SmallThinkerConfig) -> Dict[str, tuple]:
    """Every parameter by name with its shape; a product's weight is
    ``[in, out]``, an expert's carries the expert first."""
    H, F, E = cfg.hidden_size, cfg.expert_width, cfg.num_experts
    A, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    out = {"embed": (cfg.vocab_size, H)}
    for i in range(cfg.num_layers):
        out.update({
            f"h{i}/norm_1": (H,), f"h{i}/router": (H, E),
            f"h{i}/attn/q": (H, A * D), f"h{i}/attn/k": (H, K * D),
            f"h{i}/attn/v": (H, K * D), f"h{i}/attn/o": (A * D, H),
            f"h{i}/norm_2": (H,),
            f"h{i}/experts/gate": (E, H, F), f"h{i}/experts/up": (E, H, F),
            f"h{i}/experts/down": (E, F, H)})
    out["norm_f"] = (H,)
    out["lm_head"] = (H, cfg.vocab_size)
    return out


def smallthinker_param_names(cfg: SmallThinkerConfig):
    return list(smallthinker_param_shapes(cfg))


class _PagedCache:
    """What a block sees of the paged pool: its layer's leaves ``kl``,
    ``vl`` ``[num_blocks, block_size, kv_heads * head_dim]``, its tier's
    ``table [R, entries]`` (R requests in the program), ``hist`` [R], how
    many positions each request has cached, and where the N fresh rows
    (request-major) go: ``(write_block [N], write_off [N])``."""

    def __init__(self, kl, vl, table, write_block, hist, write_off,
                 block_size):
        self.kl, self.vl, self.table = kl, vl, table
        self.write_block, self.write_off = write_block, write_off
        self.hist, self.BS = hist, int(block_size)

    def read(self):
        """The cached rows of every request, ``K, V [R, T, kv_heads *
        head_dim]``, and ``pos [R, T]``, the position of each row in its
        sequence (negative: the entry holds nothing yet). Entry ``e`` of
        a table of E entries holds block ``u = e (mod E)``, the one such
        ``u`` among the last E blocks up to the block of position
        ``hist - 1``: for a table that holds every block up to that one
        (however much wider) that is ``u = e``, for a window tier's ring
        the block that was written there last."""
        import jax.numpy as jnp
        R, E = self.table.shape
        last = jnp.floor_divide(self.hist - 1, self.BS)[:, None]    # [R, 1]
        u = last - jnp.mod(last - jnp.arange(E, dtype=jnp.int32)[None], E)
        pos = (u[:, :, None] * self.BS
               + jnp.arange(self.BS, dtype=jnp.int32)[None, None])
        pos = jnp.where(u[:, :, None] >= 0, pos, -1).reshape(R, E * self.BS)
        K = self.kl[self.table].reshape(R, E * self.BS, -1)
        V = self.vl[self.table].reshape(R, E * self.BS, -1)
        return K, V, pos

    def write(self, k, v):
        """The fresh rows ``k, v [N, kv_heads * head_dim]``, in place."""
        at = (self.write_block, self.write_off)
        self.kl = self.kl.at[at].set(k.astype(self.kl.dtype))
        self.vl = self.vl.at[at].set(v.astype(self.vl.dtype))


@COMPILE_STATS.model_build("smallthinker")
def smallthinker_paged_decode_fns(cfg: SmallThinkerConfig, block_size: int,
                                  max_blocks_per_req: int):
    """``(prefill_fn, decode_fn)`` over the two-tier paged pool, both
    ``fn(params, kc, vc, io)`` with ``kc``/``vc`` a tuple of one leaf a
    layer, donated and returned. With ``<t>`` a tier's name (``global``,
    ``window``; :meth:`SmallThinkerConfig.kv_tiers`):

    - ``prefill_fn``: ``io = {"tokens": [Lb] (a run of the prompt,
      padded to its bucket), "length": () real tokens of the run,
      "hist": () positions cached before it (earlier runs of the same
      prompt), "table.<t>": [entries_t], "write_block.<t>": [Lb] the
      block each fresh row lands in (the null block for padding)}``;
      returns ``(kc, vc, next token, logits [vocab])`` from position
      ``hist + length - 1``. A prompt longer than the largest bucket is
      this program run several times with ``hist`` advancing.
    - ``decode_fn``: ``io = {"tokens", "positions", "active": [S],
      "tables.<t>": [S, E_t] (a window tier's ring: its ``entries_t``;
      a tier that keeps every block: any ``E_t <= entries_t`` that holds
      every active lane's blocks, read as far as it goes),
      "write_block.<t>": [S], "write_off": [S]}``; returns ``(kc, vc,
      next [S + 4], logits [S, vocab])``: behind the S next tokens come
      the step's :data:`PROGRAM_COUNTERS`,
      what the routers chose (idle lanes route nothing).
    """
    import jax
    import jax.numpy as jnp

    H, L = cfg.hidden_size, cfg.num_layers
    A, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = A // KV
    BS = int(block_size)
    W = cfg.window
    scale = 1.0 / np.sqrt(D)
    inv_freq = jnp.asarray(
        cfg.rope_theta ** (-np.arange(0, D, 2, dtype=np.float64) / D),
        jnp.float32)
    tier_of = {i: t for t in cfg.kv_tiers() for i in t.layers}

    def _rmsnorm(x, g):
        x = x.astype(jnp.float32)
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + cfg.rms_eps) * g.astype(jnp.float32)

    def _mm(x, w):
        return jnp.matmul(x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    def _rope(x, pos):
        # x [..., heads, D] float32, pos [...]: rotate-half over all of D
        ang = pos[..., None, None].astype(jnp.float32) * inv_freq
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin

    def _attend(window, q, k, v, qpos, valid, cache):
        """q [R, Q, A, D], k/v [R, Q, KV, D] (the fresh rows), qpos/valid
        [R, Q]: each query over its request's cached rows and the fresh
        rows up to itself, one softmax over both; ``window``: a window
        layer."""
        R, Q = qpos.shape
        Kc, Vc, cpos = cache.read()
        T = cpos.shape[1]
        Kc, Vc = Kc.reshape(R, T, KV, D), Vc.reshape(R, T, KV, D)
        dt = Kc.dtype
        see_c = (cpos >= 0) & (cpos < cache.hist[:, None])      # [R, T]
        see_c = jnp.broadcast_to(see_c[:, None, :], (R, Q, T))
        see_f = (qpos[:, None, :] <= qpos[:, :, None]) & valid[:, None, :]
        if window:
            see_c = see_c & (cpos[:, None, :] > qpos[:, :, None] - W)
            see_f = see_f & (qpos[:, None, :] > qpos[:, :, None] - W)
        qg = q.reshape(R, Q, KV, G, D).astype(dt)
        neg = jnp.float32(-1e30)
        s_c = jnp.einsum("rqhgd,rthd->rhgqt", qg, Kc.astype(dt),
                         preferred_element_type=jnp.float32) * scale
        s_f = jnp.einsum("rqhgd,rphd->rhgqp", qg, k.astype(dt),
                         preferred_element_type=jnp.float32) * scale
        s_c = jnp.where(see_c[:, None, None], s_c, neg)
        s_f = jnp.where(see_f[:, None, None], s_f, neg)
        top = jnp.maximum(jnp.max(s_c, axis=-1), jnp.max(s_f, axis=-1))
        e_c = jnp.exp(s_c - top[..., None])
        e_f = jnp.exp(s_f - top[..., None])
        den = jnp.sum(e_c, axis=-1) + jnp.sum(e_f, axis=-1)    # [R,KV,G,Q]
        out = jnp.einsum("rhgqt,rthd->rqhgd", e_c.astype(dt), Vc.astype(dt),
                         preferred_element_type=jnp.float32) \
            + jnp.einsum("rhgqp,rphd->rqhgd", e_f.astype(dt), v.astype(dt),
                         preferred_element_type=jnp.float32)
        out = out / jnp.transpose(den, (0, 3, 1, 2))[..., None]
        return out.reshape(R, Q, A * D)

    def _block(lp, x, qpos, valid, kl, vl, table, wb, hist, write_off,
               rope, window):
        """One layer on the stream ``x [R, Q, H]`` (R requests, Q fresh
        rows each): ``lp`` its parameters under their names within the
        layer, ``kl``/``vl`` its leaves, ``(table, wb)`` its tier's.
        Returns the stream, the tokens each expert served, and the
        leaves. Jitted on its own (``rope``/``window`` static: two kinds
        of layer), so that a program's trace and lowering hold each kind
        once and call it (the compiler inlines the calls): the decode
        program is built once a table width, and set-up pays for
        each."""
        cache = _PagedCache(kl, vl, table, wb, hist, write_off, BS)
        R, Q, _ = x.shape
        a = _rmsnorm(x, lp["/norm_1"])
        # the router is placed before attention
        idx, wts = topk_route(a.reshape(R * Q, H), lp["/router"],
                              cfg.experts_per_token)
        q = _mm(a, lp["/attn/q"]).reshape(R, Q, A, D)
        k = _mm(a, lp["/attn/k"]).reshape(R, Q, KV, D)
        v = _mm(a, lp["/attn/v"]).reshape(R, Q, KV, D)
        if rope:
            q, k = _rope(q, qpos), _rope(k, qpos)
        att = _attend(window, q, k, v, qpos, valid, cache)
        cache.write(k.reshape(R * Q, KV * D), v.reshape(R * Q, KV * D))
        x = x + _mm(att, lp["/attn/o"])
        m = _rmsnorm(x, lp["/norm_2"])
        y, served = dropless_topk_ffn(
            m.reshape(R * Q, H), idx, wts, lp["/experts/gate"],
            lp["/experts/up"], lp["/experts/down"],
            valid=valid.reshape(R * Q))
        return x + y.reshape(R, Q, H), served, cache.kl, cache.vl

    block = jax.jit(_block, static_argnames=("rope", "window"))

    def _stack(p, tokens, qpos, valid, kc, vc, tiers, hist, write_off):
        """Every layer over the stream; ``tiers`` maps a layer to its
        tier's ``(table, write_block)``. Returns the stream, the tokens
        each expert served a layer ``[L, E]``, and the leaves."""
        kc, vc = list(kc), list(vc)
        x = jnp.take(p["embed"], tokens, axis=0).astype(jnp.float32)
        served = []
        for i in range(L):
            sc = f"h{i}"
            lp = {n[len(sc):]: a for n, a in p.items()
                  if n.startswith(sc + "/")}
            x, n, kc[i], vc[i] = block(
                lp, x, qpos, valid, kc[i], vc[i], *tiers[i], hist,
                write_off, rope=bool(cfg.rope_layout[i]),
                window=bool(cfg.window_layout[i]))
            served.append(n)
        return _rmsnorm(x, p["norm_f"]), jnp.stack(served), \
            tuple(kc), tuple(vc)

    def _tiers(io, table_key, lift):
        per_tier = {}
        for t in cfg.kv_tiers():
            table = lift(io[t.key(table_key)])
            entries = t.table_blocks(BS, max_blocks_per_req)
            # a ring is addressed u % entries: exact. A table that keeps
            # every block may come cut to the blocks its lanes hold
            if table.shape[1] > entries or (
                    t.window is not None and table.shape[1] != entries):
                raise ValueError(
                    f"{t.key(table_key)} has {table.shape[1]} entries, the "
                    f"tier's table {entries}")
            per_tier[t.name] = (table, io[t.key("write_block")])
        return {i: per_tier[t.name] for i, t in tier_of.items()}

    def prefill_fn(params, kc, vc, io):
        tokens, length, hist = io["tokens"], io["length"], io["hist"]
        Lb = tokens.shape[0]
        g = hist + jnp.arange(Lb, dtype=jnp.int32)
        valid = jnp.arange(Lb) < length
        x, _, kc, vc = _stack(
            params, tokens[None], g[None], valid[None], kc, vc,
            _tiers(io, "table", lambda t: t[None]), hist[None], g % BS)
        h_last = jax.lax.dynamic_slice_in_dim(
            x[0], jnp.maximum(length - 1, 0), 1, axis=0)
        logits = _mm(h_last, params["lm_head"])[0]
        return kc, vc, jnp.argmax(logits).astype(jnp.int32), logits

    def decode_fn(params, kc, vc, io):
        tokens, pos, active = io["tokens"], io["positions"], io["active"]
        x, served, kc, vc = _stack(
            params, tokens[:, None], pos[:, None], active[:, None], kc, vc,
            _tiers(io, "tables", lambda t: t), pos, io["write_off"])
        logits = _mm(x[:, 0], params["lm_head"])
        counted = jnp.stack([                  # PROGRAM_COUNTERS' order
            jnp.int32(L), jnp.sum(served > 0, dtype=jnp.int32),
            jnp.sum(served, dtype=jnp.int32),
            jnp.sum(jnp.max(served, axis=1), dtype=jnp.int32)])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return kc, vc, jnp.concatenate([nxt, counted]), logits

    return prefill_fn, decode_fn


@COMPILE_STATS.model_build("smallthinker")
def smallthinker_paged_spec(cfg: SmallThinkerConfig, params):
    """A :class:`~deeplearning4j_tpu.serving.paged.PagedGenerativeSpec`
    over ``params`` (a dict by :func:`smallthinker_param_names`, or a
    callable that gives one: ``update_model`` calls it again). K and V
    are cached in the dtype of the parameters."""
    from deeplearning4j_tpu.serving.paged import PagedGenerativeSpec
    pull = params if callable(params) else (lambda: params)
    got, want = pull(), smallthinker_param_shapes(cfg)
    if set(got) != set(want):
        raise ValueError(f"parameter names differ: "
                         f"{sorted(set(got) ^ set(want))[:4]}")
    for n, shape in want.items():
        if tuple(np.shape(got[n])) != shape:
            raise ValueError(f"{n}: shape {tuple(np.shape(got[n]))}, "
                             f"the configuration gives {shape}")
    return PagedGenerativeSpec(
        params=pull,
        make_fns=lambda bs, maxb: smallthinker_paged_decode_fns(cfg, bs, maxb),
        kv_shape=lambda nb, bs: (cfg.num_layers, int(nb), cfg.num_kv_heads,
                                 int(bs), cfg.head_dim),
        vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        num_heads=cfg.num_kv_heads,
        kv_dtype=np.dtype(got["embed"].dtype).name,
        kv_tiers=cfg.kv_tiers(), program_counters=PROGRAM_COUNTERS)


__all__ = ["SmallThinkerConfig", "PROGRAM_COUNTERS",
           "smallthinker_param_shapes",
           "smallthinker_param_names", "smallthinker_paged_decode_fns",
           "smallthinker_paged_spec"]
