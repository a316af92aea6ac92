"""EvaByte (2025): a dense byte-level decoder whose attention is EVA
(Zheng et al., ICLR 2023, arXiv:2302.04542), chunk-summarised linear
attention, on the paged serving path.

The block, written ONCE over a cache interface (:func:`forward` runs it
over a whole sequence with no cache, :func:`evabyte_paged_decode_fns`
derives prefill and decode from it), with ``x`` the stream ``[tokens,
hidden]``, ``s = head_dim ** -0.5``, ``W`` the window and ``c`` the
chunk:

- ``a = rmsnorm(x) * (1 + g1)``; ``q, k, v = a Wq, a Wk, a Wv``, as many
  K/V heads as query heads, no bias; q and k rotated (rotate-half over
  the whole head) at the absolute position;
- per head ``h`` two learned vectors ``phi_h``, ``mu_h``. Chunk ``n``
  holds tokens ``c n .. c n + c - 1``: ``w_i = softmax_i(s phi_h . k_i)``
  over its ``c`` rotated keys, its SUMMARY ``k~_n = sum_i w_i k_i + mu_h``,
  ``v~_n = sum_i w_i v_i``;
- a query at position ``t`` attends, in ONE softmax, to the exact keys
  ``m`` of its own window (``W (t // W) <= m <= t``) and to the summary
  of every chunk of every EARLIER window (``n < (W / c) (t // W)``);
  ``x += o Wo``;
- ``b = rmsnorm(x) * (1 + g2)``; ``x += (silu(b Wg) * (b Wu)) Wd``;
- after the last layer ``rmsnorm(x) * (1 + gf)``, then the head: an
  untied product ``num_pred_heads x vocab`` wide whose head ``j`` is the
  columns ``[vocab j, vocab (j + 1))``; head 0 is the next byte.

The stream, the residual adds, the norms and the softmax are float32;
every product takes its operands in the dtype the parameters are handed
over in (bfloat16 as published) and accumulates in float32; exact rows
and summary rows are cached in that dtype, and a summary is computed
from the rows as they are cached.

A layer's cache is TWO stores on two tiers of the paged pool over the
same layers (``serving.paged.KVTier``, ``KVLeaf.tier``): ``exact``, the
K and V rows of the query's own window, a window that TUMBLES (given
back whole when the query enters the next one), and ``summary``, one
``k~`` and ``v~`` row per ``c`` tokens for the life of the request,
written by the run that completes the chunk (a prefill run for the
chunks it holds whole, the decode step of a chunk's last token, which
reads the chunk's earlier rows from the exact store). The serving
programs multiply by head 0's columns only; :func:`forward` gives every
head's (the entry of a later draft over the model's own heads).

There is no training graph for this block (``SameDiff.fit`` cannot run
it yet); :func:`evabyte_paged_spec` serves parameters handed over by
name.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from deeplearning4j_tpu.compilecache.cache import COMPILE_STATS


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    head_dim: int
    mlp_width: int
    chunk: int
    window: int
    pred_heads: int = 1
    rope_theta: float = 1e5
    rms_eps: float = 1e-5
    unit_offset: bool = True
    max_seq_len: int = 32768

    @classmethod
    def from_dict(cls, d: dict) -> "EvaByteConfig":
        """From the keys of the model's published ``config.json``."""
        if d.get("attention_class", "eva") != "eva":
            raise ValueError("only the eva attention class is computed")
        if d.get("tie_word_embeddings", False):
            raise ValueError("the head is untied")
        if d.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is not computed")
        if d.get("hidden_act", "silu") != "silu" \
                or d.get("attention_bias", False):
            raise ValueError("the block is SwiGLU without biases")
        A = int(d["num_attention_heads"])
        if int(d.get("num_key_value_heads", A)) != A:
            raise ValueError("every query head has its own K/V head")
        H = int(d["hidden_size"])
        return cls(
            vocab_size=int(d["vocab_size"]), hidden_size=H,
            num_layers=int(d["num_hidden_layers"]), num_heads=A,
            head_dim=int(d.get("head_dim") or H // A),
            mlp_width=int(d["intermediate_size"]),
            chunk=int(d["chunk_size"]), window=int(d["window_size"]),
            pred_heads=int(d.get("num_pred_heads", 1)),
            rope_theta=float(d["rope_theta"]),
            rms_eps=float(d["rms_norm_eps"]),
            unit_offset=bool(d.get("norm_add_unit_offset", False)),
            max_seq_len=int(d["max_position_embeddings"]))

    def __post_init__(self):
        if self.window % self.chunk:
            raise ValueError("a window is a whole number of chunks")

    def kv_tiers(self):
        """The pool's two tiers, both over every layer: the exact rows
        of the query's own window, and a summary row a chunk."""
        from deeplearning4j_tpu.serving.paged import KVTier
        layers = tuple(range(self.num_layers))
        return (KVTier("exact", layers, self.window, tumbles=True),
                KVTier("summary", layers, row_tokens=self.chunk))

    def kv_leaves(self):
        """Four leaves a layer: K and V rows, summary K and V rows."""
        from deeplearning4j_tpu.serving.paged import KVLeaf
        A, width = self.num_heads, self.num_heads * self.head_dim
        return (KVLeaf("k", width, A, tier="exact"),
                KVLeaf("v", width, A, tier="exact"),
                KVLeaf("k_summary", width, A, tier="summary"),
                KVLeaf("v_summary", width, A, tier="summary"))


#: what the decode program counts a step: the rows its active lanes'
#: queries attend to (exact rows of the window, summaries of the windows
#: before it), summed over the layers, from the masks themselves
PROGRAM_COUNTERS = ("kv_rows_attended_sum",)


def evabyte_param_shapes(cfg: EvaByteConfig) -> Dict[str, tuple]:
    """Every parameter by name with its shape; a product's weight is
    ``[in, out]``; ``phi`` and ``mu`` are one vector a head."""
    H, F, A, D = cfg.hidden_size, cfg.mlp_width, cfg.num_heads, cfg.head_dim
    out = {"embed": (cfg.vocab_size, H)}
    for i in range(cfg.num_layers):
        out.update({
            f"h{i}/norm_1": (H,),
            f"h{i}/attn/q": (H, A * D), f"h{i}/attn/k": (H, A * D),
            f"h{i}/attn/v": (H, A * D), f"h{i}/attn/o": (A * D, H),
            f"h{i}/attn/phi": (A, D), f"h{i}/attn/mu": (A, D),
            f"h{i}/norm_2": (H,),
            f"h{i}/mlp/gate": (H, F), f"h{i}/mlp/up": (H, F),
            f"h{i}/mlp/down": (F, H)})
    out["norm_f"] = (H,)
    out["lm_head"] = (H, cfg.pred_heads * cfg.vocab_size)
    return out


def evabyte_param_names(cfg: EvaByteConfig):
    return list(evabyte_param_shapes(cfg))


class _TwoStoreCache:
    """What a block sees of the paged pool: its layer's four leaves
    ``[num_blocks, block_size, heads * head_dim]`` (``kl``, ``vl`` exact
    rows, ``sl``, ``ul`` summary keys and values), each tier's ``table
    [R, entries]`` (R requests in the program), ``hist [R]``, how many
    positions each request has cached, and where fresh rows go:
    ``(write_block, write_off)`` one a fresh token (request-major) for
    the exact store, ``summary_block`` one a chunk the run can complete
    (the null block for a chunk it does not). The exact store's table is
    a ring of ``ring`` entries that refills from entry 0 at every turn
    of the window; what is handed over may be its first entries only, as
    many as hold every block a query of the program sees."""

    def __init__(self, kl, vl, sl, ul, table, write_block, summary_table,
                 summary_block, hist, write_off, block_size, ring):
        self.kl, self.vl, self.sl, self.ul = kl, vl, sl, ul
        self.table, self.write_block = table, write_block
        self.summary_table, self.summary_block = summary_table, summary_block
        self.hist, self.write_off = hist, write_off
        self.BS, self.ring = int(block_size), int(ring)

    def read_exact(self):
        """``K, V [R, T, width]`` and ``pos [R, T]``, the position of
        each row (negative: nothing yet). Entry ``e`` of the ring's E
        holds the block ``u = e (mod E)`` among the last E blocks up to
        that of position ``hist - 1``; the table's entries are the first
        of the ring's."""
        import jax.numpy as jnp
        R, n = self.table.shape
        last = jnp.floor_divide(self.hist - 1, self.BS)[:, None]
        u = last - jnp.mod(last - jnp.arange(n, dtype=jnp.int32)[None],
                           self.ring)
        pos = (u[:, :, None] * self.BS
               + jnp.arange(self.BS, dtype=jnp.int32)[None, None])
        pos = jnp.where(u[:, :, None] >= 0, pos, -1).reshape(R, n * self.BS)
        K = self.kl[self.table].reshape(R, n * self.BS, -1)
        V = self.vl[self.table].reshape(R, n * self.BS, -1)
        return K, V, pos

    def read_summaries(self):
        """``K~, V~ [R, T, width]``: row ``n`` is chunk ``n``'s summary
        (the table keeps every block: entry ``e`` is block ``e``, however
        far it was cut), written for ``n < hist // chunk``."""
        R, E = self.summary_table.shape
        K = self.sl[self.summary_table].reshape(R, E * self.BS, -1)
        V = self.ul[self.summary_table].reshape(R, E * self.BS, -1)
        return K, V

    def read_rows(self, pos):
        """The cached exact rows at ``pos [R, n]`` (positions of the
        live window; a caller masks what is not cached, and a position
        whose entry lies beyond the table reads a row of its last
        entry): ``K, V [R, n, width]``."""
        import jax.numpy as jnp
        entry = jnp.mod(jnp.floor_divide(pos, self.BS), self.ring)
        block = jnp.take_along_axis(self.table, entry, axis=1, mode="clip")
        off = jnp.mod(pos, self.BS)
        return self.kl[block, off], self.vl[block, off]

    def write(self, k, v):
        """The fresh rows ``k, v [N, width]``, in place."""
        at = (self.write_block, self.write_off)
        self.kl = self.kl.at[at].set(k.astype(self.kl.dtype))
        self.vl = self.vl.at[at].set(v.astype(self.vl.dtype))

    def write_summaries(self, ks, vs, index):
        """The summaries ``ks, vs [M, width]`` of the chunks ``index
        [M]``, in place, each into ``summary_block``'s row ``index %
        block_size``."""
        import jax.numpy as jnp
        at = (self.summary_block, jnp.mod(index, self.BS))
        self.sl = self.sl.at[at].set(ks.astype(self.sl.dtype))
        self.ul = self.ul.at[at].set(vs.astype(self.ul.dtype))


def _functions(cfg: EvaByteConfig):
    """The block's arithmetic, shared by :func:`forward` and the paged
    programs."""
    import jax
    import jax.numpy as jnp

    A, D, c, W = cfg.num_heads, cfg.head_dim, cfg.chunk, cfg.window
    scale = 1.0 / np.sqrt(D)
    inv_freq = jnp.asarray(
        cfg.rope_theta ** (-np.arange(0, D, 2, dtype=np.float64) / D),
        jnp.float32)
    neg = jnp.float32(-1e30)

    def rmsnorm(x, g):
        x = x.astype(jnp.float32)
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        g = g.astype(jnp.float32)
        return x * jax.lax.rsqrt(ms + cfg.rms_eps) * (
            1.0 + g if cfg.unit_offset else g)

    def mm(x, w):
        return jnp.matmul(x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    def rope(x, pos):
        # x [..., heads, D] float32, pos [...]: rotate-half over all of D
        ang = pos[..., None, None].astype(jnp.float32) * inv_freq
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin

    def summarise(phi, mu, kb, vb, mb):
        """Chunks of rows ``kb, vb [R, n, c, A, D]`` (float32 values of
        the rows as cached) with ``mb [R, n, c]`` the rows that exist:
        ``k~, v~ [R, n, A, D]``."""
        sc = scale * jnp.einsum("ad,rncad->rnca", phi.astype(jnp.float32),
                                kb, precision=jax.lax.Precision.HIGHEST)
        sc = jnp.where(mb[..., None], sc, neg)
        w = jax.nn.softmax(sc, axis=2)
        w = jnp.where(mb[..., None], w, 0.0)
        ks = jnp.einsum("rnca,rncad->rnad", w, kb,
                        precision=jax.lax.Precision.HIGHEST)
        vs = jnp.einsum("rnca,rncad->rnad", w, vb,
                        precision=jax.lax.Precision.HIGHEST)
        return ks + mu.astype(jnp.float32), vs

    def attend(q, groups, dt):
        """``q [R, Q, A, D]`` over ``groups`` of ``(K [R, T, A, D], V,
        see [R, Q, T])``: one softmax over all of them. Returns the heads
        ``[R, Q, A * D]`` and how many rows each query saw ``[R, Q]``."""
        R, Q = q.shape[:2]
        seen = sum(see.sum(axis=-1, dtype=jnp.int32) for _, _, see in groups)
        if Q == 1:
            return attend_one(q, groups, dt), seen
        qd = q.astype(dt)
        ss = [jnp.where(see[:, None], scale * jnp.einsum(
            "rqad,rtad->raqt", qd, K.astype(dt),
            preferred_element_type=jnp.float32), neg)
            for K, _, see in groups]
        top = ss[0].max(axis=-1)
        for sg in ss[1:]:
            top = jnp.maximum(top, sg.max(axis=-1))
        es = [jnp.exp(sg - top[..., None]) for sg in ss]
        den = sum(e.sum(axis=-1) for e in es)              # [R, A, Q]
        out = sum(jnp.einsum("raqt,rtad->rqad", e.astype(dt), V.astype(dt),
                             preferred_element_type=jnp.float32)
                  for e, (_, V, _) in zip(es, groups))
        out = out / jnp.transpose(den, (0, 2, 1))[..., None]
        return out.reshape(R, Q, A * D), seen

    def attend_one(q, groups, dt):
        """:func:`attend` for ONE query a request (a decode step), the
        same arithmetic with the rows where they lie, ``[T, A * D]``: a
        matrix product a head would first rewrite every gathered row head
        by head. Instead the query becomes ``[A * D, A]`` with head
        ``a``'s numbers in column ``a`` (zeros elsewhere), so that ``K @
        it`` is every head's score in one product over the whole row;
        and ``weights^T @ V`` is ``[A, A * D]``, of which head ``a``'s
        result is block ``a`` of row ``a``. The zeros cost 32 times the
        multiplications of a product a head, which is nothing beside
        reading the rows once."""
        R = q.shape[0]
        eye = jnp.eye(A, dtype=dt)
        qw = jnp.einsum("rad,ab->radb", q[:, 0].astype(dt), eye
                        ).reshape(R, A * D, A)
        rows = [(K.reshape(R, -1, A * D).astype(dt),
                 V.reshape(R, -1, A * D).astype(dt), see[:, 0, :, None])
                for K, V, see in groups]
        ss = [jnp.where(see, scale * jnp.einsum(
            "rtw,rwa->rta", K, qw, preferred_element_type=jnp.float32), neg)
            for K, _, see in rows]                          # [R, T, A]
        top = ss[0].max(axis=1)
        for sg in ss[1:]:
            top = jnp.maximum(top, sg.max(axis=1))
        es = [jnp.exp(sg - top[:, None]) for sg in ss]
        den = sum(e.sum(axis=1) for e in es)                # [R, A]
        wide = sum(jnp.einsum("rta,rtw->raw", e.astype(dt), V,
                              preferred_element_type=jnp.float32)
                   for e, (_, V, _) in zip(es, rows))       # [R, A, A * D]
        out = jnp.einsum("raad->rad", wide.reshape(R, A, A, D))
        return (out / den[..., None]).reshape(R, 1, A * D)

    def layer(lp, x, qpos, valid, cache, hist, tail):
        """One layer on the stream ``x [R, Q, H]`` (R requests, Q fresh
        rows each at positions ``qpos``, ``valid`` the real ones): ``lp``
        its parameters under their names within the layer, ``cache`` its
        two stores (``None``: nothing cached, :func:`forward`). A run
        starts on a chunk (``hist`` a multiple of the chunk) unless
        ``tail``: then it is ONE token a request, which finds its chunk's
        earlier rows in the exact store. Returns the stream and the rows
        the valid queries saw."""
        R, Q, _ = x.shape
        dt = lp["/attn/q"].dtype
        a = rmsnorm(x, lp["/norm_1"])
        q = rope(mm(a, lp["/attn/q"]).reshape(R, Q, A, D), qpos)
        k = rope(mm(a, lp["/attn/k"]).reshape(R, Q, A, D), qpos)
        v = mm(a, lp["/attn/v"]).reshape(R, Q, A, D)
        # the rows as they are cached
        kr = k.astype(dt).astype(jnp.float32)
        vr = v.astype(dt).astype(jnp.float32)
        first = W * jnp.floor_divide(qpos, W)     # the window's first row
        behind = jnp.floor_divide(first, c)       # chunks before it
        # the chunks the run's rows fall in, with the rows they hold
        if tail:
            at = jnp.mod(qpos, c)                                  # [R, 1]
            j = jnp.arange(c, dtype=jnp.int32)[None]               # [1, c]
            ck, cv = cache.read_rows(qpos - at + j)
            mine = (j == at)[..., None]
            kb = jnp.where(mine, kr.reshape(R, 1, A * D),
                           ck.astype(jnp.float32)).reshape(R, 1, c, A, D)
            vb = jnp.where(mine, vr.reshape(R, 1, A * D),
                           cv.astype(jnp.float32)).reshape(R, 1, c, A, D)
            mb = ((j <= at) & valid)[:, None]                   # [R, 1, c]
        else:
            pad = -Q % c
            kb, vb = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      .reshape(R, -1, c, A, D) for t in (kr, vr))
            mb = jnp.pad(valid, ((0, 0), (0, pad))).reshape(R, -1, c)
        ks, vs = summarise(lp["/attn/phi"], lp["/attn/mu"], kb, vb, mb)
        n = ks.shape[1]
        index = jnp.floor_divide(hist, c)[:, None] \
            + jnp.arange(n, dtype=jnp.int32)[None]                # [R, n]
        whole = mb.all(axis=-1)                                    # [R, n]
        # fresh rows: causal, of the query's own window
        groups = [(k, v, (qpos[:, None, :] <= qpos[:, :, None])
                   & (qpos[:, None, :] >= first[:, :, None])
                   & valid[:, None, :])]
        if not tail:
            # chunks this run completes, for the queries of later windows
            groups.append((ks, vs, whole[:, None, :]
                           & (index[:, None, :] < behind[:, :, None])))
        if cache is not None:
            Kc, Vc, cpos = cache.read_exact()
            T = cpos.shape[1]
            groups.append((Kc.reshape(R, T, A, D), Vc.reshape(R, T, A, D),
                           (cpos[:, None, :] >= first[:, :, None])
                           & (cpos < hist[:, None])[:, None, :]))
            Ks, Vs = cache.read_summaries()
            T = Ks.shape[1]
            row = jnp.arange(T, dtype=jnp.int32)[None, None]
            groups.append((Ks.reshape(R, T, A, D), Vs.reshape(R, T, A, D),
                           (row < behind[:, :, None])
                           & (row < jnp.floor_divide(hist, c)[:, None, None])))
        att, seen = attend(q, groups, dt)
        if cache is not None:
            cache.write(k.reshape(R * Q, A * D), v.reshape(R * Q, A * D))
            cache.write_summaries(ks.reshape(R * n, A * D),
                                  vs.reshape(R * n, A * D),
                                  index.reshape(R * n))
        x = x + mm(att, lp["/attn/o"])
        b = rmsnorm(x, lp["/norm_2"])
        y = mm(jax.nn.silu(mm(b, lp["/mlp/gate"])) * mm(b, lp["/mlp/up"]),
               lp["/mlp/down"])
        return x + y, jnp.sum(jnp.where(valid, seen, 0), dtype=jnp.int32)

    return rmsnorm, mm, layer


def _layer_params(p, i):
    sc = f"h{i}"
    return {n[len(sc):]: a for n, a in p.items() if n.startswith(sc + "/")}


def forward(cfg: EvaByteConfig, params, tokens):
    """Logits ``[T, num_pred_heads * vocab]`` of the whole sequence
    ``tokens [T]``, nothing cached: the block over every position at
    once, each chunk's summary made from the sequence's own rows. Head
    ``j`` is ``[:, vocab * j : vocab * (j + 1)]``."""
    import jax.numpy as jnp
    rmsnorm, mm, layer = _functions(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    qpos = jnp.arange(T, dtype=jnp.int32)[None]
    valid = jnp.ones((1, T), bool)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)[None]
    for i in range(cfg.num_layers):
        x, _ = layer(_layer_params(params, i), x, qpos, valid, None,
                     jnp.zeros(1, jnp.int32), False)
    return mm(rmsnorm(x[0], params["norm_f"]), params["lm_head"])


@COMPILE_STATS.model_build("evabyte")
def evabyte_paged_decode_fns(cfg: EvaByteConfig, block_size: int,
                             max_blocks_per_req: int):
    """``(prefill_fn, decode_fn)`` over the two-tier paged pool, both
    ``fn(params, kc, vc, io)`` with ``kc = (k leaves, summary k
    leaves)`` and ``vc = (v leaves, summary v leaves)``, each a tuple of
    one array a layer, donated and returned:

    - ``prefill_fn``: ``io = {"tokens": [Lb] (a run of the prompt, padded
      to its bucket; it starts on a chunk and lies in one window),
      "length": () real tokens of the run, "hist": () positions cached
      before it, "table.exact": [window blocks] the ring, "write_block.
      exact": [Lb] the block each fresh row lands in, "table.summary":
      [entries], "write_block.summary": [ceil(Lb / chunk)] the block of
      each chunk the run completes (the null block for one it does
      not)}``; returns ``(kc, vc, next token, logits [vocab])`` from
      position ``hist + length - 1``, head 0's.
    - ``decode_fn``: ``io = {"tokens", "positions", "active": [S],
      "tables.exact": [S, E'] (the first ``E'`` of the ring's entries,
      any that holds every active lane's blocks of its own window),
      "write_block.exact": [S], "write_off": [S], "tables.summary": [S,
      E] (any ``E`` that holds every active lane's blocks),
      "write_block.summary": [S] (the block of the chunk a lane's token
      completes, else the null block)}``;
      returns ``(kc, vc, next [S + 1], logits [S, vocab])``: behind the S
      next tokens comes the step's :data:`PROGRAM_COUNTERS`.
    """
    import jax
    import jax.numpy as jnp

    L, V = cfg.num_layers, cfg.vocab_size
    BS = int(block_size)
    exact, summary = cfg.kv_tiers()
    # the ring's entries are the configuration's (window // block_size
    # under the pool's geometry), whatever width its table is handed in
    ring = exact.table_blocks(BS, max_blocks_per_req)
    rmsnorm, mm, layer = _functions(cfg)

    def _block(lp, x, qpos, valid, kl, vl, sl, ul, table, wb, stable, swb,
               hist, write_off, tail):
        cache = _TwoStoreCache(kl, vl, sl, ul, table, wb, stable, swb, hist,
                               write_off, BS, ring)
        x, seen = layer(lp, x, qpos, valid, cache, hist, tail)
        return x, seen, cache.kl, cache.vl, cache.sl, cache.ul

    # jitted on its own (``tail`` static: a run of a prompt, or one token
    # a lane), so that a program's trace and lowering hold the layer once
    # and call it: the decode program is built once a table width
    block = jax.jit(_block, static_argnames=("tail",))

    def _stack(p, tokens, qpos, valid, kc, vc, io, table_key, lift, hist,
               write_off, tail):
        (kx, ks), (vx, vs) = (list(map(list, side)) for side in (kc, vc))
        tables = []
        for t in (exact, summary):
            table = lift(io[t.key(table_key)])
            entries = t.table_blocks(BS, max_blocks_per_req)
            # a decode step's table may be the first entries of either
            # tier's (the summaries keep every block, the ring refills
            # from entry 0); a prefill run's comes whole
            if table.shape[1] > entries or (
                    not tail and table.shape[1] != entries):
                raise ValueError(
                    f"{t.key(table_key)} has {table.shape[1]} entries, the "
                    f"tier's table {entries}")
            tables += [table, io[t.key("write_block")]]
        x = jnp.take(p["embed"], tokens, axis=0).astype(jnp.float32)
        seen = jnp.int32(0)
        for i in range(L):
            x, n, kx[i], vx[i], ks[i], vs[i] = block(
                _layer_params(p, i), x, qpos, valid, kx[i], vx[i], ks[i],
                vs[i], *tables, hist, write_off, tail=tail)
            seen = seen + n
        return rmsnorm(x, p["norm_f"]), seen, \
            (tuple(kx), tuple(ks)), (tuple(vx), tuple(vs))

    def prefill_fn(params, kc, vc, io):
        tokens, length, hist = io["tokens"], io["length"], io["hist"]
        Lb = tokens.shape[0]
        g = hist + jnp.arange(Lb, dtype=jnp.int32)
        valid = jnp.arange(Lb) < length
        x, _, kc, vc = _stack(
            params, tokens[None], g[None], valid[None], kc, vc, io, "table",
            lambda t: t[None], hist[None], g % BS, False)
        h_last = jax.lax.dynamic_slice_in_dim(
            x[0], jnp.maximum(length - 1, 0), 1, axis=0)
        logits = mm(h_last, params["lm_head"][:, :V])[0]
        return kc, vc, jnp.argmax(logits).astype(jnp.int32), logits

    def decode_fn(params, kc, vc, io):
        tokens, pos, active = io["tokens"], io["positions"], io["active"]
        x, seen, kc, vc = _stack(
            params, tokens[:, None], pos[:, None], active[:, None], kc, vc,
            io, "tables", lambda t: t, pos, io["write_off"], True)
        logits = mm(x[:, 0], params["lm_head"][:, :V])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return kc, vc, jnp.concatenate([nxt, seen[None]]), logits

    return prefill_fn, decode_fn


@COMPILE_STATS.model_build("evabyte")
def evabyte_paged_spec(cfg: EvaByteConfig, params):
    """A :class:`~deeplearning4j_tpu.serving.paged.PagedGenerativeSpec`
    over ``params`` (a dict by :func:`evabyte_param_names`, or a callable
    that gives one: ``update_model`` calls it again). Rows are cached in
    the dtype of the parameters; the vocabulary served is head 0's."""
    from deeplearning4j_tpu.serving.paged import PagedGenerativeSpec
    pull = params if callable(params) else (lambda: params)
    got, want = pull(), evabyte_param_shapes(cfg)
    if set(got) != set(want):
        raise ValueError(f"parameter names differ: "
                         f"{sorted(set(got) ^ set(want))[:4]}")
    for n, shape in want.items():
        if tuple(np.shape(got[n])) != shape:
            raise ValueError(f"{n}: shape {tuple(np.shape(got[n]))}, "
                             f"the configuration gives {shape}")
    return PagedGenerativeSpec(
        params=pull,
        make_fns=lambda bs, maxb: evabyte_paged_decode_fns(cfg, bs, maxb),
        kv_shape=lambda nb, bs: (cfg.num_layers, int(nb), cfg.num_heads,
                                 int(bs), cfg.head_dim),
        vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        num_heads=cfg.num_heads,
        kv_dtype=np.dtype(got["embed"].dtype).name,
        kv_tiers=cfg.kv_tiers(), kv_leaves=cfg.kv_leaves(),
        program_counters=PROGRAM_COUNTERS)


__all__ = ["EvaByteConfig", "PROGRAM_COUNTERS", "evabyte_param_shapes",
           "evabyte_param_names", "forward", "evabyte_paged_decode_fns",
           "evabyte_paged_spec"]
