"""GPT-style autoregressive decoder built directly on SameDiff.

Reference parity: the reference's transformer story is the imported-BERT
benchmark plus attention layers (SURVEY §2.3 zoo; attention vertices
`deeplearning4j-nn/.../layers/recurrent` and
`libnd4j/.../generic/nn/multi_head_dot_product_attention.cpp:34`). It has
no native decoder-LM; this model is the TPU-first flagship config — the
compute-dense benchmark where MXU utilization is actually reachable:

- pre-LN residual blocks, erf-gelu MLP, learned positions (GPT-2 layout);
- the attention core is ONE ``scaled_dot_product_attention`` op (f32
  scores/softmax, bf16 matmuls under mixed precision). In a train step
  on one TPU device it runs a tiled kernel, forward and backward, and
  writes no ``[B, heads, S, S]`` score array (``seq_len`` a multiple of
  128, head size 64 or 128); on the CPU, under a mesh and at any other
  shape it builds the scores whole. ``sd.attention_sites`` says which
  path each block took when the step was traced (ops/nn_ops.py,
  monitor/attention.py);
- every block records inside ``sd.remat_scope`` — the whole layer is one
  ``jax.checkpoint`` region, so live activation memory is per-layer
  boundaries only and batch*seq can grow to MXU-saturating sizes;
- weight-tied LM head (embedding matrix reused for logits), sparse
  softmax-CE on integer targets — no [B,S,vocab] one-hot ever exists.

Train step = SameDiff's single jitted fwd+bwd+updater program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from deeplearning4j_tpu.compilecache.cache import COMPILE_STATS


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 32768
    hidden_size: int = 2048
    num_layers: int = 12
    num_heads: int = 16
    intermediate_size: int = 8192
    max_seq_len: int = 1024
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-5
    remat: bool = True          # one jax.checkpoint region per block
    tie_embeddings: bool = True

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_heads


# ~510M params: the compute-dense flagship (what chip_smoke.py trains) —
# sized so f32 masters + Adam slots + grads + bf16 compute copies +
# remat-bounded activations fill (but fit) one v5e chip's 16 GB HBM
GPT_MEDIUM = GPTConfig(hidden_size=1536, num_layers=16,
                       intermediate_size=6144, num_heads=12)
GPT_TINY = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, intermediate_size=128, max_seq_len=64)


def _layer_norm(sd, scope, x, width, eps):
    g = sd.var(f"{scope}/gamma", value=np.ones(width, np.float32))
    b = sd.var(f"{scope}/beta", value=np.zeros(width, np.float32))
    return sd.invoke("layer_norm", [x, g, b], {"epsilon": eps},
                     name=f"{scope}/ln")


def _dense(sd, rng, scope, x, n_in, n_out, std):
    w = sd.var(f"{scope}/kernel",
               value=(rng.standard_normal((n_in, n_out)) * std)
               .astype(np.float32))
    b = sd.var(f"{scope}/bias", value=np.zeros(n_out, np.float32))
    h = sd.invoke("matmul", [x, w], name=f"{scope}/matmul")
    return sd.invoke("bias_add", [h, b], name=f"{scope}/bias")


@COMPILE_STATS.model_build("gpt")
def build_gpt(cfg: GPTConfig, batch: int, seq_len: int, seed: int = 0):
    """Build the decoder LM as a SameDiff graph.

    Placeholders: ``input_ids`` [batch, seq] int32, ``targets``
    [batch, seq] int32 (next-token ids). Outputs: ``logits``
    [batch, seq, vocab] and scalar ``loss`` (set as the loss variable).
    """
    from deeplearning4j_tpu.autodiff import SameDiff

    if seq_len > cfg.max_seq_len:
        raise ValueError(f"seq_len {seq_len} > max_seq_len {cfg.max_seq_len}")
    H, A, D = cfg.hidden_size, cfg.num_heads, cfg.head_size
    rng = np.random.default_rng(seed)
    std = cfg.initializer_range
    # GPT-2 scales residual-out projections by 1/sqrt(2L)
    res_std = std / np.sqrt(2.0 * cfg.num_layers)

    sd = SameDiff()
    ids = sd.placeholder("input_ids", shape=(batch, seq_len), dtype="int32")
    targets = sd.placeholder("targets", shape=(batch, seq_len), dtype="int32")

    wte = sd.var("wte", value=(rng.standard_normal((cfg.vocab_size, H))
                               * std).astype(np.float32))
    wpe = sd.var("wpe", value=(rng.standard_normal((cfg.max_seq_len, H))
                               * std).astype(np.float32))
    x = sd.invoke("embedding_lookup", [wte, ids], name="tok_emb")
    pos = sd.invoke("slice", [wpe], {"begin": (0, 0), "size": (seq_len, H)},
                    name="pos_slice")
    x = x.add(pos, name="emb")

    for i in range(cfg.num_layers):
        sc = f"h{i}"
        ctx = sd.remat_scope(sc) if cfg.remat else _null_ctx()
        with ctx:
            y = _layer_norm(sd, f"{sc}/ln_1", x, H, cfg.layer_norm_eps)
            qkv = _dense(sd, rng, f"{sc}/attn/qkv", y, H, 3 * H, std)
            # fused-kernel layout is PER-HEAD blocks [q_a|k_a|v_a] (not
            # [Q|K|V]): a contiguous shard of the 3H output dim then
            # holds complete heads, so Megatron column-parallel sharding
            # (parallel/sharding.py transformer rules) never straddles a
            # q/k/v boundary — zero resharding inside the block
            qkv = sd.invoke("reshape", [qkv],
                            {"shape": (batch, seq_len, A, 3 * D)},
                            name=f"{sc}/attn/split_heads")
            qkv = sd.invoke("permute", [qkv], {"axes": (0, 2, 1, 3)},
                            name=f"{sc}/attn/heads_t")   # [B, A, S, 3D]
            q, k, v = sd.invoke("split", [qkv],
                                {"num_split": 3, "axis": 3},
                                name=f"{sc}/attn/qkv_split", n_outputs=3)
            att = sd.invoke("scaled_dot_product_attention", [q, k, v],
                            {"causal": True}, name=f"{sc}/attn/sdpa")
            att = sd.invoke("permute", [att], {"axes": (0, 2, 1, 3)},
                            name=f"{sc}/attn/merge_t")
            att = sd.invoke("reshape", [att],
                            {"shape": (batch, seq_len, H)},
                            name=f"{sc}/attn/merge")
            att = _dense(sd, rng, f"{sc}/attn/proj", att, H, H, res_std)
            x = x.add(att, name=f"{sc}/res_1")
            y = _layer_norm(sd, f"{sc}/ln_2", x, H, cfg.layer_norm_eps)
            y = _dense(sd, rng, f"{sc}/mlp/fc", y, H, cfg.intermediate_size,
                       std)
            y = sd.invoke("gelu", [y], name=f"{sc}/mlp/act")
            y = _dense(sd, rng, f"{sc}/mlp/proj", y, cfg.intermediate_size,
                       H, res_std)
            x = x.add(y, name=f"{sc}/res_2")

    x = _layer_norm(sd, "ln_f", x, H, cfg.layer_norm_eps)
    if cfg.tie_embeddings:
        logits = sd.invoke("einsum", [x, wte],
                           {"equation": "bsh,vh->bsv"}, name="logits")
    else:
        head = sd.var("lm_head", value=(rng.standard_normal((H, cfg.vocab_size))
                                        * std).astype(np.float32))
        logits = sd.invoke("matmul", [x, head], name="logits")
    loss = sd.invoke("sparse_softmax_cross_entropy", [logits, targets],
                     name="loss")
    sd.set_loss_variables([loss])
    return sd


def _null_ctx():
    import contextlib
    return contextlib.nullcontext()


# ----------------------------------------------------------------------
# decode-mode graph hook (serving/generative.py — continuous batching)
# ----------------------------------------------------------------------
def gpt_param_names(cfg: GPTConfig):
    """The trained-variable names :func:`build_gpt` creates — the
    contract between the training graph and the decode functions below
    (the generative spec pulls arrays from the SameDiff by these
    names, the same by-name convention as ``ServingSpec.sync`` /
    ``ParallelInference.reload_from``)."""
    names = ["wte", "wpe", "ln_f/gamma", "ln_f/beta"]
    for i in range(cfg.num_layers):
        sc = f"h{i}"
        for part in ("ln_1/gamma", "ln_1/beta",
                     "attn/qkv/kernel", "attn/qkv/bias",
                     "attn/proj/kernel", "attn/proj/bias",
                     "ln_2/gamma", "ln_2/beta",
                     "mlp/fc/kernel", "mlp/fc/bias",
                     "mlp/proj/kernel", "mlp/proj/bias"):
            names.append(f"{sc}/{part}")
    if not cfg.tie_embeddings:
        names.append("lm_head")
    return names


@COMPILE_STATS.model_build("gpt")
def gpt_decode_fns(cfg: GPTConfig, quantize_weights: bool = False,
                   kv_scales=None):
    """Pure-jax ``(prefill_fn, decode_fn, verify_fn)`` mirroring
    :func:`build_gpt`'s math op-for-op (one-pass layer norm with
    ``rsqrt``, per-head-block fused qkv layout, f32 attention
    scores/softmax, tanh-gelu, tied logits) but in DECODE MODE:
    attention reads/writes preallocated per-slot KV cache slabs instead
    of recomputing the full sequence.

    KV slab layout (one array each for K and V, shared by every layer so
    a serving step donates exactly two buffers)::

        [num_layers, max_slots, heads, max_seq, head_dim]

    - ``prefill_fn(params, kc, vc, io)`` with
      ``io = {"tokens": [L] int32, "length": () int32, "slot": () int32}``
      runs the full causal forward over one request's (bucket-padded)
      prompt, writes its K/V rows into cache slot ``io["slot"]`` and
      returns ``(kc, vc, next_token, last_logits)`` — the greedy first
      generated token from the last REAL prompt position
      (``length - 1``; padded rows never influence it, causal mask).
    - ``decode_fn(params, kc, vc, io)`` with
      ``io = {"tokens": [S] int32, "positions": [S] int32,
      "active": [S] bool}`` advances EVERY active slot one token in one
      dispatch: per-slot KV written in place at that slot's position
      (inactive slots' caches untouched), attention masked to
      ``index <= position`` with masked V rows zeroed under the mask —
      so a retired slot's stale (even poisoned/NaN) cache rows can
      never leak into its successor, bit-exactly (tested). Returns
      ``(kc, vc, next_tokens, logits)``.
    - ``verify_fn(params, kc, vc, io)`` with ``io = {"tokens": [S, W]
      int32, "positions": [S] int32, "active": [S] bool}`` is the
      speculative-decoding verifier (Leviathan et al.): column 0 of the
      window is each slot's last emitted token, columns 1..W-1 a
      draft's proposals. It writes all W KV rows per active slot
      (positions ``p0..p0+W-1``) and returns ``(kc, vc, out [S, W],
      logits [S, W, vocab])`` where ``out[s, j]`` is the target's
      greedy token AFTER consuming window tokens ``0..j`` — row j of a
      W-token causal forward, so ``out[s, 0]`` is bit-identical to
      ``decode_fn`` fed the same token. The host accepts the longest
      prefix where the drafted column ``j+1`` equals ``out[:, j]`` and
      rewinds positions past it — the masked-KV discipline (stale rows
      are masked until overwritten) makes the rollback free.

    All are shape-static per (bucket, max_slots, window): the serving
    tier compiles ONE decode program, one verify program per window
    width, plus one prefill program per pow2 prompt bucket
    (docs/serving.md "Generative serving" / "Decode speed").

    ``quantize_weights=True`` expects the param dict from
    :func:`gpt_quantize_params`: matmul weights and embeddings carried
    as int8 payloads plus per-output-channel f32 ``<name>::scale``
    arrays; the dequant is applied to the [..., n_out] matmul PRODUCT
    (or folded into the activation for the tied logits einsum), so the
    weight bytes read per decode step drop 4x without an f32 copy ever
    materializing. ``kv_scales={"k": [L, A, D], "v": [L, A, D]}``
    (from :func:`gpt_kv_scales`) turns the slabs into int8: K/V are
    quantized per (layer, head, channel) at write and dequantized at
    gather, inside the same compiled step.
    """
    import jax
    import jax.numpy as jnp

    H, A, D, L = (cfg.hidden_size, cfg.num_heads, cfg.head_size,
                  cfg.num_layers)
    eps = cfg.layer_norm_eps
    scale = 1.0 / np.sqrt(D)        # matches ops scaled_dot_product_attention
    QW = bool(quantize_weights)
    KQ = kv_scales is not None
    # scales become jaxpr constants at trace time: [L, A, D] each
    ksc = np.asarray(kv_scales["k"], np.float32) if KQ else None
    vsc = np.asarray(kv_scales["v"], np.float32) if KQ else None

    def _ln(x, g, b):
        # one-pass moments + rsqrt, exactly ops/nn_ops.py layer_norm's
        # f32 path (x is f32 here)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        m2 = jnp.mean(x * x, axis=-1, keepdims=True)
        var = jnp.maximum(m2 - mean * mean, 0.0)
        inv = jax.lax.rsqrt(var + eps)
        return (x - mean) * inv * g + b

    def _matmul(p, n, x):
        # int8 path: matmul the raw int8 payload upcast in-register,
        # per-output-channel scale applied to the [..., n_out] product
        # — the dequant rides the matmul epilogue instead of
        # materializing an f32 weight copy
        if QW:
            return (x @ p[n].astype(jnp.float32)) * p[n + "::scale"]
        return x @ p[n]

    def _mlp(p, sc, x):
        y = _matmul(p, f"{sc}/mlp/fc/kernel", x) + p[f"{sc}/mlp/fc/bias"]
        y = jax.nn.gelu(y, approximate=True)    # ops gelu default
        return _matmul(p, f"{sc}/mlp/proj/kernel", y) \
            + p[f"{sc}/mlp/proj/bias"]

    def _tok_emb(p, tokens):
        e = jnp.take(p["wte"], tokens, axis=0)
        if QW:
            e = e.astype(jnp.float32) * p["wte::scale"]
        return e

    def _logits(p, x):
        if cfg.tie_embeddings:
            if QW:
                # (wte_i8 * s_h) contracted over h == wte_i8 contracted
                # with (x * s_h): fold the per-hidden-channel scale into
                # the small activation, keep the big operand int8
                return jnp.einsum("...h,vh->...v", x * p["wte::scale"],
                                  p["wte"].astype(jnp.float32))
            return jnp.einsum("...h,vh->...v", x, p["wte"])
        return _matmul(p, "lm_head", x)

    def _q_store(x, dt, s):
        # symmetric int8 at write: one round+clip per fresh K/V row
        if s is None:
            return x.astype(dt)
        return jnp.clip(jnp.round(x / s), -127, 127).astype(dt)

    def _q_load(x, s):
        # dequant at gather, fused into the score/att matmul producers
        if s is None:
            return x
        return x.astype(jnp.float32) * s

    def prefill_fn(params, kc, vc, io):
        p = params
        tokens, length, slot = io["tokens"], io["length"], io["slot"]
        Lb = tokens.shape[0]
        x = _tok_emb(p, tokens) + p["wpe"][:Lb]             # [Lb, H]
        cm = jnp.tril(jnp.ones((Lb, Lb), bool))
        for i in range(L):
            sc = f"h{i}"
            y = _ln(x, p[f"{sc}/ln_1/gamma"], p[f"{sc}/ln_1/beta"])
            qkv = _matmul(p, f"{sc}/attn/qkv/kernel", y) \
                + p[f"{sc}/attn/qkv/bias"]
            # per-head blocks [q_a|k_a|v_a] — build_gpt's fused layout
            qkv = jnp.transpose(qkv.reshape(Lb, A, 3 * D), (1, 0, 2))
            q, k, v = jnp.split(qkv, 3, axis=-1)        # [A, Lb, D]
            scores = jnp.einsum(
                "aqd,akd->aqk", q, k,
                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(cm, scores, jnp.float32(-1e30))
            probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
            att = jnp.einsum("aqk,akd->aqd", probs, v)
            # write this slot's prompt K/V rows (positions 0..Lb-1);
            # rows past the real length hold padding-token K/V — decode
            # masks them until its own writes land there. All start
            # indices int32 (dynamic_update_slice requires one type;
            # x64 mode would make bare python ints int64)
            z = jnp.asarray(0, jnp.int32)
            starts = (jnp.asarray(i, jnp.int32),
                      jnp.asarray(slot, jnp.int32), z, z, z)
            kc = jax.lax.dynamic_update_slice(
                kc, _q_store(k, kc.dtype,
                             ksc[i][:, None, :] if KQ else None)[None, None],
                starts)
            vc = jax.lax.dynamic_update_slice(
                vc, _q_store(v, vc.dtype,
                             vsc[i][:, None, :] if KQ else None)[None, None],
                starts)
            att = jnp.transpose(att, (1, 0, 2)).reshape(Lb, H)
            att = _matmul(p, f"{sc}/attn/proj/kernel", att) \
                + p[f"{sc}/attn/proj/bias"]
            x = x + att
            y = _ln(x, p[f"{sc}/ln_2/gamma"], p[f"{sc}/ln_2/beta"])
            x = x + _mlp(p, sc, y)
        x = _ln(x, p["ln_f/gamma"], p["ln_f/beta"])
        h_last = jax.lax.dynamic_slice_in_dim(
            x, jnp.maximum(length - 1, 0), 1, axis=0)       # [1, H]
        logits = _logits(p, h_last)[0]                      # [vocab]
        return kc, vc, jnp.argmax(logits).astype(jnp.int32), logits

    def decode_fn(params, kc, vc, io):
        p = params
        tokens, active = io["tokens"], io["active"]
        S, T = kc.shape[1], kc.shape[3]
        pos = jnp.clip(io["positions"], 0, T - 1)
        x = _tok_emb(p, tokens) \
            + jnp.take(p["wpe"], pos, axis=0)               # [S, H]
        si = jnp.arange(S)[:, None]
        ai = jnp.arange(A)[None, :]
        # attend to indices <= position; everything later in the slab
        # is a future write or a retired occupant's stale rows
        mask = jnp.arange(T)[None, None, :] <= pos[:, None, None]
        for i in range(L):
            sc = f"h{i}"
            y = _ln(x, p[f"{sc}/ln_1/gamma"], p[f"{sc}/ln_1/beta"])
            qkv = _matmul(p, f"{sc}/attn/qkv/kernel", y) \
                + p[f"{sc}/attn/qkv/bias"]
            q, k, v = jnp.split(qkv.reshape(S, A, 3 * D), 3, axis=-1)
            # in-place per-slot writes at each slot's own position;
            # inactive slots keep their existing rows (forensics — and
            # a free slot's cache is fully rewritten by prefill anyway)
            cur_k = kc[i, si, ai, pos[:, None]]
            cur_v = vc[i, si, ai, pos[:, None]]
            k_st = _q_store(k, kc.dtype, ksc[i][None] if KQ else None)
            v_st = _q_store(v, vc.dtype, vsc[i][None] if KQ else None)
            kc = kc.at[i, si, ai, pos[:, None]].set(
                jnp.where(active[:, None, None], k_st, cur_k))
            vc = vc.at[i, si, ai, pos[:, None]].set(
                jnp.where(active[:, None, None], v_st, cur_v))
            ctx_k = _q_load(kc[i], ksc[i][None, :, None, :] if KQ else None)
            ctx_v = _q_load(vc[i], vsc[i][None, :, None, :] if KQ else None)
            scores = jnp.einsum(
                "sad,satd->sat", q, ctx_k,
                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(mask, scores, jnp.float32(-1e30))
            probs = jax.nn.softmax(scores, axis=-1).astype(ctx_v.dtype)
            # zero masked V rows: a softmax weight of exactly 0 times a
            # NaN/Inf stale row would still be NaN — the where makes
            # slot reuse provably independent of retired-cache contents
            v_safe = jnp.where(mask[..., None], ctx_v, 0)
            att = jnp.einsum("sat,satd->sad", probs, v_safe)
            att = att.reshape(S, H)
            att = _matmul(p, f"{sc}/attn/proj/kernel", att) \
                + p[f"{sc}/attn/proj/bias"]
            x = x + att
            y = _ln(x, p[f"{sc}/ln_2/gamma"], p[f"{sc}/ln_2/beta"])
            x = x + _mlp(p, sc, y)
        x = _ln(x, p["ln_f/gamma"], p["ln_f/beta"])
        logits = _logits(p, x)                              # [S, vocab]
        return kc, vc, jnp.argmax(logits, axis=-1).astype(jnp.int32), \
            logits

    def verify_fn(params, kc, vc, io):
        p = params
        tokens, active = io["tokens"], io["active"]         # [S, W], [S]
        S, W = tokens.shape
        T = kc.shape[3]
        pos = jnp.clip(io["positions"][:, None]
                       + jnp.arange(W, dtype=jnp.int32)[None, :],
                       0, T - 1)                            # [S, W]
        x = _tok_emb(p, tokens) \
            + jnp.take(p["wpe"], pos, axis=0)               # [S, W, H]
        si = jnp.arange(S)
        ai = jnp.arange(A)
        # window row w attends to global index <= its own position —
        # the causal mask over history + the in-window prefix
        mask = jnp.arange(T)[None, None, :] <= pos[:, :, None]  # [S, W, T]
        # rows beyond each slot's LAST window position are stale
        # (retired occupants / future writes) and may be poisoned;
        # in-window rows masked for earlier w are FRESH finite writes
        # whose -1e30 score gives an exactly-0 weight — so zeroing by
        # the per-slot upper bound is the same poisoned-slab discipline
        # as decode_fn's full mask, without a [S,W,T,D] where
        vmask = jnp.arange(T)[None, :] <= pos[:, -1][:, None]   # [S, T]
        for i in range(L):
            sc = f"h{i}"
            y = _ln(x, p[f"{sc}/ln_1/gamma"], p[f"{sc}/ln_1/beta"])
            qkv = _matmul(p, f"{sc}/attn/qkv/kernel", y) \
                + p[f"{sc}/attn/qkv/bias"]
            q, k, v = jnp.split(qkv.reshape(S, W, A, 3 * D), 3, axis=-1)
            # scatter all W rows per slot at positions p0..p0+W-1;
            # inactive slots keep their existing rows (same contract as
            # decode_fn)
            idx = (i, si[:, None, None], ai[None, None, :],
                   pos[:, :, None])
            cur_k = kc[idx]
            cur_v = vc[idx]
            k_st = _q_store(k, kc.dtype,
                            ksc[i][None, None] if KQ else None)
            v_st = _q_store(v, vc.dtype,
                            vsc[i][None, None] if KQ else None)
            ok = active[:, None, None, None]
            kc = kc.at[idx].set(jnp.where(ok, k_st, cur_k))
            vc = vc.at[idx].set(jnp.where(ok, v_st, cur_v))
            ctx_k = _q_load(kc[i], ksc[i][None, :, None, :] if KQ else None)
            ctx_v = _q_load(vc[i], vsc[i][None, :, None, :] if KQ else None)
            scores = jnp.einsum(
                "swad,satd->swat", q, ctx_k,
                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(mask[:, :, None, :], scores,
                               jnp.float32(-1e30))
            probs = jax.nn.softmax(scores, axis=-1).astype(ctx_v.dtype)
            v_safe = jnp.where(vmask[:, None, :, None], ctx_v, 0)
            att = jnp.einsum("swat,satd->swad", probs, v_safe)
            att = att.reshape(S, W, H)
            att = _matmul(p, f"{sc}/attn/proj/kernel", att) \
                + p[f"{sc}/attn/proj/bias"]
            x = x + att
            y = _ln(x, p[f"{sc}/ln_2/gamma"], p[f"{sc}/ln_2/beta"])
            x = x + _mlp(p, sc, y)
        x = _ln(x, p["ln_f/gamma"], p["ln_f/beta"])
        logits = _logits(p, x)                          # [S, W, vocab]
        return kc, vc, jnp.argmax(logits, axis=-1).astype(jnp.int32), \
            logits

    return prefill_fn, decode_fn, verify_fn


@COMPILE_STATS.model_build("gpt")
def gpt_paged_decode_fns(cfg: GPTConfig, block_size: int,
                         max_blocks_per_req: int,
                         quantize_weights: bool = False, kv_scales=None):
    """Pure-jax ``(prefill_fn, decode_fn, verify_fn)`` over a PAGED KV
    pool — the same math as :func:`gpt_decode_fns` op-for-op, but
    attention reads/writes fixed-size token BLOCKS addressed through
    per-request block tables (vLLM's PagedAttention layout, Kwon et al.
    SOSP '23) instead of one contiguous ``max_seq`` row per slot.

    The pool is ONE ARRAY A LAYER: ``kc`` and ``vc`` are tuples of
    ``num_layers`` leaves, each::

        [num_blocks, block_size, heads * head_dim]

    a block's tokens together, a token's row over all heads (heads
    outermost) as the qkv product makes it. Layer ``i`` touches leaf
    ``i`` only: one scatter of whole rows into it, in place, and one
    gather ``leaf[tables]`` straight from it, so no value of the whole
    pool's size exists in any program and nothing of that size can be
    copied or converted (PERF.md section 5 has what the TPU compiler did
    to a single ``[layers, blocks, heads, block, head_dim]`` array).
    With a last axis of a model's whole width (1600 floats for GPT-2
    XL) in place of one head's 64, a float32 leaf's default TPU layout
    is row-major with next to no padding, the one the scatter and the
    gather both use: a leaf goes from one program to the next
    untouched. All three programs take the leaves and return them (the
    server donates them). Each leaf is viewed as that shape on the way
    in, whatever shape it arrives in.

    Block 0 is the NULL block: never handed out by the pool, the target
    of every unused table entry and every inactive decode lane's write —
    so inactive-lane scatters are harmless by construction and gathered
    trash is provably masked (V rows zeroed under the mask, the same
    poisoned-cache discipline as the slotted decode).

    - ``prefill_fn(params, kc, vc, io)`` with ``io = {"tokens": [Lb]
      int32 (the bucket-padded prompt SUFFIX after any prefix-cache
      hit), "length": () int32 (real suffix length), "hist": () int32
      (cached-prefix length, a multiple of block_size), "table": [MAXB]
      int32}`` scatters the suffix K/V into its table's blocks, attends
      causally over the WHOLE table (cached prefix + fresh suffix) and
      returns ``(kc, vc, next_token, last_logits)`` — the greedy token
      from global position ``hist + length - 1``. ONE program shape
      serves both the cold path (``hist = 0``) and every prefix hit.
    - ``decode_fn(params, kc, vc, io)`` with ``io = {"tokens": [S],
      "positions": [S], "active": [S] bool, "tables": [S, W] int32,
      "write_block": [S] int32, "write_off": [S] int32}`` advances
      every active lane one token in ONE dispatch: the new K/V lands at
      host-computed ``(write_block, write_off)`` (inactive lanes write
      the null block), each lane attends over its own gathered table
      masked to ``index <= position``. The table's width ``W`` is the
      INPUT's: any ``W <= MAXB`` whose ``W * block_size`` positions
      hold every active lane's position (the server sends the
      narrowest of ``serving.paged.table_widths`` that does), and the
      gather, the mask and both products run over that many blocks a
      lane. The columns a narrower table leaves out are those whose
      weights were 0 and whose V rows were zeroed; at ``W = MAXB`` it
      is the program it always was.
    - ``verify_fn(params, kc, vc, io)`` — the speculative-decoding
      verifier over the paged pool: ``io`` carries a [S, W] token window
      plus [S, W] ``write_block``/``write_off`` (host-computed per
      window position; inactive lanes point every column at the null
      block) and returns ``(kc, vc, out [S, W], logits [S, W, vocab])``
      with the same row-j semantics as the dense
      ``gpt_decode_fns`` verifier.

    Because a table slot ``u`` covers exactly global positions
    ``[u * block_size, (u+1) * block_size)``, the gathered context is
    position-ordered — with ``max_blocks_per_req * block_size ==
    max_seq`` it is ELEMENTWISE identical to the dense slab's context,
    so greedy outputs match the dense server bit-for-bit
    (tests/test_paged.py).

    ``quantize_weights`` / ``kv_scales`` follow the
    :func:`gpt_decode_fns` contract: int8 weight payloads with
    ``::scale`` dequant in the matmul epilogue, and int8 KV blocks
    quantized per (layer, head, channel) at write / dequantized at
    gather — which DOUBLES vs f16 (4x vs f32) the tokens a fixed-byte
    ``BlockPool`` holds, compounding with prefix caching.
    """
    import jax
    import jax.numpy as jnp

    H, A, D, L = (cfg.hidden_size, cfg.num_heads, cfg.head_size,
                  cfg.num_layers)
    BS = int(block_size)
    MAXB = int(max_blocks_per_req)
    T = MAXB * BS                   # gathered context length per request
    eps = cfg.layer_norm_eps
    scale = 1.0 / np.sqrt(D)
    QW = bool(quantize_weights)
    KQ = kv_scales is not None
    ksc = np.asarray(kv_scales["k"], np.float32) if KQ else None
    vsc = np.asarray(kv_scales["v"], np.float32) if KQ else None

    def _ln(x, g, b):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        m2 = jnp.mean(x * x, axis=-1, keepdims=True)
        var = jnp.maximum(m2 - mean * mean, 0.0)
        inv = jax.lax.rsqrt(var + eps)
        return (x - mean) * inv * g + b

    def _matmul(p, n, x):
        if QW:
            return (x @ p[n].astype(jnp.float32)) * p[n + "::scale"]
        return x @ p[n]

    def _mlp(p, sc, x):
        y = _matmul(p, f"{sc}/mlp/fc/kernel", x) + p[f"{sc}/mlp/fc/bias"]
        y = jax.nn.gelu(y, approximate=True)
        return _matmul(p, f"{sc}/mlp/proj/kernel", y) \
            + p[f"{sc}/mlp/proj/bias"]

    def _tok_emb(p, tokens):
        e = jnp.take(p["wte"], tokens, axis=0)
        if QW:
            e = e.astype(jnp.float32) * p["wte::scale"]
        return e

    def _logits(p, x):
        if cfg.tie_embeddings:
            if QW:
                return jnp.einsum("...h,vh->...v", x * p["wte::scale"],
                                  p["wte"].astype(jnp.float32))
            return jnp.einsum("...h,vh->...v", x, p["wte"])
        return _matmul(p, "lm_head", x)

    def _q_store(x, dt, s):
        if s is None:
            return x.astype(dt)
        return jnp.clip(jnp.round(x / s), -127, 127).astype(dt)

    def _q_load(x, s):
        if s is None:
            return x
        return x.astype(jnp.float32) * s

    def _leaves(side):
        # one array a layer, each taken as [num_blocks, block_size, H]:
        # rows of one token over all heads, a block's rows together
        return [leaf.reshape(-1, BS, H) for leaf in side]

    def _heads_first(ctx):
        # gathered rows [..., T, A, D] -> [..., A, T, D], the dense
        # slab's order of the same elements
        return jnp.swapaxes(ctx, -3, -2)

    # Each program's layer is jitted on its own, so that the program's
    # trace and lowering hold the layer once and call it ``num_layers``
    # times (the compiler inlines the calls): a decode program is built
    # once a table width, and set-up pays for each. A layer takes ``lp``,
    # its parameters under their names within the layer, ``kl``/``vl``,
    # its leaves, and last ``ks``/``vs``, its K/V scales or None.
    def _layers(layer, p, x, kc, vc, *args):
        for i in range(L):
            sc = f"h{i}"
            lp = {n[len(sc):]: a for n, a in p.items()
                  if n.startswith(sc + "/")}
            x, kc[i], vc[i] = layer(
                lp, x, kc[i], vc[i], *args,
                ksc[i] if KQ else None, vsc[i] if KQ else None)
        return x

    @jax.jit
    def _prefill_layer(lp, x, kl, vl, table, blk, off, cm, valid, ks, vs):
        Lb = x.shape[0]
        y = _ln(x, lp["/ln_1/gamma"], lp["/ln_1/beta"])
        qkv = _matmul(lp, "/attn/qkv/kernel", y) + lp["/attn/qkv/bias"]
        q, k, v = jnp.split(qkv.reshape(Lb, A, 3 * D), 3, axis=-1)
        q = jnp.transpose(q, (1, 0, 2))                      # [A, Lb, D]
        # write the suffix K/V FIRST, then gather the whole table —
        # suffix self-attention reads its own fresh rows. A token's
        # row over all heads goes into the leaf as it comes out of the
        # qkv product
        kl = kl.at[blk, off].set(
            _q_store(k, kl.dtype, None if ks is None else ks[None])
            .reshape(Lb, H))
        vl = vl.at[blk, off].set(
            _q_store(v, vl.dtype, None if vs is None else vs[None])
            .reshape(Lb, H))
        ctx_k = _q_load(_heads_first(kl[table].reshape(T, A, D)),
                        None if ks is None else ks[:, None, :])
        ctx_v = _q_load(_heads_first(vl[table].reshape(T, A, D)),
                        None if vs is None else vs[:, None, :])
        # zero unwritten rows BEFORE the matmuls: null-block trash
        # (even NaN-poisoned) must not reach any reduction
        ctx_k = jnp.where(valid[0][:, None], ctx_k, 0)
        ctx_v = jnp.where(valid[0][:, None], ctx_v, 0)
        scores = jnp.einsum(
            "aqd,akd->aqk", q, ctx_k,
            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(cm[None], scores, jnp.float32(-1e30))
        probs = jax.nn.softmax(scores, axis=-1).astype(ctx_v.dtype)
        att = jnp.einsum("aqk,akd->aqd", probs, ctx_v)
        att = jnp.transpose(att, (1, 0, 2)).reshape(Lb, H)
        att = _matmul(lp, "/attn/proj/kernel", att) + lp["/attn/proj/bias"]
        x = x + att
        y = _ln(x, lp["/ln_2/gamma"], lp["/ln_2/beta"])
        return x + _mlp(lp, "", y), kl, vl

    def prefill_fn(params, kc, vc, io):
        p = params
        kc, vc = _leaves(kc), _leaves(vc)
        tokens, length = io["tokens"], io["length"]
        hist, table = io["hist"], io["table"]
        Lb = tokens.shape[0]
        # global positions of the suffix rows; clip keeps the padded
        # tail's wpe lookups in range (those rows never reach logits)
        g = hist + jnp.arange(Lb, dtype=jnp.int32)
        gpos = jnp.clip(g, 0, cfg.max_seq_len - 1)
        x = _tok_emb(p, tokens) \
            + jnp.take(p["wpe"], gpos, axis=0)               # [Lb, H]
        # scatter targets: suffix row j lands in table slot g//BS at
        # offset g%BS; padding rows (j >= length) land in null block 0
        slot_of = jnp.clip(g // BS, 0, MAXB - 1)
        blk = jnp.where(jnp.arange(Lb) < length, table[slot_of], 0)
        off = jnp.clip(g, 0, T - 1) % BS
        # causal mask over the gathered context: key index t is a
        # GLOBAL position (table slot u holds positions [u*BS,(u+1)*BS))
        cm = jnp.arange(T)[None, :] <= g[:, None]            # [Lb, T]
        # rows past hist+length are unwritten blocks / null-block trash
        valid = jnp.arange(T)[None, :] < hist + length       # [1, T]
        x = _layers(_prefill_layer, p, x, kc, vc, table, blk, off, cm,
                    valid)
        x = _ln(x, p["ln_f/gamma"], p["ln_f/beta"])
        h_last = jax.lax.dynamic_slice_in_dim(
            x, jnp.maximum(length - 1, 0), 1, axis=0)        # [1, H]
        logits = _logits(p, h_last)[0]
        return tuple(kc), tuple(vc), \
            jnp.argmax(logits).astype(jnp.int32), logits

    @jax.jit
    def _decode_layer(lp, x, kl, vl, tables, wb, wo, mask, ks, vs):
        S, T = x.shape[0], mask.shape[-1]
        y = _ln(x, lp["/ln_1/gamma"], lp["/ln_1/beta"])
        qkv = _matmul(lp, "/attn/qkv/kernel", y) + lp["/attn/qkv/bias"]
        q, k, v = jnp.split(qkv.reshape(S, A, 3 * D), 3, axis=-1)
        # unconditional scatter: the host points inactive lanes at
        # the null block, so no active request's rows are touched
        # (active lanes own disjoint blocks — no write collisions)
        kl = kl.at[wb, wo].set(
            _q_store(k, kl.dtype, None if ks is None else ks[None])
            .reshape(S, H))
        vl = vl.at[wb, wo].set(
            _q_store(v, vl.dtype, None if vs is None else vs[None])
            .reshape(S, H))
        ctx_k = _q_load(
            _heads_first(kl[tables].reshape(S, T, A, D)),
            None if ks is None else ks[None, :, None, :])
        ctx_v = _q_load(
            _heads_first(vl[tables].reshape(S, T, A, D)),
            None if vs is None else vs[None, :, None, :])
        scores = jnp.einsum(
            "sad,satd->sat", q, ctx_k,
            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask, scores, jnp.float32(-1e30))
        probs = jax.nn.softmax(scores, axis=-1).astype(ctx_v.dtype)
        # zero masked V rows — same poisoned-slab-reuse discipline
        # as the slotted decode: weight 0 x NaN trash is still NaN
        v_safe = jnp.where(mask[..., None], ctx_v, 0)
        att = jnp.einsum("sat,satd->sad", probs, v_safe)
        att = att.reshape(S, H)
        att = _matmul(lp, "/attn/proj/kernel", att) + lp["/attn/proj/bias"]
        x = x + att
        y = _ln(x, lp["/ln_2/gamma"], lp["/ln_2/beta"])
        return x + _mlp(lp, "", y), kl, vl

    def decode_fn(params, kc, vc, io):
        p = params
        kc, vc = _leaves(kc), _leaves(vc)
        tokens, active = io["tokens"], io["active"]
        tables = io["tables"]                        # [S, W], W <= MAXB
        wb, wo = io["write_block"], io["write_off"]
        if tables.shape[1] > MAXB:
            raise ValueError(f"tables has {tables.shape[1]} entries, a "
                             f"request at most {MAXB} blocks")
        T = tables.shape[1] * BS        # the context gathered, a lane
        pos = jnp.clip(io["positions"], 0, cfg.max_seq_len - 1)
        x = _tok_emb(p, tokens) \
            + jnp.take(p["wpe"], pos, axis=0)                # [S, H]
        # causal over global positions (index t IS position t since the
        # host builds tables in order); rows past pos are masked
        mask = jnp.arange(T)[None, None, :] <= pos[:, None, None]
        x = _layers(_decode_layer, p, x, kc, vc, tables, wb, wo, mask)
        x = _ln(x, p["ln_f/gamma"], p["ln_f/beta"])
        logits = _logits(p, x)                               # [S, vocab]
        return tuple(kc), tuple(vc), \
            jnp.argmax(logits, axis=-1).astype(jnp.int32), logits

    @jax.jit
    def _verify_layer(lp, x, kl, vl, tables, wb, wo, mask, vmask, ks, vs):
        S, W, _ = x.shape
        y = _ln(x, lp["/ln_1/gamma"], lp["/ln_1/beta"])
        qkv = _matmul(lp, "/attn/qkv/kernel", y) + lp["/attn/qkv/bias"]
        q, k, v = jnp.split(qkv.reshape(S, W, A, 3 * D), 3, axis=-1)
        # unconditional [S, W] scatter: active lanes own disjoint
        # in-order (block, off) pairs, inactive lanes' W columns all
        # target the null block (colliding writes there are trash
        # over trash by construction)
        kl = kl.at[wb, wo].set(
            _q_store(k, kl.dtype, None if ks is None else ks[None, None])
            .reshape(S, W, H))
        vl = vl.at[wb, wo].set(
            _q_store(v, vl.dtype, None if vs is None else vs[None, None])
            .reshape(S, W, H))
        ctx_k = _q_load(
            _heads_first(kl[tables].reshape(S, T, A, D)),
            None if ks is None else ks[None, :, None, :])
        ctx_v = _q_load(
            _heads_first(vl[tables].reshape(S, T, A, D)),
            None if vs is None else vs[None, :, None, :])
        scores = jnp.einsum(
            "swad,satd->swat", q, ctx_k,
            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask[:, :, None, :], scores,
                           jnp.float32(-1e30))
        probs = jax.nn.softmax(scores, axis=-1).astype(ctx_v.dtype)
        v_safe = jnp.where(vmask[:, None, :, None], ctx_v, 0)
        att = jnp.einsum("swat,satd->swad", probs, v_safe)
        att = att.reshape(S, W, H)
        att = _matmul(lp, "/attn/proj/kernel", att) + lp["/attn/proj/bias"]
        x = x + att
        y = _ln(x, lp["/ln_2/gamma"], lp["/ln_2/beta"])
        return x + _mlp(lp, "", y), kl, vl

    def verify_fn(params, kc, vc, io):
        p = params
        kc, vc = _leaves(kc), _leaves(vc)
        tokens, active = io["tokens"], io["active"]          # [S, W]
        tables = io["tables"]                                # [S, MAXB]
        wb, wo = io["write_block"], io["write_off"]          # [S, W]
        S, W = tokens.shape
        pos = jnp.clip(io["positions"][:, None]
                       + jnp.arange(W, dtype=jnp.int32)[None, :],
                       0, cfg.max_seq_len - 1)               # [S, W]
        x = _tok_emb(p, tokens) \
            + jnp.take(p["wpe"], pos, axis=0)                # [S, W, H]
        mask = jnp.arange(T)[None, None, :] <= pos[:, :, None]
        # per-slot stale-row bound — see the dense verify_fn: in-window
        # rows masked for earlier w are fresh finite writes, rows past
        # the window's last position may be poisoned trash
        vmask = jnp.arange(T)[None, :] <= pos[:, -1][:, None]
        x = _layers(_verify_layer, p, x, kc, vc, tables, wb, wo, mask,
                    vmask)
        x = _ln(x, p["ln_f/gamma"], p["ln_f/beta"])
        logits = _logits(p, x)                           # [S, W, vocab]
        return tuple(kc), tuple(vc), \
            jnp.argmax(logits, axis=-1).astype(jnp.int32), logits

    return prefill_fn, decode_fn, verify_fn


def _quantized_param_names(cfg: GPTConfig):
    """The matmul weights + embeddings that carry int8 payloads under
    ``quantize_weights`` — the big operands whose bytes dominate decode
    HBM traffic. Layer norms and biases stay f32 (tiny, precision-
    critical)."""
    names = [n for n in gpt_param_names(cfg) if n.endswith("/kernel")]
    names.append("wte")
    if not cfg.tie_embeddings:
        names.append("lm_head")
    return names


def gpt_quantize_params(raw: dict, cfg: GPTConfig) -> dict:
    """Symmetric per-output-channel int8 of the decode parameters:
    every ``/kernel`` plus the embedding matrix becomes an int8 payload
    with a float32 ``<name>::scale`` companion (absmax scales via
    :func:`evaluation.calibration.channel_scales` — weights have no
    outlier tail worth clipping, so every value stays representable).
    ``wte``'s channels are the HIDDEN axis, so the same scale serves
    the embedding take and the tied-logits einsum. Pure: re-pulling
    after ``fit()`` + ``update_model()`` re-quantizes the new weights.
    """
    from deeplearning4j_tpu.evaluation.calibration import channel_scales

    out = {}
    qnames = set(_quantized_param_names(cfg))
    for n, a in raw.items():
        if n in qnames:
            w = np.asarray(a, np.float32)
            s = channel_scales(w, method="absmax")          # [n_out]
            out[n] = np.clip(np.round(w / s), -127, 127).astype(np.int8)
            out[n + "::scale"] = s
        else:
            out[n] = a
    return out


def gpt_kv_scales(sd, cfg: GPTConfig, prompts=None,
                  method: str = "quantile", quantile: float = 0.9995):
    """Calibrate per-(layer, head, channel) int8 scales for the KV
    cache: run the FULL-PRECISION prefill over calibration prompts on a
    one-slot slab, read back the K/V rows it wrote, and feed them
    through :func:`evaluation.calibration.channel_scales` (quantile
    clipping by default — K/V activations have outlier tails that
    absmax would let starve the int8 grid). Returns ``{"k": [L, A, D],
    "v": [L, A, D]}`` float32, the ``kv_scales`` contract of
    :func:`gpt_decode_fns` / :func:`gpt_paged_decode_fns`.

    ``prompts=None`` synthesizes a small deterministic prompt set —
    fine for smoke use; real deployments should pass prompts drawn
    from their actual traffic distribution (docs/serving.md "Decode
    speed")."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.evaluation.calibration import channel_scales

    names = gpt_param_names(cfg)
    params = {n: sd._arrays[n] for n in names}
    prefill_fn, _, _ = gpt_decode_fns(cfg)
    jit_prefill = jax.jit(prefill_fn)
    if prompts is None:
        rng = np.random.default_rng(0)
        span = min(32, cfg.max_seq_len - 1)
        prompts = [rng.integers(0, cfg.vocab_size, size=span)
                   for _ in range(4)]
    k_rows, v_rows = [], []
    for pr in prompts:
        pr = np.asarray(pr, np.int32).reshape(-1)
        Lp = int(pr.size)
        shape = (cfg.num_layers, 1, cfg.num_heads, Lp, cfg.head_size)
        kc, vc, _, _ = jit_prefill(
            params, jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape, jnp.float32),
            {"tokens": pr, "length": np.int32(Lp), "slot": np.int32(0)})
        k_rows.append(np.asarray(kc)[:, 0])         # [L, A, Lp, D]
        v_rows.append(np.asarray(vc)[:, 0])

    def _scales(rows):
        obs = np.concatenate(rows, axis=2)          # [L, A, N, D]
        flat = np.transpose(obs, (2, 0, 1, 3)).reshape(obs.shape[2], -1)
        s = channel_scales(flat, method=method, quantile=quantile)
        return s.reshape(cfg.num_layers, cfg.num_heads, cfg.head_size)

    return {"k": _scales(k_rows), "v": _scales(v_rows)}


def _check_decode_params(sd, cfg: GPTConfig):
    names = gpt_param_names(cfg)
    missing = [n for n in names if n not in sd._arrays]
    if missing:
        raise ValueError(
            f"graph is missing decode parameters {missing[:4]}"
            f"{'...' if len(missing) > 4 else ''} — was it built by "
            f"zoo.gpt.build_gpt with this config?")
    return names


def _params_pull(sd, cfg: GPTConfig, names, quantize_weights: bool):
    if quantize_weights:
        return lambda: gpt_quantize_params(
            {n: sd._arrays[n] for n in names}, cfg)
    return lambda: {n: sd._arrays[n] for n in names}


@COMPILE_STATS.model_build("gpt")
def gpt_paged_spec(sd, cfg: GPTConfig, quantize_weights: bool = False,
                   quantize_kv: bool = False, calibration_prompts=None):
    """The PAGED decode-mode graph hook: a
    :class:`~deeplearning4j_tpu.serving.paged.PagedGenerativeSpec` over
    a trained :func:`build_gpt` graph — what
    ``serving.paged.PagedGenerativeServer`` consumes. Same by-name
    parameter sync as :func:`gpt_generative_spec`; the decode functions
    are built per (block_size, max_blocks_per_req) geometry by the
    server (and memoized, so every server over the same model and
    geometry shares one compile set).

    ``quantize_weights`` serves int8 weight payloads (4x fewer weight
    bytes per decode step); ``quantize_kv`` makes the BLOCK POOL int8 —
    ``kv_dtype`` flips to ``"int8"``, so the server's equal-byte pool
    holds 4x the f32 token capacity — with scales calibrated via
    :func:`gpt_kv_scales` over ``calibration_prompts``."""
    from deeplearning4j_tpu.serving.paged import PagedGenerativeSpec

    names = _check_decode_params(sd, cfg)
    kv_scales = gpt_kv_scales(sd, cfg, prompts=calibration_prompts) \
        if quantize_kv else None
    return PagedGenerativeSpec(
        params=_params_pull(sd, cfg, names, quantize_weights),
        make_fns=lambda block_size, max_blocks: gpt_paged_decode_fns(
            cfg, block_size, max_blocks,
            quantize_weights=quantize_weights, kv_scales=kv_scales),
        kv_shape=lambda num_blocks, block_size: (
            cfg.num_layers, int(num_blocks), cfg.num_heads,
            int(block_size), cfg.head_size),
        vocab_size=cfg.vocab_size,
        max_seq_len=cfg.max_seq_len,
        num_heads=cfg.num_heads,
        kv_dtype="int8" if quantize_kv else "float32")


@COMPILE_STATS.model_build("gpt")
def gpt_generative_spec(sd, cfg: GPTConfig, quantize_weights: bool = False,
                        quantize_kv: bool = False,
                        calibration_prompts=None):
    """The decode-mode graph hook: a
    :class:`~deeplearning4j_tpu.serving.generative.GenerativeSpec` over
    a trained :func:`build_gpt` graph — what
    ``serving.generative.GenerativeServer`` consumes. Parameters are
    pulled from the SameDiff BY NAME at sync time, so further ``fit()``
    followed by ``server.update_model()`` serves the new weights (the
    quantized pull re-quantizes them). The spec carries the verify
    program, so any server over it can act as a speculative-decoding
    TARGET; a second (smaller) spec passed as ``draft_spec=`` acts as
    the draft. ``quantize_weights`` / ``quantize_kv`` follow the
    :func:`gpt_paged_spec` contract (int8 payloads + ``kv_dtype``
    flip), with KV scales calibrated over ``calibration_prompts``."""
    from deeplearning4j_tpu.serving.generative import GenerativeSpec

    names = _check_decode_params(sd, cfg)
    kv_scales = gpt_kv_scales(sd, cfg, prompts=calibration_prompts) \
        if quantize_kv else None
    prefill_fn, decode_fn, verify_fn = gpt_decode_fns(
        cfg, quantize_weights=quantize_weights, kv_scales=kv_scales)
    return GenerativeSpec(
        params=_params_pull(sd, cfg, names, quantize_weights),
        prefill=prefill_fn,
        decode=decode_fn,
        kv_shape=lambda max_slots, max_seq: (
            cfg.num_layers, int(max_slots), cfg.num_heads, int(max_seq),
            cfg.head_size),
        vocab_size=cfg.vocab_size,
        max_seq_len=cfg.max_seq_len,
        kv_dtype="int8" if quantize_kv else "float32",
        verify=verify_fn)
