"""LFM2-8B-A1B's block (``model_type`` ``lfm2_moe``), plainly: the
benchmark's reference and its seeded weights.

Written from the published configuration (``LiquidAI/LFM2-8B-A1B``
``config.json``) and the family's description (gated short convolutions
of length ``conv_L_cache`` beside grouped-query attention layers, as
``layer_types`` lays them out; 32 experts, 4 a token, chosen by their
sigmoids and an expert bias; two leading dense layers). With ``x`` the
stream ``[T, hidden]``, no bias on any projection, every norm ``rms(x;
g) = g * x / sqrt(mean(x^2) + norm_eps)``, and layer ``i``:

- ``u = rms(x; g_op)``; on a ``conv`` layer ``[B | C | xt] = u @ W_in``
  (split in that order), ``v = B * xt``, ``z_t = sum_{k < L} w[:, k] *
  v_{t - (L - 1) + k}`` with ``v`` zero before the sequence (the
  sequence padded in front by ``L - 1`` zero rows, the sum written out),
  ``m = (C * z) @ W_out``; on a ``full_attention`` layer ``q, k, v = u @
  Wq, u @ Wk, u @ Wv`` (32 query heads over 8 K/V heads of 64, query head
  ``h`` reads K/V head ``h // 4``), q and k each through ``rms`` over a
  head's 64 numbers with a gain of their own and THEN rotated
  (rotate-half, theta ``rope_theta``, position = row), position ``p``
  sees ``j <= p``, scores ``/ sqrt(64)``, softmax, ``m = heads @ Wo``;
- ``h = x + m``, ``n = rms(h; g_ffn)``; the first ``num_dense_layers``
  layers ``y = (silu(n @ W1) * (n @ W3)) @ W2``; the others ``s =
  sigmoid(n @ Wr)``, the ``num_experts_per_tok`` experts with the
  largest ``s + b`` (``b`` the expert bias), their weights
  ``routed_scaling_factor * s_e / (sum of the chosen s + 1e-6)``, ``s``
  WITHOUT ``b``, ``y = sum_e w_e * ((silu(n @ G_e) * (n @ U_e)) @ D_e)``;
  ``x = h + y``;
- after the last layer ``rms(x; g_f) @ E^T``, the embedding ``E`` tied.

A full forward over a whole sequence: no cache, no state, no chunks; the
causal mask is a mask; the experts are a loop over all of them with each
token's weight for the expert (0 where it was not chosen). Float32
throughout, every product at ``Precision.HIGHEST``. It imports nothing
of the program under test (the seed's key is the GPT-2 reference's) and
is given nothing the program made.

Departures from the published code, each on purpose:

- the configuration may be cut in depth: the first ``num_hidden_layers``
  entries of ``layer_types`` count;
- weights are random from the seed (normal, std 0.02; the norms' gains
  ``1 + 0.02 n`` so that a dropped gain shows; the expert bias normal
  with std ``router_bias_std``, 0.1 where the file names none, against
  sigmoids that spread by 0.2, so that it moves the choice; the
  convolution's three leaves with std ``conv_std`` where the file names
  one: the mixer's output goes as the fifth power of the spread times
  hidden squared, so 0.02 gives it the stream's size at the published
  2,048 and a thousandth of it at a test's 64, where 0.08 gives it the
  published size), ROUNDED TO BFLOAT16, the dtype the configuration
  states for its parameters, and raised to float32 to compute: the
  rounded values are the parameters;
- readings of the configuration (its ``assumed``): the head is tied; the
  router's divisor adds 1e-6; an RMSNorm gain has no unit offset;
- attention goes over BLOCKS of query rows; every block sees all the keys
  under the mask, so the numbers are those of the whole product.

The work goes a LAYER AT A TIME over all the rows it is given: one
layer's leaves (0.7 GB in bfloat16 at the published widths) and then the
embedding are all it holds beside the rows' streams.

``mode`` is the arithmetic: ``"float32"`` is the reference; ``"bfloat16"``
and ``"float8"`` are the CONTROLS (the reference put in the program's
place one precision below what a configuration states), never a
reference. Four more keep float32 and get the MODEL wrong
(:func:`control_of`): ``"conv_taps_reversed"`` (tap ``k`` applied to
``v_{t - k}``: a kernel laid out the wrong way round), ``"bias_off"``
(the choice by ``s`` alone), ``"state_dropped"`` (every run of the
prompt after the first, a chunk of :data:`STATE_CHUNK` positions or of
the ``<n>`` of ``state_dropped_<n>``, starts its convolutions from zeros)
and ``"qk_norm_off"`` (q and k rotated without their norms).

What is COMPARED (:func:`served_gaps`, ``adapters/lfm2_moe.py``
``check_served``): each served token's gap under this reference's best
logit, at the positions where every router of THIS reference chose by
at least :data:`CLEAR_MARGIN` (its 4th largest ``s + b`` over its 5th).
A top-k choice is a step function: where two experts stand closer than a
program's rounding, the program may sort them the other way, and one
other expert moves the position's logits by more than lower precision or
a wrong rule does; such a position is excused, by the reference's own
margin and by nothing the program reports. The excused set is the same
for the program and for a control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gpt2 import seed_key

CONV_KINDS = ("conv_in", "conv_w", "conv_out")
ATTN_KINDS = ("q", "k", "v", "o", "q_norm", "k_norm")
DENSE_KINDS = ("mlp_gate", "mlp_up", "mlp_down")
MOE_KINDS = ("router", "router_bias", "gate", "up", "down")
LAYER_KINDS = ("norm_op", "norm_ffn") + CONV_KINDS + ATTN_KINDS \
    + DENSE_KINDS + MOE_KINDS
TOP_KINDS = ("embed", "norm_f")
ALL_KINDS = TOP_KINDS + LAYER_KINDS
STD = 0.02
BIAS_STD = 0.1
#: the router's divisor (the family's code)
EPS = 1e-6
#: query rows a block of attention
QUERY_BLOCK = 512
#: the least margin of a CLEAR choice: a served token is compared where
#: in every expert layer the last expert the reference chose stands at
#: least this far over the first one it left out, in ``s + b``. Closer
#: than that is a near-tie, which a program that rounds its operands to
#: bfloat16 (as the configuration states) may settle the other way: on
#: the chip at the published widths, through 14 expert layers, the
#: program's widest gap read 0.46-0.99 over the positions clear by
#: 0.002, 0.11-0.46 by 0.004 and 0.028-0.051 by 0.008 (2-4% of 2,200-
#: 3,600 served tokens, three seeds), where the nearest control read
#: 0.82 (PERF.md section 2)
CLEAR_MARGIN = 0.008
#: the prompt's runs a ``state_dropped`` control cuts at, where its name
#: gives none: the cell's largest bucket
STATE_CHUNK = 512
#: the row lengths the reference is compiled for: a row is padded behind
#: to the first that holds it (or to the server's ``max_seq_len``)
PADS = (512, 2048)


def sizes(cfg: dict) -> dict:
    L = int(cfg["num_hidden_layers"])
    H, A = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"V": int(cfg["vocab_size"]), "H": H, "L": L, "A": A,
            "KV": int(cfg["num_key_value_heads"]), "D": H // A,
            "LC": int(cfg["conv_L_cache"]),
            "I": int(cfg["intermediate_size"]),
            "F": int(cfg["moe_intermediate_size"]),
            "E": int(cfg["num_experts"]), "K": int(cfg["num_experts_per_tok"]),
            "dense": int(cfg["num_dense_layers"]),
            "attn": tuple(t == "full_attention"
                          for t in cfg["layer_types"][:L])}


def layer_kinds(cfg: dict, layer: int) -> tuple:
    """The leaves of layer ``layer`` (from 0)."""
    z = sizes(cfg)
    return LAYER_KINDS[:2] + (ATTN_KINDS if z["attn"][layer]
                              else CONV_KINDS) \
        + (DENSE_KINDS if layer < z["dense"] else MOE_KINDS)


def kind_shape(cfg: dict, kind: str) -> tuple:
    z = sizes(cfg)
    H, A, KV, D, I, F, E = (z[k] for k in ("H", "A", "KV", "D", "I", "F",
                                           "E"))
    return {"embed": (z["V"], H), "norm_f": (H,), "norm_op": (H,),
            "norm_ffn": (H,), "conv_in": (H, 3 * H), "conv_w": (H, z["LC"]),
            "conv_out": (H, H), "q": (H, A * D), "k": (H, KV * D),
            "v": (H, KV * D), "o": (A * D, H), "q_norm": (D,),
            "k_norm": (D,), "mlp_gate": (H, I), "mlp_up": (H, I),
            "mlp_down": (I, H), "router": (H, E), "router_bias": (E,),
            "gate": (E, H, F), "up": (E, H, F), "down": (E, F, H)}[kind]


@functools.partial(jax.jit, static_argnames=("shape", "std", "gain"))
def _draw(key, layer, shape, std, gain):
    x = jax.random.normal(jax.random.fold_in(key, layer), shape,
                          jnp.float32) * std
    return ((1.0 + x) if gain else x).astype(jnp.bfloat16)


def draw(cfg: dict, seed: int, kind: str, layer: int = 0):
    """The leaf ``kind`` of ``layer`` (0 for a top-level kind, ``i + 1``
    for layer ``i``) for ``seed``, made on the device: bfloat16, the
    parameter itself."""
    key = jax.random.fold_in(seed_key(seed), ALL_KINDS.index(kind))
    std = float(cfg.get("router_bias_std", BIAS_STD)) \
        if kind == "router_bias" else float(cfg.get("conv_std", STD)) \
        if kind in CONV_KINDS else STD
    return _draw(key, jnp.int32(layer), kind_shape(cfg, kind), std,
                 "norm" in kind)


# ----------------------------------------------------------------------
# arithmetic
_HI = jax.lax.Precision.HIGHEST
_F8 = jnp.float8_e4m3fn


def _mm(eq: str, a, b, mode: str):
    """One product in ``mode``'s arithmetic, float32 out."""
    if mode == "float32":
        return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=_HI, preferred_element_type=jnp.float32)
    if mode == "float8":
        a, b = a.astype(_F8), b.astype(_F8)
    return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.bfloat16
                      ).astype(jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g.astype(jnp.float32)


def _rotate(x, theta):
    """x [T, heads, D]: rotate-half over all of D, position = row."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _conv(u, p, seg, mode, variant):
    """The gated short convolution on ``u [T, H]``; ``seg [T]`` is the run
    of the prompt each position lies in (all 0 but for
    ``state_dropped``: a tap reaches no position of another run)."""
    T, H = u.shape
    LC = p["conv_w"].shape[1]
    bcx = _mm("th,hk->tk", u, p["conv_in"], mode)
    b, c, xt = bcx[:, :H], bcx[:, H:2 * H], bcx[:, 2 * H:]
    v = jnp.concatenate([jnp.zeros((LC - 1, H), jnp.float32), b * xt])
    sv = jnp.concatenate([jnp.full((LC - 1,), -1, seg.dtype), seg])
    w = p["conv_w"].astype(jnp.float32)
    if variant == "conv_taps_reversed":
        w = w[:, ::-1]
    z = jnp.zeros((T, H), jnp.float32)
    for k in range(LC):
        # v_{t - (L - 1) + k} is row t + k of the padded sequence
        same = (sv[k:k + T] == seg)[:, None]
        z = z + w[:, k] * jnp.where(same, v[k:k + T], 0.0)
    return _mm("th,hk->tk", c * z, p["conv_out"], mode)


def _attention(u, p, z, theta, eps, mode, variant):
    T = u.shape[0]
    A, KV, D = z["A"], z["KV"], z["D"]
    q = _mm("th,hk->tk", u, p["q"], mode).reshape(T, A, D)
    k = _mm("th,hk->tk", u, p["k"], mode).reshape(T, KV, D)
    v = _mm("th,hk->tk", u, p["v"], mode).reshape(T, KV, D)
    if variant != "qk_norm_off":
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    q, k = _rotate(q, theta), _rotate(k, theta)
    blk = min(T, QUERY_BLOCK)
    if T % blk:
        raise ValueError(f"{T} rows are no whole blocks of {blk}")
    j = jnp.arange(T)[None, :]

    def one_block(_, qi):
        qb, i = qi                        # [blk, KV, G, D], [blk]
        s = _mm("qhgd,khd->hgqk", qb, k, mode) / math.sqrt(D)
        w = jax.nn.softmax(jnp.where((j <= i[:, None])[None, None], s,
                                     -1e30), axis=-1)
        return None, _mm("hgqk,khd->qhgd", w, v, mode)

    _, o = jax.lax.scan(one_block, None,
                        (q.reshape(T // blk, blk, KV, A // KV, D),
                         jnp.arange(T).reshape(T // blk, blk)))
    return _mm("tk,kh->th", o.reshape(T, A * D), p["o"], mode)


def _gated(m, gate, up, down, mode):
    h = jax.nn.silu(_mm("th,hf->tf", m, gate, mode)) \
        * _mm("th,hf->tf", m, up, mode)
    return _mm("tf,fh->th", h, down, mode)


def _experts(n, p, z, scale, mode, variant):
    """The routed experts on ``n`` and the MARGIN of the router's choice
    ``[T]``: by how much the last expert chosen stands over the first one
    left out in ``s + b``."""
    T, K = n.shape[0], z["K"]
    s = jax.nn.sigmoid(_mm("th,he->te", n, p["router"], mode))
    sb = s if variant == "bias_off" \
        else s + p["router_bias"].astype(jnp.float32)[None]
    _, chosen = jax.lax.top_k(sb, K)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    share = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True) + EPS)
    weight = jnp.zeros_like(s).at[jnp.arange(T)[:, None], chosen].set(share)
    edge = jax.lax.top_k(sb, K + 1)[0]

    def one_expert(y, e):
        g, u, d, w = e
        return y + w[:, None] * _gated(n, g, u, d, mode), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(n),
                        (p["gate"], p["up"], p["down"], weight.T))
    return y, edge[:, K - 1] - edge[:, K]


class _Sizes(dict):
    """``sizes`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnames=(
    "z", "attn", "dense", "theta", "eps", "scale", "mode", "variant"))
def _layer(x, p, seg, z, attn, dense, theta, eps, scale, mode, variant):
    """One layer on ``x`` [T, H]. Returns the stream and the margin of
    its router's choice (:func:`_experts`; infinite for a dense layer)."""
    u = _rms(x, p["norm_op"], eps)
    h = x + (_attention(u, p, z, theta, eps, mode, variant) if attn
             else _conv(u, p, seg, mode, variant))
    n = _rms(h, p["norm_ffn"], eps)
    if dense:
        return h + _gated(n, p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                          mode), jnp.full((x.shape[0],), jnp.inf)
    y, margin = _experts(n, p, z, scale, mode, variant)
    return h + y, margin


def _segments(T: int, prompt_len: int, chunk: int):
    """The run of the prompt each of ``T`` positions lies in, cut every
    ``chunk`` positions of the prompt; the served positions after it lie
    in its last run (a decode step carries the state)."""
    t = np.minimum(np.arange(T), max(prompt_len - 1, 0))
    return jnp.asarray(t // chunk, jnp.int32)


def hidden(cfg: dict, seed: int, seqs, mode: str = "float32",
           variant=None, segs=None):
    """The stream after the last layer (before the final norm) for each
    of ``seqs`` (int arrays, all of the lengths a caller wants compiled:
    pad them alike), and the margin of each EXPERT layer's choice
    ``[expert layers, T]`` a sequence (:func:`_experts`). ``segs`` gives
    each sequence's runs (:func:`_segments`; one run where None)."""
    z = _Sizes(sizes(cfg))
    eps, theta = float(cfg["norm_eps"]), float(cfg["rope_theta"])
    scale = float(cfg.get("routed_scaling_factor", 1.0))
    embed = draw(cfg, seed, "embed")
    xs = [embed[jnp.asarray(s, jnp.int32)].astype(jnp.float32)
          for s in seqs]
    del embed
    if segs is None:
        segs = [jnp.zeros(len(s), jnp.int32) for s in seqs]
    margin = [[] for _ in seqs]
    for i in range(z["L"]):
        p = {k: draw(cfg, seed, k, i + 1) for k in layer_kinds(cfg, i)}
        for r, x in enumerate(xs):
            xs[r], g = _layer(x, p, segs[r], z, z["attn"][i],
                              i < z["dense"], theta, eps, scale, mode,
                              variant)
            if i >= z["dense"]:
                margin[r].append(np.asarray(g))
        del p
    return xs, [np.stack(g) for g in margin]


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, g, w, eps, mode):
    return _mm("th,vh->tv", _rms(x, g, eps), w, mode)


def logits(cfg: dict, seed: int, seqs, positions=None,
           mode: str = "float32", variant=None, margins: bool = False,
           segs=None):
    """Float32 logits ``[len(positions[r]), V]`` of each sequence, at
    every position where ``positions`` is None. With ``margins`` also,
    a sequence, the LEAST margin of the expert layers' choices at each of
    those positions."""
    xs, least = hidden(cfg, seed, seqs, mode, variant, segs)
    g, w = draw(cfg, seed, "norm_f"), draw(cfg, seed, "embed")
    eps = float(cfg["norm_eps"])
    out = []
    for r, x in enumerate(xs):
        least[r] = least[r].min(axis=0, initial=np.inf)
        if positions is not None:
            x = x[jnp.asarray(positions[r], jnp.int32)]
            least[r] = least[r][np.asarray(positions[r])]
        out.append(_head(x, g, w, eps, mode))
    return (out, least) if margins else out


CONTROLS = ("bfloat16", "float8", "conv_taps_reversed", "bias_off",
            "state_dropped", "qk_norm_off")


def control_of(name: str) -> tuple:
    """``(mode, variant, chunk)`` under which the reference computes the
    control ``name``; ``chunk`` the prompt's runs of ``state_dropped`` (or
    ``state_dropped_<n>``), else None."""
    if name in ("bfloat16", "float8"):
        return name, None, None
    if name.startswith("state_dropped"):
        rest = name[len("state_dropped"):]
        return "float32", "state_dropped", \
            int(rest[1:]) if rest else STATE_CHUNK
    if name in CONTROLS:
        return "float32", name, None
    raise ValueError(f"no control {name!r}")


def served_rows(rows, pad_to: int):
    """``rows`` is a list of ``(prompt, served)`` int sequences. Gives
    what the reference runs over, a row: ``prompt + served`` without its
    last token (never fed back: it conditions nothing that was served),
    padded behind to the first of :data:`PADS` or ``pad_to`` that holds
    it, so that a few programs serve all lengths (the mask is causal and
    the convolution looks back: padding reaches nothing); and the
    positions whose logits chose the served tokens."""
    seqs, spans = [], []
    for prompt, served in rows:
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served, np.int32)])
        n, m = len(prompt), len(served)
        if n + m > pad_to:
            raise ValueError(f"row of {n + m} tokens > pad_to {pad_to}")
        pad = min(p for p in PADS + (pad_to,)
                  if n + m - 1 <= p <= pad_to)
        toks = np.zeros(pad, np.int32)
        toks[:n + m - 1] = seq[:-1]
        seqs.append(toks)
        spans.append(np.arange(n - 1, n + m - 1))
    return seqs, spans


def gaps_under(lgs, picked):
    """For each row's logits ``[m, V]`` and tokens ``[m]``: by how much
    each token's logit lies below the best at its position."""
    out = []
    for lg, tok in zip(lgs, picked):
        got = jnp.take_along_axis(lg, jnp.asarray(tok)[:, None], axis=-1)
        out.append(np.asarray(jnp.max(lg, axis=-1) - got[:, 0], np.float64))
    return out


def served_gaps(cfg: dict, seed: int, rows, pad_to: int,
                control: str | None = None):
    """Runs the reference once over each of ``rows``
    (:func:`served_rows`) and returns two arrays a row: for each served
    token, the gap by which its reference logit lies below the
    reference's best at that position (0 where the served token is the
    reference's own choice), and the least margin of the reference's
    routers at that position (:data:`CLEAR_MARGIN` says what it is for).

    With ``control`` set (:func:`control_of`) nothing served is read: at
    each of the same positions the token the control puts first takes
    the served token's place."""
    seqs, spans = served_rows(rows, pad_to)
    picked = [np.asarray(r[1], np.int32) for r in rows]
    if control is not None:
        mode, variant, chunk = control_of(control)
        segs = None if chunk is None else [
            _segments(len(s), len(r[0]), chunk) for s, r in zip(seqs, rows)]
        picked = [np.asarray(jnp.argmax(lg, axis=-1))
                  for lg in logits(cfg, seed, seqs, spans, mode, variant,
                                   segs=segs)]
    lgs, least = logits(cfg, seed, seqs, spans, margins=True)
    return gaps_under(lgs, picked), least
