"""Command A+'s block (``model_type`` ``cohere2_moe``), plainly: the
benchmark's reference and its seeded weights.

Written from the published configuration (``CohereLabs/command-a-plus-
05-2026`` ``config.json``) and the model's description (a parallel
attention and expert block; window layers with rotary positions and
global layers with none, three to one; 128 experts, 8 a token chosen by
their sigmoids, 4 shared experts averaged). With ``x`` the stream ``[T,
hidden]``, no bias on any projection, and layer ``i``:

- ``n = layernorm(x; g)`` (mean and variance, a gain, no bias);
- ``q, k, v = n @ Wq, n @ Wk, n @ Wv`` (128 query heads over 8 K/V heads
  of 128, query head ``h`` reads K/V head ``h // 16``); on a window layer
  (``layer_types[i]`` ``sliding_attention``) q and k rotated GPT-J style
  (the interleaved pairs ``(2i, 2i + 1)``, theta 50,000, position = row)
  and position ``p`` sees ``p - 4096 < j <= p``; on a global layer no
  rotation and ``j <= p``; scores ``/ sqrt(128)``, softmax, ``a = heads @
  Wo``;
- ``s = sigmoid(n @ Wr)`` over the router's 128 experts, the 8 largest
  chosen, weights ``s_e / (sum of the chosen s + 1e-20)``; ``m = sum over
  the chosen e HELD here of w_e * ((silu(n @ G_e) * (n @ U_e)) @ D_e) +
  (1/4) sum over the 4 shared j of (silu(n @ Gs_j) * (n @ Us_j)) @ Ds_j``;
- ``x = x + a + m``: both branches read the same ``n``;
- after the last layer ``logit_scale * layernorm(x; gf) @ E^T``, the
  embedding ``E`` tied.

A full forward over a whole sequence: no cache, no chunks, no tiers; the
causal and window masks are masks; the routed experts are a loop over
the HELD experts with each token's weight for the expert (0 where it was
not chosen), the shared experts four separate products, averaged. Float32
throughout, every product at ``Precision.HIGHEST``. It imports nothing
of the program under test (the seed's key is the GPT-2 reference's) and
is given nothing the program made.

Departures from the published code, each on purpose:

- the configuration is ONE CHIP'S SHARE of a layer divided over chips
  (``deployment`` in its file): experts ``first_expert`` .. ``first_expert
  + num_experts - 1`` of the router's ``router_experts`` are held, and
  what the others would add is left out here as in the program; the
  vocabulary is the slice held (``vocab_size``); the first
  ``num_hidden_layers`` entries of ``layer_types`` count;
- weights are random from the seed (normal, std 0.02; the norms' gains
  ``1 + 0.02 n`` so that a dropped gain shows), ROUNDED TO BFLOAT16, the
  dtype the configuration states for its parameters, and raised to
  float32 to compute: the rounded values are the parameters. An expert's
  weights are drawn from its own number, so that two shares of a layer
  hold the experts the whole layer holds;
- three readings of the configuration (its ``assumed``): the shared
  experts' ``average`` is their mean, added to the routed sum; the
  LayerNorm has a gain and no bias; the window counts the current token
  (``j > p - window``);
- the vision tower of the published model is absent: the configuration
  holds the language model alone;
- attention goes over BLOCKS of query rows (float32 scores of 128 heads
  x 10,752 x 10,752 are 59 GB whole); every block sees all the keys
  under the masks, so the numbers are those of the whole product.

The work goes a LAYER AT A TIME over all the rows it is given: one
layer's leaves (2.3 GB in bfloat16 at the cell's size) and then the
embedding are all it holds beside the rows' streams.

``mode`` is the arithmetic: ``"float32"`` is the reference;
``"bfloat16"`` and ``"float8"`` are the CONTROLS (the reference put in the
program's place one precision below what a configuration states), never
a reference. Four more controls keep float32 and get the BLOCK wrong
(:func:`control_of`): ``"sequential_block"`` (attention first, the
experts on a second norm of the updated stream), ``"shared_summed"``
(the shared experts summed, not averaged), ``"rope_half"`` (rotate-half
where the file says GPT-J) and ``"window_off"`` (window layers see every
earlier position).

What is COMPARED (:func:`served_gaps`, ``adapters/cohere2_moe.py``
``check_served``): each served token's gap under this reference's best
logit, at the positions where no router of THIS reference stood within
:data:`CLEAR_MARGIN` between its 8th and 9th expert with one of the two
held here. A top-k choice over near-tied sigmoids is a step function:
where two experts stand closer than a program's rounding, the program
may sort them the other way, and one expert more or less here moves the
position's logits by more than lower precision or a wrong rule does. A
swap between two experts held elsewhere moves this chip's result only
through the renormalisation, by less than the margin itself. The excused
set is the reference's own and the same for the program and a control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gpt2 import seed_key

LAYER_KINDS = ("norm", "q", "k", "v", "o", "router", "gate", "up", "down",
               "shared_gate", "shared_up", "shared_down")
TOP_KINDS = ("embed", "norm_f")
ALL_KINDS = TOP_KINDS + LAYER_KINDS
#: kinds drawn one expert at a time, from the expert's own number
EXPERT_KINDS = ("gate", "up", "down")
SHARED_KINDS = ("shared_gate", "shared_up", "shared_down")
STD = 0.02
#: query rows a block of attention
QUERY_BLOCK = 128
#: the least margin of a CLEAR choice: a served token is compared where,
#: in every layer, the reference's 8th and 9th largest sigmoids stand at
#: least this far apart or neither of the two experts is held here.
#: Closer than that is a near-tie, which a program that rounds its
#: operands to bfloat16 (as the configuration states) may settle the
#: other way (PERF.md section 2 gives the readings it was set from)
CLEAR_MARGIN = 0.004
#: the row lengths the reference is compiled for: a row is padded behind
#: to the first that holds it (or to the server's ``max_seq_len``)
PADS = (512, 2048)


def sizes(cfg: dict) -> dict:
    L = int(cfg["num_hidden_layers"])
    return {"V": int(cfg["vocab_size"]), "H": int(cfg["hidden_size"]),
            "L": L, "A": int(cfg["num_attention_heads"]),
            "KV": int(cfg["num_key_value_heads"]),
            "D": int(cfg["head_dim"]), "F": int(cfg["intermediate_size"]),
            "E": int(cfg["num_experts"]),
            "ER": int(cfg.get("router_experts", cfg["num_experts"])),
            "first": int(cfg.get("first_expert", 0)),
            "K": int(cfg["num_experts_per_tok"]),
            "S": int(cfg["num_shared_experts"]),
            "W": int(cfg["sliding_window"]),
            "window": tuple(t == "sliding_attention"
                            for t in cfg["layer_types"][:L])}


def kind_shape(cfg: dict, kind: str) -> tuple:
    """A leaf's shape; for an expert kind, ONE expert's."""
    z = sizes(cfg)
    H, F, A, KV, D = z["H"], z["F"], z["A"], z["KV"], z["D"]
    return {"embed": (z["V"], H), "norm_f": (H,), "norm": (H,),
            "q": (H, A * D), "k": (H, KV * D), "v": (H, KV * D),
            "o": (A * D, H), "router": (H, z["ER"]),
            "gate": (H, F), "up": (H, F), "down": (F, H),
            "shared_gate": (H, F), "shared_up": (H, F),
            "shared_down": (F, H)}[kind]


@functools.partial(jax.jit, static_argnames=("shape", "gain"))
def _draw(key, layer, shape, gain):
    x = jax.random.normal(jax.random.fold_in(key, layer), shape,
                          jnp.float32) * STD
    return ((1.0 + x) if gain else x).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("shape",))
def _draw_each(key, layer, ids, shape):
    k = jax.random.fold_in(key, layer)
    return jax.vmap(lambda e: (jax.random.normal(
        jax.random.fold_in(k, e), shape, jnp.float32) * STD)
        .astype(jnp.bfloat16))(ids)


def draw(cfg: dict, seed: int, kind: str, layer: int = 0):
    """The leaf ``kind`` of ``layer`` (0 for a top-level kind, ``i + 1``
    for layer ``i``) for ``seed``, made on the device: bfloat16, the
    parameter itself. A routed expert kind gives the HELD experts
    ``[num_experts, ...]`` (experts ``first_expert`` on), a shared kind
    the shared experts ``[num_shared_experts, ...]``, each expert drawn
    from its own number."""
    z = sizes(cfg)
    key = jax.random.fold_in(seed_key(seed), ALL_KINDS.index(kind))
    if kind in EXPERT_KINDS or kind in SHARED_KINDS:
        ids = (jnp.arange(z["E"], dtype=jnp.int32) + z["first"]
               if kind in EXPERT_KINDS
               else jnp.arange(z["S"], dtype=jnp.int32))
        return _draw_each(key, jnp.int32(layer), ids, kind_shape(cfg, kind))
    return _draw(key, jnp.int32(layer), kind_shape(cfg, kind),
                 kind.startswith("norm"))


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


def _layernorm(x, g, eps):
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True)
                             + eps) * g.astype(jnp.float32)


def _angles(T, D, theta):
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    return jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]


def _rotate(x, theta, half: bool = False):
    """x [T, heads, D], position = row: GPT-J's interleaved pairs ``(2i,
    2i + 1)``, or with ``half`` the rotate-half of the other families
    (the ``rope_half`` control)."""
    T, heads, D = x.shape
    ang = _angles(T, D, theta)[:, None]                   # [T, 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if half:
        x1, x2 = x[..., :D // 2], x[..., D // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(T, heads, D)


def _gated(m, gate, up, down, mode):
    h = jax.nn.silu(_mm("th,hf->tf", m, gate, mode)) \
        * _mm("th,hf->tf", m, up, mode)
    return _mm("tf,fh->th", h, down, mode)


def _attention(n, p, z, window, theta, mode, variant):
    T = n.shape[0]
    A, KV, D, W = z["A"], z["KV"], z["D"], z["W"]
    q = _mm("th,hk->tk", n, p["q"], mode).reshape(T, A, D)
    k = _mm("th,hk->tk", n, p["k"], mode).reshape(T, KV, D)
    v = _mm("th,hk->tk", n, p["v"], mode).reshape(T, KV, D)
    # ``window`` is traced (one program serves both kinds of layer): the
    # rotation and the window's mask are taken where it is true
    half = variant == "rope_half"
    q = jnp.where(window, _rotate(q, theta, half), q)
    k = jnp.where(window, _rotate(k, theta, half), k)
    blk = min(T, QUERY_BLOCK)
    if T % blk:
        raise ValueError(f"{T} rows are no whole blocks of {blk}")
    j = jnp.arange(T)[None, :]

    def one_block(_, qi):
        qb, i = qi                        # [blk, KV, G, D], [blk]
        s = _mm("qhgd,khd->hgqk", qb, k, mode) / math.sqrt(D)
        see = j <= i[:, None]
        if variant != "window_off":
            see = see & (~window | (j > i[:, None] - W))
        w = jax.nn.softmax(jnp.where(see[None, None], s, -1e30), axis=-1)
        return None, _mm("hgqk,khd->qhgd", w, v, mode)

    _, o = jax.lax.scan(one_block, None,
                        (q.reshape(T // blk, blk, KV, A // KV, D),
                         jnp.arange(T).reshape(T // blk, blk)))
    return _mm("tk,kh->th", o.reshape(T, A * D), p["o"], mode)


def _experts(n, p, z, mode, variant):
    """The held experts' part and the shared experts' mean on ``n``, and
    the MARGIN of the router's choice ``[T]``: how far its 8th largest
    sigmoid stands over its 9th where one of those two experts is held
    here (infinite where neither is)."""
    T = n.shape[0]
    K, E, first = z["K"], z["E"], z["first"]
    s = jax.nn.sigmoid(_mm("th,he->te", n, p["router"], mode))
    _, chosen = jax.lax.top_k(s, K)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    share = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    weight = jnp.zeros_like(s).at[
        jnp.arange(T)[:, None], chosen].set(share)[:, first:first + E]
    edge, at = jax.lax.top_k(s, K + 1)
    held = ((at[:, K - 1:] >= first) & (at[:, K - 1:] < first + E)).any(-1)
    margin = jnp.where(held, edge[:, K - 1] - edge[:, K], jnp.inf)

    def one_expert(y, e):
        g, u, d, w = e
        return y + w[:, None] * _gated(n, g, u, d, mode), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(n),
                        (p["gate"], p["up"], p["down"], weight.T))
    shared = sum(_gated(n, p["shared_gate"][j], p["shared_up"][j],
                        p["shared_down"][j], mode)
                 for j in range(z["S"]))
    if variant != "shared_summed":
        shared = shared / z["S"]
    return y + shared, margin


@functools.partial(jax.jit, static_argnames=(
    "z", "theta", "eps", "mode", "variant"))
def _layer(x, p, z, window, theta, eps, mode, variant):
    """One layer on ``x`` [T, H]; ``window`` a traced boolean, true for
    a window layer. Returns the stream and the margin of its router's
    choice (:func:`_experts`)."""
    n = _layernorm(x, p["norm"], eps)
    a = _attention(n, p, z, window, theta, mode, variant)
    if variant == "sequential_block":
        x = x + a
        y, margin = _experts(_layernorm(x, p["norm"], eps), p, z, mode,
                             variant)
        return x + y, margin
    y, margin = _experts(n, p, z, mode, variant)
    return x + a + y, margin


class _Sizes(dict):
    """``sizes`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def hidden(cfg: dict, seed: int, seqs, mode: str = "float32",
           variant=None):
    """The stream after the last layer (before the final norm) for each
    of ``seqs`` (int arrays, all of the lengths a caller wants compiled:
    pad them alike), and the margin of each layer's choice ``[L, T]`` a
    sequence (:func:`_experts`)."""
    z = _Sizes(sizes(cfg))
    eps = float(cfg["layer_norm_eps"])
    theta = float(cfg["rope_theta"])
    embed = draw(cfg, seed, "embed")
    xs = [embed[jnp.asarray(s, jnp.int32)].astype(jnp.float32)
          for s in seqs]
    del embed
    margin = [[] for _ in seqs]
    for i in range(z["L"]):
        p = {k: draw(cfg, seed, k, i + 1) for k in LAYER_KINDS}
        for r, x in enumerate(xs):
            xs[r], g = _layer(x, p, z, jnp.bool_(z["window"][i]), theta,
                              eps, mode, variant)
            margin[r].append(np.asarray(g))
        del p
    return xs, [np.stack(g) for g in margin]


@functools.partial(jax.jit, static_argnames=("eps", "scale", "mode"))
def _head(x, g, w, eps, scale, mode):
    return _mm("th,vh->tv", _layernorm(x, g, eps), w, mode) * scale


def logits(cfg: dict, seed: int, seqs, positions=None,
           mode: str = "float32", variant=None, margins: bool = False):
    """Float32 logits ``[len(positions[r]), V]`` of each sequence, at
    every position where ``positions`` is None. With ``margins`` also,
    a sequence, the LEAST margin of the layers' choices at each of those
    positions."""
    xs, least = hidden(cfg, seed, seqs, mode, variant)
    g, w = draw(cfg, seed, "norm_f"), draw(cfg, seed, "embed")
    eps, scale = float(cfg["layer_norm_eps"]), float(
        cfg.get("logit_scale", 1.0))
    out = []
    for r, x in enumerate(xs):
        least[r] = least[r].min(axis=0, initial=np.inf)
        if positions is not None:
            x = x[jnp.asarray(positions[r], jnp.int32)]
            least[r] = least[r][np.asarray(positions[r])]
        out.append(_head(x, g, w, eps, scale, mode))
    return (out, least) if margins else out


CONTROLS = ("bfloat16", "float8", "sequential_block", "shared_summed",
            "rope_half", "window_off")


def control_of(name: str) -> tuple:
    """``(mode, variant)`` under which the reference computes the
    control ``name``."""
    if name in ("bfloat16", "float8"):
        return name, None
    if name in CONTROLS:
        return "float32", name
    raise ValueError(f"no control {name!r}")


def served_rows(rows, pad_to: int):
    """``rows`` is a list of ``(prompt, served)`` int sequences. Gives
    what the reference runs over, a row: ``prompt + served`` without its
    last token (never fed back: it conditions nothing that was served),
    padded behind to the first of :data:`PADS` or ``pad_to`` that holds
    it, so that a few programs serve all lengths (the mask is causal:
    padding reaches nothing); and the positions whose logits chose the
    served tokens."""
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
        mode, variant = control_of(control)
        picked = [np.asarray(jnp.argmax(lg, axis=-1))
                  for lg in logits(cfg, seed, seqs, spans, mode, variant)]
    lgs, least = logits(cfg, seed, seqs, spans, margins=True)
    return gaps_under(lgs, picked), least
