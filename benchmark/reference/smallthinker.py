"""SmallThinker, plainly: the benchmark's reference and its seeded weights.

Written from the published configuration (``PowerInfer/SmallThinker-21BA3B-
Instruct`` ``config.json``) and the model's description (52 layers, a
4096-token window with RoPE on three of every four and global attention
with no positional term on the fourth; 64 sparse ReGLU experts, six a
token, the router placed before attention). With ``x`` the stream
``[T, hidden]`` and layer ``i``:

- ``a = rmsnorm(x; g1)``; router ``r = a @ Wr``, the six largest of ``r``,
  their weights a softmax over those six;
- ``q, k, v = a @ Wq, a @ Wk, a @ Wv`` (28 query heads over 4 K/V heads of
  128, query head ``h`` reads K/V head ``h // 7``); where ``rope_layout[i]``
  is 1, q and k rotated (rotate-half over the whole head, theta 1.5e6,
  position = index in the sequence); scores ``/ sqrt(128)``; position ``p``
  sees ``j <= p`` and, where ``sliding_window_layout[i]`` is 1, only ``j > p
  - 4096``; softmax; ``x = x + heads @ Wo``;
- ``m = rmsnorm(x; g2)``; ``x = x + sum over the six e of w_e * ((relu(m @
  G_e) * (m @ U_e)) @ D_e)``;
- after the last layer ``rmsnorm(x; gf) @ Wh``.

A full forward over a whole sequence: no cache, no chunks, no tiers; the
causal and window masks are masks; the experts are a loop over all 64 with
each token's weight for the expert (0 where it was not chosen). Float32
throughout, every product at ``Precision.HIGHEST``. It imports nothing of
the program under test (the seed's key is the GPT-2 reference's) and is
given nothing the program made.

Departures from the published code, each on purpose:

- weights are random from the seed (normal, std 0.02; the norms' gains
  ``1 + 0.02 n`` so that a dropped gain shows), ROUNDED TO BFLOAT16, the
  dtype the configuration states for its parameters, and raised to float32
  to compute: the rounded values are the parameters;
- the description's "secondary experts" have no key in ``config.json`` and
  are absent; no projection has a bias;
- the window counts the current token (``j > p - window``);
- the configuration may be cut in depth: the first ``num_hidden_layers``
  entries of the two layouts count.

The work goes a LAYER AT A TIME over all the rows it is given: one layer's
leaves (1.6 GB in float32 at the published widths) and then the head's are
all it holds beside the rows' streams.

``mode`` is the arithmetic: ``"float32"`` is the reference; ``"bfloat16"``
and ``"float8"`` are the CONTROLS (the reference put in the program's place
one precision below what a configuration states), never a reference. Two
more controls keep float32 and get the WINDOW wrong, as a program's ring of
blocks or its mask could (:func:`control_of`): ``"window_off"`` (window layers
see every earlier position) and ``"window_less_<n>"`` (a window ``n``
positions short: a block given back one step early is ``n = block_size``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gpt2 import seed_key

LAYER_KINDS = ("norm_1", "router", "q", "k", "v", "o", "norm_2",
               "gate", "up", "down")
TOP_KINDS = ("embed", "norm_f", "head")
ALL_KINDS = TOP_KINDS + LAYER_KINDS
STD = 0.02


def sizes(cfg: dict) -> dict:
    return {"V": int(cfg["vocab_size"]), "H": int(cfg["hidden_size"]),
            "L": int(cfg["num_hidden_layers"]),
            "A": int(cfg["num_attention_heads"]),
            "KV": int(cfg["num_key_value_heads"]),
            "D": int(cfg["head_dim"]),
            "F": int(cfg["moe_ffn_hidden_size"]),
            "E": int(cfg["moe_num_primary_experts"]),
            "K": int(cfg["moe_num_active_primary_experts"]),
            "W": int(cfg["sliding_window_size"])}


def kind_shape(cfg: dict, kind: str) -> tuple:
    z = sizes(cfg)
    H, F, E = z["H"], z["F"], z["E"]
    return {"embed": (z["V"], H), "norm_f": (H,), "head": (H, z["V"]),
            "norm_1": (H,), "norm_2": (H,), "router": (H, E),
            "q": (H, z["A"] * z["D"]), "k": (H, z["KV"] * z["D"]),
            "v": (H, z["KV"] * z["D"]), "o": (z["A"] * z["D"], H),
            "gate": (E, H, F), "up": (E, H, F), "down": (E, F, H)}[kind]


@functools.partial(jax.jit, static_argnames=("shape", "gain"))
def _draw(key, layer, shape, gain):
    x = jax.random.normal(jax.random.fold_in(key, layer), shape,
                          jnp.float32) * STD
    return ((1.0 + x) if gain else x).astype(jnp.bfloat16)


def draw(cfg: dict, seed: int, kind: str, layer: int = 0):
    """The leaf ``kind`` of ``layer`` (0 for a top-level kind) for
    ``seed``, made on the device: bfloat16, the parameter itself."""
    key = jax.random.fold_in(seed_key(seed), ALL_KINDS.index(kind))
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


def _rmsnorm(x, g, eps):
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


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "top", "window", "rope", "theta", "eps", "mode"))
def _layer(x, p, heads, kv_heads, top, window, rope, theta, eps, mode):
    """One layer on ``x`` [T, H]; ``window`` None for a global layer."""
    T, H = x.shape
    D = p["q"].shape[1] // heads
    a = _rmsnorm(x, p["norm_1"], eps)
    # the router, placed before attention
    r = _mm("th,he->te", a, p["router"], mode)
    best, chosen = jax.lax.top_k(r, top)
    share = jax.nn.softmax(best, axis=-1)                   # [T, top]
    weight = jnp.zeros_like(r).at[
        jnp.arange(T)[:, None], chosen].set(share)          # [T, E]
    q = _mm("th,hk->tk", a, p["q"], mode).reshape(T, heads, D)
    k = _mm("th,hk->tk", a, p["k"], mode).reshape(T, kv_heads, D)
    v = _mm("th,hk->tk", a, p["v"], mode).reshape(T, kv_heads, D)
    if rope:
        q, k = _rotate(q, theta), _rotate(k, theta)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (j > i - window)
    group = heads // kv_heads

    def one_kv_head(_, qkv):
        qh, kh, vh = qkv                  # [group, T, D], [T, D], [T, D]
        s = _mm("gqd,kd->gqk", qh, kh, mode) / math.sqrt(D)
        w = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return None, _mm("gqk,kd->gqd", w, vh, mode)

    qg = q.reshape(T, kv_heads, group, D).transpose(1, 2, 0, 3)
    _, o = jax.lax.scan(one_kv_head, None,
                        (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(2, 0, 1, 3).reshape(T, heads * D)   # head = kv * group
    x = x + _mm("tk,kh->th", o, p["o"], mode)
    m = _rmsnorm(x, p["norm_2"], eps)

    def one_expert(y, e):
        g, u, d, w = e
        h = jax.nn.relu(_mm("th,hf->tf", m, g, mode)) \
            * _mm("th,hf->tf", m, u, mode)
        return y + w[:, None] * _mm("tf,fh->th", h, d, mode), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (p["gate"], p["up"], p["down"], weight.T))
    return x + y, chosen


def hidden(cfg: dict, seed: int, seqs, mode: str = "float32",
           routes: bool = False):
    """The stream after the last layer (before the final norm) for each
    of ``seqs`` (int arrays, all of the lengths a caller wants compiled:
    pad them alike). With ``routes`` also the experts each layer's router
    chose, ``[L, T, top]`` a sequence."""
    z = sizes(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    embed = draw(cfg, seed, "embed")
    xs = [embed[jnp.asarray(s, jnp.int32)].astype(jnp.float32)
          for s in seqs]
    del embed
    chosen = [[] for _ in seqs]
    for i in range(z["L"]):
        p = {k: draw(cfg, seed, k, i + 1) for k in LAYER_KINDS}
        for r, x in enumerate(xs):
            xs[r], c = _layer(
                x, p, z["A"], z["KV"], z["K"],
                z["W"] if cfg["sliding_window_layout"][i] else None,
                bool(cfg["rope_layout"][i]), theta, eps, mode)
            if routes:
                chosen[r].append(np.asarray(c))
        del p
    if routes:
        return xs, [np.stack(c) for c in chosen]
    return xs


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, g, w, eps, mode):
    return _mm("th,hv->tv", _rmsnorm(x, g, eps), w, mode)


def logits(cfg: dict, seed: int, seqs, positions=None,
           mode: str = "float32"):
    """Float32 logits ``[len(positions[r]), V]`` of each sequence, at
    every position where ``positions`` is None."""
    xs = hidden(cfg, seed, seqs, mode)
    g, w = draw(cfg, seed, "norm_f"), draw(cfg, seed, "head")
    eps = float(cfg["rms_norm_eps"])
    out = []
    for r, x in enumerate(xs):
        if positions is not None:
            x = x[jnp.asarray(positions[r], jnp.int32)]
        out.append(_head(x, g, w, eps, mode))
    return out


def control_of(cfg: dict, name: str) -> tuple:
    """``(configuration, mode)`` under which the reference computes the
    control ``name``."""
    if name in ("bfloat16", "float8"):
        return cfg, name
    if name == "window_off":
        return dict(cfg, sliding_window_layout=[
            0 for _ in cfg["sliding_window_layout"]]), "float32"
    if name.startswith("window_less_"):
        short = int(cfg["sliding_window_size"]) - int(name[12:])
        return dict(cfg, sliding_window_size=short), "float32"
    raise ValueError(f"no control {name!r}")


def served_gaps(cfg: dict, seed: int, rows, pad_to: int,
                control: str | None = None):
    """``rows`` is a list of ``(prompt, served)`` int sequences. Runs the
    reference once over each ``prompt + served`` (padded behind to 512 or
    to ``pad_to``, so that two programs serve all lengths; the mask is
    causal, so padding reaches nothing) and returns one array per row:
    for each served token, the gap by which its reference logit lies
    below the reference's best at that position (0 where the served
    token is the reference's own choice).

    With ``control`` set (:func:`control_of`) nothing served is read: at
    each of the same positions the token the control puts first takes
    the served token's place."""
    seqs, spans = [], []
    for prompt, served in rows:
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served, np.int32)])
        n, m = len(prompt), len(served)
        if n + m > pad_to:
            raise ValueError(f"row of {n + m} tokens > pad_to {pad_to}")
        pad = min(p for p in (min(512, pad_to), pad_to) if p >= n + m - 1)
        toks = np.zeros(pad, np.int32)
        # the last served token is never fed back: it conditions
        # nothing that was served
        toks[:n + m - 1] = seq[:-1]
        seqs.append(toks)
        spans.append(np.arange(n - 1, n + m - 1))
    picked = [np.asarray(r[1], np.int32) for r in rows]
    if control is not None:
        wrong, mode = control_of(cfg, control)
        picked = [np.asarray(jnp.argmax(lg, axis=-1))
                  for lg in logits(wrong, seed, seqs, spans, mode)]
    out = []
    for lg, tok in zip(logits(cfg, seed, seqs, spans), picked):
        got = jnp.take_along_axis(lg, jnp.asarray(tok)[:, None], axis=-1)
        out.append(np.asarray(jnp.max(lg, axis=-1) - got[:, 0], np.float64))
    return out
