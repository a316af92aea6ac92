"""EvaByte, plainly: the benchmark's reference and its seeded weights.

Written from the published configuration (``EvaByte/EvaByte``
``config.json``: ``attention_class`` ``eva``, ``chunk_size`` 16,
``window_size`` 2048, 32 heads of 128, SwiGLU 4096 -> 11008 -> 4096,
``num_pred_heads`` 8 over a vocabulary of 320 bytes) and the paper its
attention is named after (EVA: Zheng et al., ICLR 2023,
arXiv:2302.04542). With ``x`` the stream ``[T, hidden]``, ``s = 128 **
-0.5``, ``W`` the window and ``c`` the chunk, a layer is:

- ``a = rmsnorm(x) * (1 + g1)`` (``norm_add_unit_offset``, eps 1e-5);
  ``q, k, v = a Wq, a Wk, a Wv``; q and k rotated (rotate-half over the
  128, theta 100000, position = index in the sequence);
- per head ``h`` two learned vectors ``phi_h``, ``mu_h``; chunk ``n``
  holds tokens ``c n .. c n + c - 1``: ``w_i = softmax_i(s phi_h . k_i)``
  over its ``c`` rotated keys, ``k~_n = sum_i w_i k_i + mu_h``, ``v~_n =
  sum_i w_i v_i``;
- a query at ``t`` (its window ``t // W``) attends in ONE softmax to the
  exact keys ``E = {m : W (t // W) <= m <= t}`` and to the summaries ``C =
  {n : n < (W / c) (t // W)}`` of every chunk of every earlier window:
  ``o_t = (sum_E e^{s q.k_m} v_m + sum_C e^{s q.k~_n} v~_n) / (sum_E
  e^{s q.k_m} + sum_C e^{s q.k~_n})``; ``x = x + o Wo``;
- ``b = rmsnorm(x) * (1 + g2)``; ``x = x + (silu(b Wg) * (b Wu)) Wd``;
- after the last layer ``rmsnorm(x) * (1 + gf)``, logits ``= h W_head``,
  ``num_pred_heads * vocab`` wide: head ``j`` is the columns ``[vocab j,
  vocab (j + 1))``, head 0 the next byte.

A full forward over a whole sequence: no cache, no tiers, no runs; every
chunk's summary is computed from the sequence's own rows, and attention
is explicit masks over ``[exact | summaries]``. Float32 throughout, every
product at ``Precision.HIGHEST``. It imports nothing of the program under
test (the seed's key is the GPT-2 reference's) and is given nothing the
program made.

The work goes a LAYER AT A TIME over all the rows it is given, and within
a layer a WINDOW OF QUERY ROWS at a time: a window's queries are held
against the exact rows of their own window and the one before it (the
reference masks the one before it out; the control ``window_slides``
needs it) and against every summary, so a row of 21k positions costs 11
blocks of ``[2048, 4096 + T / 16]`` scores a head and not a ``[T, T]``
array. A sequence is padded behind to a whole power-of-two number of
windows (causal masks: padding reaches nothing).

Departures from the published description, each on purpose:

- weights are random from the seed (normal, std ``init_std`` 0.01275,
  ``phi`` and ``mu`` the same; the norms' gains ``0.02 n`` under the unit
  offset, so that a dropped gain shows), ROUNDED TO BFLOAT16, the dtype
  the configuration file states for its parameters, and raised to
  float32 to compute: the rounded values are the parameters;
- what ``config.json`` does not say and the file's ``assumed`` does: the
  scale ``s`` on ``phi . k`` (``phi`` is drawn like a key, so the same
  scale keeps the chunk's softmax as soft as the query's); ``mu`` is
  added to the summary KEY (EVA's control variate shifts the key of the
  chunk's estimate) and nothing to the value; keys are summarised AFTER
  rotation, so that a summary is a mean of the keys the exact softmax
  would have used; no dropout, no attention mask but the causal one;
- the heads beyond the first (the model's multi-byte draft) are computed
  by :func:`logits` and compared by the CPU tests; the served comparison
  reads head 0, which is what a server samples;
- the configuration may be cut in depth.

``mode`` is the arithmetic: ``"float32"`` is the reference; ``"float8"``
(operands rounded to 8 bits) is the CONTROL one precision below the
bfloat16 the file states. Two more controls keep float32 and get the
CACHE wrong, as a program's two stores could (:func:`control_of`):
``"summaries_off"`` (``C`` empty: a program that lost the far context) and
``"window_slides"`` (``E`` the last ``W`` positions, ``C`` every chunk
wholly behind them: a program whose window slid and did not tumble).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gpt2 import seed_key

LAYER_KINDS = ("norm_1", "q", "k", "v", "o", "phi", "mu", "norm_2",
               "gate", "up", "down")
TOP_KINDS = ("embed", "norm_f", "head")
ALL_KINDS = TOP_KINDS + LAYER_KINDS
#: the spread of a norm's gain under the unit offset
GAIN_STD = 0.02
VARIANTS = ("eva", "summaries_off", "window_slides")


def sizes(cfg: dict) -> dict:
    H, A = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"V": int(cfg["vocab_size"]), "H": H,
            "L": int(cfg["num_hidden_layers"]), "A": A, "D": H // A,
            "F": int(cfg["intermediate_size"]),
            "P": int(cfg["num_pred_heads"]),
            "c": int(cfg["chunk_size"]), "W": int(cfg["window_size"])}


def kind_shape(cfg: dict, kind: str) -> tuple:
    z = sizes(cfg)
    H, F, AD = z["H"], z["F"], z["A"] * z["D"]
    return {"embed": (z["V"], H), "norm_f": (H,),
            "head": (H, z["P"] * z["V"]),
            "norm_1": (H,), "norm_2": (H,),
            "q": (H, AD), "k": (H, AD), "v": (H, AD), "o": (AD, H),
            "phi": (z["A"], z["D"]), "mu": (z["A"], z["D"]),
            "gate": (H, F), "up": (H, F), "down": (F, H)}[kind]


@functools.partial(jax.jit, static_argnames=("shape", "std"))
def _draw(key, layer, shape, std):
    return (jax.random.normal(jax.random.fold_in(key, layer), shape,
                              jnp.float32) * std).astype(jnp.bfloat16)


def draw(cfg: dict, seed: int, kind: str, layer: int = 0):
    """The leaf ``kind`` of ``layer`` (0 for a top-level kind) for
    ``seed``, made on the device: bfloat16, the parameter itself."""
    key = jax.random.fold_in(seed_key(seed), ALL_KINDS.index(kind))
    std = GAIN_STD if kind.startswith("norm") else float(cfg["init_std"])
    return _draw(key, jnp.int32(layer), kind_shape(cfg, kind), std)


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


def _rmsnorm(x, g, eps, offset):
    g = g.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + g if offset else g)


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
    "heads", "chunk", "window", "theta", "eps", "offset", "mode",
    "variant"))
def _layer(x, p, heads, chunk, window, theta, eps, offset, mode, variant):
    """One layer on ``x`` [T, H], T a whole number of windows."""
    T, H = x.shape
    A, c, W = heads, chunk, window
    D = p["q"].shape[1] // A
    s = 1.0 / math.sqrt(D)
    a = _rmsnorm(x, p["norm_1"], eps, offset)
    q = _rotate(_mm("th,hk->tk", a, p["q"], mode).reshape(T, A, D), theta)
    k = _rotate(_mm("th,hk->tk", a, p["k"], mode).reshape(T, A, D), theta)
    v = _mm("th,hk->tk", a, p["v"], mode).reshape(T, A, D)
    # every chunk's summary, from the sequence's own rows
    kb, vb = k.reshape(T // c, c, A, D), v.reshape(T // c, c, A, D)
    w = jax.nn.softmax(s * _mm("ad,ncad->nca", p["phi"], kb, mode), axis=1)
    ks = jnp.einsum("nca,ncad->nad", w, kb, precision=_HI) \
        + p["mu"].astype(jnp.float32)[None]
    vs = jnp.einsum("nca,ncad->nad", w, vb, precision=_HI)
    # a window of queries against the rows of its own window and of the
    # one before it (a window of zeros before the first), and every chunk
    front = jnp.zeros((W, A, D), jnp.float32)
    kp, vp = jnp.concatenate([front, k]), jnp.concatenate([front, v])
    n = jnp.arange(T // c)[None, :]                       # chunk [1, N]

    def one_window(_, b):
        t = b * W + jnp.arange(W)[:, None]                # query  [W, 1]
        m = (b - 1) * W + jnp.arange(2 * W)[None, :]      # key [1, 2W]
        if variant == "window_slides":
            exact = (m <= t) & (m > t - W) & (m >= 0)
            far = c * n + c - 1 <= t - W
        else:
            exact = (m <= t) & (m >= b * W)
            far = (n < (W // c) * b) & (variant != "summaries_off")
        qb = jax.lax.dynamic_slice_in_dim(q, b * W, W)
        ke = jax.lax.dynamic_slice_in_dim(kp, b * W, 2 * W)
        ve = jax.lax.dynamic_slice_in_dim(vp, b * W, 2 * W)

        def one_head(_, h):
            qh, keh, veh, ksh, vsh = h
            sc = jnp.concatenate([
                jnp.where(exact, s * _mm("qd,kd->qk", qh, keh, mode), -1e30),
                jnp.where(far, s * _mm("qd,kd->qk", qh, ksh, mode), -1e30)],
                axis=-1)
            pr = jax.nn.softmax(sc, axis=-1)
            return None, _mm("qk,kd->qd", pr,
                             jnp.concatenate([veh, vsh]), mode)

        _, o = jax.lax.scan(one_head, None, (
            qb.transpose(1, 0, 2), ke.transpose(1, 0, 2),
            ve.transpose(1, 0, 2), ks.transpose(1, 0, 2),
            vs.transpose(1, 0, 2)))
        o = o.transpose(1, 0, 2).reshape(W, A * D)
        xb = jax.lax.dynamic_slice_in_dim(x, b * W, W) \
            + _mm("tk,kh->th", o, p["o"], mode)
        g = _rmsnorm(xb, p["norm_2"], eps, offset)
        y = jax.nn.silu(_mm("th,hf->tf", g, p["gate"], mode)) \
            * _mm("th,hf->tf", g, p["up"], mode)
        return None, xb + _mm("tf,fh->th", y, p["down"], mode)

    _, out = jax.lax.scan(one_window, None, jnp.arange(T // W))
    return out.reshape(T, H)


def _padded(cfg: dict, n: int) -> int:
    """``n`` rows padded to a power-of-two number of windows."""
    W = int(cfg["window_size"])
    windows = 1
    while windows * W < n:
        windows *= 2
    return windows * W


def hidden(cfg: dict, seed: int, seqs, mode: str = "float32",
           variant: str = "eva"):
    """The stream after the last layer (before the final norm) for each
    of ``seqs`` (int arrays), each padded behind with zeros to a
    power-of-two number of windows."""
    if variant not in VARIANTS:
        raise ValueError(f"no attention variant {variant!r}")
    z = sizes(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    offset = bool(cfg["norm_add_unit_offset"])
    embed = draw(cfg, seed, "embed")
    xs = []
    for s in seqs:
        toks = np.zeros(_padded(cfg, len(s)), np.int32)
        toks[:len(s)] = np.asarray(s, np.int32)
        xs.append(embed[jnp.asarray(toks)].astype(jnp.float32))
    del embed
    for i in range(z["L"]):
        p = {k: draw(cfg, seed, k, i + 1) for k in LAYER_KINDS}
        for r, x in enumerate(xs):
            xs[r] = _layer(x, p, z["A"], z["c"], z["W"], theta, eps, offset,
                           mode, variant)
        del p
    return xs


@functools.partial(jax.jit, static_argnames=("eps", "offset", "mode"))
def _head(x, g, w, eps, offset, mode):
    return _mm("th,hv->tv", _rmsnorm(x, g, eps, offset), w, mode)


def logits(cfg: dict, seed: int, seqs, positions=None,
           mode: str = "float32", variant: str = "eva",
           heads: int | None = None):
    """Float32 logits ``[len(positions[r]), heads * V]`` of each
    sequence (every position of the sequence where ``positions`` is
    None), of the first ``heads`` prediction heads (all of them where
    None)."""
    xs = hidden(cfg, seed, seqs, mode, variant)
    g, w = draw(cfg, seed, "norm_f"), draw(cfg, seed, "head")
    if heads is not None:
        w = w[:, :heads * int(cfg["vocab_size"])]
    eps, offset = float(cfg["rms_norm_eps"]), bool(
        cfg["norm_add_unit_offset"])
    out = []
    for r, x in enumerate(xs):
        at = np.arange(len(seqs[r])) if positions is None else positions[r]
        out.append(_head(x[jnp.asarray(at, jnp.int32)], g, w, eps, offset,
                         mode))
    return out


def control_of(name: str) -> tuple:
    """``(mode, variant)`` under which the reference computes the
    control ``name``."""
    if name == "float8":
        return name, "eva"
    if name in ("summaries_off", "window_slides"):
        return "float32", name
    raise ValueError(f"no control {name!r}")


def served_gaps(cfg: dict, seed: int, rows, pad_to: int,
                control: str | None = None):
    """``rows`` is a list of ``(prompt, served)`` int sequences. Runs the
    reference once over each ``prompt + served`` (no longer than
    ``pad_to``) and returns one array per row: for each served token,
    the gap by which its reference logit (head 0, the next byte) lies
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
        # the last served token is never fed back: it conditions
        # nothing that was served
        seqs.append(seq[:-1])
        spans.append(np.arange(n - 1, n + m - 1))
    picked = [np.asarray(r[1], np.int32) for r in rows]
    if control is not None:
        mode, variant = control_of(control)
        picked = [np.asarray(jnp.argmax(lg, axis=-1))
                  for lg in logits(cfg, seed, seqs, spans, mode, variant,
                                   heads=1)]
    out = []
    for lg, tok in zip(logits(cfg, seed, seqs, spans, heads=1), picked):
        got = jnp.take_along_axis(lg, jnp.asarray(tok)[:, None], axis=-1)
        out.append(np.asarray(jnp.max(lg, axis=-1) - got[:, 0], np.float64))
    return out
