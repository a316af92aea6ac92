"""GLM-4.7-Flash's block (``model_type`` ``glm4_moe_lite``), plainly: the
benchmark's reference and its seeded weights.

Written from the published configuration (``zai-org/GLM-4.7-Flash``
``config.json``) in the PUBLISHED form of latent attention: the context
is expanded to per-head K and V through ``Wkvb``; the absorbed form that
a serving program may use is nowhere here. With ``x`` the stream ``[T,
hidden]``, no bias on any projection, and layer ``i``:

- ``a = rmsnorm(x; g1)``; ``q = rmsnorm(a @ Wqa; gq) @ Wqb``, a head's
  columns ``[q_nope | q_rope]``; ``[c, kr] = a @ Wkva``, ``c = rmsnorm(c;
  gkv)``, ``kr`` ONE rotary key for all heads; ``q_rope`` and ``kr``
  rotated (rotate-half over the rotary width, theta 1e6, position = row);
  ``[k_nope_h | v_h] = c @ Wkvb`` head after head, ``k_h = [k_nope_h,
  kr]``; scores ``q_h . k_h / sqrt(qk_nope + qk_rope)``, position ``p``
  sees ``j <= p`` (a mask), softmax, ``o = concat_h(p_h @ v_h)``; ``x = x
  + o @ Wo``;
- ``m = rmsnorm(x; g2)``; the first ``first_k_dense_replace`` layers:
  ``x = x + (silu(m @ G) * (m @ U)) @ D``; the others: ``s = sigmoid(m @
  Wr)``, the ``num_experts_per_tok`` experts with the largest ``s + b``
  (``n_group`` = ``topk_group`` = 1: the group limit keeps every expert),
  their weights ``routed_scaling_factor * s_e / (sum of the chosen s +
  1e-20)``, ``s`` WITHOUT ``b``; ``x = x + sum_e w_e * ((silu(m @ G_e) *
  (m @ U_e)) @ D_e) + (silu(m @ Gs) * (m @ Us)) @ Ds``, the last the
  shared expert;
- after the last layer ``rmsnorm(x; gf) @ Wh``.

A full forward over a whole sequence: no cache, no chunks; the experts
are a loop over all of them with each token's weight for the expert (0
where it was not chosen); the shared expert is a plain product. Float32
throughout, every product at ``Precision.HIGHEST``. It imports nothing
of the program under test (the seed's key is the GPT-2 reference's) and
is given nothing the program made.

Departures from the published code, each on purpose:

- weights are random from the seed (normal, std 0.02; the norms' gains
  ``1 + 0.02 n`` so that a dropped gain shows; the router's correction
  bias normal with std ``router_bias_std``, 0.1 where the file names
  none, against sigmoids near 0.5, so that it moves the choice), ROUNDED
  TO BFLOAT16, the dtype the configuration states for its parameters,
  and raised to float32 to compute: the rounded values are the
  parameters;
- the next-token-prediction module (``num_nextn_predict_layers``) is no
  part of the model's logits and is absent;
- rotate-half over the rotary numbers as they come out of the projection
  (no interleaving permutation of the checkpoint's columns: the weights
  are random);
- the configuration may be cut in depth: the first ``num_hidden_layers``
  layers count;
- attention goes over BLOCKS of query rows (float32 scores of 20 x
  16,384 x 16,384 are 21 GB whole); every block sees all the keys under
  the mask, so the numbers are those of the whole product.

The work goes a LAYER AT A TIME over all the rows it is given: one
layer's leaves (1.3 GB in bfloat16 at the published widths) and then the
head's are all it holds beside the rows' streams.

``mode`` is the arithmetic: ``"float32"`` is the reference; ``"bfloat16"``
and ``"float8"`` are the CONTROLS (the reference put in the program's
place one precision below what a configuration states), never a
reference. Two more controls keep float32 and get the ROUTER's rules
wrong (:func:`control_of`): ``"bias_off"`` (selection by the bare
sigmoids) and ``"scale_off"`` (the chosen weights sum to 1, not to
``routed_scaling_factor``).

What is COMPARED (:func:`served_gaps`, ``adapters/glm4_moe_lite.py``
``check_served``): each served token's gap under this reference's best
logit, at the positions where every router of THIS reference chose by
at least :data:`CLEAR_MARGIN`. A top-k choice is a step function: where
two experts stand closer than a program's rounding, the program may
sort them the other way, and one other expert of weight 0.45 moves the
position's logits by more than lower precision or a wrong rule does;
such a position is excused, by the reference's own margin and by
nothing the program reports. The excused set is the same for the
program and for a control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gpt2 import seed_key

ATTN_KINDS = ("norm_1", "q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b",
              "o", "norm_2")
DENSE_KINDS = ("mlp_gate", "mlp_up", "mlp_down")
MOE_KINDS = ("router", "router_bias", "gate", "up", "down", "shared_gate",
             "shared_up", "shared_down")
LAYER_KINDS = ATTN_KINDS + DENSE_KINDS + MOE_KINDS
TOP_KINDS = ("embed", "norm_f", "head")
ALL_KINDS = TOP_KINDS + LAYER_KINDS
STD = 0.02
BIAS_STD = 0.1
#: query rows a block of attention
QUERY_BLOCK = 1024
#: the least margin of a CLEAR choice: a served token is compared where
#: in every expert layer the last expert the reference chose stands at
#: least this far over the first one it left out, in ``s + b``. Closer
#: than that is a near-tie, which a program that rounds its operands to
#: bfloat16 (as the configuration states) may settle the other way; one
#: other expert then moves that position's logits by more than the gap
#: between a sound program and a faulty one, so such a position says
#: nothing of either and is excused. On the chip at the published widths
#: (5,823 served tokens of five seeds, ``experiments/glm_gap_margin.py``)
#: a parted token's gap passed 0.1 at 20% of the positions under 0.0005,
#: at 2.2% from 0.002 to 0.003, at 3 of 1,058 from 0.003 to 0.005 (the
#: last at 0.0044) and at none of 1,428 beyond; a tenth of the positions
#: are clear by 0.008 (PERF.md section 2)
CLEAR_MARGIN = 0.008


def sizes(cfg: dict) -> dict:
    return {"V": int(cfg["vocab_size"]), "H": int(cfg["hidden_size"]),
            "L": int(cfg["num_hidden_layers"]),
            "A": int(cfg["num_attention_heads"]),
            "QR": int(cfg["q_lora_rank"]), "C": int(cfg["kv_lora_rank"]),
            "DN": int(cfg["qk_nope_head_dim"]),
            "DR": int(cfg["qk_rope_head_dim"]),
            "DV": int(cfg["v_head_dim"]),
            "I": int(cfg["intermediate_size"]),
            "F": int(cfg["moe_intermediate_size"]),
            "E": int(cfg["n_routed_experts"]),
            "K": int(cfg["num_experts_per_tok"]),
            "S": int(cfg["n_shared_experts"]),
            "dense": int(cfg["first_k_dense_replace"])}


def layer_kinds(cfg: dict, layer: int) -> tuple:
    """The leaves of layer ``layer`` (from 0)."""
    z = sizes(cfg)
    if layer < z["dense"]:
        return ATTN_KINDS + DENSE_KINDS
    return ATTN_KINDS + (MOE_KINDS if z["S"] else MOE_KINDS[:5])


def kind_shape(cfg: dict, kind: str) -> tuple:
    z = sizes(cfg)
    H, A, F, E, I = z["H"], z["A"], z["F"], z["E"], z["I"]
    SF = z["S"] * F
    return {"embed": (z["V"], H), "norm_f": (H,), "head": (H, z["V"]),
            "norm_1": (H,), "norm_2": (H,),
            "q_a": (H, z["QR"]), "q_norm": (z["QR"],),
            "q_b": (z["QR"], A * (z["DN"] + z["DR"])),
            "kv_a": (H, z["C"] + z["DR"]), "kv_norm": (z["C"],),
            "kv_b": (z["C"], A * (z["DN"] + z["DV"])),
            "o": (A * z["DV"], H),
            "mlp_gate": (H, I), "mlp_up": (H, I), "mlp_down": (I, H),
            "router": (H, E), "router_bias": (E,),
            "gate": (E, H, F), "up": (E, H, F), "down": (E, F, H),
            "shared_gate": (H, SF), "shared_up": (H, SF),
            "shared_down": (SF, H)}[kind]


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
        if kind == "router_bias" else STD
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


def _gated(m, gate, up, down, mode):
    h = jax.nn.silu(_mm("th,hf->tf", m, gate, mode)) \
        * _mm("th,hf->tf", m, up, mode)
    return _mm("tf,fh->th", h, down, mode)


def _scores(m, router, bias, mode: str):
    """``(s, s + b)`` of the published router on ``m [T, H]``."""
    s = jax.nn.sigmoid(_mm("th,he->te", m, router, mode))
    return s, s + bias.astype(jnp.float32)[None]


def _pick(s, sb, top: int, scale: float):
    _, chosen = jax.lax.top_k(sb, top)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    share = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True)
                              + 1e-20)
    weight = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(share)
    return chosen, weight


def route(m, router, bias, top: int, scale: float, mode: str = "float32"):
    """The published router on ``m [T, H]``: ``(chosen [T, top], weight
    [T, E])``, the weight 0 where an expert was not chosen."""
    return _pick(*_scores(m, router, bias, mode), top, scale)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "top", "scale", "theta", "eps", "mode"))
def _layer(x, p, heads, nope, rope, top, scale, theta, eps, mode):
    """One layer on ``x`` [T, H]; ``p`` holds a dense layer's leaves or
    an expert layer's. Returns the stream and the MARGIN of the router's
    choice ``[T]``: by how much the last expert chosen stands over the
    first one left out in ``s + b`` (infinite for a dense layer)."""
    T, H = x.shape
    C = p["kv_norm"].shape[0]
    a = _rmsnorm(x, p["norm_1"], eps)
    cq = _rmsnorm(_mm("th,hr->tr", a, p["q_a"], mode), p["q_norm"], eps)
    q = _mm("tr,rk->tk", cq, p["q_b"], mode).reshape(T, heads, nope + rope)
    ckr = _mm("th,hc->tc", a, p["kv_a"], mode)
    c = _rmsnorm(ckr[:, :C], p["kv_norm"], eps)
    kr = _rotate(ckr[:, None, C:], theta)                   # [T, 1, rope]
    kv = _mm("tc,ck->tk", c, p["kv_b"], mode).reshape(T, heads, -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(kr, (T, heads, rope))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], theta)], -1)
    blk = min(T, QUERY_BLOCK)
    if T % blk:
        raise ValueError(f"{T} rows are no whole blocks of {blk}")
    j = jnp.arange(T)[None, :]

    def one_block(_, qi):
        qb, i = qi                        # [blk, heads, D], [blk]
        s = _mm("qad,kad->aqk", qb, k, mode) / math.sqrt(nope + rope)
        w = jax.nn.softmax(jnp.where((j <= i[:, None])[None], s, -1e30),
                           axis=-1)
        return None, _mm("aqk,kav->qav", w, v, mode)

    _, o = jax.lax.scan(one_block, None,
                        (q.reshape(T // blk, blk, heads, nope + rope),
                         jnp.arange(T).reshape(T // blk, blk)))
    x = x + _mm("tk,kh->th", o.reshape(T, -1), p["o"], mode)
    m = _rmsnorm(x, p["norm_2"], eps)
    if "mlp_gate" in p:
        y = _gated(m, p["mlp_gate"], p["mlp_up"], p["mlp_down"], mode)
        return x + y, jnp.full((T,), jnp.inf, jnp.float32)
    s, sb = _scores(m, p["router"], p["router_bias"], mode)
    _, weight = _pick(s, sb, top, scale)
    edge = jax.lax.top_k(sb, top + 1)[0]
    margin = edge[:, top - 1] - edge[:, top]

    def one_expert(y, e):
        g, u, d, w = e
        return y + w[:, None] * _gated(m, g, u, d, mode), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (p["gate"], p["up"], p["down"], weight.T))
    if "shared_gate" in p:
        y = y + _gated(m, p["shared_gate"], p["shared_up"],
                       p["shared_down"], mode)
    return x + y, margin


def hidden(cfg: dict, seed: int, seqs, mode: str = "float32"):
    """The stream after the last layer (before the final norm) for each
    of ``seqs`` (int arrays, all of the lengths a caller wants compiled:
    pad them alike), and the margin of each EXPERT layer's choice
    ``[expert layers, T]`` a sequence (:func:`_layer`)."""
    z = sizes(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    embed = draw(cfg, seed, "embed")
    xs = [embed[jnp.asarray(s, jnp.int32)].astype(jnp.float32)
          for s in seqs]
    del embed
    margin = [[] for _ in seqs]
    for i in range(z["L"]):
        p = {k: draw(cfg, seed, k, i + 1) for k in layer_kinds(cfg, i)}
        for r, x in enumerate(xs):
            xs[r], g = _layer(
                x, p, z["A"], z["DN"], z["DR"], z["K"],
                float(cfg["routed_scaling_factor"]), theta, eps, mode)
            if i >= z["dense"]:
                margin[r].append(np.asarray(g))
        del p
    return xs, [np.stack(g) if g else np.empty((0, x.shape[0]))
                for g, x in zip(margin, xs)]


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, g, w, eps, mode):
    return _mm("th,hv->tv", _rmsnorm(x, g, eps), w, mode)


def logits(cfg: dict, seed: int, seqs, positions=None,
           mode: str = "float32", margins: bool = False):
    """Float32 logits ``[len(positions[r]), V]`` of each sequence, at
    every position where ``positions`` is None. With ``margins`` also,
    a sequence, the LEAST margin of the expert layers' choices at each
    of those positions."""
    xs, least = hidden(cfg, seed, seqs, mode)
    g, w = draw(cfg, seed, "norm_f"), draw(cfg, seed, "head")
    eps = float(cfg["rms_norm_eps"])
    out = []
    for r, x in enumerate(xs):
        least[r] = least[r].min(axis=0, initial=np.inf)
        if positions is not None:
            x = x[jnp.asarray(positions[r], jnp.int32)]
            least[r] = least[r][np.asarray(positions[r])]
        out.append(_head(x, g, w, eps, mode))
    return (out, least) if margins else out


def control_of(cfg: dict, name: str) -> tuple:
    """``(configuration, mode)`` under which the reference computes the
    control ``name``."""
    if name in ("bfloat16", "float8"):
        return cfg, name
    if name == "bias_off":
        # a correction bias of zeros: selection by the bare sigmoids
        return dict(cfg, router_bias_std=0.0), "float32"
    if name == "scale_off":
        return dict(cfg, routed_scaling_factor=1.0), "float32"
    raise ValueError(f"no control {name!r}")


def served_rows(rows, pad_to: int):
    """``rows`` is a list of ``(prompt, served)`` int sequences. Gives
    what the reference runs over, a row: ``prompt + served`` without its
    last token (never fed back: it conditions nothing that was served),
    padded behind to 512 or to ``pad_to`` so that two programs serve all
    lengths (the mask is causal: padding reaches nothing); and the
    positions whose logits chose the served tokens."""
    seqs, spans = [], []
    for prompt, served in rows:
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served, np.int32)])
        n, m = len(prompt), len(served)
        if n + m > pad_to:
            raise ValueError(f"row of {n + m} tokens > pad_to {pad_to}")
        pad = min(p for p in (min(512, pad_to), pad_to) if p >= n + m - 1)
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
        wrong, mode = control_of(cfg, control)
        picked = [np.asarray(jnp.argmax(lg, axis=-1))
                  for lg in logits(wrong, seed, seqs, spans, mode)]
    lgs, least = logits(cfg, seed, seqs, spans, margins=True)
    return gaps_under(lgs, picked), least
