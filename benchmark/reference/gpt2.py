"""GPT-2, plainly: the benchmark's reference and its seeded weights.

Written from the GPT-2 description (Radford et al. 2019; the layer
equations of ``openai-community/gpt2`` ``modeling_gpt2``): learned token
and position embeddings, pre-LN blocks, one fused ``c_attn`` projection
whose columns are ``[Q | K | V]``, causal softmax attention scaled by
``1/sqrt(head)``, ``gelu_new`` (the tanh form), a final layer norm and a
head tied to the token embedding. Float32 throughout with every matrix
product at ``Precision.HIGHEST``. It imports nothing of the program
under test and is given nothing the program made: weights come from
:func:`init_kind`, drawn from the seed, one key per kind of leaf.

Weights live STACKED over layers (``[L, ...]`` per kind), so a forward is
one ``lax.scan`` and compiles in seconds; rows go through in blocks so
GPT-2 XL fits beside nothing else on a 16 GB chip.

``mode`` is the arithmetic: ``"float32"`` is the reference; ``"bfloat16"``
and ``"float8"`` are the CONTROLS (the reference put in the program's
place one precision below what a configuration states), never a
reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: kinds of per-layer leaf -> shape as a function of (H, I); GPT-2 names
LAYER_KINDS = {
    "ln_1.g": lambda H, I: (H,), "ln_1.b": lambda H, I: (H,),
    "attn.c_attn.w": lambda H, I: (H, 3 * H),
    "attn.c_attn.b": lambda H, I: (3 * H,),
    "attn.c_proj.w": lambda H, I: (H, H),
    "attn.c_proj.b": lambda H, I: (H,),
    "ln_2.g": lambda H, I: (H,), "ln_2.b": lambda H, I: (H,),
    "mlp.c_fc.w": lambda H, I: (H, I), "mlp.c_fc.b": lambda H, I: (I,),
    "mlp.c_proj.w": lambda H, I: (I, H), "mlp.c_proj.b": lambda H, I: (H,),
}
TOP_KINDS = ("wte", "wpe", "ln_f.g", "ln_f.b")
ALL_KINDS = TOP_KINDS + tuple(LAYER_KINDS)


def sizes(cfg: dict):
    """(V, P, H, L, A, I) from a GPT-2 ``config.json``-style dict."""
    H = int(cfg["n_embd"])
    inner = cfg.get("n_inner") or 4 * H
    return (int(cfg["vocab_size"]), int(cfg["n_positions"]), H,
            int(cfg["n_layer"]), int(cfg["n_head"]), int(inner))


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def kind_shape(cfg: dict, kind: str):
    V, P, H, L, _, I = sizes(cfg)
    if kind == "wte":
        return (V, H)
    if kind == "wpe":
        return (P, H)
    if kind in ("ln_f.g", "ln_f.b"):
        return (H,)
    return (L,) + LAYER_KINDS[kind](H, I)


def _draw(key, cfg: dict, kind: str):
    """One kind of leaf, all layers at once. GPT-2's init (normal, std
    ``initializer_range``; residual projections scaled by 1/sqrt(2L)),
    except that biases and layer-norm terms are drawn too (GPT-2 starts
    them at 0 and 1), so that a dropped bias or gain shows."""
    std = float(cfg.get("initializer_range", 0.02))
    L = int(cfg["n_layer"])
    k = jax.random.fold_in(key, ALL_KINDS.index(kind))
    x = jax.random.normal(k, kind_shape(cfg, kind), jnp.float32)
    if kind.endswith(".g"):
        return 1.0 + std * x
    if kind in ("attn.c_proj.w", "mlp.c_proj.w"):
        return x * (std / math.sqrt(2.0 * L))
    return x * std


def init_kind(cfg: dict, seed: int, kind: str):
    """The stacked float32 leaf ``kind`` for ``seed``, made on the device
    in one jitted call."""
    fn = jax.jit(functools.partial(_draw, cfg=_frozen(cfg), kind=kind))
    return fn(seed_key(seed))


def init_params(cfg: dict, seed: int) -> dict:
    """All leaves, stacked over layers: what the reference computes on."""
    return {k: init_kind(cfg, seed, k) for k in ALL_KINDS}


class _frozen(dict):
    """A hashable view of a config dict, for ``functools.partial`` under
    ``jax.jit`` (the numbers only)."""

    def __hash__(self):
        return hash(tuple(sorted((k, v) for k, v in self.items()
                                 if isinstance(v, (int, float, str)))))


# ----------------------------------------------------------------------
# arithmetic
_HI = jax.lax.Precision.HIGHEST
_F8 = jnp.float8_e4m3fn


def _act_dtype(mode: str):
    return jnp.float32 if mode == "float32" else jnp.bfloat16


def _mm(eq: str, a, b, mode: str):
    """One matrix product in ``mode``'s arithmetic."""
    if mode == "float32":
        return jnp.einsum(eq, a, b, precision=_HI,
                          preferred_element_type=jnp.float32)
    if mode == "float8":
        a = a.astype(_F8).astype(jnp.bfloat16)
        b = b.astype(_F8).astype(jnp.bfloat16)
    return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.bfloat16)


def _ln(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def _gelu_new(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


def _block(x, p, n_head: int, eps: float, mode: str):
    """One GPT-2 block on ``x`` [R, T, H] with this layer's leaves."""
    R, T, H = x.shape
    D = H // n_head
    dt = x.dtype
    a = _ln(x, p["ln_1.g"], p["ln_1.b"], eps)
    qkv = _mm("rth,hk->rtk", a, p["attn.c_attn.w"], mode) \
        + p["attn.c_attn.b"].astype(dt)
    q, k, v = (z.reshape(R, T, n_head, D)
               for z in jnp.split(qkv, 3, axis=-1))
    s = _mm("rqad,rkad->raqk", q, k, mode).astype(jnp.float32) \
        / math.sqrt(D)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, jnp.float32(-1e30))
    w = jax.nn.softmax(s, axis=-1).astype(dt)
    o = _mm("raqk,rkad->rqad", w, v, mode).reshape(R, T, H)
    x = x + _mm("rth,hk->rtk", o, p["attn.c_proj.w"], mode) \
        + p["attn.c_proj.b"].astype(dt)
    m = _ln(x, p["ln_2.g"], p["ln_2.b"], eps)
    m = _mm("rth,hk->rtk", m, p["mlp.c_fc.w"], mode) \
        + p["mlp.c_fc.b"].astype(dt)
    m = _mm("rtk,kh->rth", _gelu_new(m), p["mlp.c_proj.w"], mode) \
        + p["mlp.c_proj.b"].astype(dt)
    return x + m


def hidden(params: dict, tokens, cfg: dict, mode: str = "float32",
           remat: bool = False):
    """Final-layer-norm output [R, T, H] for ``tokens`` [R, T]."""
    _, _, _, _, A, _ = sizes(cfg)
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    dt = _act_dtype(mode)
    T = tokens.shape[1]
    x = (params["wte"][tokens] + params["wpe"][:T][None]).astype(dt)
    layers = {k: params[k] for k in LAYER_KINDS}

    def body(x, p):
        return _block(x, p, A, eps, mode), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, layers)
    return _ln(x, params["ln_f.g"], params["ln_f.b"], eps)


def logits(params: dict, tokens, cfg: dict, mode: str = "float32",
           remat: bool = False):
    """[R, T, V] float32 logits (tied head)."""
    h = hidden(params, tokens, cfg, mode, remat)
    return _mm("rth,vh->rtv", h, params["wte"], mode).astype(jnp.float32)


# ----------------------------------------------------------------------
# a served model: how far below the reference's best each token lies
@functools.partial(jax.jit, static_argnames=("cfg",))
def _gap_block(params, tokens, picked, cfg):
    """Per position t: reference's best logit after tokens[:t+1] minus
    its logit of ``picked[t]`` (the token somebody put at t+1)."""
    lg = logits(params, tokens, cfg, "float32")
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, picked[..., None].astype(jnp.int32),
                              axis=-1)[..., 0]
    return best - got


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def _argmax_block(params, tokens, cfg, mode):
    return jnp.argmax(logits(params, tokens, cfg, mode),
                      axis=-1).astype(jnp.int32)


def served_gaps(params: dict, cfg: dict, rows, pad_to: int,
                rows_per_block: int = 2, control: str | None = None):
    """``rows`` is a list of ``(prompt, served)`` int sequences. Runs the
    reference once over each ``prompt + served`` (padded to ``pad_to``;
    the mask is causal, so padding reaches nothing) and returns one
    array per row: for each served token, the gap by which its reference
    logit lies below the reference's best at that position (0 where the
    served token is the reference's own choice).

    With ``control`` set to a lower-precision mode nothing served is
    read: at each of the same positions the token that mode puts first
    takes the served token's place."""
    cfg = _frozen(cfg)
    out = []
    for i in range(0, len(rows), rows_per_block):
        blk = rows[i:i + rows_per_block]
        toks = np.zeros((rows_per_block, pad_to), np.int32)
        picked = np.zeros((rows_per_block, pad_to), np.int32)
        spans = []
        for r, (prompt, served) in enumerate(blk):
            seq = np.concatenate([np.asarray(prompt, np.int32),
                                  np.asarray(served, np.int32)])
            n, m = len(prompt), len(served)
            if n + m > pad_to:
                raise ValueError(f"row of {n + m} tokens > pad_to {pad_to}")
            # the last served token is never fed back: it conditions
            # nothing that was served
            toks[r, :n + m - 1] = seq[:-1]
            picked[r, n - 1:n + m - 1] = seq[n:]
            spans.append((n - 1, n + m - 1))
        toks_d = jnp.asarray(toks)
        if control is not None:
            picked_d = _argmax_block(params, toks_d, cfg, control)
        else:
            picked_d = jnp.asarray(picked)
        gaps = np.asarray(_gap_block(params, toks_d, picked_d, cfg))
        for r, (a, b) in enumerate(spans):
            out.append(gaps[r, a:b].astype(np.float64))
    return out


# ----------------------------------------------------------------------
# training: loss, gradients, Adam as the configuration states it
def _loss_sum(params, ids, targets, cfg, mode):
    lg = logits(params, ids, cfg, mode, remat=True)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(
        logp, targets[..., None].astype(jnp.int32), axis=-1))


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def _grad_block(params, ids, targets, cfg, mode):
    return jax.value_and_grad(_loss_sum)(params, ids, targets, cfg, mode)


@jax.jit
def _acc(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def loss_and_grads(params: dict, ids, targets, cfg: dict,
                   rows_per_block: int = 2, mode: str = "float32"):
    """Mean next-token cross-entropy over all of ``ids`` [B, S] and its
    gradient, accumulated over blocks of rows so the float32 logits of a
    50257-row head fit."""
    cfg = _frozen(cfg)
    B, S = ids.shape
    total, grads = 0.0, None
    for i in range(0, B, rows_per_block):
        l, g = _grad_block(params, jnp.asarray(ids[i:i + rows_per_block]),
                           jnp.asarray(targets[i:i + rows_per_block]),
                           cfg, mode)
        total = total + l
        grads = g if grads is None else _acc(grads, g)
    n = float(B * S)
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def adam_step(params, grads, m, v, t, lr, b1, b2, eps):
    """Adam in the form the configuration states (Kingma & Ba 2015,
    section 2, the 'efficient' ordering): ``alpha_t = lr * sqrt(1 -
    b2^t) / (1 - b1^t)``, ``p -= alpha_t * m / (sqrt(v) + eps)``;
    ``t`` counts from 1."""
    t = jnp.asarray(t, jnp.float32)
    alpha = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)

    def leaf(p, g, m, v):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        return p - alpha * m / (jnp.sqrt(v) + eps), m, v

    out = jax.tree_util.tree_map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(          # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def leaf_norms(tree):
    """L2 norm of every LEAF as the model has them: a stacked kind gives
    one norm per layer. Returns ``{kind: [L] or []}``."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)),
                                axis=tuple(range(1, v.ndim))))
            if k in LAYER_KINDS else
            jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def train_steps(cfg: dict, seed: int, batches, opt: dict,
                rows_per_block: int = 2, mode: str = "float32"):
    """Follow ``batches`` (a list of ``(ids, targets)``) from the seeded
    weights with the stated optimizer. Returns the per-step losses and,
    per leaf, the norm of Adam's first moment and of the parameters'
    change after the last step."""
    params = init_params(cfg, seed)
    start = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for t, (ids, targets) in enumerate(batches, start=1):
        loss, grads = loss_and_grads(params, ids, targets, cfg,
                                     rows_per_block, mode)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads)
        losses.append(float(loss))
        params, m, v = adam_step(params, grads, m, v, t,
                                 lr=float(opt["learning_rate"]),
                                 b1=float(opt["beta1"]),
                                 b2=float(opt["beta2"]),
                                 eps=float(opt["epsilon"]))
    delta = jax.tree_util.tree_map(jnp.subtract, params, start)
    return {"losses": losses,
            "moment_norms": jax.device_get(leaf_norms(m)),
            "change_norms": jax.device_get(leaf_norms(delta))}
