"""Mixture-of-Experts with expert parallelism (EP).

No reference analogue (the reference has no MoE); this is new TPU-native
capability following the Switch Transformer / GShard recipe the way a
TPU framework expresses it:

- **Static shapes everywhere**: routing uses capacity-based dispatch/
  combine einsums (token → (expert, slot) one-hots), so the compiled
  step has NO data-dependent shapes — overflow tokens are dropped by
  construction and their combine weights are zero.
- **Expert parallelism is sharding, not message passing**: expert-major
  tensors (E, C, d) and expert weights (E, d, f) carry a sharding
  constraint on the EXPERT_AXIS mesh axis; GSPMD inserts the all-to-alls
  that move token slots between devices. No hand-written collectives.
- The load-balancing auxiliary loss is the standard fraction·probability
  dot product (Switch eq. 4), returned for the caller to add to the
  task loss.

Beside the Switch layer, which drops what overflows an expert's
capacity, stands a DROPLESS top-k layer for models whose reference drops
nothing (:func:`topk_route` or :func:`sigmoid_bias_route`, then
:func:`dropless_topk_ffn`): the (token, choice) pairs are sorted by
expert and go through one grouped product (``jax.lax.ragged_dot``, or
:func:`tiled_grouped_dot` where the caller names it) per weight, so its
shapes are static too (``tokens x k`` rows whatever the load of an
expert) and only the experts that have a token are read. It is told
which experts it holds, routes over all of them, and returns the part of
the result its own experts give: a chip that holds a share of the
experts runs the same function, and the shares add up to the whole
layer. A SHARED expert, which every token passes, is no part of it: it
is the caller's dense product, which every share computes alike and
which is added once.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.lax import with_sharding_constraint
from jax.sharding import PartitionSpec as P

EXPERT_AXIS = "expert"


def switch_gating(x, gate_w, capacity: int):
    """Top-1 (Switch) routing with per-expert capacity.

    x: (N, d) tokens; gate_w: (d, E). Returns (dispatch (N, E, C) f32
    one-hots, combine (N, E, C) f32 weights, aux_loss scalar).
    """
    e = gate_w.shape[1]
    logits = jnp.matmul(x.astype(jnp.float32), gate_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)               # (N, E)
    expert_idx = jnp.argmax(probs, axis=-1)               # (N,)
    expert_1h = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)
    gate = jnp.sum(probs * expert_1h, axis=-1)            # (N,)

    # position of each token within its expert's queue (arrival order).
    # Accumulated in int32: a float32 cumsum loses exactness past 2^24
    # tokens per group, silently corrupting queue positions (and thus
    # capacity drops) at scale.
    expert_1h_i = expert_1h.astype(jnp.int32)
    pos_in_expert = jnp.cumsum(expert_1h_i, axis=0) - expert_1h_i
    pos = jnp.sum(pos_in_expert * expert_1h_i, axis=-1)   # (N,) int32
    keep = pos < capacity                                 # overflow drops
    slot_1h = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
    dispatch = (expert_1h * keep[:, None])[:, :, None] * slot_1h[:, None, :]
    combine = dispatch * gate[:, None, None]

    # load-balancing aux loss (Switch Transformer eq. 4)
    frac_tokens = jnp.mean(expert_1h, axis=0)             # (E,)
    frac_probs = jnp.mean(probs, axis=0)                  # (E,)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux


def moe_ffn(x, gate_w, w_in, w_out, b_in=None, b_out=None,
            capacity_factor: float = 1.25,
            activation: Callable = jax.nn.gelu,
            expert_sharded: bool = False, n_groups: int = 1):
    """Switch-routed expert FFN over flattened tokens.

    x: (N, d); gate_w: (d, E); w_in: (E, d, f); w_out: (E, f, d).
    Returns (y (N, d), aux_loss). With ``expert_sharded`` the
    expert-major intermediates and weights get a sharding constraint on
    EXPERT_AXIS (call under a Mesh; GSPMD does the token all-to-alls).

    ``n_groups``: GShard-style token grouping. The materialized dispatch
    tensor is (G, S, E, C) with S = N/G and C ≈ cf·S/E, i.e. TOTAL size
    G·S·E·C = cf·N²/G — memory falls linearly in G (per-group it is
    cf·N²/G²). At large N pick G so that cf·N²/G fits the budget (e.g.
    G = N/1024 caps it at cf·N·1024); G=1 recovers plain Switch
    routing. Routing, capacity, and overflow drops become per-group.
    """
    n, d = x.shape
    e = gate_w.shape[1]
    if n % n_groups:
        raise ValueError(f"tokens {n} not divisible by n_groups "
                         f"{n_groups}")
    s = n // n_groups
    capacity = max(int(capacity_factor * s / e), 1)

    def route(xg):
        return switch_gating(xg, gate_w, capacity)

    if n_groups == 1:
        dispatch, combine, aux = route(x)
        dispatch = dispatch[None]
        combine = combine[None]
        xg = x[None]
    else:
        xg = x.reshape(n_groups, s, d)
        dispatch, combine, aux = jax.vmap(route)(xg)
        aux = jnp.mean(aux)

    expert_inputs = jnp.einsum("gsec,gsd->gecd",
                               dispatch.astype(x.dtype), xg)
    if expert_sharded:
        spec = P(None, EXPERT_AXIS, None, None)
        expert_inputs = with_sharding_constraint(expert_inputs, spec)
        w_in = with_sharding_constraint(w_in, P(EXPERT_AXIS, None, None))
        w_out = with_sharding_constraint(w_out, P(EXPERT_AXIS, None, None))
    h = jnp.einsum("gecd,edf->gecf", expert_inputs, w_in.astype(x.dtype))
    if b_in is not None:
        h = h + b_in.astype(x.dtype)[None, :, None, :]
    h = activation(h)
    out = jnp.einsum("gecf,efd->gecd", h, w_out.astype(x.dtype))
    if b_out is not None:
        out = out + b_out.astype(x.dtype)[None, :, None, :]
    if expert_sharded:
        out = with_sharding_constraint(out, P(None, EXPERT_AXIS, None,
                                              None))
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), out)
    return y.reshape(n, d), jnp.asarray(aux, jnp.float32)


def topk_route(x, router_w, k: int):
    """Dropless top-k routing over ALL experts. x: (N, d); router_w:
    (d, E). The router's product is float32 at the highest precision
    (a choice between near-equal experts should not turn on operand
    rounding); the weights of a token's k experts are a softmax over
    those k logits, so they sum to one. Returns (idx (N, k) int32,
    weights (N, k) float32)."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(logits, int(k))
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def sigmoid_bias_route(x, router_w, bias, k: int, scale: float = 1.0,
                       eps: float = 1e-20):
    """Dropless top-k routing by sigmoid scores with a correction bias
    (the load balancing without an auxiliary loss of DeepSeek-V3,
    ``noaux_tc`` in the published configurations). x: (N, d); router_w:
    (d, E); bias: (E,), or None for a router without one (the k largest
    ``s`` alone; nothing is added). With ``s = sigmoid(x @ router_w)``
    (float32 at the highest precision, as :func:`topk_route` and for its
    reason) the k experts of a token are those with the largest ``s +
    bias``; their weights are ``scale * s / (sum of the k s + eps)``
    (a family's own divisor: LFM2's code adds 1e-6): the bias steers the
    CHOICE and never enters a weight. Returns
    ``(idx (N, k) int32, weights (N, k) float32, moved (N,) int32)``,
    the first two as :func:`topk_route` gives them; ``moved`` counts the
    token's choices that are not among the k largest of ``s`` alone,
    which is how hard the correction steers (0 without a bias)."""
    k = int(k)
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s if bias is None
                           else s + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                                + eps)
    if bias is None:
        return idx.astype(jnp.int32), weights, \
            jnp.zeros(s.shape[0], jnp.int32)
    # a choice the bias moved scores under the k-th largest bare score
    moved = jnp.sum(chosen < jax.lax.top_k(s, k)[0][:, -1:], axis=-1)
    return idx.astype(jnp.int32), weights, moved.astype(jnp.int32)


#: rows and output columns a tile of :func:`tiled_grouped_dot` holds
#: (the best of eight tilings on a v5e at 2,048 rows over 64 groups of
#: 2,048 x 1,536 bfloat16: ``experiments/glm_grouped_product.py``)
GROUPED_TILE_ROWS, GROUPED_TILE_COLS = 128, 512


def tiled_grouped_dot(lhs, rhs, group_sizes):
    """``jax.lax.ragged_dot`` for MANY rows: ``lhs (M, K)`` sorted by
    group, ``rhs (G, K, N)``, ``group_sizes (G,)`` int32 with a sum of at
    most M; float32 out. JAX's TPU grouped-matmul kernel (Pallas,
    ``megablox.gmm``) in tiles of ``GROUPED_TILE_ROWS`` rows, the whole of
    K and ``GROUPED_TILE_COLS`` columns: a group's weights are read once a
    tile of rows that holds one of its rows, where ``ragged_dot`` at 2,048
    rows reads every group's (0.66 ms against 1.35 a product of the shape
    above; at a decode step's 32 rows ``ragged_dot`` reads only the groups
    that have a row, and is what :func:`dropless_topk_ffn` takes where a
    caller names nothing). M fills whole tiles of rows and N whole tiles
    of columns (ValueError). Rows past the last group belong to nobody and
    their result is not one (the kernel wants every row in a group: they ride
    with the last). The kernel is a TPU's: on another backend it is
    interpreted (the tests' path)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = lhs.shape
    if m % GROUPED_TILE_ROWS or rhs.shape[2] % GROUPED_TILE_COLS:
        raise ValueError(
            f"{m} rows -> {rhs.shape[2]} columns fill no whole tiles of "
            f"{GROUPED_TILE_ROWS} rows and {GROUPED_TILE_COLS} columns")
    sizes = group_sizes.astype(jnp.int32)
    sizes = sizes.at[-1].add(jnp.int32(m) - jnp.sum(sizes, dtype=jnp.int32))
    return gmm(lhs, rhs, sizes, jnp.float32,
               (GROUPED_TILE_ROWS, k, GROUPED_TILE_COLS),
               interpret=jax.default_backend() != "tpu")


def dropless_topk_ffn(x, idx, weights, w_gate, w_up, w_down,
                      first_expert: int = 0, valid=None,
                      activation: Callable = jax.nn.relu,
                      grouped: Optional[Callable] = None):
    """The held experts' part of a gated top-k expert layer
    (``(activation(x @ gate) * (x @ up)) @ down``: ReGLU where none is
    named, SwiGLU with ``jax.nn.silu``), no token dropped whatever the
    load of an expert.

    x: (N, d); ``idx``/``weights``: (N, k) from :func:`topk_route` or
    :func:`sigmoid_bias_route`, over all experts; ``w_gate``/``w_up``:
    (held, d, f) and ``w_down``:
    (held, f, d) are experts ``first_expert .. first_expert + held - 1``.
    A pair routed to an expert held elsewhere adds nothing here, and
    neither does a token where ``valid`` (N,) is false (padding, an idle
    decode lane): such pairs sort behind every group and no expert is
    read for them. Returns ``(y (N, d) float32, tokens (held,) int32)``,
    the second the number of tokens each held expert served.

    Operands go into the products in the weights' dtype and accumulate
    in float32. ``grouped(lhs, rhs, group_sizes)`` is the grouped product
    (``jax.lax.ragged_dot`` where none is named; a caller that knows its
    run is long names :func:`tiled_grouped_dot`)."""
    n, k = idx.shape
    held = w_gate.shape[0]
    local = idx - jnp.int32(first_expert)
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine = mine & valid[:, None]
    flat = jnp.where(mine, local, held).reshape(-1)        # (N k,)
    tokens = jnp.zeros(held + 1, jnp.int32).at[flat].add(1)[:held]
    order = jnp.argsort(flat, stable=True)
    rows = x[order // k].astype(w_gate.dtype)              # (N k, d)

    def product(lhs, rhs):
        if grouped is not None:
            return grouped(lhs, rhs, tokens)
        return jax.lax.ragged_dot(lhs, rhs, tokens,
                                  preferred_element_type=jnp.float32)

    h = activation(product(rows, w_gate)) * product(rows, w_up)
    out = product(h.astype(w_down.dtype), w_down)          # (N k, d)
    # rows past the last group belong to nobody here: whatever the
    # grouped product left there is not a result
    out = jnp.where((jnp.arange(n * k) < jnp.sum(tokens))[:, None], out, 0.0)
    back = jnp.zeros(n * k, jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    w = jnp.where(mine, weights, 0.0).astype(jnp.float32)
    y = jnp.sum(out[back].reshape(n, k, -1) * w[:, :, None], axis=1)
    return y, tokens


def init_moe_params(rng, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32):
    """Expert weight pytree: gate (d,E), w_in (E,d,f), w_out (E,f,d)."""
    k1, k2, k3 = (rng.normal(size=s).astype(dtype) for s in
                  ((d_model, n_experts), (n_experts, d_model, d_ff),
                   (n_experts, d_ff, d_model)))
    return {
        "gate_w": k1 * (1.0 / jnp.sqrt(d_model)).astype(dtype),
        "w_in": k2 * (1.0 / jnp.sqrt(d_model)).astype(dtype),
        "w_out": k3 * (1.0 / jnp.sqrt(d_ff)).astype(dtype),
    }


def expert_parallel_specs():
    """NamedSharding PartitionSpecs for the MoE param pytree: experts
    sharded over EXPERT_AXIS, gate replicated."""
    return {
        "gate_w": P(None, None),
        "w_in": P(EXPERT_AXIS, None, None),
        "w_out": P(EXPERT_AXIS, None, None),
    }


def moe_train_step(params, x, targets, lr: float = 1e-2,
                   aux_weight: float = 0.01, expert_sharded: bool = False):
    """One SGD step on an MoE regression head — the EP building block the
    multichip dryrun compiles over a ('data','expert') mesh."""
    def loss_fn(p):
        y, aux = moe_ffn(x, p["gate_w"], p["w_in"], p["w_out"],
                         expert_sharded=expert_sharded)
        return jnp.mean((y - targets) ** 2) + aux_weight * aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    new_params = jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                        params, grads)
    return new_params, loss
