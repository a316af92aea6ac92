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
:func:`dropless_topk_ffn`). Its shapes are static too whatever the load
of an expert, and only the experts that have a token are read, by one of
two products chosen from the call's shape. A FEW tokens (a decode step,
up to :data:`STREAM_TOKENS`) stream the experts
(:func:`expert_stream_ffn`): every touched expert multiplies every row,
its weights read once at the HBM's rate, which costs nothing while the
rows are below the chip's ridge (FLOPs a byte of weights). MANY tokens
(a prefill chunk) sort the (token, choice) pairs by expert into one
grouped product a weight (``jax.lax.ragged_dot``, or
:func:`tiled_grouped_dot` where the caller names it), ``tokens x k``
rows, each pair's row multiplied by its own expert alone. It is told
which experts it holds, routes over all of them, and returns the part of
the result its own experts give: a chip that holds a share of the
experts runs the same function, and the shares add up to the whole
layer. A SHARED expert, which every token passes, is no part of it: it
is the caller's dense product, which every share computes alike and
which is added once.
"""
from __future__ import annotations

import functools
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
    above; a decode step's few rows are streamed instead,
    :func:`expert_stream_ffn`). M fills whole tiles of rows and N whole
    tiles of columns (ValueError). Rows past the last group belong to
    nobody and their result is not one (the kernel wants every row in a
    group: they ride with the last). The kernel is a TPU's: on another
    backend it is interpreted (the tests' path)."""
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


#: tokens of a call up to which :func:`dropless_topk_ffn` streams the
#: experts (:func:`expert_stream_ffn`): every touched expert multiplies
#: every row, N FLOPs a byte of bfloat16 weights, and a v5e's ridge is
#: 197e12 / 819e9 = 240 FLOPs a byte (``benchmark/peaks.json``), so at
#: 128 rows the MXU's work is at most about half the weights' stream and
#: hides under it. A decode step's 8 to 64 lanes stream; a 512-token
#: prefill chunk would pass the ridge and keeps the grouped product. On a
#: v5e at the four MoE families' decode calls the streamed layer read
#: its experts at 84-90% of the HBM's rate where ``ragged_dot`` read them
#: at 46-81%, GLM-4.7-Flash's 8 x 4 of 64 (81%) included, so no call
#: below the constant is better sorted (``experiments/expert_stream.py``)
STREAM_TOKENS = 128

#: the largest tile of an expert's width :func:`expert_stream_ffn` reads
#: a grid step, and the VMEM its three weight tiles may fill, double
#: buffered: at those calls tiles of 128 to 1,024 read within 2% of one
#: another but for GLM-4.7-Flash's 128 (5% slower)
STREAM_TILE, STREAM_TILE_BYTES = 512, 24 << 20


def streams_experts(tokens: int) -> bool:
    """Whether :func:`dropless_topk_ffn` over a call of ``tokens`` tokens
    streams the experts (:data:`STREAM_TOKENS`); a decode program counts
    its expert layers by this (``moe_stream_layer_steps``)."""
    return int(tokens) <= STREAM_TOKENS


def _stream_tile(width: int, d: int, itemsize: int) -> int:
    """The tile of an expert's ``width`` a grid step reads: the largest
    multiple of 128 that divides it, is at most :data:`STREAM_TILE` and
    whose three tiles of ``d`` fit :data:`STREAM_TILE_BYTES` double
    buffered; the whole width where no multiple of 128 divides it."""
    for t in range(min(STREAM_TILE, width) // 128 * 128, 0, -128):
        if width % t == 0 and 2 * 3 * d * t * itemsize <= STREAM_TILE_BYTES:
            return t
    return width


def _stream_kernel(src_ref, fixed_ref, x_ref, w_ref, g_ref, u_ref, dn_ref,
                   y_ref, acc_ref, *, activation, tiles: int):
    from jax.experimental import pallas as pl
    e, j = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (j == 0))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(fixed_ref[e] < 0)                  # the expert has a token
    def _():
        x = x_ref[...]
        g = jnp.dot(x, g_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)
        h = (activation(g) * u).astype(dn_ref.dtype)
        part = jnp.dot(h, dn_ref[...], preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = part

        @pl.when(j > 0)
        def _():
            acc_ref[...] += part

        @pl.when(j == tiles - 1)
        def _():
            # the routing weights' column of this expert
            w = w_ref[...]
            lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
            col = jnp.sum(jnp.where(lane == e, w, 0.0), axis=1,
                          keepdims=True)
            y_ref[...] += acc_ref[...] * col


def expert_stream_ffn(x, w, tokens, w_gate, w_up, w_down,
                      activation: Callable = jax.nn.relu):
    """The gated experts over a FEW tokens, expert by expert: ``x (N,
    d)``, ``w (N, held)`` float32 each token's routing weight for each
    held expert (0 where it did not choose it), ``tokens (held,)`` the
    tokens each expert serves; the weights as :func:`dropless_topk_ffn`
    takes them. Returns ``y (N, d)`` float32, ``sum_e w[:, e] *
    (activation(x @ gate_e) * (x @ up_e)) @ down_e``.

    A Pallas kernel over the grid (held expert, tile of its width): the
    call's rows stay in VMEM and every expert with a token multiplies all
    of them, its three matrices read tile by tile exactly once a call. An
    expert without a token is read not at all: its steps name the block
    of the next expert that has one (behind the last, the last's), so no
    copy is issued for them and the next expert's first tile is on its
    way while they pass, and its body does not run. Operands in the
    weights' dtype, products accumulated in float32; the activation's
    product goes into ``down`` in the weights' dtype, as the grouped
    products take it; a step reads :func:`_stream_tile` of the width. The
    kernel is a TPU's: on another backend it is interpreted (the tests'
    path)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, d = x.shape
    held, _, width = w_gate.shape
    item = jnp.dtype(w_gate.dtype).itemsize
    tf = _stream_tile(width, d, item)
    tiles = width // tf
    # rows up to whole sublane tiles of the narrowest operand
    rows = -(-n // 16) * 16
    x = jnp.pad(x.astype(w_gate.dtype), ((0, rows - n), (0, 0)))
    w = jnp.pad(w.astype(jnp.float32), ((0, rows - n), (0, 0)))
    # each expert's block: its own tiles where it has a token (``fixed``
    # -1); else the first tile of the next expert that has one, or behind
    # the last such the last's last tile, the block already there
    touched = tokens > 0
    e = jnp.arange(held, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(touched, e, held), axis=0, reverse=True)
    last = jax.lax.cummax(jnp.where(touched, e, -1), axis=0)
    src = jnp.where(nxt < held, nxt, jnp.maximum(last, 0))
    fixed = jnp.where(touched, -1, jnp.where(nxt < held, 0, tiles - 1))

    def part(e, j, src, fixed):
        return src[e], jnp.where(fixed[e] < 0, j, fixed[e])

    def wide(e, j, *s):                         # gate, up: (d, tf)
        at, t = part(e, j, *s)
        return at, 0, t

    def tall(e, j, *s):                         # down: (tf, d)
        at, t = part(e, j, *s)
        return at, t, 0

    whole = lambda *_: (0, 0)                              # noqa: E731
    # the weights' tiles, x, w and y double-buffered, the expert's sums
    # and the activations
    vmem = 2 * (3 * d * tf * item + rows * d * item + rows * held * 4
                + rows * d * 4) + rows * d * 4 + 3 * rows * tf * 4
    return pl.pallas_call(
        functools.partial(_stream_kernel, activation=activation,
                          tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(held, tiles),
            in_specs=[pl.BlockSpec((rows, d), whole),
                      pl.BlockSpec((rows, held), whole),
                      pl.BlockSpec((None, d, tf), wide),
                      pl.BlockSpec((None, d, tf), wide),
                      pl.BlockSpec((None, tf, d), tall)],
            out_specs=pl.BlockSpec((rows, d), whole),
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, int(vmem * 1.25))),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        interpret=jax.default_backend() != "tpu",
        name="expert_stream_ffn",
    )(src.astype(jnp.int32), fixed.astype(jnp.int32), x, w, w_gate, w_up,
      w_down)[:n]


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
    decode lane): such pairs weigh nothing and no expert is read for
    them. Returns ``(y (N, d) float32, tokens (held,) int32)``, the
    second the number of tokens each held expert served.

    Operands go into the products in the weights' dtype and accumulate
    in float32. Which product runs follows the call's shape
    (:func:`streams_experts`): up to :data:`STREAM_TOKENS` tokens (a
    decode step) the experts are STREAMED (:func:`expert_stream_ffn`),
    each touched expert's weights read once at the HBM's rate and every
    row multiplied, which costs nothing below the chip's ridge; a longer
    call (a prefill chunk, past the ridge) sorts its pairs by expert and
    runs ``grouped(lhs, rhs, group_sizes)``, the grouped product
    (``jax.lax.ragged_dot`` where none is named; a caller that knows its
    run is long names :func:`tiled_grouped_dot`), which multiplies each
    pair's row alone."""
    n, k = idx.shape
    held = w_gate.shape[0]
    local = idx - jnp.int32(first_expert)
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine = mine & valid[:, None]
    flat = jnp.where(mine, local, held).reshape(-1)        # (N k,)
    tokens = jnp.zeros(held + 1, jnp.int32).at[flat].add(1)[:held]
    w = jnp.where(mine, weights, 0.0).astype(jnp.float32)
    if streams_experts(n):
        return _streamed(x, flat, w, tokens, w_gate, w_up, w_down,
                         activation), tokens
    return _sorted_grouped(x, flat, w, tokens, w_gate, w_up, w_down,
                           activation, grouped), tokens


def stream_kernel() -> bool:
    """Whether :func:`dropless_topk_ffn` streams the experts through the
    kernel, :func:`expert_stream_ffn`: on a TPU. Elsewhere (the CPU) it
    computes the kernel's sums in XLA, every held expert over every row
    (:func:`_expert_major`): the kernel interpreted would cost a CPU
    program some 0.5 ms a layer (tests put the kernel in its place)."""
    return jax.default_backend() == "tpu"


def _expert_major(x, w, tokens, w_gate, w_up, w_down, activation):
    """:func:`expert_stream_ffn`'s result in XLA: every held expert over
    every row, each weighed by its column of ``w`` (``tokens`` unread).
    The operands are rounded to the weights' dtype and multiplied in
    float32, which is exact for them (XLA's CPU takes no batched
    bfloat16 product into float32)."""
    def f32(a, dtype=w_gate.dtype):
        return a.astype(dtype).astype(jnp.float32)

    x = f32(x)
    g = jnp.einsum("nd,edf->enf", x, f32(w_gate))
    u = jnp.einsum("nd,edf->enf", x, f32(w_up))
    out = jnp.einsum("enf,efd->end", f32(activation(g) * u, w_down.dtype),
                     f32(w_down))
    return jnp.einsum("ne,end->nd", w.astype(jnp.float32), out)


def _streamed(x, flat, w, tokens, w_gate, w_up, w_down, activation):
    """:func:`dropless_topk_ffn`'s experts streamed: each pair's weight
    ``w (N, k)`` put in its expert's column (``flat``, the pairs' held
    expert or ``held`` for none, weighs nothing there)."""
    n, k = w.shape
    held = w_gate.shape[0]
    cols = jnp.zeros((n, held + 1), jnp.float32).at[
        jnp.arange(n)[:, None], flat.reshape(n, k)].add(w)
    product = expert_stream_ffn if stream_kernel() else _expert_major
    return product(x, cols[:, :held], tokens, w_gate, w_up, w_down,
                   activation)


def _sorted_grouped(x, flat, w, tokens, w_gate, w_up, w_down, activation,
                    grouped=None):
    """:func:`dropless_topk_ffn`'s pairs sorted by expert through a
    grouped product (``jax.lax.ragged_dot`` where ``grouped`` is None);
    the pairs of no held expert sort behind every group and read
    nothing."""
    n, k = w.shape
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
    return jnp.sum(out[back].reshape(n, k, -1) * w[:, :, None], axis=1)


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
