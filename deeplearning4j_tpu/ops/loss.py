"""Loss ops.

Reference parity: ops/declarable/generic/loss/ (softmax_cross_entropy,
sigm_cross_entropy, hinge, huber, log_loss, mean_pairwssqerr, mean_sqerr,
absolute_difference, cosine_distance, ctc) and the DL4J ILossFunction set
(nd4j-api .../lossfunctions/impl/). ``reduction`` follows the reference modes:
"none" | "sum" | "mean_by_weight" | "mean_by_nonzero_weight" (the reference's
NONE/SUM/MEAN_BY_WEIGHT/MEAN_BY_NONZERO_WEIGHT_COUNT).
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.registry import op

_L = "loss"

#: Active softmax/CE tail dtype policy (None = upcast to f32, the safe
#: default). Set via :func:`softmax_dtype_scope`; consulted at TRACE
#: time, so the scope must wrap the jitted function's execution — the
#: train step builder (SameDiff._build_step_parts) does this when
#: ``MixedPrecision.softmax_dtype`` is set.
_SOFTMAX_DTYPE: contextvars.ContextVar = contextvars.ContextVar(
    "dl4j_softmax_dtype", default=None)


@contextlib.contextmanager
def softmax_dtype_scope(dtype):
    """While active, the softmax-CE losses keep their log-softmax tail
    in ``dtype`` instead of upcasting to float32. The per-example
    losses are STILL reduced to the scalar loss in f32 (the accumulation
    is where bf16 actually loses training signal); what changes is the
    [batch..., vocab]-shaped exp/log/normalize tail — on a 32k vocab
    that tail is the single largest f32 tensor in a bf16 LM step and
    the MXU/VPU runs it at twice the rate in bf16. Routed from ``MixedPrecision.softmax_dtype``
    (docs/training_performance.md)."""
    token = _SOFTMAX_DTYPE.set(None if dtype is None else jnp.dtype(dtype))
    try:
        yield
    finally:
        _SOFTMAX_DTYPE.reset(token)


def _f32(x):
    """Loss math runs internally in float32: under bf16 compute the
    log-softmax/log reductions would otherwise lose the precision the
    training signal lives in. XLA fuses the cast into the producer."""
    return x.astype(jnp.float32) if x.dtype in (jnp.bfloat16, jnp.float16) \
        else x


def _tail(x):
    """Softmax-CE tail dtype: the policy dtype when a
    :func:`softmax_dtype_scope` is active, else the f32 upcast."""
    dt = _SOFTMAX_DTYPE.get()
    if dt is None:
        return _f32(x)
    return x.astype(dt)


def _reduce_loss(per_ex, weights, reduction: str):
    if weights is None:
        weights = jnp.ones_like(per_ex)
    w = jnp.broadcast_to(weights, per_ex.shape)
    weighted = per_ex * w
    r = reduction.lower()
    if r == "none":
        return weighted
    # the reduction to the scalar loss is where bf16 actually loses the
    # training signal (an 8-bit mantissa stops accumulating once the
    # running sum is ~256x a term): force an f32 accumulator whenever
    # the per-example losses arrive in a low-precision dtype — the same
    # contract the dense softmax-CE vocab sum already keeps
    acc = jnp.float32 if weighted.dtype in (jnp.bfloat16, jnp.float16) \
        else None
    if r == "sum":
        return jnp.sum(weighted, dtype=acc)
    if r in ("mean_by_weight", "weighted_mean"):
        return jnp.sum(weighted, dtype=acc) / \
            jnp.maximum(jnp.sum(w, dtype=acc), 1e-12)
    if r in ("mean_by_nonzero_weight", "mean"):
        # the nonzero COUNT accumulates f32 regardless: counting in
        # bf16 saturates at 256 examples
        nz = jnp.sum(w != 0, dtype=jnp.float32)
        return jnp.sum(weighted, dtype=acc) / \
            jnp.maximum(nz, 1.0).astype(weighted.dtype if acc is None
                                        else acc)
    raise ValueError(f"unknown reduction {reduction}")


@op("mean_sqerr_loss", _L, aliases=("mse_loss", "l2_loss_full"))
def mean_sqerr_loss(predictions, labels, weights=None, reduction: str = "mean"):
    predictions, labels = _f32(predictions), _f32(labels)
    per = jnp.mean(jnp.square(predictions - labels), axis=-1)
    return _reduce_loss(per, weights, reduction)


@op("absolute_difference_loss", _L, aliases=("mae_loss", "l1_loss"))
def absolute_difference_loss(predictions, labels, weights=None, reduction: str = "mean"):
    per = jnp.mean(jnp.abs(predictions - labels), axis=-1)
    return _reduce_loss(per, weights, reduction)


@op("softmax_cross_entropy", _L, aliases=("softmax_cross_entropy_loss",))
def softmax_cross_entropy(logits, labels, weights=None, reduction: str = "mean",
                          label_smoothing: float = 0.0):
    """(reference: generic/loss/softmaxCrossEntropy.cpp) labels are
    one-hot/probability distributions. The log-softmax tail honors
    :func:`softmax_dtype_scope`; the per-example reduction to the
    scalar loss is always f32."""
    logits, labels = _tail(logits), _tail(labels)
    if label_smoothing > 0.0:
        n = labels.shape[-1]
        labels = labels * (1.0 - label_smoothing) + label_smoothing / n
    logp = jax.nn.log_softmax(logits, axis=-1)
    # the vocab-axis accumulation is where bf16 actually loses signal:
    # force an f32 accumulator even when the tail runs in bf16
    per = -jnp.sum(labels * logp, axis=-1, dtype=jnp.float32)
    return _reduce_loss(per, weights, reduction)


@op("sparse_softmax_cross_entropy", _L)
def sparse_softmax_cross_entropy(logits, labels, weights=None, reduction: str = "mean"):
    """labels are integer class ids (reference:
    sparseSoftmaxCrossEntropyWithLogits.cpp). The log-softmax tail over
    the vocab axis honors :func:`softmax_dtype_scope` — the lever that
    shrinks the [B, S, 32k] f32 tail of a bf16 LM step; the gathered
    per-token losses are reduced in f32 regardless."""
    logits = _tail(logits)
    logp = jax.nn.log_softmax(logits, axis=-1)
    per = _f32(-jnp.take_along_axis(
        logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0])
    return _reduce_loss(per, weights, reduction)


@op("sigm_cross_entropy", _L, aliases=("sigmoid_cross_entropy",))
def sigm_cross_entropy(logits, labels, weights=None, reduction: str = "mean",
                       label_smoothing: float = 0.0):
    logits, labels = _f32(logits), _f32(labels)
    if label_smoothing > 0.0:
        labels = labels * (1.0 - label_smoothing) + 0.5 * label_smoothing
    # numerically stable: max(x,0) - x*z + log(1+exp(-|x|))
    per_el = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    per = jnp.mean(per_el, axis=-1)
    return _reduce_loss(per, weights, reduction)


@op("hinge_loss", _L)
def hinge_loss(predictions, labels, weights=None, reduction: str = "mean"):
    """labels in {0,1} mapped to {-1,1} (reference: hingeLoss.cpp)."""
    all_ones = jnp.ones_like(labels)
    lab = 2.0 * labels - all_ones
    per = jnp.mean(jnp.maximum(0.0, all_ones - lab * predictions), axis=-1)
    return _reduce_loss(per, weights, reduction)


@op("squared_hinge_loss", _L)
def squared_hinge_loss(predictions, labels, weights=None, reduction: str = "mean"):
    lab = 2.0 * labels - 1.0
    per = jnp.mean(jnp.square(jnp.maximum(0.0, 1.0 - lab * predictions)), axis=-1)
    return _reduce_loss(per, weights, reduction)


@op("huber_loss", _L)
def huber_loss(predictions, labels, weights=None, delta: float = 1.0,
               reduction: str = "mean"):
    err = jnp.abs(predictions - labels)
    quad = jnp.minimum(err, delta)
    per_el = 0.5 * quad * quad + delta * (err - quad)
    per = jnp.mean(per_el, axis=-1)
    return _reduce_loss(per, weights, reduction)


@op("log_loss", _L)
def log_loss(predictions, labels, weights=None, epsilon: float = 1e-7,
             reduction: str = "mean"):
    predictions, labels = _f32(predictions), _f32(labels)
    p = jnp.clip(predictions, epsilon, 1.0 - epsilon)
    per_el = -labels * jnp.log(p) - (1.0 - labels) * jnp.log(1.0 - p)
    per = jnp.mean(per_el, axis=-1)
    return _reduce_loss(per, weights, reduction)


@op("poisson_loss", _L)
def poisson_loss(predictions, labels, weights=None, reduction: str = "mean",
                 log_input: bool = False):
    predictions, labels = _f32(predictions), _f32(labels)
    if log_input:
        per_el = jnp.exp(predictions) - labels * predictions
    else:
        per_el = predictions - labels * jnp.log(jnp.maximum(predictions, 1e-12))
    per = jnp.mean(per_el, axis=-1)
    return _reduce_loss(per, weights, reduction)


@op("kl_divergence_loss", _L, aliases=("kld_loss",))
def kl_divergence_loss(predictions, labels, weights=None, reduction: str = "mean"):
    predictions, labels = _f32(predictions), _f32(labels)
    per = jnp.sum(labels * (jnp.log(jnp.maximum(labels, 1e-12)) -
                            jnp.log(jnp.maximum(predictions, 1e-12))), axis=-1)
    return _reduce_loss(per, weights, reduction)


@op("cosine_distance_loss", _L)
def cosine_distance_loss(predictions, labels, weights=None, axis: int = -1,
                         reduction: str = "mean"):
    per = 1.0 - jnp.sum(predictions * labels, axis=axis)
    return _reduce_loss(per, weights, reduction)


@op("mean_pairwssqerr_loss", _L)
def mean_pairwssqerr_loss(predictions, labels, weights=None, reduction: str = "mean"):
    """Mean pairwise squared error (reference: meanPairWsSqErr.cpp)."""
    d = predictions - labels
    n = d.shape[-1]
    sum_d = jnp.sum(d, axis=-1, keepdims=True)
    sum_d2 = jnp.sum(d * d, axis=-1, keepdims=True)
    # sum over pairs (i<j) of (d_i - d_j)^2 = n*sum(d^2) - (sum d)^2
    pair = (n * sum_d2 - sum_d * sum_d)[..., 0]
    denom = max(n * (n - 1) // 2, 1)
    per = pair / (2.0 * denom)
    return _reduce_loss(per, weights, reduction)


@op("l2_loss", _L, n_inputs=1)
def l2_loss(x):
    return 0.5 * jnp.sum(x * x)


@op("ctc_loss", _L)
def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank: int = 0):
    """CTC loss via dynamic-programming scan (reference: generic/nn/ctcLoss.cpp,
    helpers/ctcLoss). log_probs: (B, T, C) log-softmaxed; labels: (B, S) int.

    Implemented as a lax.scan over time with a (B, 2S+1) alpha lattice —
    XLA-friendly: no data-dependent shapes.
    """
    b, t_max, _ = log_probs.shape
    s_max = labels.shape[1]
    # extended label sequence with blanks: length 2S+1
    ext = jnp.full((b, 2 * s_max + 1), blank, dtype=labels.dtype)
    ext = ext.at[:, 1::2].set(labels)
    ext_len = 2 * label_lengths + 1

    neg_inf = jnp.asarray(-1e30, dtype=log_probs.dtype)
    alpha0 = jnp.full((b, 2 * s_max + 1), neg_inf)
    alpha0 = alpha0.at[:, 0].set(log_probs[:, 0, blank])
    alpha0 = alpha0.at[:, 1].set(
        jnp.take_along_axis(log_probs[:, 0, :], ext[:, 1:2], axis=1)[:, 0])

    same_as_two_back = jnp.concatenate(
        [jnp.ones((b, 2), dtype=bool),
         ext[:, 2:] == ext[:, :-2]], axis=1)

    def step(alpha, logp_t):
        # logp_t: (B, C)
        emit = jnp.take_along_axis(logp_t, ext, axis=1)  # (B, 2S+1)
        shift1 = jnp.concatenate([jnp.full((b, 1), neg_inf), alpha[:, :-1]], axis=1)
        shift2 = jnp.concatenate([jnp.full((b, 2), neg_inf), alpha[:, :-2]], axis=1)
        shift2 = jnp.where(same_as_two_back, neg_inf, shift2)
        merged = jnp.logaddexp(jnp.logaddexp(alpha, shift1), shift2)
        return merged + emit, None

    def scan_step(carry, inp):
        alpha, t = carry
        logp_t = inp
        new_alpha, _ = step(alpha, logp_t)
        # freeze past input_length
        active = (t < input_lengths)[:, None]
        alpha = jnp.where(active, new_alpha, alpha)
        return (alpha, t + 1), None

    (alpha_T, _), _ = jax.lax.scan(scan_step, (alpha0, jnp.asarray(1)),
                                   jnp.swapaxes(log_probs, 0, 1)[1:])
    idx_last = jnp.clip(ext_len - 1, 0, 2 * s_max)
    idx_prev = jnp.clip(ext_len - 2, 0, 2 * s_max)
    p_last = jnp.take_along_axis(alpha_T, idx_last[:, None], axis=1)[:, 0]
    p_prev = jnp.take_along_axis(alpha_T, idx_prev[:, None], axis=1)[:, 0]
    return -jnp.logaddexp(p_last, p_prev)
