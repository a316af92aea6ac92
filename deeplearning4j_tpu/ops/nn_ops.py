"""Neural-network ops: conv, pooling, normalization, attention, recurrent.

Reference parity: ops/declarable/generic/nn/ (conv2d.cpp:39, conv2d_bp,
pooling2d, batchnorm.cpp, dot_product_attention.cpp:34,
multi_head_dot_product_attention.cpp:34, lstmLayer via helpers/lstmLayer.h,
...). The reference implements these as im2col+GEMM or cuDNN calls; here they
lower to lax.conv_general_dilated / lax.reduce_window / dot_general which XLA
maps straight onto the MXU — backward passes come from jax AD instead of the
reference's hand-written *_bp ops.

Data formats: DL4J convs default to NCHW with NHWC configurable
(nn/conf/CNN2DFormat.java); both are supported via the data_format attr.
Weight layout convention here is HWIO for 2d convs (TPU/XLA-preferred).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.ops.registry import op

_N = "nn"


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


def _same_pad(in_size: int, stride: int, k_eff: int) -> Tuple[int, int]:
    out = -(-in_size // stride)
    total = max(0, (out - 1) * stride + k_eff - in_size)
    return total // 2, total - total // 2


def _conv_padding(pad, in_sizes, strides, k_effs):
    if isinstance(pad, str):
        p = pad.upper()
        if p == "SAME":
            return [_same_pad(i, s, k) for i, s, k in zip(in_sizes, strides, k_effs)]
        if p == "VALID":
            return [(0, 0)] * len(in_sizes)
        raise ValueError(f"unknown padding {pad}")
    pads = [_pair(p) for p in pad] if isinstance(pad, (list, tuple)) else [_pair(pad)] * len(in_sizes)
    return pads


# ----------------------------------------------------------------------
# convolutions
# ----------------------------------------------------------------------
@op("conv2d", _N, n_inputs=2)
def conv2d(x, w, bias=None, strides=(1, 1), padding="SAME", dilation=(1, 1),
           data_format: str = "NCHW"):
    """2D convolution (reference: generic/nn/convo/conv2d.cpp:39).

    ``w`` layout: HWIO (kH, kW, inC, outC) — the reference's [kH,kW,iC,oC]
    default weights format matches.
    """
    strides = _pair(strides)
    dilation = _pair(dilation)
    dn = ("NCHW", "HWIO", "NCHW") if data_format == "NCHW" else ("NHWC", "HWIO", "NHWC")
    spatial = (2, 3) if data_format == "NCHW" else (1, 2)
    k_effs = [(w.shape[i] - 1) * dilation[i] + 1 for i in range(2)]
    pad = _conv_padding(padding, [x.shape[s] for s in spatial], strides, k_effs)
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad, rhs_dilation=dilation,
        dimension_numbers=dn)
    if bias is not None:
        out = out + (bias.reshape(1, -1, 1, 1) if data_format == "NCHW"
                     else bias.reshape(1, 1, 1, -1))
    return out


@op("conv1d", _N, n_inputs=2)
def conv1d(x, w, bias=None, stride=1, padding="SAME", dilation=1,
           data_format: str = "NCW"):
    """1D convolution (reference: generic/nn/convo/conv1d.cpp). w: (k, inC, outC)."""
    dn = ("NCH", "HIO", "NCH") if data_format in ("NCW", "NCH") else ("NHC", "HIO", "NHC")
    spatial = 2 if data_format in ("NCW", "NCH") else 1
    k_eff = (w.shape[0] - 1) * dilation + 1
    pad = _conv_padding(padding, [x.shape[spatial]], [stride], [k_eff])
    out = lax.conv_general_dilated(
        x, w, window_strides=(stride,), padding=pad, rhs_dilation=(dilation,),
        dimension_numbers=dn)
    if bias is not None:
        out = out + (bias.reshape(1, -1, 1) if spatial == 2 else bias.reshape(1, 1, -1))
    return out


@op("conv3d", _N, n_inputs=2)
def conv3d(x, w, bias=None, strides=(1, 1, 1), padding="SAME",
           dilation=(1, 1, 1), data_format: str = "NCDHW"):
    """3D convolution (reference: generic/nn/convo/conv3d.cpp). w: (kD,kH,kW,inC,outC)."""
    strides = tuple(strides) if not isinstance(strides, int) else (strides,) * 3
    dilation = tuple(dilation) if not isinstance(dilation, int) else (dilation,) * 3
    dn = (("NCDHW", "DHWIO", "NCDHW") if data_format == "NCDHW"
          else ("NDHWC", "DHWIO", "NDHWC"))
    spatial = (2, 3, 4) if data_format == "NCDHW" else (1, 2, 3)
    k_effs = [(w.shape[i] - 1) * dilation[i] + 1 for i in range(3)]
    pad = _conv_padding(padding, [x.shape[s] for s in spatial], strides, k_effs)
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad, rhs_dilation=dilation,
        dimension_numbers=dn)
    if bias is not None:
        shape = [1] * 5
        shape[1 if data_format == "NCDHW" else 4] = -1
        out = out + bias.reshape(shape)
    return out


@op("depthwise_conv2d", _N, n_inputs=2)
def depthwise_conv2d(x, w, bias=None, strides=(1, 1), padding="SAME",
                     dilation=(1, 1), data_format: str = "NCHW"):
    """Depthwise conv (reference: generic/nn/convo/depthwiseConv2d.cpp).

    w: (kH, kW, inC, multiplier) — reference layout.
    """
    strides = _pair(strides)
    dilation = _pair(dilation)
    c_in = x.shape[1] if data_format == "NCHW" else x.shape[3]
    mult = w.shape[3]
    # XLA depthwise = grouped conv with feature_group_count = C
    w_r = w.reshape(w.shape[0], w.shape[1], 1, c_in * mult)
    dn = ("NCHW", "HWIO", "NCHW") if data_format == "NCHW" else ("NHWC", "HWIO", "NHWC")
    spatial = (2, 3) if data_format == "NCHW" else (1, 2)
    k_effs = [(w.shape[i] - 1) * dilation[i] + 1 for i in range(2)]
    pad = _conv_padding(padding, [x.shape[s] for s in spatial], strides, k_effs)
    out = lax.conv_general_dilated(
        x, w_r, window_strides=strides, padding=pad, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=c_in)
    if bias is not None:
        out = out + (bias.reshape(1, -1, 1, 1) if data_format == "NCHW"
                     else bias.reshape(1, 1, 1, -1))
    return out


@op("separable_conv2d", _N, n_inputs=3)
def separable_conv2d(x, depth_w, point_w, bias=None, strides=(1, 1),
                     padding="SAME", dilation=(1, 1), data_format: str = "NCHW"):
    """Separable conv (reference: generic/nn/convo/sconv2d.cpp)."""
    y = depthwise_conv2d(x, depth_w, None, strides, padding, dilation, data_format)
    return conv2d(y, point_w, bias, (1, 1), "VALID", (1, 1), data_format)


@op("deconv2d", _N, n_inputs=2, aliases=("conv2d_transpose",))
def deconv2d(x, w, bias=None, strides=(1, 1), padding="SAME",
             dilation=(1, 1), data_format: str = "NCHW"):
    """Transposed conv (reference: generic/nn/convo/deconv2d.cpp). w: HWIO
    with I = output channels of the deconv (weights stored like the fwd conv
    they transpose: (kH, kW, oC, iC))."""
    strides = _pair(strides)
    dn = ("NCHW", "HWIO", "NCHW") if data_format == "NCHW" else ("NHWC", "HWIO", "NHWC")
    out = lax.conv_transpose(
        x, w, strides=strides,
        padding=padding if isinstance(padding, str) else [_pair(p) for p in padding],
        rhs_dilation=_pair(dilation), dimension_numbers=dn,
        transpose_kernel=True)
    if bias is not None:
        out = out + (bias.reshape(1, -1, 1, 1) if data_format == "NCHW"
                     else bias.reshape(1, 1, 1, -1))
    return out


@op("im2col", _N, n_inputs=1)
def im2col(x, kernel=(1, 1), strides=(1, 1), padding=(0, 0), dilation=(1, 1)):
    """Patch extraction (reference: helpers/im2col.h). x: NCHW →
    (N, C, kH, kW, outH, outW). Exists for parity/debug; convs do NOT go
    through im2col here — XLA lowers conv directly."""
    kh, kw = _pair(kernel)
    sh, sw = _pair(strides)
    ph, pw = _pair(padding)
    dh, dw = _pair(dilation)
    n, c, h, w_ = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    oh = (h + 2 * ph - (kh - 1) * dh - 1) // sh + 1
    ow = (w_ + 2 * pw - (kw - 1) * dw - 1) // sw + 1
    patches = []
    for i in range(kh):
        for j in range(kw):
            patches.append(x[:, :, i * dh:i * dh + oh * sh:sh, j * dw:j * dw + ow * sw:sw])
    out = jnp.stack(patches, axis=2)  # N, C, kh*kw, oh, ow
    return out.reshape(n, c, kh, kw, oh, ow)


@op("upsampling2d", _N, n_inputs=1)
def upsampling2d(x, factor=(2, 2), data_format: str = "NCHW"):
    """Nearest-neighbour upsampling (reference: generic/nn/convo/upsampling2d.cpp)."""
    fh, fw = _pair(factor)
    if data_format == "NCHW":
        return jnp.repeat(jnp.repeat(x, fh, axis=2), fw, axis=3)
    return jnp.repeat(jnp.repeat(x, fh, axis=1), fw, axis=2)


# ----------------------------------------------------------------------
# pooling
# ----------------------------------------------------------------------
def _pool2d(x, kernel, strides, padding, data_format, init, reduce_fn, post=None):
    kh, kw = _pair(kernel)
    sh, sw = _pair(strides)
    if data_format == "NCHW":
        dims, strd = (1, 1, kh, kw), (1, 1, sh, sw)
        spatial = (2, 3)
    else:
        dims, strd = (1, kh, kw, 1), (1, sh, sw, 1)
        spatial = (1, 2)
    pads = _conv_padding(padding, [x.shape[s] for s in spatial], (sh, sw), (kh, kw))
    full_pad = [(0, 0), (0, 0), pads[0], pads[1]] if data_format == "NCHW" else \
               [(0, 0), pads[0], pads[1], (0, 0)]
    out = lax.reduce_window(x, init, reduce_fn, dims, strd, full_pad)
    if post is not None:
        out = post(out, x, dims, strd, full_pad)
    return out


@op("max_pool2d", _N, n_inputs=1, aliases=("maxpool2d",))
def max_pool2d(x, kernel=(2, 2), strides=None, padding="VALID",
               data_format: str = "NCHW"):
    strides = strides if strides is not None else kernel
    return _pool2d(x, kernel, strides, padding, data_format,
                   -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
                   lax.max)


@op("avg_pool2d", _N, n_inputs=1, aliases=("avgpool2d",))
def avg_pool2d(x, kernel=(2, 2), strides=None, padding="VALID",
               data_format: str = "NCHW", count_include_pad: bool = True):
    strides = strides if strides is not None else kernel
    def post(out, xin, dims, strd, full_pad):
        if count_include_pad:
            k = 1
            for d in dims:
                k *= d
            return out / k
        ones = jnp.ones_like(xin)
        counts = lax.reduce_window(ones, 0.0, lax.add, dims, strd, full_pad)
        return out / counts
    return _pool2d(x, kernel, strides, padding, data_format, 0.0, lax.add, post)


@op("pnorm_pool2d", _N, n_inputs=1)
def pnorm_pool2d(x, kernel=(2, 2), strides=None, padding="VALID", pnorm: int = 2,
                 data_format: str = "NCHW"):
    """P-norm pooling (reference: pooling2d PNORM mode, SubsamplingLayer)."""
    strides = strides if strides is not None else kernel
    powed = jnp.power(jnp.abs(x), pnorm)
    s = _pool2d(powed, kernel, strides, padding, data_format, 0.0, lax.add)
    return jnp.power(s, 1.0 / pnorm)


@op("max_pool3d", _N, n_inputs=1)
def max_pool3d(x, kernel=(2, 2, 2), strides=None, padding="VALID",
               data_format: str = "NCDHW"):
    strides = strides if strides is not None else kernel
    k = tuple(kernel) if not isinstance(kernel, int) else (kernel,) * 3
    s = tuple(strides) if not isinstance(strides, int) else (strides,) * 3
    if data_format == "NCDHW":
        dims, strd, spatial = (1, 1) + k, (1, 1) + s, (2, 3, 4)
    else:
        dims, strd, spatial = (1,) + k + (1,), (1,) + s + (1,), (1, 2, 3)
    pads = _conv_padding(padding, [x.shape[a] for a in spatial], s, k)
    fp = ([(0, 0), (0, 0)] + pads) if data_format == "NCDHW" else ([(0, 0)] + pads + [(0, 0)])
    return lax.reduce_window(x, -jnp.inf, lax.max, dims, strd, fp)


@op("avg_pool3d", _N, n_inputs=1)
def avg_pool3d(x, kernel=(2, 2, 2), strides=None, padding="VALID",
               data_format: str = "NCDHW"):
    strides = strides if strides is not None else kernel
    k = tuple(kernel) if not isinstance(kernel, int) else (kernel,) * 3
    s = tuple(strides) if not isinstance(strides, int) else (strides,) * 3
    if data_format == "NCDHW":
        dims, strd, spatial = (1, 1) + k, (1, 1) + s, (2, 3, 4)
    else:
        dims, strd, spatial = (1,) + k + (1,), (1,) + s + (1,), (1, 2, 3)
    pads = _conv_padding(padding, [x.shape[a] for a in spatial], s, k)
    fp = ([(0, 0), (0, 0)] + pads) if data_format == "NCDHW" else ([(0, 0)] + pads + [(0, 0)])
    kn = 1
    for d in k:
        kn *= d
    return lax.reduce_window(x, 0.0, lax.add, dims, strd, fp) / kn


@op("global_avg_pool", _N, n_inputs=1)
def global_avg_pool(x, data_format: str = "NCHW", keep_dims: bool = False):
    ax = (2, 3) if data_format == "NCHW" else (1, 2)
    return jnp.mean(x, axis=ax, keepdims=keep_dims)


@op("global_max_pool", _N, n_inputs=1)
def global_max_pool(x, data_format: str = "NCHW", keep_dims: bool = False):
    ax = (2, 3) if data_format == "NCHW" else (1, 2)
    return jnp.max(x, axis=ax, keepdims=keep_dims)


# ----------------------------------------------------------------------
# normalization
# ----------------------------------------------------------------------
@op("batchnorm", _N, aliases=("batch_norm",))
def batchnorm(x, mean, variance, gamma=None, beta=None, epsilon: float = 1e-5,
              axis: int = 1):
    """Inference-form batch norm (reference: generic/nn/batchnorm.cpp —
    applyScale/applyOffset flags map to gamma/beta being present).

    Output is always x's dtype: under the mixed-precision policy the
    running stats stay float32 masters while activations are bf16 —
    without the final cast, jax type promotion would silently upcast the
    whole downstream graph to f32."""
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    inv = lax.rsqrt(variance.astype(jnp.float32) + epsilon)
    a = inv if gamma is None else gamma.astype(jnp.float32) * inv
    b = -mean.astype(jnp.float32) * a
    if beta is not None:
        b = b + beta.astype(jnp.float32)
    # one elementwise pass in x's dtype (per-channel a,b precomputed)
    return x * a.astype(x.dtype).reshape(shape) + b.astype(x.dtype).reshape(shape)


@op("batchnorm_train", _N)
def batchnorm_train(x, gamma, beta, running_mean, running_var,
                    momentum: float = 0.9, epsilon: float = 1e-5, axis: int = 1):
    """Training-form batch norm: batch stats + updated running stats.

    Returns (out, new_running_mean, new_running_var). Reference decay
    semantics (BatchNormalization.java 'decay'): new = decay*old + (1-decay)*batch.

    Batch statistics are computed in float32 regardless of x's dtype —
    bf16 mean/variance reductions over large batches lose the low bits
    that the running-stat EMA depends on. The big-tensor math stays in
    x's dtype: the reductions accumulate in f32 (XLA fuses the convert
    into the reduce, reading bf16 from HBM once), and the normalization
    is a per-channel scale+shift a*x+b with a/b derived from the f32
    stats — so no f32 copy of the activation is ever materialized
    (HBM bandwidth is the TPU bottleneck, not FLOPs).
    """
    red = tuple(i for i in range(x.ndim) if i != axis)
    lowp = x.dtype in (jnp.bfloat16, jnp.float16)
    xf = x.astype(jnp.float32) if lowp else x
    # ONE-PASS moments: jnp.var is two-pass (read x for the mean, re-read
    # for (x-mean)^2) — profiled at ~30% of the ResNet-50 step as
    # subtract_subtract/convert_reduce fusions. Sibling mean reductions
    # fuse into a single multi-output fusion that reads x from HBM once;
    # E[x^2]-E[x]^2 in f32 is plenty for normalization statistics.
    mean = jnp.mean(xf, axis=red)                 # convert fused into reduce
    m2 = jnp.mean(xf * xf, axis=red)
    var = jnp.maximum(m2 - mean * mean, 0.0)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    inv = lax.rsqrt(var + epsilon)
    # per-channel (tiny) f32 math, then one bf16 elementwise pass
    a = (gamma.astype(jnp.float32) * inv).astype(x.dtype)
    b = (beta.astype(jnp.float32)
         - gamma.astype(jnp.float32) * inv * mean).astype(x.dtype)
    out = x * a.reshape(shape) + b.reshape(shape)
    n = x.size // x.shape[axis]
    unbiased = var * n / max(n - 1, 1)
    new_mean = momentum * running_mean + (1 - momentum) * mean.astype(running_mean.dtype)
    new_var = momentum * running_var + (1 - momentum) * unbiased.astype(running_var.dtype)
    return out, new_mean, new_var


@op("layer_norm", _N, aliases=("layernorm",))
def layer_norm(x, gamma, beta=None, axis=-1, epsilon: float = 1e-5):
    """Layer norm (reference: generic/nn/layer_norm.cpp — standardize +
    scale + optional shift)."""
    ax = tuple(axis) if isinstance(axis, (list, tuple)) else (axis,)
    # one-pass moments (see batchnorm_train): sibling means fuse into one
    # read of x; avoids jnp.var's second full pass. Statistics in f32 —
    # E[x^2]-E[x]^2 cancels catastrophically in bf16 when |mean| >> std;
    # XLA fuses the convert into the reduces so x is still read once in
    # its own dtype and no f32 copy is materialized.
    lowp = x.dtype in (jnp.bfloat16, jnp.float16)
    xf = x.astype(jnp.float32) if lowp else x
    mean = jnp.mean(xf, axis=ax, keepdims=True)
    m2 = jnp.mean(xf * xf, axis=ax, keepdims=True)
    var = jnp.maximum(m2 - mean * mean, 0.0)
    inv = lax.rsqrt(var + epsilon)
    out = (x - mean.astype(x.dtype)) * inv.astype(x.dtype) * gamma
    if beta is not None:
        out = out + beta
    return out


@op("standardize", _N, n_inputs=1)
def standardize(x, axis=-1, epsilon: float = 0.0):
    ax = tuple(axis) if isinstance(axis, (list, tuple)) else (axis,)
    mean = jnp.mean(x, axis=ax, keepdims=True)
    std = jnp.std(x, axis=ax, keepdims=True)
    return (x - mean) / jnp.maximum(std, 1e-12 if epsilon == 0.0 else epsilon)


@op("lrn", _N, n_inputs=1)
def lrn(x, depth: int = 5, bias: float = 1.0, alpha: float = 1.0,
        beta: float = 0.5, data_format: str = "NCHW"):
    """Local response normalization (reference: generic/nn/lrn.cpp).

    depth = half-window (n/2), matching the reference's LRN config k/n/alpha/beta.
    """
    caxis = 1 if data_format == "NCHW" else x.ndim - 1
    sq = jnp.square(x)
    win = 2 * depth + 1
    mv = jnp.moveaxis(sq, caxis, -1)
    padded = jnp.pad(mv, [(0, 0)] * (x.ndim - 1) + [(depth, depth)])
    acc = jnp.zeros_like(mv)
    for i in range(win):
        acc = acc + lax.dynamic_slice_in_dim(padded, i, mv.shape[-1], axis=x.ndim - 1)
    acc = jnp.moveaxis(acc, -1, caxis)
    return x / jnp.power(bias + alpha * acc, beta)


# ----------------------------------------------------------------------
# embeddings & misc
# ----------------------------------------------------------------------
@op("embedding_lookup", _N, n_inputs=2)
def embedding_lookup(table, ids):
    """(reference: generic/parity_ops/embedding_lookup.cpp) — gather rows;
    one-hot-matmul is used automatically by XLA where it wins on TPU."""
    return jnp.take(table, ids, axis=0)


@op("bias_add", _N, n_inputs=2)
def bias_add(x, bias, data_format: str = "NHWC"):
    if data_format == "NCHW" and x.ndim > 2:
        shape = [1] * x.ndim
        shape[1] = -1
        return x + bias.reshape(shape)
    return x + bias


@op("linear_layer", _N, aliases=("xw_plus_b",))
def linear_layer(x, w, b=None):
    out = jnp.matmul(x, w)
    return out + b if b is not None else out


# ----------------------------------------------------------------------
# attention (reference: generic/nn/dot_product_attention.cpp:34 and
# multi_head_dot_product_attention.cpp:34)
# ----------------------------------------------------------------------
@op("dot_product_attention", _N)
def dot_product_attention(queries, keys, values, mask=None, scaled: bool = True,
                          with_weights: bool = False):
    """Single-head scaled dot-product attention.

    Shapes follow jax convention (..., seq, depth); the nn layer adapters
    handle the reference's [batch, depth, seq] layout.
    """
    d = queries.shape[-1]
    scores = jnp.matmul(queries, jnp.swapaxes(keys, -1, -2))
    if scaled:
        scores = scores / jnp.sqrt(jnp.asarray(d, dtype=scores.dtype))
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, jnp.finfo(scores.dtype).min)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.matmul(weights, values)
    return (out, weights) if with_weights else out


#: What the tracer of the running program knows and the op below cannot
#: read off its arrays: a ``monitor.attention.AttentionSites`` (how many
#: devices the program is traced for, and the tally of sites). Set by
#: :func:`attention_trace_scope`; consulted at TRACE time, so the scope
#: wraps the graph fn's execution inside the jitted step — the train
#: step builder (SameDiff._build_step_parts) does this, as it does for
#: ``ops.loss.softmax_dtype_scope``.
_ATTENTION_TRACE: contextvars.ContextVar = contextvars.ContextVar(
    "dl4j_attention_trace", default=None)


@contextlib.contextmanager
def attention_trace_scope(sites):
    """While active, ``scaled_dot_product_attention`` may take its tiled
    kernel where ``sites.devices`` is 1, and tells ``sites`` which path
    each call took (``sites.note(reason)``, None for the kernel)."""
    token = _ATTENTION_TRACE.set(sites)
    try:
        yield sites
    finally:
        _ATTENTION_TRACE.reset(token)


#: the tiled kernel's smallest block, the largest this op hands it, and
#: the head sizes at which blocks of that size fit the v5e's VMEM in
#: bf16 and f32 at every seq_len to 4096 (compiled for a described chip:
#: tests/test_attention_tiled.py; 256 does not fit)
_TILE, _TILE_MAX = 128, 1024
_TILED_HEAD_SIZES = (64, 128)


def _tile_of(seq_len: int) -> int:
    """The block along the sequence: the largest multiple of 128 up to
    1024 that divides ``seq_len``. A tile that the diagonal crosses is
    computed whole, so a smaller block skips more of the causal half,
    and a larger one pays fewer grid steps: on the v5e at
    ``[16, 16, 1024, 64]`` the second outweighs the first up to 1024
    (PERF.md §6, PR 31)."""
    return max(b for b in range(_TILE, min(seq_len, _TILE_MAX) + 1, _TILE)
               if seq_len % b == 0)


def _plain_reason(q, k, v, mask, causal, sites) -> Optional[str]:
    """Why this call takes the plain path (None: the tiled kernel can
    run it). Everything here is known while the program is traced."""
    if sites is None:
        return "not traced as a train step"
    if jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()}"
    if jax.config.jax_enable_x64:
        # the kernel's lowering for the chip recurses without end on a
        # 64-bit scalar (jax 0.9.0)
        return "jax_enable_x64"
    if sites.devices > 1:
        # pallas_call has no partitioning rule
        return f"traced for a mesh of {sites.devices} devices"
    if mask is not None:
        return "mask"
    if not causal:
        return "not causal"
    if q.ndim != 4 or not q.shape == k.shape == v.shape:
        return f"shapes {q.shape} {k.shape} {v.shape}"
    if q.shape[-2] % _TILE:
        return f"seq_len {q.shape[-2]} is not a multiple of {_TILE}"
    if q.shape[-1] not in _TILED_HEAD_SIZES:
        return f"head size {q.shape[-1]}"
    if not (q.dtype == k.dtype == v.dtype
            and q.dtype in (jnp.bfloat16, jnp.float32)):
        return f"dtypes {q.dtype} {k.dtype} {v.dtype}"
    return None


def _tiled_causal_attention(q, k, v, scale: float):
    """Causal attention tile by tile with a running maximum and sum
    (JAX's ``splash_attention`` Pallas kernel): no ``[B, H, S, S]`` array
    reaches HBM. Its ``custom_vjp`` keeps q, k, v, the output and each
    row's log-sum-exp and recomputes the tiles in ONE backward kernel.
    Tiles wholly above the diagonal are not in the grid (there are none
    until ``seq`` passes the largest block). Scores, statistics
    and accumulation are float32; the operands of the backward's
    products are the inputs' dtype, the forward's second product runs in
    float32. The kernel has no scale of its own, so it goes onto q
    first: exact where it is a power of two (head size 64), one more
    rounding of q to its dtype elsewhere (head size 128)."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    heads, seq_len = q.shape[1], q.shape[2]
    t = _tile_of(seq_len)
    # the forward works through a tile of 1024 keys in two halves (1.22
    # against 1.33 ms); a tile of 512 in halves is slower (1.71 against
    # 1.33), and the backward is fastest whole
    blocks = sa.BlockSizes(
        block_q=t, block_kv=t, block_kv_compute=512 if t == _TILE_MAX else t,
        block_q_dkv=t, block_kv_dkv=t, block_kv_dkv_compute=t,
        use_fused_bwd_kernel=True,
        q_layout=sa.QKVLayout.SEQ_MINOR, k_layout=sa.QKVLayout.SEQ_MINOR,
        v_layout=sa.QKVLayout.SEQ_MINOR)
    kernel = sa.make_splash_mha_single_device(
        sa.MultiHeadMask([sa.CausalMask((seq_len, seq_len))] * heads),
        block_sizes=blocks)
    return jax.vmap(kernel)(q * jnp.asarray(scale, q.dtype), k, v)


@op("scaled_dot_product_attention", _N, aliases=("sdpa",))
def scaled_dot_product_attention(q, k, v, mask=None, causal: bool = False,
                                 scale: float = None):
    """Multi-head attention core, TPU-shaped: q/k/v are
    (batch, heads, seq, head_dim); scores and softmax are f32 regardless
    of input dtype (bf16-safe — the MXU accumulates f32 natively so the
    upcast is free).

    ``causal=True`` applies the autoregressive mask; ``mask`` (broadcast
    to [batch, heads, sq, sk], nonzero = attend) composes with it.
    Reference: multi_head_dot_product_attention.cpp:34 computes the same
    math head-by-head via mmul/softmax graph ops; here it is one op.

    TWO PATHS, one algorithm, chosen from what the op can observe while
    it is traced; no keyword, config field or environment variable
    decides. Inside a train step on ONE TPU device
    (:func:`attention_trace_scope`), causal self-attention without a
    mask, with ``seq`` a multiple of 128, a head size in
    ``_TILED_HEAD_SIZES`` and bf16 or f32 inputs runs
    :func:`_tiled_causal_attention`: forward, recomputed forward and
    backward write no score matrix. Every other call (a padding mask,
    cross attention, another backend, a short or ragged ``seq``, a step
    traced for a mesh, 64-bit mode: :func:`_plain_reason`) builds the
    whole f32 ``[batch, heads, sq, sk]`` score array, masks it, runs a
    softmax over it and casts the probabilities to the value dtype for
    the PV product, which XLA differentiates as a unit."""
    d = q.shape[-1]
    s = (1.0 / np.sqrt(d)) if scale is None else scale
    sites = _ATTENTION_TRACE.get()
    reason = _plain_reason(q, k, v, mask, causal, sites)
    if sites is not None:
        sites.note(reason)
    if reason is None:
        return _tiled_causal_attention(q, k, v, s)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(cm, scores, jnp.float32(-1e30))
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@op("multi_head_dot_product_attention", _N)
def multi_head_dot_product_attention(queries, keys, values, wq, wk, wv, wo,
                                     nheads: int, mask=None, scaled: bool = True):
    """Multi-head attention with projection weights (reference:
    multi_head_dot_product_attention.cpp:34 — projects with Wq/Wk/Wv, applies
    scaled dot-product per head, recombines with Wo).

    queries/keys/values: (batch, seq, dmodel); wq/wk/wv: (dmodel, nheads*dk);
    wo: (nheads*dv, dmodel); ``nheads`` is explicit (the reference derives it
    from rank-3 per-head weight tensors, which 2-D projections can't encode).
    """
    b, sq, _ = queries.shape
    sk = keys.shape[1]
    q = jnp.matmul(queries, wq)
    k = jnp.matmul(keys, wk)
    v = jnp.matmul(values, wv)

    def split_heads(t, seq):
        return jnp.transpose(t.reshape(b, seq, nheads, -1), (0, 2, 1, 3))

    att = dot_product_attention(split_heads(q, sq), split_heads(k, sk),
                                split_heads(v, sk), mask=mask, scaled=scaled)
    merged = jnp.transpose(att, (0, 2, 1, 3)).reshape(b, sq, -1)
    return jnp.matmul(merged, wo)


# ----------------------------------------------------------------------
# recurrent cells (reference: helpers/lstmLayer.h, generic/recurrent/)
# ----------------------------------------------------------------------
@op("lstm_cell", _N)
def lstm_cell(x, h_prev, c_prev, w_ih, w_hh, b):
    """One LSTM step. Gate order [i, f, g, o] (reference lstmLayer gate order
    with forget-gate semantics; cIFOG handled at the layer adapter).

    x: (batch, in), h/c: (batch, units), w_ih: (in, 4*units),
    w_hh: (units, 4*units), b: (4*units,).
    """
    z = jnp.matmul(x, w_ih) + jnp.matmul(h_prev, w_hh) + b
    i, f, g, o = jnp.split(z, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)
    c = f * c_prev + i * g
    h = o * jnp.tanh(c)
    return h, c


@op("lstm_layer", _N, aliases=("lstmLayer",))
def lstm_layer(x, h0, c0, w_ih, w_hh, b, time_major: bool = False,
               return_sequences: bool = True):
    """Full-sequence LSTM via lax.scan — ONE compiled loop, not per-step
    dispatch (reference: generic/recurrent/lstmLayer.cpp executes the same
    recurrence as a C++ loop over time steps).
    """
    xs = x if time_major else jnp.swapaxes(x, 0, 1)  # (T, B, in)

    def step(carry, xt):
        h, c = carry
        h2, c2 = lstm_cell(xt, h, c, w_ih, w_hh, b)
        return (h2, c2), h2

    (hT, cT), hs = lax.scan(step, (h0, c0), xs)
    if return_sequences:
        out = hs if time_major else jnp.swapaxes(hs, 0, 1)
        return out, hT, cT
    return hT, hT, cT


@op("rnn_init_state", _N, n_inputs=1, differentiable=False)
def rnn_init_state(x, units: int, time_major: bool = False):
    """Zero initial hidden state (batch, units) derived from the sequence
    input inside the graph — keeps batch size dynamic (no host-side shape
    dependency; reference layers allocate h0/c0 eagerly per minibatch)."""
    batch = x.shape[0] if not time_major else x.shape[1]
    return jnp.zeros((batch, units), x.dtype)


@op("gru_cell", _N)
def gru_cell(x, h_prev, w_ih, w_hh, b_ih, b_hh):
    """One GRU step (reference: generic/recurrent/gruCell.cpp gate order r,u,c)."""
    gi = jnp.matmul(x, w_ih) + b_ih
    gh = jnp.matmul(h_prev, w_hh) + b_hh
    i_r, i_u, i_c = jnp.split(gi, 3, axis=-1)
    h_r, h_u, h_c = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid(i_r + h_r)
    u = jax.nn.sigmoid(i_u + h_u)
    c = jnp.tanh(i_c + r * h_c)
    return u * h_prev + (1 - u) * c


@op("gru_layer", _N, aliases=("gru",))
def gru_layer(x, h0, w_ih, w_hh, b_ih, b_hh, time_major: bool = False):
    xs = x if time_major else jnp.swapaxes(x, 0, 1)

    def step(h, xt):
        h2 = gru_cell(xt, h, w_ih, w_hh, b_ih, b_hh)
        return h2, h2

    hT, hs = lax.scan(step, h0, xs)
    out = hs if time_major else jnp.swapaxes(hs, 0, 1)
    return out, hT


def _rnn_activation(name: str):
    """Resolve an activation registry-op name to its jnp-level fn (any
    elementwise activation op works; layers pre-resolve DL4J aliases)."""
    from deeplearning4j_tpu.ops import registry
    key = name.lower()
    if key in ("identity", "linear"):
        return lambda z: z
    if registry.has_op(key):
        return registry.get_op(key).fn
    raise ValueError(f"unknown rnn activation {name!r}")


@op("simple_rnn_cell", _N, aliases=("sru_cell_simple",))
def simple_rnn_cell(x, h_prev, w_ih, w_hh, b, activation: str = "tanh"):
    act = _rnn_activation(activation)
    return act(jnp.matmul(x, w_ih) + jnp.matmul(h_prev, w_hh) + b)


@op("simple_rnn_layer", _N)
def simple_rnn_layer(x, h0, w_ih, w_hh, b, time_major: bool = False,
                     activation: str = "tanh"):
    xs = x if time_major else jnp.swapaxes(x, 0, 1)

    def step(h, xt):
        h2 = simple_rnn_cell(xt, h, w_ih, w_hh, b, activation)
        return h2, h2

    hT, hs = lax.scan(step, h0, xs)
    out = hs if time_major else jnp.swapaxes(hs, 0, 1)
    return out, hT
