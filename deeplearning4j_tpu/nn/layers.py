"""Layer configuration classes.

Reference parity: org.deeplearning4j.nn.conf.layers.* (DenseLayer,
ConvolutionLayer, SubsamplingLayer, BatchNormalization, LSTM,
EmbeddingLayer, OutputLayer, GlobalPoolingLayer, ActivationLayer,
DropoutLayer, LossLayer, …) and nn.conf.inputs.InputType.

TPU-native redesign: the reference implements each layer TWICE — a config
class plus an imperative forward/backprop impl in nn/layers/* built from
INDArray calls with hand-derived gradients. Here a layer config has ONE
``build`` method that records ops into the shared SameDiff graph; backprop
comes from jax.grad of the whole graph, and XLA fuses across layer
boundaries (the reference's per-layer workspaces + cuDNN helper hooks have
no analogue: fusion and memory planning are the compiler's job).

Layout conventions (TPU-first, diverging from the reference where its
layout is CUDA-idiomatic): CNN = NCHW with HWIO kernels (XLA-native),
RNN = (batch, time, features) — the reference's NCW RNN format is a
cuDNN-ism; time-minor keeps the feature dim contiguous for the MXU.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from deeplearning4j_tpu.nn.activations import apply_activation
from deeplearning4j_tpu.nn.weights import init_weights


# ----------------------------------------------------------------------
# InputType (reference: nn/conf/inputs/InputType)
@dataclasses.dataclass(frozen=True)
class InputType:
    kind: str                      # "ff" | "cnn" | "cnn3d" | "rnn" | "ids"
    dims: Tuple[int, ...]          # ff: (n,); cnn: (c, h, w);
    #                                cnn3d: (c, d, h, w);
    #                                rnn: (features, timesteps); ids: (t,)

    @staticmethod
    def feed_forward(n: int) -> "InputType":
        return InputType("ff", (int(n),))

    @staticmethod
    def convolutional(height: int, width: int, channels: int) -> "InputType":
        return InputType("cnn", (int(channels), int(height), int(width)))

    @staticmethod
    def convolutional3d(depth: int, height: int, width: int,
                        channels: int) -> "InputType":
        """Volumetric data, placeholder (B, C, D, H, W) (reference:
        InputType.convolutional3D)."""
        return InputType("cnn3d", (int(channels), int(depth), int(height),
                                   int(width)))

    @staticmethod
    def recurrent(size: int, timesteps: int = -1) -> "InputType":
        return InputType("rnn", (int(size), int(timesteps)))

    @property
    def flat_size(self) -> int:
        if self.kind == "ff":
            return self.dims[0]
        if self.kind in ("cnn", "cnn3d"):
            return int(np.prod(self.dims))
        raise ValueError(f"cannot flatten {self}")

    def placeholder_shape(self) -> Tuple[int, ...]:
        if self.kind == "ff":
            return (-1, self.dims[0])
        if self.kind in ("cnn", "cnn3d"):
            return (-1,) + self.dims
        if self.kind == "rnn":
            return (-1, self.dims[1], self.dims[0])  # (B, T, C)
        raise ValueError(self.kind)

    def to_json(self):
        return {"kind": self.kind, "dims": list(self.dims)}

    @staticmethod
    def from_json(d):
        return InputType(d["kind"], tuple(d["dims"]))


def _as_pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


def _conv_out(size: int, k: int, s: int, mode: str, d: int = 1) -> int:
    if mode.upper() == "SAME":
        out = -(-size // s)
    else:
        k_eff = (k - 1) * d + 1
        out = (size - k_eff) // s + 1
    if out < 1:
        # config-time validation (reference: InputTypeUtil.getOutputType*
        # throwing DL4JInvalidConfigException): a collapsed spatial dim
        # must fail HERE with layer math, not as a cryptic zero-dim
        # reshape inside the compiled graph
        raise ValueError(
            f"layer output spatial size {out} < 1 (input {size}, kernel "
            f"{k}, stride {s}, dilation {d}, mode {mode}): the network is "
            f"deeper/stride-ier than the input size supports")
    return out


def _pad_mode(mode: str) -> str:
    """ConvolutionMode → XLA padding string (reference: ConvolutionMode
    {Same, Truncate, Strict, Causal}; Truncate/Strict share the VALID
    output formula — the reference differs only in whether it *errors* on
    non-exact sizes, which static XLA shapes make moot)."""
    m = mode.upper()
    if m == "SAME":
        return "SAME"
    if m in ("VALID", "TRUNCATE", "STRICT"):
        return "VALID"
    raise ValueError(f"unsupported convolution_mode {mode!r} "
                     f"(use Same/Truncate/Strict/Valid)")


# ----------------------------------------------------------------------
class BaseLayer:
    """Common layer contract. Subclasses are dataclasses; ``build`` records
    the layer's ops into ``sd`` and returns (output var, output InputType)."""

    # subclass dataclass fields double as serde schema
    def build(self, ctx: "BuildContext", x, itype: InputType):
        raise NotImplementedError

    def output_type(self, itype: InputType) -> InputType:
        raise NotImplementedError

    def to_json(self) -> dict:
        d = {"@class": type(self).__name__}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            d[f.name] = list(v) if isinstance(v, tuple) else v
        return d

    # legacy field renames: {class name: {old json key: new field name}}
    _FIELD_ALIASES = {"TransformerEncoderLayer": {"dropout": "drop_prob"}}

    @staticmethod
    def from_json(d: dict) -> "BaseLayer":
        d = dict(d)
        cls = LAYER_TYPES[d.pop("@class")]
        if hasattr(cls, "_from_json_fields"):   # nested-layer configs
            return cls._from_json_fields(d)
        for old, new in BaseLayer._FIELD_ALIASES.get(cls.__name__, {}).items():
            if old in d and new not in d:
                d[new] = d.pop(old)
        kw = {}
        for f in dataclasses.fields(cls):
            if f.name in d:
                v = d[f.name]
                kw[f.name] = tuple(v) if isinstance(v, list) else v
        return cls(**kw)


@dataclasses.dataclass
class BuildContext:
    """Carries graph + init RNG + train/infer mode through layer builds."""
    sd: object                      # SameDiff
    rng: np.random.Generator
    training: bool
    dtype: str = "float32"
    idx: int = 0                    # current layer index
    prefix: Optional[str] = None    # vertex name (ComputationGraph builds)
    labels_var: object = None       # labels placeholder (for loss heads)
    output_var: object = None       # set by the output layer
    loss_var: object = None         # set by the output layer
    # TBPTT mode: when set, recurrent layers carry their hidden state in
    # persistent state vars of shape (tbptt_batch, units) instead of
    # in-graph zeros — the train step's stop_gradient on state-var inputs
    # IS the truncation (reference: MultiLayerNetwork.doTruncatedBPTT:2083)
    tbptt_batch: Optional[int] = None
    rnn_state_vars: list = dataclasses.field(default_factory=list)
    # runtime layout for cnn tensors. InputType dims stay (c, h, w) and the
    # network's EXTERNAL contract stays NCHW (reference convention; users
    # feed/receive NCHW) — but internally the compiled graph runs NHWC:
    # logical-NCHW convs on TPU force physical transposes of every
    # activation. One permute at the network input; zero in the body.
    cnn_format: str = "NHWC"

    def lname(self, kind: str) -> str:
        """Parameter/op name stem: vertex name in graph builds, layer index
        in sequential builds (reference: param keys '0_W' vs 'dense1_W')."""
        return self.prefix if self.prefix else f"layer{self.idx}_{kind}"

    def param(self, name: str, shape, scheme: str):
        """Create (or look up, for the second graph build) a parameter."""
        return self.sd.var(name, value=init_weights(scheme, tuple(shape),
                                                    self.rng),
                           dtype=self.dtype)

    def state(self, name: str, value):
        return self.sd.state_var(name, np.asarray(value), dtype=self.dtype)


def _rnn_initial_states(ctx: BuildContext, lname: str, x, units: int,
                        names=("h0",)):
    """Initial recurrent state(s): in-graph zeros normally; persistent
    zero-initialized state vars in TBPTT mode (reset per sequence batch by
    fit_tbptt, carried across chunks by the train step)."""
    outs = []
    for nm in names:
        if ctx.tbptt_batch:
            sv = ctx.state(f"{lname}_{nm}_state",
                           np.zeros((ctx.tbptt_batch, units)))
            ctx.rnn_state_vars.append(sv.name)
            outs.append(sv)
        else:
            outs.append(ctx.sd.invoke("rnn_init_state", [x],
                                      {"units": units}, name=f"{lname}_{nm}"))
    return outs


def _rnn_carry_states(ctx: BuildContext, pairs):
    """Declare state-var carries (state_var, final_state_var) in TBPTT
    mode; no-op otherwise."""
    if ctx.tbptt_batch:
        for sv, fv in pairs:
            ctx.sd.update_state(sv, fv)


def _maybe_dropout(ctx: BuildContext, x, p: float, lname: str):
    """Input dropout (reference: BaseLayer.dropOut — p = retain prob)."""
    if p and 0 < p < 1 and ctx.training:
        return ctx.sd.invoke("dropout", [x], {"p": p}, name=f"{lname}_drop")
    return x


# ----------------------------------------------------------------------
@dataclasses.dataclass
class DenseLayer(BaseLayer):
    """Fully connected (reference: nn/conf/layers/DenseLayer + the mmul in
    layers/BaseLayer.preOutput, BaseLayer.java:300-322)."""
    n_out: int = 0
    activation: str = "relu"
    weight_init: str = "XAVIER"
    bias_init: float = 0.0
    dropout: float = 0.0
    has_bias: bool = True

    def output_type(self, itype):
        if itype.kind == "rnn":
            # per-timestep dense — the reference reaches the same semantics
            # via the RnnToFeedForward/FeedForwardToRnn preprocessor pair
            # (merge time into batch, dense, split back); here the matmul
            # broadcasts over (B, T) directly
            return InputType.recurrent(self.n_out, itype.dims[1])
        return InputType.feed_forward(self.n_out)

    def build(self, ctx, x, itype):
        lname = ctx.lname("dense")
        n_in = itype.dims[0] if itype.kind == "rnn" else itype.flat_size
        x = _maybe_dropout(ctx, x, self.dropout, lname)
        w = ctx.param(f"{lname}_W", (n_in, self.n_out), self.weight_init)
        z = x.mmul(w, name=f"{lname}_mm")
        if self.has_bias:
            b = ctx.sd.var(f"{lname}_b",
                           value=np.full((self.n_out,), self.bias_init),
                           dtype=ctx.dtype)
            z = z.add(b, name=f"{lname}_z")
        out = apply_activation(ctx.sd, z, self.activation, lname)
        return out, self.output_type(itype)


@dataclasses.dataclass
class EmbeddingLayer(BaseLayer):
    """Index → vector lookup (reference: nn/conf/layers/EmbeddingLayer;
    native op generic/nn/embedding_lookup)."""
    n_in: int = 0        # vocabulary size
    n_out: int = 0
    weight_init: str = "XAVIER"

    def output_type(self, itype):
        return InputType.feed_forward(self.n_out)

    def build(self, ctx, x, itype):
        lname = ctx.lname("embedding")
        if itype.flat_size != 1:
            raise ValueError(
                f"EmbeddingLayer expects a single index column "
                f"(InputType.feed_forward(1)); got {itype} — the reference "
                f"EmbeddingLayer validates nIn the same way")
        table = ctx.param(f"{lname}_W", (self.n_in, self.n_out),
                          self.weight_init)
        ids = ctx.sd.invoke("reshape", [x], {"shape": (-1,)},
                            name=f"{lname}_ids")
        ids = ids.cast("int32")
        out = ctx.sd.invoke("embedding_lookup", [table, ids], {},
                            name=f"{lname}_out")
        return out, self.output_type(itype)


@dataclasses.dataclass
class ConvolutionLayer(BaseLayer):
    """2D convolution (reference: nn/conf/layers/ConvolutionLayer; native
    conv2d, generic/nn/convo/conv2d.cpp:39). NCHW / HWIO."""
    n_out: int = 0
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    convolution_mode: str = "SAME"       # reference ConvolutionMode Same/Truncate
    dilation: Tuple[int, int] = (1, 1)
    activation: str = "identity"
    weight_init: str = "RELU"
    bias_init: float = 0.0
    has_bias: bool = True
    dropout: float = 0.0

    def output_type(self, itype):
        c, h, w = itype.dims
        kh, kw = _as_pair(self.kernel_size)
        sh, sw = _as_pair(self.stride)
        dh, dw = _as_pair(self.dilation)
        return InputType("cnn", (self.n_out,
                                 _conv_out(h, kh, sh, self.convolution_mode, dh),
                                 _conv_out(w, kw, sw, self.convolution_mode, dw)))

    def build(self, ctx, x, itype):
        lname = ctx.lname("conv")
        c_in = itype.dims[0]
        kh, kw = _as_pair(self.kernel_size)
        x = _maybe_dropout(ctx, x, self.dropout, lname)
        w = ctx.param(f"{lname}_W", (kh, kw, c_in, self.n_out),
                      self.weight_init)
        inputs = [x, w]
        attrs = {"strides": _as_pair(self.stride),
                 "padding": _pad_mode(self.convolution_mode),
                 "dilation": _as_pair(self.dilation),
                 "data_format": ctx.cnn_format}
        if self.has_bias:
            b = ctx.sd.var(f"{lname}_b",
                           value=np.full((self.n_out,), self.bias_init),
                           dtype=ctx.dtype)
            inputs.append(b)
        z = ctx.sd.invoke("conv2d", inputs, attrs, name=f"{lname}_z")
        out = apply_activation(ctx.sd, z, self.activation, lname)
        return out, self.output_type(itype)


@dataclasses.dataclass
class SubsamplingLayer(BaseLayer):
    """Pooling (reference: nn/conf/layers/SubsamplingLayer, PoolingType
    MAX/AVG/PNORM; native maxpool2d/avgpool2d/pnormpool2d)."""
    pooling_type: str = "MAX"
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Optional[Tuple[int, int]] = None
    convolution_mode: str = "VALID"
    pnorm: int = 2

    def output_type(self, itype):
        c, h, w = itype.dims
        kh, kw = _as_pair(self.kernel_size)
        sh, sw = _as_pair(self.stride or self.kernel_size)
        return InputType("cnn", (c,
                                 _conv_out(h, kh, sh, self.convolution_mode),
                                 _conv_out(w, kw, sw, self.convolution_mode)))

    def build(self, ctx, x, itype):
        lname = ctx.lname("pool")
        op = {"MAX": "max_pool2d", "AVG": "avg_pool2d",
              "PNORM": "pnorm_pool2d"}[self.pooling_type.upper()]
        attrs = {"kernel": _as_pair(self.kernel_size),
                 "strides": _as_pair(self.stride or self.kernel_size),
                 "padding": _pad_mode(self.convolution_mode),
                 "data_format": ctx.cnn_format}
        if self.pooling_type.upper() == "PNORM":
            attrs["pnorm"] = self.pnorm
        out = ctx.sd.invoke(op, [x], attrs, name=lname)
        return out, self.output_type(itype)


@dataclasses.dataclass
class BatchNormalization(BaseLayer):
    """Batch norm (reference: nn/conf/layers/BatchNormalization — 'decay' is
    the running-average momentum; layers/normalization/BatchNormalization).
    Running stats live as SameDiff state vars updated inside the step."""
    decay: float = 0.9
    eps: float = 1e-5

    def output_type(self, itype):
        return itype

    def build(self, ctx, x, itype):
        lname = ctx.lname("bn")
        n = itype.dims[0]
        gamma = ctx.sd.var(f"{lname}_gamma", value=np.ones((n,)),
                           dtype=ctx.dtype)
        beta = ctx.sd.var(f"{lname}_beta", value=np.zeros((n,)),
                          dtype=ctx.dtype)
        mean = ctx.state(f"{lname}_mean", np.zeros((n,)))
        var = ctx.state(f"{lname}_var", np.ones((n,)))
        # feature axis: 2 for (B, T, C) sequences; -1 for NHWC cnn tensors;
        # 1 for NCHW / (B, n)
        if itype.kind == "rnn":
            axis = 2
        elif itype.kind in ("cnn", "cnn3d") and ctx.cnn_format.endswith("C"):
            axis = -1
        else:
            axis = 1
        if ctx.training:
            out, new_mean, new_var = ctx.sd.invoke(
                "batchnorm_train", [x, gamma, beta, mean, var],
                {"momentum": self.decay, "epsilon": self.eps, "axis": axis},
                name=lname, n_outputs=3)
            ctx.sd.update_state(mean, new_mean)
            ctx.sd.update_state(var, new_var)
        else:
            out = ctx.sd.invoke(
                "batchnorm", [x, mean, var, gamma, beta],
                {"epsilon": self.eps, "axis": axis}, name=lname)
        return out, itype


@dataclasses.dataclass
class ActivationLayer(BaseLayer):
    """Standalone activation (reference: nn/conf/layers/ActivationLayer)."""
    activation: str = "relu"

    def output_type(self, itype):
        return itype

    def build(self, ctx, x, itype):
        return (apply_activation(ctx.sd, x, self.activation,
                                 ctx.lname("act")), itype)


@dataclasses.dataclass
class DropoutLayer(BaseLayer):
    """Standalone dropout (reference: nn/conf/layers/DropoutLayer;
    p = retain probability, matching nn/conf/dropout/Dropout)."""
    dropout: float = 0.5

    def output_type(self, itype):
        return itype

    def build(self, ctx, x, itype):
        lname = ctx.lname("dropout")
        if ctx.training and 0 < self.dropout < 1:
            x = ctx.sd.invoke("dropout", [x], {"p": self.dropout}, name=lname)
        return x, itype


@dataclasses.dataclass
class LSTMLayer(BaseLayer):
    """LSTM over sequences (reference: nn/conf/layers/LSTM +
    layers/recurrent/LSTMHelpers; native generic/recurrent/lstmLayer.cpp).
    Input/output layout (B, T, C); lax.scan compiles the recurrence into
    one XLA While loop."""
    n_out: int = 0
    weight_init: str = "XAVIER"
    forget_gate_bias_init: float = 1.0
    return_sequences: bool = True
    dropout: float = 0.0

    def output_type(self, itype):
        if self.return_sequences:
            return InputType.recurrent(self.n_out, itype.dims[1])
        return InputType.feed_forward(self.n_out)

    def build(self, ctx, x, itype):
        lname = ctx.lname("lstm")
        n_in = itype.dims[0]
        u = self.n_out
        x = _maybe_dropout(ctx, x, self.dropout, lname)
        w_ih = ctx.param(f"{lname}_Wih", (n_in, 4 * u), self.weight_init)
        w_hh = ctx.param(f"{lname}_Whh", (u, 4 * u), self.weight_init)
        b0 = np.zeros((4 * u,))
        b0[u:2 * u] = self.forget_gate_bias_init  # [i, f, g, o] gate order
        b = ctx.sd.var(f"{lname}_b", value=b0, dtype=ctx.dtype)
        h0, c0 = _rnn_initial_states(ctx, lname, x, u, ("h0", "c0"))
        out, hT, cT = ctx.sd.invoke(
            "lstm_layer", [x, h0, c0, w_ih, w_hh, b],
            {"time_major": False, "return_sequences": self.return_sequences},
            name=lname, n_outputs=3)
        _rnn_carry_states(ctx, [(h0, hT), (c0, cT)])
        result = out if self.return_sequences else hT
        return result, self.output_type(itype)


@dataclasses.dataclass
class GlobalPoolingLayer(BaseLayer):
    """Global pooling over spatial or time dims (reference:
    nn/conf/layers/GlobalPoolingLayer, PoolingType MAX/AVG/SUM)."""
    pooling_type: str = "AVG"

    def output_type(self, itype):
        if itype.kind in ("cnn", "cnn3d", "rnn"):
            return InputType.feed_forward(itype.dims[0])
        raise ValueError("GlobalPoolingLayer needs cnn or rnn input "
                         "(reference GlobalPoolingLayer rejects FF input too)")

    def build(self, ctx, x, itype):
        self.output_type(itype)  # validate input kind
        lname = ctx.lname("gpool")
        if itype.kind in ("cnn", "cnn3d") and ctx.cnn_format.endswith("C"):
            axis = {"cnn": (1, 2), "cnn3d": (1, 2, 3)}[itype.kind]
        else:
            axis = {"cnn": (2, 3), "cnn3d": (2, 3, 4), "rnn": (1,)}[itype.kind]
        opname = {"AVG": "reduce_mean", "MAX": "reduce_max",
                  "SUM": "reduce_sum"}[self.pooling_type.upper()]
        out = ctx.sd.invoke(opname, [x], {"axis": axis}, name=lname)
        return out, self.output_type(itype)


_LOSS_OPS = {
    "MCXENT": "softmax_cross_entropy",           # reference LossMCXENT
    "NEGATIVELOGLIKELIHOOD": "softmax_cross_entropy",
    "MSE": "mean_sqerr_loss",
    "L1": "absolute_difference_loss",
    "XENT": "sigm_cross_entropy",                # binary cross-entropy on logits
    "HINGE": "hinge_loss",
    "SQUARED_HINGE": "squared_hinge_loss",
    "POISSON": "poisson_loss",
    "KL_DIVERGENCE": "kl_divergence_loss",
    "COSINE_PROXIMITY": "cosine_distance_loss",
}

# losses that fuse the activation and therefore take PRE-activation logits
_FUSED_LOGIT_LOSSES = ("softmax_cross_entropy", "sigm_cross_entropy")


def _attach_loss_head(ctx, z, out, loss_function: str):
    """Wire a loss head: pick the loss op, feed it logits (fused losses)
    or activations, mark it, and record output/loss on the build context.
    Shared by OutputLayer, LossLayer, RnnOutputLayer."""
    ctx.output_var = out
    loss_op = _LOSS_OPS[loss_function.upper()]
    loss_in = z if loss_op in _FUSED_LOGIT_LOSSES else out
    loss = ctx.sd.invoke(loss_op, [loss_in, ctx.labels_var], {}, name="loss")
    loss.mark_as_loss()
    ctx.loss_var = loss
    return loss


@dataclasses.dataclass
class OutputLayer(BaseLayer):
    """Dense + loss head (reference: nn/conf/layers/OutputLayer with
    LossFunction; loss computed from PRE-activation logits where the loss
    fuses the activation — MCXENT+softmax, XENT+sigmoid — matching the
    reference's fused loss implementations)."""
    n_out: int = 0
    loss_function: str = "MCXENT"
    activation: str = "softmax"
    weight_init: str = "XAVIER"
    bias_init: float = 0.0
    has_bias: bool = True

    def output_type(self, itype):
        return InputType.feed_forward(self.n_out)

    def build(self, ctx, x, itype):
        lname = ctx.lname("out")
        n_in = itype.flat_size
        w = ctx.param(f"{lname}_W", (n_in, self.n_out), self.weight_init)
        z = x.mmul(w, name=f"{lname}_mm")
        if self.has_bias:
            b = ctx.sd.var(f"{lname}_b",
                           value=np.full((self.n_out,), self.bias_init),
                           dtype=ctx.dtype)
            z = z.add(b, name=f"{lname}_z")
        out = apply_activation(ctx.sd, z, self.activation, lname)
        _attach_loss_head(ctx, z, out, self.loss_function)
        return out, self.output_type(itype)


@dataclasses.dataclass
class LossLayer(BaseLayer):
    """Loss without params (reference: nn/conf/layers/LossLayer)."""
    loss_function: str = "MSE"
    activation: str = "identity"

    def output_type(self, itype):
        return itype

    def build(self, ctx, x, itype):
        out = apply_activation(ctx.sd, x, self.activation, ctx.lname("act"))
        _attach_loss_head(ctx, x, out, self.loss_function)
        return out, itype


LAYER_TYPES: Dict[str, type] = {c.__name__: c for c in [
    DenseLayer, EmbeddingLayer, ConvolutionLayer, SubsamplingLayer,
    BatchNormalization, ActivationLayer, DropoutLayer, LSTMLayer,
    GlobalPoolingLayer, OutputLayer, LossLayer,
]}
