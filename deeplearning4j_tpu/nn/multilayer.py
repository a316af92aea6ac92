"""MultiLayerNetwork — sequential network compiled through SameDiff.

Reference parity: org.deeplearning4j.nn.multilayer.MultiLayerNetwork
(MultiLayerNetwork.java — fit :1647/1664, output :2471, score, save/load via
util/ModelSerializer). The reference runs per-layer imperative
forward/backprop with per-op JNI dispatch inside Solver/StochasticGradient-
Descent (SURVEY.md §3.2); here `fit` delegates to the SameDiff whole-graph
training step — one compiled XLA computation per minibatch shape, params
donated between steps.

Two graphs are built from the same config + seed (identical parameter names
and initial values): a training graph (dropout active, batch-stat BN with
running-stat state updates) and an inference graph (no dropout, running-stat
BN). Parameters live in the training graph; `output()` syncs them (reference
analogue: the single parameter view array shared by train/eval paths).
"""
from __future__ import annotations

import io
import json
import zipfile
from typing import Dict, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
from deeplearning4j_tpu.autodiff.staging import stage_fit_state
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import (
    BaseLayer, BuildContext, ConvolutionLayer, DenseLayer, EmbeddingLayer,
    GlobalPoolingLayer, InputType, LSTMLayer, OutputLayer, SubsamplingLayer)

_WANTED_KIND = {
    # accepted input kinds per layer class; first entry = preferred kind a
    # preprocessor should convert to when none of the accepted kinds match
    "DenseLayer": ("ff", "rnn"),   # rnn input = per-timestep dense
    "OutputLayer": ("ff",), "EmbeddingLayer": ("ff",),
    "ConvolutionLayer": ("cnn",), "SubsamplingLayer": ("cnn",),
    "LSTMLayer": ("rnn",), "SimpleRnnLayer": ("rnn",),
    "Bidirectional": ("rnn",), "RnnOutputLayer": ("rnn",),
    "LastTimeStepLayer": ("rnn",), "Convolution1DLayer": ("rnn",),
    "Convolution3DLayer": ("cnn3d",), "Subsampling3DLayer": ("cnn3d",),
    "Deconvolution2DLayer": ("cnn",), "DepthwiseConvolution2DLayer": ("cnn",),
    "SeparableConvolution2DLayer": ("cnn",),
    "LocalResponseNormalization": ("cnn",), "Upsampling2DLayer": ("cnn",),
    "ZeroPaddingLayer": ("cnn",), "Cropping2DLayer": ("cnn",),
    # wave 2 (layers_ext)
    "VariationalAutoencoderLayer": ("ff",),
    "Yolo2OutputLayer": ("cnn",), "PrimaryCapsulesLayer": ("cnn",),
    "DotProductAttentionLayer": ("rnn",),
    "RecurrentAttentionLayer": ("rnn",),
    "GravesLSTMLayer": ("rnn",), "GRULayer": ("rnn",),
    "RepeatVectorLayer": ("ff",),
    "ElementWiseMultiplicationLayer": ("ff",),
    "Subsampling1DLayer": ("rnn",), "ZeroPadding1DLayer": ("rnn",),
    "Cropping1DLayer": ("rnn",), "Upsampling1DLayer": ("rnn",),
    "Upsampling3DLayer": ("cnn3d",), "ZeroPadding3DLayer": ("cnn3d",),
    "SpaceToDepthLayer": ("cnn",), "DepthToSpaceLayer": ("cnn",),
    "CnnLossLayer": ("cnn",), "RnnLossLayer": ("rnn",),
    "CenterLossOutputLayer": ("ff",),
}


def _adapt_itype(itype: InputType, layer: BaseLayer, idx: int) -> InputType:
    """Preprocessor-kind rule — the ONE place deciding how an input type
    adapts to a layer's wanted kind (reference:
    nn/conf/preprocessor/{CnnToFeedForward,...}PreProcessor, added
    automatically by setInputType). Used by both graph build and type
    walking so they cannot desynchronize."""
    # wrapper layers adapt by their INNER layer's wanted kind
    probe = layer
    while type(probe).__name__ == "FrozenLayer" and \
            getattr(probe, "layer", None) is not None:
        probe = probe.layer
    accepted = _WANTED_KIND.get(type(probe).__name__)
    if accepted is None or itype.kind in accepted:
        return itype
    wanted = accepted[0]
    if itype.kind in ("cnn", "cnn3d") and wanted == "ff":
        return InputType.feed_forward(itype.flat_size)
    if itype.kind == "rnn" and wanted == "ff":
        # reference RnnToFeedForwardPreProcessor merges time into batch;
        # here the common intent after an LSTM is "last step" — use
        # LSTMLayer(return_sequences=False) or GlobalPoolingLayer instead
        raise ValueError(
            f"layer {idx} ({type(layer).__name__}) wants flat input but got "
            f"a sequence; use LSTMLayer(return_sequences=False) or "
            f"GlobalPoolingLayer before it")
    raise ValueError(f"no preprocessor from {itype.kind} to {wanted} "
                     f"(layer {idx}, {type(layer).__name__})")


def _adapt_input(sd, x, itype: InputType, layer: BaseLayer, idx,
                 name_stem: Optional[str] = None):
    """Apply _adapt_itype's decision to the graph (emit the reshape).
    Shared by MultiLayerNetwork and ComputationGraph builds."""
    new_itype = _adapt_itype(itype, layer, idx)
    if new_itype is itype:
        return x, itype
    x = sd.invoke("reshape", [x], {"shape": (-1, new_itype.flat_size)},
                  name=name_stem or f"layer{idx}_cnn2ff")
    return x, new_itype


def _type_walk(conf: MultiLayerConfiguration):
    """Yield (idx, layer, adapted input type, output type) — the single
    source of truth for preprocessor-kind adaptation, shared by graph
    build sizing, summary() and _final_output_type()."""
    itype = conf.input_type
    for idx, layer in enumerate(conf.layers):
        itype = _adapt_itype(itype, layer, idx)
        otype = layer.output_type(itype)
        yield idx, layer, itype, otype
        itype = otype


def _final_output_type(conf: MultiLayerConfiguration) -> InputType:
    itype = conf.input_type
    for _, _, _, otype in _type_walk(conf):
        itype = otype
    return itype


def _to_internal_layout(sd, x, itype: InputType, fmt: str, name: str):
    """Users feed NCHW (reference convention); internally cnn tensors run
    NHWC on TPU (one permute here, none in the network body — logical-NCHW
    convs cost a physical transpose per op on TPU)."""
    if fmt != "NHWC" or itype.kind not in ("cnn", "cnn3d"):
        return x
    axes = (0, 2, 3, 1) if itype.kind == "cnn" else (0, 2, 3, 4, 1)
    return sd.invoke("permute", [x], {"axes": axes}, name=name)


def _to_external_layout(sd, x, itype: InputType, fmt: str, name: str):
    """Inverse of _to_internal_layout for cnn-typed network outputs."""
    if fmt != "NHWC" or itype.kind not in ("cnn", "cnn3d"):
        return x
    axes = (0, 3, 1, 2) if itype.kind == "cnn" else (0, 4, 1, 2, 3)
    return sd.invoke("permute", [x], {"axes": axes}, name=name)


def _build_graph(conf: MultiLayerConfiguration, training: bool,
                 tbptt_batch=None):
    sd = SameDiff()
    rng = np.random.default_rng(conf.seed)
    fmt = getattr(conf, "cnn_data_format", "NHWC")
    ctx = BuildContext(sd=sd, rng=rng, training=training, dtype=conf.dtype,
                       cnn_format=fmt, tbptt_batch=tbptt_batch)
    x = sd.placeholder("input", shape=conf.input_type.placeholder_shape(),
                       dtype=conf.dtype)
    final = _final_output_type(conf)
    # labels default to the head's output shape; heads whose target
    # layout differs (yolo: (B, 4+C, H, W) vs the A*(5+C) prediction
    # grid) override via labels_placeholder_shape — a wrong declared
    # shape is never enforced at feed time, but it poisons shape
    # inference and the static analyzer (graph.shape_mismatch)
    lab_hook = getattr(conf.layers[-1] if conf.layers else None,
                       "labels_placeholder_shape", None)
    lab_shape = lab_hook(final) if lab_hook is not None else None
    ctx.labels_var = sd.placeholder(
        "labels",
        shape=lab_shape if lab_shape is not None
        else final.placeholder_shape(),
        dtype=conf.dtype)
    cur = _to_internal_layout(sd, x, conf.input_type, fmt, "input_nhwc")
    itype = conf.input_type
    for idx, layer in enumerate(conf.layers):
        cur, itype = _adapt_input(sd, cur, itype, layer, idx)
        ctx.idx = idx
        cur, itype = layer.build(ctx, cur, itype)
    if ctx.output_var is None:
        ctx.output_var = cur
    if itype.kind in ("cnn", "cnn3d"):
        # cnn-typed network output goes back to the external NCHW contract
        # (also when a loss head set output_var itself, e.g. Yolo2/CnnLoss)
        ctx.output_var = _to_external_layout(sd, ctx.output_var, itype, fmt,
                                             "output_nchw")
    ctx.output_var.rename("output")
    return sd, ctx


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self._sd_train: Optional[SameDiff] = None
        self._sd_infer: Optional[SameDiff] = None
        self._score = float("nan")

    # ------------------------------------------------------------------
    def init(self) -> "MultiLayerNetwork":
        """Build both graphs (reference: MultiLayerNetwork.init())."""
        self._sd_train, _ = _build_graph(self.conf, training=True)
        self._sd_infer, _ = _build_graph(self.conf, training=False)
        self._sd_train.training_config = TrainingConfig(
            updater=self.conf.updater,
            data_set_feature_mapping=["input"],
            data_set_label_mapping=["labels"],
            regularization=self.conf.regularization,
            grad_clip_value=self.conf.grad_clip_value,
            mixed_precision=self.conf.mixed_precision,
            gradient_normalization=self.conf.gradient_normalization,
            gradient_normalization_threshold=
                self.conf.gradient_normalization_threshold,
        )
        return self

    def _require_init(self):
        if self._sd_train is None:
            raise RuntimeError("call init() first")

    @property
    def samediff(self) -> SameDiff:
        """The underlying training graph (single execution path)."""
        self._require_init()
        return self._sd_train

    # ------------------------------------------------------------------
    def fit(self, data, labels=None, epochs: int = 1, batch_size: int = 32,
            listeners: Sequence = (), fused_steps: Optional[int] = None,
            accum_steps: Optional[int] = None,
            sentinel: Optional[bool] = None):
        """Train. ``data`` = DataSetIterator-alike (yielding (features,
        labels) / DataSet / dict) or a feature array with ``labels=``.

        ``fused_steps``/``accum_steps`` override the TrainingConfig knobs
        for this and subsequent fits: K fused steps per compiled dispatch
        / gradient accumulation (docs/training_performance.md).
        ``sentinel`` arms the device-side divergence sentinel
        (docs/fault_tolerance.md)."""
        self._require_init()
        if fused_steps is not None:
            self._sd_train.training_config.fused_steps = int(fused_steps)
        if accum_steps is not None:
            self._sd_train.training_config.accum_steps = int(accum_steps)
        if sentinel is not None:
            self._sd_train.training_config.sentinel = bool(sentinel)
        if labels is not None:
            data = _ArrayIterator(np.asarray(data), np.asarray(labels),
                                  batch_size)
        history = self._sd_train.fit(data, epochs=epochs, listeners=listeners)
        self._score = history.final_loss()
        return history

    def fit_tbptt(self, features, labels, tbptt_length: int,
                  epochs: int = 1, batch_size: int = 32):
        """Truncated backprop through time (reference:
        MultiLayerNetwork.doTruncatedBPTT, MultiLayerNetwork.java:2083).

        features (B, T, C) / labels (B, T, C_out) split into
        ``tbptt_length`` chunks along time. TPU-native design: each
        recurrent layer's initial state is a persistent STATE VAR carried
        across chunk steps by the compiled train step (state-var inputs
        are stop-gradiented there, which IS the truncation); states reset
        to zero per sequence minibatch. Equivalent to full BPTT when
        tbptt_length >= T (tested).

        Truncation segments are a natural fused window: all full-length
        chunks of one minibatch dispatch as ONE compiled lax.scan
        (SameDiff.make_train_window), with a single extra dispatch for a
        ragged final chunk when ``T % tbptt_length != 0``. Per-chunk
        losses stay in the window's device-side buffer — ONE stacked
        fetch per fit instead of thousands of device scalars held across
        epochs."""
        import jax
        import jax.numpy as jnp
        self._require_init()
        X = np.asarray(features)
        Y = np.asarray(labels)
        if X.ndim != 3 or Y.ndim != 3:
            raise ValueError("fit_tbptt needs sequence features (B, T, C) "
                             "and per-timestep labels (B, T, C_out)")
        T = X.shape[1]
        if Y.shape[1] != T:
            raise ValueError(f"labels T={Y.shape[1]} != features T={T}")
        # dedicated TBPTT train graph for this batch size (cached)
        key = ("tbptt", batch_size)
        cached = getattr(self, "_tbptt_graphs", None) or {}
        if key not in cached:
            sd, ctx = _build_graph(self.conf, training=True,
                                   tbptt_batch=batch_size)
            sd.training_config = TrainingConfig(
                updater=self.conf.updater,
                data_set_feature_mapping=["input"],
                data_set_label_mapping=["labels"],
                regularization=self.conf.regularization,
                grad_clip_value=self.conf.grad_clip_value,
                mixed_precision=self.conf.mixed_precision,
                gradient_normalization=self.conf.gradient_normalization,
                gradient_normalization_threshold=
                    self.conf.gradient_normalization_threshold)
            cached[key] = (sd, list(ctx.rnn_state_vars))
            self._tbptt_graphs = cached
        sd, rnn_states = cached[key]
        # current weights in (same names, same init seed)
        for n, arr in self._sd_train._arrays.items():
            if n in sd._arrays and \
                    tuple(sd._arrays[n].shape) == tuple(arr.shape):
                sd._arrays[n] = arr

        from deeplearning4j_tpu.autodiff.training import History
        # the divergence sentinel follows the network's main config onto
        # the dedicated TBPTT graph — an armed rail must not silently go
        # inert on this fit path (docs/fault_tolerance.md)
        use_sentinel = bool(getattr(self._sd_train.training_config,
                                    "sentinel", False))
        sd.training_config.sentinel = use_sentinel
        step = sd.make_train_step(sentinel=use_sentinel)
        window_fn = sd.make_train_window(sentinel=use_sentinel)
        tc = sd.training_config
        # working copies (the step and the window donate them); the
        # optimizer state persists across calls, like fit()
        params, svars, state, staged = stage_fit_state(sd, tc)
        constants = sd.constants_map()
        iteration = getattr(tc, "iteration_count", 0)
        it_dev = jnp.asarray(iteration, jnp.int32)
        base_key = jax.random.key(sd._seed)
        sd._seed += 1
        n = (len(X) // batch_size) * batch_size
        if n == 0:
            raise ValueError("dataset smaller than one batch")
        if n < len(X):
            import warnings
            warnings.warn(
                f"fit_tbptt: dropping {len(X) - n} of {len(X)} sequences "
                f"that do not fill a full batch of {batch_size} (TBPTT "
                f"state vars have a fixed batch dimension)")
        history = History()
        # host-side zero templates: fresh device arrays per batch (the
        # step DONATES state buffers, so device zeros can't be reused)
        zero_np = {nm: np.zeros(svars[nm].shape,
                                np.asarray(svars[nm]).dtype)
                   for nm in rnn_states}
        # truncation segments as ONE fused window per minibatch: the
        # n_full full-length chunks stack on a leading axis and dispatch
        # as one lax.scan; a ragged tail chunk (T % L != 0) is one extra
        # per-step dispatch of its own compiled shape (as before)
        n_full = T // tbptt_length
        rem = T % tbptt_length
        t_full = n_full * tbptt_length
        epoch_means = []   # DEVICE scalars; ONE stacked fetch at fit end
        for epoch in range(epochs):
            losses = []    # device loss buffers, never fetched per chunk
            bads = []      # sentinel markers, device (one per dispatch)
            epoch_start_iter = iteration
            for i in range(0, n, batch_size):
                # new sequences: recurrent carries restart at zero
                svars = {**svars, **{nm: jnp.asarray(z)
                                     for nm, z in zero_np.items()}}
                if n_full:
                    xb = X[i:i + batch_size, :t_full].reshape(
                        batch_size, n_full, tbptt_length, *X.shape[2:])
                    yb = Y[i:i + batch_size, :t_full].reshape(
                        batch_size, n_full, tbptt_length, *Y.shape[2:])
                    win = {"input": jnp.asarray(xb.swapaxes(0, 1)),
                           "labels": jnp.asarray(yb.swapaxes(0, 1))}
                    if use_sentinel:
                        (params, svars, state, it_dev, win_losses,
                         bad) = window_fn(params, svars, state, it_dev,
                                          constants, win, base_key)
                        bads.append(bad)
                    else:
                        params, svars, state, it_dev, win_losses = window_fn(
                            params, svars, state, it_dev, constants, win,
                            base_key)
                    iteration += n_full
                    losses.append(win_losses)
                if rem:
                    ph = {"input": jnp.asarray(X[i:i + batch_size, t_full:]),
                          "labels": jnp.asarray(Y[i:i + batch_size, t_full:])}
                    if use_sentinel:
                        params, svars, state, it_dev, loss_val, ok = step(
                            params, svars, state, it_dev, constants, ph,
                            base_key)
                        # normalize the per-step flag to the window
                        # tier's bad-step form (-1 = clean)
                        bads.append(jnp.where(ok, jnp.int32(-1),
                                              jnp.int32(iteration)))
                    else:
                        params, svars, state, it_dev, loss_val = step(
                            params, svars, state, it_dev, constants, ph,
                            base_key)
                    iteration += 1
                    losses.append(loss_val[None])
            if bads:
                # one stacked verdict fetch per epoch (the sentinel's
                # only extra sync on this path)
                from deeplearning4j_tpu.faults.sentinels import \
                    check_bad_steps
                check_bad_steps(np.asarray(jnp.stack(bads)), epoch,
                                epoch_start_iter)
            epoch_means.append(jnp.mean(jnp.concatenate(losses))
                               if losses else jnp.asarray(float("nan")))
            history.add_epoch(epoch, None)
        fetched = np.asarray(jnp.stack(epoch_means))     # one transfer
        history.loss_curve.losses = [float(v) for v in fetched]
        # trained params back into BOTH graphs (by name)
        for tgt in (sd, self._sd_train):
            for pn, arr in params.items():
                if pn in tgt._arrays:
                    tgt._arrays[pn] = arr
        for sn, arr in svars.items():
            if sn in sd._arrays:
                sd._arrays[sn] = arr
            if sn in self._sd_train._arrays and sn not in rnn_states:
                self._sd_train._arrays[sn] = arr   # e.g. BN running stats
        sd._updater_state = state
        tc.iteration_count = iteration
        # dispatch accounting, as fit()'s tiers give it: a batch is one
        # window of its full-length chunks and one step of a ragged tail
        batches = n // batch_size
        sd.last_fit_stats = self._sd_train.last_fit_stats = {
            "tier": "tbptt", "fused_steps": max(n_full, 1),
            "accum_steps": 1,
            "steps_per_epoch": batches * (n_full + bool(rem)),
            "dispatches_per_epoch": batches * (bool(n_full) + bool(rem)),
            "window_compiles": 0, **staged}
        self._score = history.final_loss()
        return history

    def _sync_infer(self):
        # same param names in both graphs; move references, not data
        tgt = self._sd_infer
        for n, arr in self._sd_train._arrays.items():
            if n in tgt._vars and n in tgt._arrays:
                tgt._arrays[n] = arr

    def serving_spec(self):
        """Replica-extraction hook for the serving/ subsystem: the
        inference graph, its IO names, and the parameter sync that pulls
        current trained weights into it. Serving executes the SAME graph
        ``output()`` uses, so served results match it bit for bit."""
        self._require_init()
        return self._sd_infer, ["input"], ["output"], self._sync_infer

    def output(self, x, training: bool = False):
        """Forward pass (reference: MultiLayerNetwork.output :2471)."""
        self._require_init()
        if training:
            return self._sd_train.output({"input": x}, ["output"])["output"]
        self._sync_infer()
        return self._sd_infer.output({"input": x}, ["output"])["output"]

    def predict(self, x) -> np.ndarray:
        """Class indices (reference: MultiLayerNetwork.predict)."""
        return np.asarray(self.output(x).to_numpy().argmax(axis=-1))

    def score(self) -> float:
        """Most recent training loss (reference: MultiLayerNetwork.score)."""
        return self._score

    def evaluate(self, data, labels=None, evaluation=None, batch_size: int = 256):
        """Evaluate over an iterator or arrays (reference:
        MultiLayerNetwork.evaluate(DataSetIterator)). Returns the
        Evaluation (or supplied metric accumulator) after streaming all
        batches through inference."""
        from deeplearning4j_tpu.evaluation import Evaluation
        ev = evaluation or Evaluation()
        if labels is not None:
            data = _ArrayIterator(np.asarray(data), np.asarray(labels),
                                  batch_size)
        if hasattr(data, "reset"):
            data.reset()
        for batch in data:
            if isinstance(batch, dict):
                feats, labs = batch["input"], batch["labels"]
            elif hasattr(batch, "features"):
                feats, labs = batch.features, batch.labels
            else:
                feats, labs = batch
            preds = self.output(feats)
            ev.eval(labs, preds)
        return ev

    # ------------------------------------------------------------------
    def params(self) -> Dict[str, np.ndarray]:
        self._require_init()
        return {n: np.asarray(a) for n, a in
                {**self._sd_train.trainable_params(),
                 **self._sd_train.state_vars_map()}.items()}

    def set_param(self, name: str, value) -> None:
        self._require_init()
        self._sd_train.set_arr_for_var(name, value)

    def num_params(self) -> int:
        return sum(int(np.prod(a.shape))
                   for a in self._sd_train.trainable_params().values())

    def summary(self) -> str:
        lines = [f"MultiLayerNetwork: {len(self.conf.layers)} layers, "
                 f"{self.num_params() if self._sd_train else '?'} params"]
        for i, layer, itype, otype in _type_walk(self.conf):
            lines.append(f"  {i}: {type(layer).__name__:<22} "
                         f"{itype.dims} -> {otype.dims}")
        return "\n".join(lines)

    # ------------------------------------------------------------------
    # checkpointing (checkpoint/ subsystem: atomic, async, bit-exact)
    def capture_training_state(self, epoch: int = 0, normalizer=None):
        """Host snapshot of params/updater/counters/RNG for the
        checkpoint manager (checkpoint.capture_training_state)."""
        from deeplearning4j_tpu.checkpoint import capture_training_state
        self._require_init()
        return capture_training_state(self, epoch=epoch,
                                      normalizer=normalizer)

    def restore_training_state(self, state, strict: bool = True):
        """Restore a TrainingState snapshot into this initialized net;
        returns the rebuilt Normalizer (or None)."""
        from deeplearning4j_tpu.checkpoint import restore_training_state
        self._require_init()
        return restore_training_state(self, state, strict=strict)

    # ------------------------------------------------------------------
    # serde (reference: util/ModelSerializer zip of config JSON + params +
    # updater state)
    def save(self, path, include_updater_state: bool = True) -> None:
        from deeplearning4j_tpu.nn.model_serde import save_net_zip
        self._require_init()
        save_net_zip(path, self.conf.to_json(), self._sd_train,
                     include_updater_state)

    @staticmethod
    def load(path) -> "MultiLayerNetwork":
        from deeplearning4j_tpu.nn.model_serde import (read_net_zip,
                                                       restore_net_state)
        conf_json, arrays, updater_leaves, iteration = read_net_zip(path)
        conf = MultiLayerConfiguration.from_json(conf_json)
        net = MultiLayerNetwork(conf).init()
        return restore_net_state(net, conf, arrays, updater_leaves, iteration)


class _ArrayIterator:
    """In-memory batch iterator over one or more feature/label arrays
    (shared by MultiLayerNetwork and ComputationGraph fit(X, Y) paths)."""

    def __init__(self, X, Y, batch: int):
        self.Xs = list(X) if isinstance(X, (list, tuple)) else [X]
        self.Ys = list(Y) if isinstance(Y, (list, tuple)) else [Y]
        self.batch = batch

    def reset(self):
        pass

    def __iter__(self):
        n = len(self.Xs[0])
        for i in range(0, n, self.batch):
            feats = [X[i:i + self.batch] for X in self.Xs]
            labs = [Y[i:i + self.batch] for Y in self.Ys]
            yield (feats if len(feats) > 1 else feats[0],
                   labs if len(labs) > 1 else labs[0])
