"""Wave-2 layers: finite-difference gradient checks, serde round-trips,
and end-to-end training (reference test strategy: gradientcheck/* +
IntegrationTestRunner overfit sanity)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.nn import (
    CapsuleLayer, CapsuleStrengthLayer, CenterLossOutputLayer, CnnLossLayer,
    ConvolutionLayer, Cropping1DLayer, DenseLayer, DepthToSpaceLayer,
    DotProductAttentionLayer, ElementWiseMultiplicationLayer, FrozenLayer,
    GravesLSTMLayer, GRULayer, InputType, LossLayer, MultiLayerNetwork,
    NeuralNetConfiguration, OutputLayer, PReLULayer, PrimaryCapsulesLayer,
    RecurrentAttentionLayer, RepeatVectorLayer, RnnLossLayer,
    SpaceToDepthLayer, Subsampling1DLayer, Upsampling1DLayer,
    Upsampling3DLayer, VariationalAutoencoderLayer, Yolo2OutputLayer,
    ZeroPadding1DLayer, ZeroPadding3DLayer)
from deeplearning4j_tpu.nn.layers import BaseLayer
from deeplearning4j_tpu.ops import registry


def _net(layers, itype, lr=1e-2, seed=0):
    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(lr)).list()
    for l in layers:
        b = b.layer(l)
    return MultiLayerNetwork(b.set_input_type(itype).build()).init()


def _numeric_grad(f, x, eps=1e-4):
    g = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    while not it.finished:
        i = it.multi_index
        xp = x.copy(); xp[i] += eps
        xm = x.copy(); xm[i] -= eps
        g[i] = (f(xp) - f(xm)) / (2 * eps)
        it.iternext()
    return g


# --- gradient checks on the new ops ----------------------------------------
def test_capsule_routing_grad_check():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 4, 3).astype(np.float64) * 0.5
    w = rng.randn(4, 3, 3, 2).astype(np.float64) * 0.5
    fn = registry.get_op("capsule_routing").fn

    def loss_w(wv):
        return float(jnp.sum(jnp.square(fn(jnp.asarray(x), jnp.asarray(wv),
                                           routings=3))))

    ana = np.asarray(jax.grad(
        lambda wv: jnp.sum(jnp.square(fn(jnp.asarray(x), wv, routings=3))))(
        jnp.asarray(w)))
    num = _numeric_grad(loss_w, w, eps=1e-5)
    np.testing.assert_allclose(ana, num, rtol=1e-4, atol=1e-6)


def test_graves_lstm_grad_check():
    rng = np.random.RandomState(1)
    u, n_in = 3, 2
    x = rng.randn(2, 4, n_in).astype(np.float64) * 0.5
    w_ih = rng.randn(n_in, 4 * u) * 0.3
    w_hh = rng.randn(u, 4 * u) * 0.3
    w_p = rng.randn(3, u) * 0.2
    b = np.zeros(4 * u)
    h0 = np.zeros((2, u)); c0 = np.zeros((2, u))
    fn = registry.get_op("graves_lstm_layer").fn

    def out_sum(wp):
        o, _, _ = fn(jnp.asarray(x), jnp.asarray(h0), jnp.asarray(c0),
                     jnp.asarray(w_ih), jnp.asarray(w_hh), wp,
                     jnp.asarray(b))
        return jnp.sum(jnp.square(o))

    ana = np.asarray(jax.grad(out_sum)(jnp.asarray(w_p)))
    num = _numeric_grad(lambda wp: float(out_sum(jnp.asarray(wp))), w_p,
                        eps=1e-5)
    np.testing.assert_allclose(ana, num, rtol=1e-4, atol=1e-6)
    # peepholes actually matter: zero vs nonzero peephole output differ
    o1, _, _ = fn(jnp.asarray(x), jnp.asarray(h0), jnp.asarray(c0),
                  jnp.asarray(w_ih), jnp.asarray(w_hh),
                  jnp.zeros_like(jnp.asarray(w_p)), jnp.asarray(b))
    o2, _, _ = fn(jnp.asarray(x), jnp.asarray(h0), jnp.asarray(c0),
                  jnp.asarray(w_ih), jnp.asarray(w_hh), jnp.asarray(w_p),
                  jnp.asarray(b))
    assert float(jnp.max(jnp.abs(o1 - o2))) > 1e-4


def test_yolo2_loss_grad_and_values():
    rng = np.random.RandomState(2)
    B, H, W, A, C = 2, 4, 4, 2, 3
    pred = rng.randn(B, H, W, A * (5 + C)).astype(np.float64) * 0.3
    labels = np.zeros((B, H, W, 4 + C))
    # one object in cell (1,2) of each batch elem, class 1
    labels[:, 1, 2, 0:4] = [2.0, 1.0, 3.0, 2.0]   # x1,y1,x2,y2 grid units
    labels[:, 1, 2, 4 + 1] = 1.0
    fn = registry.get_op("yolo2_loss").fn
    anchors = (1.0, 1.0, 2.0, 2.0)
    loss = float(fn(jnp.asarray(pred), jnp.asarray(labels), anchors=anchors))
    assert np.isfinite(loss) and loss > 0
    ana = np.asarray(jax.grad(
        lambda p: fn(p, jnp.asarray(labels), anchors=anchors))(
        jnp.asarray(pred)))
    assert np.isfinite(ana).all()
    # numeric spot-check on a few entries
    flat_idx = [(0, 1, 2, 3), (1, 1, 2, 7), (0, 0, 0, 4)]
    def f(p):
        return float(fn(jnp.asarray(p), jnp.asarray(labels), anchors=anchors))
    for idx in flat_idx:
        pp = pred.copy(); pp[idx] += 1e-5
        pm = pred.copy(); pm[idx] -= 1e-5
        num = (f(pp) - f(pm)) / 2e-5
        np.testing.assert_allclose(ana[idx], num, rtol=2e-3, atol=1e-7)


# --- training e2e -----------------------------------------------------------
def test_vae_trains_unsupervised():
    rng = np.random.RandomState(0)
    X = rng.randn(64, 8).astype(np.float32)
    net = _net([VariationalAutoencoderLayer(
        n_out=3, encoder_layer_sizes=(16,), decoder_layer_sizes=(16,),
        kl_weight=0.1)], InputType.feed_forward(8), lr=5e-3)
    Y = np.zeros((64, 3), np.float32)    # labels unused by the ELBO loss
    h = net.fit(X, Y, epochs=30, batch_size=32)
    losses = h.loss_curve.losses
    assert losses[-1] < losses[0] * 0.8, losses[::10]
    latent = np.asarray(net.output(X[:5]).data)
    assert latent.shape == (5, 3)


def test_capsnet_trains():
    rng = np.random.RandomState(0)
    X = rng.rand(32, 1, 8, 8).astype(np.float32)
    y = (X.mean((1, 2, 3)) > X.mean()).astype(int)
    Y = np.eye(2, dtype=np.float32)[y]
    net = _net([
        ConvolutionLayer(n_out=8, kernel_size=(3, 3), activation="relu",
                         convolution_mode="VALID"),
        PrimaryCapsulesLayer(capsules=4, capsule_dimensions=4,
                             kernel_size=(3, 3), stride=(2, 2)),
        CapsuleLayer(capsules=2, capsule_dimensions=4, routings=2),
        CapsuleStrengthLayer(),
        LossLayer(loss_function="MSE", activation="identity"),
    ], InputType.convolutional(8, 8, 1), lr=5e-3)
    h = net.fit(X, Y, epochs=25, batch_size=32)
    assert h.loss_curve.losses[-1] < h.loss_curve.losses[0]


def test_yolo2_output_layer_trains():
    rng = np.random.RandomState(0)
    B, H, W, A, C = 8, 4, 4, 2, 2
    X = rng.rand(B, 3, 16, 16).astype(np.float32)
    labels = np.zeros((B, 4 + C, H, W), np.float32)
    labels[:, 0:4, 2, 2] = np.array([1.5, 1.5, 2.5, 2.5], np.float32)
    labels[:, 4, 2, 2] = 1.0
    net = _net([
        ConvolutionLayer(n_out=16, kernel_size=(3, 3), stride=(2, 2),
                         activation="relu"),
        ConvolutionLayer(n_out=A * (5 + C), kernel_size=(3, 3),
                         stride=(2, 2)),
        Yolo2OutputLayer(anchors=(1.0, 1.0, 2.0, 2.0)),
    ], InputType.convolutional(16, 16, 3), lr=1e-3)
    h = net.fit(X, labels, epochs=20, batch_size=8)
    assert h.loss_curve.losses[-1] < h.loss_curve.losses[0]
    out = np.asarray(net.output(X[:2]).data)
    assert out.shape == (2, A * (5 + C), H, W)    # NCHW external contract


def test_attention_layers_train():
    rng = np.random.RandomState(0)
    X = rng.randn(32, 6, 5).astype(np.float32)    # (B, T, C)
    y = (X[:, :, 0].mean(1) > 0).astype(int)
    Y = np.eye(2, dtype=np.float32)[y]
    for layer in (DotProductAttentionLayer(n_out=8, n_heads=2),
                  RecurrentAttentionLayer(n_out=8)):
        net = _net([layer,
                    GRULayer(n_out=8, return_sequences=False),
                    OutputLayer(n_out=2, loss_function="MCXENT")],
                   InputType.recurrent(5, 6), lr=5e-3)
        h = net.fit(X, Y, epochs=15, batch_size=32)
        assert h.loss_curve.losses[-1] < h.loss_curve.losses[0], type(layer)


def test_graves_lstm_trains():
    rng = np.random.RandomState(0)
    X = rng.randn(32, 5, 4).astype(np.float32)
    Y = np.eye(2, dtype=np.float32)[(X.sum((1, 2)) > 0).astype(int)]
    net = _net([GravesLSTMLayer(n_out=8, return_sequences=False),
                OutputLayer(n_out=2, loss_function="MCXENT")],
               InputType.recurrent(4, 5), lr=1e-2)
    h = net.fit(X, Y, epochs=15, batch_size=32)
    assert h.loss_curve.losses[-1] < h.loss_curve.losses[0]


def test_center_loss_output_layer():
    rng = np.random.RandomState(0)
    X = rng.randn(32, 6).astype(np.float32)
    Y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 32)]
    net = _net([DenseLayer(n_out=8, activation="relu"),
                CenterLossOutputLayer(n_out=3, lambda_=0.1)],
               InputType.feed_forward(6), lr=1e-2)
    h = net.fit(X, Y, epochs=20, batch_size=32)
    assert h.loss_curve.losses[-1] < h.loss_curve.losses[0]
    # centers updated away from init
    sd = net.samediff
    centers = [n for n in sd.state_vars_map() if "centers" in n]
    assert centers and float(np.abs(
        np.asarray(sd.state_vars_map()[centers[0]])).sum()) > 0


def test_frozen_layer_freezes():
    rng = np.random.RandomState(0)
    X = rng.randn(16, 4).astype(np.float32)
    Y = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 16)]
    net = _net([FrozenLayer(layer=DenseLayer(n_out=8, activation="relu")),
                OutputLayer(n_out=2, loss_function="MCXENT")],
               InputType.feed_forward(4))
    sd = net.samediff
    frozen = [n for n in sd._vars if "dense" in n and n.endswith("_W")]
    assert frozen
    before = np.asarray(sd.get_arr_for_var(frozen[0]).data).copy()
    assert frozen[0] not in sd.trainable_params()
    net.fit(X, Y, epochs=3, batch_size=16)
    after = np.asarray(net.samediff.get_arr_for_var(frozen[0]).data)
    np.testing.assert_array_equal(before, after)


# --- structural layers: shapes + loss flows ---------------------------------
def test_structural_shapes():
    rng = np.random.RandomState(0)
    # rnn family
    Xr = rng.randn(4, 6, 3).astype(np.float32)
    net = _net([ZeroPadding1DLayer(padding=(1, 2)),
                Cropping1DLayer(cropping=(1, 0)),
                Upsampling1DLayer(size=2),
                Subsampling1DLayer(kernel_size=2),
                GlobalP := __import__("deeplearning4j_tpu.nn",
                                      fromlist=["GlobalPoolingLayer"]
                                      ).GlobalPoolingLayer(),
                OutputLayer(n_out=2, loss_function="MCXENT")],
               InputType.recurrent(3, 6))
    out = np.asarray(net.output(Xr).data)
    assert out.shape == (4, 2)

    # cnn family: s2d -> d2s round-trips shape
    Xc = rng.randn(2, 4, 8, 8).astype(np.float32)
    net2 = _net([SpaceToDepthLayer(block_size=2),
                 DepthToSpaceLayer(block_size=2),
                 CnnLossLayer(loss_function="MSE")],
                InputType.convolutional(8, 8, 4))
    oc = np.asarray(net2.output(Xc).data)
    assert oc.shape == (2, 4, 8, 8)

    # ff family
    Xf = rng.randn(4, 5).astype(np.float32)
    net3 = _net([ElementWiseMultiplicationLayer(),
                 PReLULayer(),
                 RepeatVectorLayer(n=3),
                 RnnLossLayer(loss_function="MSE", activation="identity")],
                InputType.feed_forward(5))
    of = np.asarray(net3.output(Xf).data)
    assert of.shape == (4, 3, 5)

    # cnn3d family
    X3 = rng.randn(2, 1, 2, 4, 4).astype(np.float32)
    net4 = _net([Upsampling3DLayer(size=(2, 1, 1)),
                 ZeroPadding3DLayer(padding=(0, 0, 1, 1, 0, 0)),
                 __import__("deeplearning4j_tpu.nn",
                            fromlist=["GlobalPoolingLayer"]
                            ).GlobalPoolingLayer(),
                 OutputLayer(n_out=2, loss_function="MCXENT")],
                InputType.convolutional3d(2, 4, 4, 1))
    o3 = np.asarray(net4.output(X3).data)
    assert o3.shape == (2, 2)


def test_wave2_serde_roundtrip():
    layers = [
        VariationalAutoencoderLayer(n_out=3, encoder_layer_sizes=(8,),
                                    decoder_layer_sizes=(8,)),
        Yolo2OutputLayer(anchors=(1.0, 2.0, 3.0, 4.0), lambda_coord=3.0),
        PrimaryCapsulesLayer(capsules=4, capsule_dimensions=8),
        CapsuleLayer(capsules=10, capsule_dimensions=16, routings=2),
        CapsuleStrengthLayer(),
        DotProductAttentionLayer(n_out=8, n_heads=2),
        RecurrentAttentionLayer(n_out=8),
        GravesLSTMLayer(n_out=8, return_sequences=False),
        GRULayer(n_out=8),
        RepeatVectorLayer(n=4),
        PReLULayer(),
        ElementWiseMultiplicationLayer(activation="tanh"),
        Subsampling1DLayer(kernel_size=3, pooling_type="AVG"),
        ZeroPadding1DLayer(padding=(2, 0)),
        Cropping1DLayer(cropping=(1, 1)),
        Upsampling1DLayer(size=3),
        Upsampling3DLayer(size=(1, 2, 2)),
        ZeroPadding3DLayer(),
        SpaceToDepthLayer(block_size=4),
        DepthToSpaceLayer(block_size=2),
        CnnLossLayer(loss_function="L1"),
        RnnLossLayer(loss_function="MSE"),
        CenterLossOutputLayer(n_out=5, alpha=0.1, lambda_=0.3),
        FrozenLayer(layer=DenseLayer(n_out=7, activation="relu")),
    ]
    for l in layers:
        d = l.to_json()
        l2 = BaseLayer.from_json(d)
        assert type(l2) is type(l)
        if isinstance(l, FrozenLayer):
            assert type(l2.layer) is DenseLayer and l2.layer.n_out == 7
        else:
            for f in dataclasses.fields(l):
                assert getattr(l2, f.name) == getattr(l, f.name), \
                    (type(l).__name__, f.name)


def test_layer_config_count_target():
    """Breadth target: >= 55 layer/vertex config types."""
    from deeplearning4j_tpu.nn.graph import VERTEX_TYPES
    from deeplearning4j_tpu.nn.layers import LAYER_TYPES
    assert len(LAYER_TYPES) + len(VERTEX_TYPES) >= 55, \
        (len(LAYER_TYPES), len(VERTEX_TYPES))
