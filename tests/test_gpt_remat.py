"""GPT decoder zoo model + SameDiff remat_scope + the SDPA op.

Covers the training path of `build_gpt` (the benchmark's `medium_train`):
the scaled_dot_product_attention op against a numpy reference, its tiled
kernel path against its plain path (the kernel interpreted on the CPU),
which path a call takes and what the tally of sites reads, remat-scope
gradient equivalence (checkpointing must change memory, never numerics),
serde round-trip of the remat group field, and GPT_TINY learning.
"""
import contextlib
import functools

import numpy as np
import pytest

from deeplearning4j_tpu.monitor.attention import (AttentionSites,
                                                  last_train_step)
from deeplearning4j_tpu.ops import nn_ops, registry


def _np_sdpa(q, k, v, causal=False, mask=None):
    d = q.shape[-1]
    s = q.astype(np.float64) @ np.swapaxes(k.astype(np.float64), -1, -2)
    s /= np.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        s = np.where(cm, s, -np.inf)
    if mask is not None:
        s = np.where(mask.astype(bool), s, -np.inf)
    e = np.exp(s - s.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    return p @ v.astype(np.float64)


class TestSDPA:
    def setup_method(self):
        self.rng = np.random.default_rng(7)
        self.q = self.rng.standard_normal((2, 3, 5, 8)).astype(np.float32)
        self.k = self.rng.standard_normal((2, 3, 5, 8)).astype(np.float32)
        self.v = self.rng.standard_normal((2, 3, 5, 8)).astype(np.float32)

    def test_matches_numpy_plain(self):
        out = registry.exec_op("scaled_dot_product_attention",
                               self.q, self.k, self.v)
        np.testing.assert_allclose(np.asarray(out.data),
                                   _np_sdpa(self.q, self.k, self.v),
                                   rtol=1e-5, atol=1e-5)

    def test_matches_numpy_causal(self):
        out = registry.exec_op("scaled_dot_product_attention",
                               self.q, self.k, self.v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out.data),
            _np_sdpa(self.q, self.k, self.v, causal=True),
            rtol=1e-5, atol=1e-5)

    def test_causal_first_row_attends_only_self(self):
        out = np.asarray(registry.exec_op(
            "scaled_dot_product_attention", self.q, self.k, self.v,
            causal=True).data)
        np.testing.assert_allclose(out[..., 0, :], self.v[..., 0, :],
                                   rtol=1e-5, atol=1e-5)

    def test_padding_mask(self):
        mask = np.ones((2, 1, 1, 5), np.float32)
        mask[..., 3:] = 0          # keys 3,4 masked out
        out = registry.exec_op("scaled_dot_product_attention",
                               self.q, self.k, self.v, mask=mask)
        np.testing.assert_allclose(
            np.asarray(out.data),
            _np_sdpa(self.q, self.k, self.v, mask=mask),
            rtol=1e-5, atol=1e-5)

    def test_bf16_inputs_finite_and_close(self):
        import jax.numpy as jnp
        qb = jnp.asarray(self.q, jnp.bfloat16)
        kb = jnp.asarray(self.k, jnp.bfloat16)
        vb = jnp.asarray(self.v, jnp.bfloat16)
        out = np.asarray(registry.get_op("scaled_dot_product_attention")
                         (qb, kb, vb, causal=True), np.float32)
        ref = _np_sdpa(self.q, self.k, self.v, causal=True)
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, ref, rtol=0.1, atol=0.1)


def _sdpa(q, k, v, **kw):
    return registry.get_op("scaled_dot_product_attention")(q, k, v, **kw)


def _parents_sdpa(q, k, v, mask=None, causal=False):
    """The op's text before it had two paths (PR 30), for the dispatch
    tests: every plain call must still give these bits."""
    import jax
    import jax.numpy as jnp
    s = 1.0 / np.sqrt(q.shape[-1])
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


@pytest.fixture
def as_tpu(monkeypatch):
    """The dispatch's backend condition, lifted by the test: the op asks
    ``jax.default_backend()`` and nothing else in the package does while
    a step is traced. So is 64-bit mode, which the kernel's lowering for
    the chip cannot take."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.enable_x64(False):     # the chip runs 32-bit, these tests 64
        yield


@contextlib.contextmanager
def _plain_interpreter():
    """The kernel run by Pallas' plain interpreter (``interpret=True``)."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    real = sa.make_splash_mha_single_device
    sa.make_splash_mha_single_device = functools.partial(real,
                                                         interpret=True)
    try:
        yield
    finally:
        sa.make_splash_mha_single_device = real


def _interpreter(batch: int):
    """The TPU interpreter (``force_tpu_interpret_mode``) where it can run
    the call. In jax 0.9.0 it cannot where ``vmap`` over a batch of more
    than one adds an axis to the kernel's grid, nor inside a
    ``jax.checkpoint`` region (it works through io callbacks): there the
    plain interpreter runs the same kernel."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.force_tpu_interpret_mode() if batch == 1 \
        else _plain_interpreter()


@pytest.fixture
def interpreted_kernel():
    with _plain_interpreter():
        yield


def _qkv(shape, dtype, seed=11, n=4):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(shape).astype(np.float32),
                        dtype) for _ in range(n)]


def _out_and_grads(q, k, v, do, sites):
    import jax
    with nn_ops.attention_trace_scope(sites):
        out, vjp = jax.vjp(lambda q, k, v: _sdpa(q, k, v, causal=True),
                           q, k, v)
        return (out,) + vjp(do)


class TestSDPATiled:
    """The kernel path against the plain path, the kernel interpreted on
    the CPU (:func:`_interpreter`)."""

    @pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                           ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("shape", [(1, 2, 256, 64), (2, 3, 384, 64)])
    def test_output_and_gradients_match_the_plain_path(self, as_tpu, shape,
                                                       dtype, tol):
        q, k, v, do = _qkv(shape, dtype)
        sites = AttentionSites()
        with _interpreter(shape[0]):
            tiled = _out_and_grads(q, k, v, do, sites)
        assert sites.counts() == (1, 0, None)
        plain = _out_and_grads(q, k, v, do, None)
        for name, a, b in zip(("out", "dq", "dk", "dv"), tiled, plain):
            assert a.dtype == b.dtype and a.shape == b.shape, name
            b = np.asarray(b, np.float32)
            np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                       rtol=tol, atol=tol * np.abs(b).max(),
                                       err_msg=name)

    def test_row_0_attends_only_to_itself(self, as_tpu):
        q, k, v = _qkv((1, 3, 384, 64), "float32", n=3)
        sites = AttentionSites()
        with _interpreter(1), nn_ops.attention_trace_scope(sites):
            out = _sdpa(q, k, v, causal=True)
        assert sites.counts() == (1, 0, None)
        np.testing.assert_allclose(np.asarray(out)[..., 0, :],
                                   np.asarray(v)[..., 0, :],
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("seq_len,tile", [
        (128, 128), (384, 384), (1024, 1024), (1152, 384), (2048, 1024),
        (4096, 1024), (1280, 640)])
    def test_the_tile_is_a_function_of_the_sequence(self, seq_len, tile):
        assert nn_ops._tile_of(seq_len) == tile
        assert seq_len % tile == 0 and tile % 128 == 0

    def test_a_whole_gpt_step_is_the_same_on_both_paths(
            self, monkeypatch, interpreted_kernel):
        """2 layers, S = 256, head size 64, remat on, MixedPrecision():
        loss and every gradient within bf16's tolerance, and the tally
        reads both sites on the path taken."""
        import jax
        from deeplearning4j_tpu.autodiff import (MixedPrecision,
                                                 TrainingConfig)
        from deeplearning4j_tpu.learning.updaters import Adam
        from deeplearning4j_tpu.zoo.gpt import GPTConfig, build_gpt
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=2, intermediate_size=256, max_seq_len=256)
        sd = build_gpt(cfg, batch=2, seq_len=256, seed=3)
        sd.training_config = TrainingConfig(
            updater=Adam(1e-3), mixed_precision=MixedPrecision(),
            data_set_feature_mapping=["input_ids"],
            data_set_label_mapping=["targets"])
        rng = np.random.default_rng(5)
        ph = {n: rng.integers(0, cfg.vocab_size, (2, 256)).astype(np.int32)
              for n in ("input_ids", "targets")}

        def grads_and_loss():
            grad_fn, _, _ = sd._build_step_parts()
            g, _, loss = jax.jit(grad_fn)(
                sd.trainable_params(), sd.state_vars_map(), 0,
                sd.constants_map(), ph, jax.random.key(0))
            return g, float(loss), sd.attention_sites.counts()

        g_plain, l_plain, n_plain = grads_and_loss()
        assert n_plain == (0, 2, "backend cpu")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with jax.enable_x64(False):
            g_tiled, l_tiled, n_tiled = grads_and_loss()
        assert n_tiled == (2, 0, None)
        assert last_train_step() is sd.attention_sites
        assert abs(l_tiled - l_plain) < 2e-3 * abs(l_plain)
        assert set(g_tiled) == set(g_plain)
        for n in g_plain:
            a, b = np.asarray(g_tiled[n]), np.asarray(g_plain[n])
            assert np.linalg.norm(a - b) <= 3e-2 * np.linalg.norm(b) + 1e-7, n


class TestSDPADispatch:
    """Which path a call takes, from backend, shape, mask and mesh alone;
    a plain call gives the bits it gave before the op had two paths."""

    CASES = {
        # name: (q shape, k/v shape, kwargs, devices, lifted, reason)
        "padding_mask": ((2, 2, 256, 64), None, {"causal": True,
                                                 "mask": "pad"}, 1, True,
                         "mask"),
        "not_causal": ((2, 2, 256, 64), None, {}, 1, True, "not causal"),
        "sq_not_sk": ((2, 2, 128, 64), (2, 2, 256, 64), {"causal": True}, 1,
                      True, "shapes (2, 2, 128, 64) (2, 2, 256, 64) "
                            "(2, 2, 256, 64)"),
        "seq_5": ((2, 3, 5, 8), None, {"causal": True}, 1, True,
                  "seq_len 5 is not a multiple of 128"),
        "seq_200": ((1, 2, 200, 64), None, {"causal": True}, 1, True,
                    "seq_len 200 is not a multiple of 128"),
        "head_size_32": ((1, 2, 256, 32), None, {"causal": True}, 1, True,
                         "head size 32"),
        "cpu_backend": ((1, 2, 256, 64), None, {"causal": True}, 1, False,
                        "backend cpu"),
        "mesh_of_2": ((2, 2, 256, 64), None, {"causal": True}, 2, True,
                      "traced for a mesh of 2 devices"),
        "x64": ((2, 2, 256, 64), None, {"causal": True}, 1, "in 64 bits",
                "jax_enable_x64"),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_the_plain_path_and_the_parents_bits(self, monkeypatch, case):
        import jax
        import jax.numpy as jnp
        qs, ks, kw, devices, lifted, reason = self.CASES[case]
        if lifted:
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        q, = _qkv(qs, "float32", n=1)
        k, v = _qkv(ks or qs, "float32", seed=12, n=2)
        kw = dict(kw)
        if kw.get("mask") == "pad":
            mask = np.ones((qs[0], 1, 1, qs[2]), np.float32)
            mask[..., qs[2] - 56:] = 0
            kw["mask"] = jnp.asarray(mask)
        sites = AttentionSites(devices=devices)
        # the tests run in 64 bits, the chip in 32
        with jax.enable_x64(lifted == "in 64 bits"), \
                nn_ops.attention_trace_scope(sites):
            got = jax.jit(lambda q, k, v: _sdpa(q, k, v, **kw))(q, k, v)
            want = jax.jit(
                lambda q, k, v: _parents_sdpa(q, k, v, **kw))(q, k, v)
        assert sites.counts() == (0, 1, reason)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_outside_a_traced_step_the_path_is_plain(self, as_tpu):
        """Nobody has said how many devices the program spans, and a
        Pallas call cannot be partitioned: the kernel needs a yes."""
        q, k, v = _qkv((1, 2, 256, 64), "float32", n=3)
        assert nn_ops._plain_reason(q, k, v, None, True, None) == \
            "not traced as a train step"
        assert np.array_equal(np.asarray(_sdpa(q, k, v, causal=True)),
                              np.asarray(_parents_sdpa(q, k, v, causal=True)))

    def test_the_tally_keeps_the_first_reason(self, as_tpu):
        q, k, v = _qkv((1, 2, 200, 64), "float32", n=3)
        sites = AttentionSites()
        with nn_ops.attention_trace_scope(sites):
            _sdpa(q, k, v, causal=True)
            _sdpa(q, k, v)
        assert sites.counts() == (0, 2,
                                  "seq_len 200 is not a multiple of 128")
        assert sites.to_json() == {
            "devices": 1, "kernel": 0, "plain": 2,
            "first_reason": "seq_len 200 is not a multiple of 128"}

    def test_a_tiny_gpt_on_the_cpu_reads_no_kernel_site(self):
        from deeplearning4j_tpu.autodiff import TrainingConfig
        from deeplearning4j_tpu.dataset import DeviceCachedIterator
        from deeplearning4j_tpu.learning.updaters import Adam
        from deeplearning4j_tpu.zoo.gpt import GPT_TINY, build_gpt
        sd = build_gpt(GPT_TINY, batch=4, seq_len=16)
        assert sd.attention_sites is None
        sd.training_config = TrainingConfig(
            updater=Adam(1e-3), data_set_feature_mapping=["input_ids"],
            data_set_label_mapping=["targets"])
        ids = np.zeros((8, 16), np.int32)
        sd.fit(DeviceCachedIterator([ids], [ids], batch_size=4), epochs=1)
        assert sd.attention_sites.counts() == (
            0, GPT_TINY.num_layers, "backend cpu")
        assert last_train_step() is sd.attention_sites

    def test_a_step_traced_for_a_mesh_of_2_is_plain(self, as_tpu,
                                                    interpreted_kernel):
        """One model, one cached step function: traced for one device it
        takes the kernel at both sites; moved onto a mesh of 2
        (``TrainingConfig.sharding``, set in place) it is traced again
        and takes none, though nothing else about the calls changed."""
        from deeplearning4j_tpu.autodiff import TrainingConfig
        from deeplearning4j_tpu.dataset import DeviceCachedIterator
        from deeplearning4j_tpu.learning.updaters import Adam
        from deeplearning4j_tpu.parallel import ShardingSpec
        from deeplearning4j_tpu.zoo.gpt import GPTConfig, build_gpt
        cfg = GPTConfig(vocab_size=64, hidden_size=128, num_layers=2,
                        num_heads=2, intermediate_size=128, max_seq_len=128)
        sd = build_gpt(cfg, batch=2, seq_len=128)
        sd.training_config = TrainingConfig(
            updater=Adam(1e-3), data_set_feature_mapping=["input_ids"],
            data_set_label_mapping=["targets"])
        ids = np.zeros((4, 128), np.int32)
        it = DeviceCachedIterator([ids], [ids], batch_size=2)
        sd.fit(it, epochs=1)
        assert sd.attention_sites.counts() == (2, 0, None)
        sd.training_config.sharding = ShardingSpec(axes={"data": 2})
        sd.fit(it, epochs=1)
        assert sd.attention_sites.counts() == (
            0, 2, "traced for a mesh of 2 devices")


class TestRematScope:
    def _mlp(self, remat):
        from deeplearning4j_tpu.autodiff import SameDiff
        rng = np.random.default_rng(3)
        sd = SameDiff()
        x = sd.placeholder("x", shape=(4, 8))
        cur, n_in = x, 8
        for i in range(3):
            ctx = sd.remat_scope(f"blk{i}") if remat else _null()
            with ctx:
                w = sd.var(f"w{i}", value=rng.standard_normal(
                    (n_in, 8)).astype(np.float32) * 0.3)
                cur = sd.nn.relu(cur.mmul(w), name=f"h{i}")
        loss = sd.invoke("reduce_sum", [cur.mul(cur)], name="loss")
        sd.set_loss_variables([loss])
        return sd

    def test_grads_identical_with_and_without_remat(self):
        x = np.random.default_rng(0).standard_normal((4, 8)).astype(np.float32)
        g_plain = self._mlp(False).calculate_gradients({"x": x})
        g_remat = self._mlp(True).calculate_gradients({"x": x})
        assert set(g_plain) == set(g_remat)
        for n in g_plain:
            np.testing.assert_allclose(np.asarray(g_plain[n].data),
                                       np.asarray(g_remat[n].data),
                                       rtol=1e-6, atol=1e-6,
                                       err_msg=n)

    def test_forward_identical(self):
        x = np.random.default_rng(0).standard_normal((4, 8)).astype(np.float32)
        o1 = self._mlp(False).output({"x": x}, outputs=["loss"])
        o2 = self._mlp(True).output({"x": x}, outputs=["loss"])
        np.testing.assert_allclose(float(o1["loss"].data),
                                   float(o2["loss"].data), rtol=1e-6)

    def test_group_serde_roundtrip(self, tmp_path):
        sd = self._mlp(True)
        groups = [n.group for n in sd.ops()]
        assert any(g is not None for g in groups)
        p = tmp_path / "remat.sdz"
        sd.save(str(p))
        from deeplearning4j_tpu.autodiff import SameDiff
        sd2 = SameDiff.load(str(p))
        assert [n.group for n in sd2.ops()] == groups
        x = np.random.default_rng(0).standard_normal((4, 8)).astype(np.float32)
        np.testing.assert_allclose(
            float(sd.output({"x": x}, outputs=["loss"])["loss"].data),
            float(sd2.output({"x": x}, outputs=["loss"])["loss"].data),
            rtol=1e-6)

    def test_remat_with_random_op_deterministic_per_trace(self):
        """Dropout inside a remat scope: forward and recomputed-backward
        must see the SAME mask (jax.checkpoint replays the fold_in key)."""
        from deeplearning4j_tpu.autodiff import SameDiff
        rng = np.random.default_rng(1)
        sd = SameDiff()
        x = sd.placeholder("x", shape=(32, 16))
        with sd.remat_scope("blk"):
            w = sd.var("w", value=rng.standard_normal(
                (16, 16)).astype(np.float32) * 0.3)
            h = sd.invoke("dropout", [x.mmul(w)], {"p": 0.5}, name="drop")
        loss = sd.invoke("reduce_sum", [h.mul(h)], name="loss")
        sd.set_loss_variables([loss])
        xv = rng.standard_normal((32, 16)).astype(np.float32)
        g = sd.calculate_gradients({"x": xv})
        assert np.isfinite(np.asarray(g["w"].data)).all()


class TestGPT:
    def test_tiny_overfits(self):
        from deeplearning4j_tpu.autodiff import TrainingConfig
        from deeplearning4j_tpu.dataset import DeviceCachedIterator
        from deeplearning4j_tpu.learning.updaters import Adam
        from deeplearning4j_tpu.zoo.gpt import GPT_TINY, build_gpt

        sd = build_gpt(GPT_TINY, batch=4, seq_len=16)
        sd.training_config = TrainingConfig(
            updater=Adam(1e-3),
            data_set_feature_mapping=["input_ids"],
            data_set_label_mapping=["targets"])
        rng = np.random.default_rng(0)
        ids = rng.integers(0, GPT_TINY.vocab_size, (8, 16)).astype(np.int32)
        tgt = rng.integers(0, GPT_TINY.vocab_size, (8, 16)).astype(np.int32)
        it = DeviceCachedIterator([ids], [tgt], batch_size=4)
        h = sd.fit(it, epochs=120)
        assert h.loss_curve.losses[-1] < h.loss_curve.losses[0] * 0.2

    def test_logits_shape_and_causality(self):
        """Changing a future token must not change past logits (the
        causal-mask end-to-end check)."""
        from deeplearning4j_tpu.zoo.gpt import GPT_TINY, build_gpt
        sd = build_gpt(GPT_TINY, batch=2, seq_len=8)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, GPT_TINY.vocab_size, (2, 8)).astype(np.int32)
        tgt = np.zeros((2, 8), np.int32)
        base = np.asarray(sd.output({"input_ids": ids, "targets": tgt},
                                    outputs=["logits"])["logits"].data)
        ids2 = ids.copy()
        ids2[:, -1] = (ids2[:, -1] + 1) % GPT_TINY.vocab_size
        pert = np.asarray(sd.output({"input_ids": ids2, "targets": tgt},
                                    outputs=["logits"])["logits"].data)
        np.testing.assert_allclose(base[:, :-1], pert[:, :-1],
                                   rtol=1e-5, atol=1e-5)
        assert base.shape == (2, 8, GPT_TINY.vocab_size)

    def test_weight_tying(self):
        from deeplearning4j_tpu.zoo.gpt import GPT_TINY, build_gpt
        sd = build_gpt(GPT_TINY, batch=2, seq_len=8)
        names = [v.name for v in sd.variables()]
        assert "wte" in names and "lm_head" not in names


def _null():
    import contextlib
    return contextlib.nullcontext()
