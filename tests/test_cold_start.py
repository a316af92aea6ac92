"""compilecache/ tests: persistent compilation cache wiring, AOT
precompilation (train + serve), and compile observability
(docs/cold_start.md).

The acceptance bars from the subsystem issue:

- cache-hit regression: two fresh SameDiff graphs of the same model
  sharing a cache dir — the second compiles NOTHING (cache-miss count
  0) and its compile spans are marked ``cache_hit``;
- AOT: ``precompile()`` then ``fit`` triggers no new backend compile
  (all window shapes incl. pow2 tails prebuilt), and a warmed
  ``ParallelInference`` serves mixed-size traffic with a zero
  ``compiles`` counter;
- bit-exactness: precompiled and lazily-compiled paths produce
  identical parameters, losses and serving outputs.
"""
import os

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.autodiff import (MixedPrecision, SameDiff,
                                         ScoreIterationListener,
                                         TrainingConfig)
from deeplearning4j_tpu.compilecache import (COMPILE_STATS, AOTDispatch,
                                             install_compile_watcher,
                                             ph_shape_sig)
from deeplearning4j_tpu.environment import environment
from deeplearning4j_tpu.learning.updaters import Adam, Sgd
from deeplearning4j_tpu.monitor import TRACER, disable_tracing, \
    enable_tracing

install_compile_watcher()

N_IN, N_OUT = 16, 4


@pytest.fixture()
def cache_env(tmp_path):
    """A live persistent cache in a tmp dir, wired through Environment
    (exercising the programmatic-set path end to end), torn back down
    after the test."""
    env = environment()
    env.set("compilation_cache_dir", str(tmp_path / "xla_cache"))
    env.set("compilation_cache_min_entry_size", -1)
    env.set("compilation_cache_min_compile_time", 0.0)
    try:
        yield str(tmp_path / "xla_cache")
    finally:
        env.reset("compilation_cache_dir")
        env.reset("compilation_cache_min_entry_size")
        env.reset("compilation_cache_min_compile_time")


def _mlp(seed=0, fused_steps=1, accum_steps=1, sentinel=False, lr=1e-2):
    rng = np.random.default_rng(seed)
    sd = SameDiff()
    x = sd.placeholder("x", shape=(-1, N_IN))
    w0 = sd.var("w0", value=rng.normal(0, 0.1, (N_IN, 8))
                .astype(np.float32))
    b0 = sd.var("b0", value=np.zeros(8, np.float32))
    h = sd.nn.relu(x.mmul(w0).add(b0), name="h")
    w1 = sd.var("w1", value=rng.normal(0, 0.1, (8, N_OUT))
                .astype(np.float32))
    logits = h.mmul(w1, name="logits")
    labels = sd.placeholder("labels", shape=(-1, N_OUT))
    sd.loss.softmax_cross_entropy(logits, labels, name="loss")
    sd.set_loss_variables(["loss"])
    sd.training_config = (TrainingConfig.builder().updater(Adam(lr))
                          .data_set_feature_mapping("x")
                          .data_set_label_mapping("labels")
                          .fused_steps(fused_steps)
                          .accum_steps(accum_steps)
                          .sentinel(sentinel).build())
    return sd


def _data(n=112, batch=8, seed=1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, N_IN)).astype(np.float32)
    Y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, n)]
    return [(X[i:i + batch], Y[i:i + batch]) for i in range(0, n, batch)]


def _quiet_listener():
    return ScoreIterationListener(print_every=10 ** 9,
                                  print_fn=lambda *a: None)


def _params(sd):
    return {n: np.asarray(a) for n, a in sd.trainable_params().items()}


# ---------------------------------------------------------------------------
# Environment wiring

def test_cache_dir_set_applies_live_and_reset_undoes(tmp_path):
    env = environment()
    d = str(tmp_path / "cc")
    env.set("compilation_cache_dir", d)
    try:
        assert jax.config.jax_compilation_cache_dir == d
        assert env.compilation_cache_dir() == d
    finally:
        env.reset("compilation_cache_dir")
    # reset() re-applies the resolved placement, never "no cache"
    assert jax.config.jax_compilation_cache_dir == \
        env.compilation_cache_dir() != d


def test_cache_dir_placed_from_outside_or_fixed_in_checkout(
        monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR set -> the cache lives there and no
    code path (fit, precompile) moves it; unset -> the one fixed
    directory in the checkout, next to pyproject.toml."""
    env = environment()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(repo, ".jax_cache")
    assert os.path.exists(os.path.join(repo, "pyproject.toml"))
    assert env.compilation_cache_dir() == fixed
    try:
        outside = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        sd = _mlp()
        sd.fit(_data(n=16), epochs=1)
        assert jax.config.jax_compilation_cache_dir == outside
        sd.precompile(batch_size=8)
        assert jax.config.jax_compilation_cache_dir == outside
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == outside
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        _mlp().fit(_data(n=16), epochs=1)
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        env.apply_compilation_cache()


def test_cache_admission_knobs_apply_live():
    env = environment()
    env.set("compilation_cache_min_entry_size", -1)
    env.set("compilation_cache_min_compile_time", 0.25)
    try:
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
        assert jax.config.jax_persistent_cache_min_compile_time_secs \
            == 0.25
    finally:
        env.reset("compilation_cache_min_entry_size")
        env.reset("compilation_cache_min_compile_time")
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0


def test_compile_stats_counts_backend_compiles():
    import jax.numpy as jnp
    mark = COMPILE_STATS.mark()

    @jax.jit
    def fresh(v):
        return jnp.sin(v) * jnp.float32(ord("q"))   # unique-ish program

    fresh(jnp.arange(7, dtype=jnp.float32)).block_until_ready()
    delta = COMPILE_STATS.delta(mark)
    assert delta["backend_compiles"] >= 1
    assert delta["backend_compile_seconds"] > 0.0


# ---------------------------------------------------------------------------
# cache-hit regression: a "restarted" graph recompiles nothing

def test_cache_hit_regression_second_graph_compiles_nothing(cache_env):
    data = _data()
    sd1 = _mlp(fused_steps=4)
    sd1.fit(data, epochs=1, listeners=[_quiet_listener()])

    # a fresh graph of the SAME model = a simulated process restart
    # (fresh jit closures, no in-process executable reuse)
    sd2 = _mlp(fused_steps=4)
    enable_tracing(reset=True)
    mark = COMPILE_STATS.mark()
    try:
        sd2.fit(data, epochs=1, listeners=[_quiet_listener()])
    finally:
        disable_tracing()
    delta = COMPILE_STATS.delta(mark)
    assert delta["cache_misses"] == 0, \
        f"warm restart recompiled: {delta}"
    assert delta["cache_hits"] >= 1
    hits = [s for s in TRACER.spans()
            if s.name == "compile.backend" and s.args.get("cache_hit")]
    assert hits, "no compile.backend span marked cache_hit"


# ---------------------------------------------------------------------------
# AOT precompile: train tiers

def test_precompile_then_windowed_fit_no_new_compiles():
    data = _data()                      # 14 batches: windows 4,4,4 + 2
    sd_warm = _mlp(fused_steps=4)       # warms the eager helper programs
    sd_warm.fit(data, epochs=1, listeners=[_quiet_listener()])

    sd = _mlp(fused_steps=4)
    info = sd.precompile(batch_size=8)
    # window K=4 plus pow2 tail buckets {2, 1} = log2(K)+1 shapes
    assert info["compiled"] == 3
    disp = sd.make_train_window(accum_steps=1)
    assert isinstance(disp, AOTDispatch) and len(disp.aot) == 3
    mark = COMPILE_STATS.mark()
    sd.fit(data, epochs=1, listeners=[_quiet_listener()])
    delta = COMPILE_STATS.delta(mark)
    assert delta["backend_compiles"] == 0, \
        f"fit compiled after precompile: {delta}"
    assert sd.last_fit_stats["window_compiles"] == 0


def test_precompile_non_pow2_window_covers_all_tail_buckets():
    """fused_steps=6, 11 batches → windows 6, then tail 5 = pow2
    buckets [4, 1]: k=4 is NOT in {6} ∪ halvings of 6, so the bucket
    set must be every pow2 ≤ K-1 (regression: the halving-only set
    missed it and the first tail window compiled lazily)."""
    data = _data(n=88, batch=8)         # 11 batches
    warm = _mlp(fused_steps=6)
    warm.fit(data, epochs=1, listeners=[_quiet_listener()])

    sd = _mlp(fused_steps=6)
    info = sd.precompile(batch_size=8)
    assert info["compiled"] == 4        # {6, 4, 2, 1}
    mark = COMPILE_STATS.mark()
    sd.fit(data, epochs=1, listeners=[_quiet_listener()])
    assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
    assert sd.last_fit_stats["window_compiles"] == 0
    assert sorted(sd.last_fit_stats["window_sizes"]) == [1, 4, 6]


def test_precompile_bit_exact_vs_lazy():
    data = _data()
    lazy = _mlp(fused_steps=4)
    h_lazy = lazy.fit(data, epochs=2, listeners=[_quiet_listener()])
    pre = _mlp(fused_steps=4)
    pre.precompile(batch_size=8)
    h_pre = pre.fit(data, epochs=2, listeners=[_quiet_listener()])
    pl, pp = _params(lazy), _params(pre)
    assert all(np.array_equal(pl[n], pp[n]) for n in pl)
    assert h_lazy.loss_curve.losses == h_pre.loss_curve.losses


def test_precompile_per_step_tier_no_new_compiles():
    data = _data(n=40, batch=8)
    warm = _mlp()
    # warms the eager helper programs (same epochs: the end-of-fit
    # deferred-mean stack shape depends on the epoch count)
    warm.fit(data, epochs=2)
    sd = _mlp()
    info = sd.precompile(batch_size=8)
    assert info["compiled"] == 1        # the per-step train fn
    mark = COMPILE_STATS.mark()
    sd.fit(data, epochs=2)
    assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0


def test_precompile_scanned_epoch_tier():
    from deeplearning4j_tpu.dataset import DeviceCachedIterator
    rng = np.random.default_rng(3)
    n, batch = 32, 8
    X = rng.normal(size=(n, N_IN)).astype(np.float32)
    Y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, n)]
    it = DeviceCachedIterator(X, Y, batch_size=batch)

    lazy = _mlp()
    h_lazy = lazy.fit(it, epochs=2)

    pre = _mlp()
    info = pre.precompile(batch_size=batch, epoch_steps=n // batch)
    assert info["compiled"] >= 2        # step fn + scanned-epoch fn
    mark = COMPILE_STATS.mark()
    h_pre = pre.fit(it, epochs=2)
    assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
    pl, pp = _params(lazy), _params(pre)
    assert all(np.array_equal(pl[n_], pp[n_]) for n_ in pl)
    assert h_lazy.loss_curve.losses == h_pre.loss_curve.losses


def test_precompile_unpredicted_shape_falls_back_to_lazy():
    sd = _mlp(fused_steps=4)
    sd.precompile(batch_size=8)
    # a ragged final BATCH (3 rows) nobody precompiled: must still train
    data = _data(n=35, batch=8)         # 4 full batches + one of 3 rows
    h = sd.fit(data, epochs=1, listeners=[_quiet_listener()])
    assert len(h.loss_curve.losses) == 1
    assert np.isfinite(h.loss_curve.losses[0])


def test_aot_dispatch_predicted_shape_rejection_is_not_absorbed():
    # a jax Compiled raises ValueError when called with mesh-committed
    # inputs against an executable lowered from unsharded specs. That
    # is a defect at the lowering site; re-dispatching through lazy jit
    # would hide it as a compile under the first request, so it raises
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    fn = jax.jit(lambda ph: {k: v * 2.0 for k, v in ph.items()})
    disp = AOTDispatch(fn, ph_arg=0)
    spec = {"x": jax.ShapeDtypeStruct((8, 4), jnp.float32)}
    disp.aot[ph_shape_sig(spec)] = disp.lower(spec).compile()
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    sharded = jax.device_put(
        x, NamedSharding(mesh, PartitionSpec("data", None)))
    with pytest.raises(ValueError, match="shardings"):
        disp({"x": sharded})
    # a shape nobody predicted still takes the lazy path
    out = disp({"x": x[:3]})
    assert np.array_equal(np.asarray(out["x"]), x[:3] * 2)


def test_resharding_drops_stale_aot_executables():
    """Executables are lowered for one placement; moving the model to a
    mesh must forget them (the lazy jit re-specialises), or the next
    dispatch of a precompiled shape is rejected."""
    from deeplearning4j_tpu.parallel import DeviceMesh, data_parallel
    from deeplearning4j_tpu.parallel.trainer import shard_model
    sd = _mlp(fused_steps=2)
    sd.precompile(batch_size=8)
    disp = sd.make_train_window(accum_steps=1)
    assert disp.aot
    strategy = data_parallel(DeviceMesh.create(devices=jax.devices()[:2]))
    shard_model(sd, strategy)
    assert not disp.aot
    sd.precompile(batch_size=8)
    shard_model(sd, strategy)           # same placement: nothing moved
    assert disp.aot


@pytest.mark.parametrize("fused_steps", [1, 4])
def test_precompile_under_sharding_lowers_for_the_mesh(fused_steps):
    """TrainingConfig.sharding: fit() places the model on the mesh and
    feeds sharded batches, so precompile() must lower for exactly those
    placements — then the sharded fit dispatches the prebuilt
    executables and compiles nothing (bare-shape lowering used to be
    rejected and silently recompiled under the first window)."""
    from deeplearning4j_tpu.parallel import ShardingSpec

    def sharded(seed=0):
        sd = _mlp(seed=seed, fused_steps=fused_steps)
        sd.training_config.sharding = ShardingSpec(
            axes={"data": 2, "model": 2}, preset="tensor_parallel")
        return sd

    data = _data(n=96, batch=8)         # 12 batches: windows 4,4,4
    listeners = [_quiet_listener()] if fused_steps > 1 else []
    sharded().fit(data, epochs=2, listeners=listeners)   # eager helpers
    sd = sharded()
    info = sd.precompile(batch_size=8)
    assert info["compiled"] >= 1
    w0 = sd.trainable_params()["w0"]
    assert len({s.device for s in w0.addressable_shards}) == 4
    mark = COMPILE_STATS.mark()
    sd.fit(data, epochs=2, listeners=listeners)
    assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
    disp = sd.make_train_window(accum_steps=1) if fused_steps > 1 \
        else sd.make_train_step()
    assert disp.aot, "the sharded fit dropped the precompiled programs"


def test_precompile_after_parallel_trainer_placement():
    """No TrainingConfig.sharding: the placement comes from a
    ParallelTrainer. precompile() after it lowers for that placement,
    and the trainer's fit dispatches the executables as they are."""
    from deeplearning4j_tpu.parallel import (DeviceMesh, ParallelTrainer,
                                             data_parallel)
    data = _data(n=96, batch=8)
    mesh = DeviceMesh.create(devices=jax.devices()[:4])

    def trainer():
        return ParallelTrainer(_mlp(fused_steps=4), data_parallel(mesh))

    trainer().fit(data, epochs=1, listeners=[_quiet_listener()])
    t = trainer()
    t.shard_params()
    assert t.sd.precompile(batch_size=8)["compiled"] == 3
    mark = COMPILE_STATS.mark()
    t.fit(data, epochs=1, listeners=[_quiet_listener()])
    assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
    assert t.sd.make_train_window(accum_steps=1).aot


def test_serving_warmup_of_a_mesh_placed_model_zero_compiles():
    """shard_params, THEN warm, then serve: precompile_output lowers
    for the placement the arrays carry, so the first request runs the
    warmed executable (a bare-shape lowering rejected the mesh-committed
    parameters — and nothing absorbs that any more)."""
    from deeplearning4j_tpu.parallel import (DeviceMesh, ParallelTrainer,
                                             data_parallel)
    from deeplearning4j_tpu.serving import InferenceMode, ParallelInference
    net = _net()
    x = np.random.default_rng(0).normal(size=(3, N_IN)).astype(np.float32)
    want = np.asarray(net.output(x).to_numpy())
    mesh = DeviceMesh.create(devices=jax.devices()[:4])
    ParallelTrainer(net, data_parallel(mesh)).shard_params()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED,
                           max_batch_size=8, max_delay_ms=1.0,
                           warmup_buckets=True)
    try:
        mark = COMPILE_STATS.mark()
        got = np.asarray(pi.output(x))
        assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
        assert pi.metrics.counters["compiles"] == 0
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    finally:
        pi.shutdown()


def test_precompile_output_after_a_sharded_fit():
    """A fit under TrainingConfig.sharding leaves mesh-committed
    parameters behind; precompile_output + output on them compiles
    once, at precompile."""
    from deeplearning4j_tpu.parallel import ShardingSpec
    sd = _mlp()
    sd.training_config.sharding = ShardingSpec(
        axes={"data": 2, "model": 2}, preset="tensor_parallel")
    sd.fit(_data(n=32), epochs=1)
    x = _data(n=4, batch=4)[0][0]
    lazy = np.asarray(sd.output({"x": x}, ["logits"])["logits"].to_numpy())
    sd.precompile_output({"x": (8, N_IN)}, outputs=["logits"])
    mark = COMPILE_STATS.mark()
    x8 = np.concatenate([x, x])
    got = np.asarray(sd.output({"x": x8}, ["logits"])["logits"].to_numpy())
    assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
    np.testing.assert_allclose(got[:4], lazy, rtol=1e-5, atol=1e-6)


def test_mesh_inference_drops_executables_of_the_old_placement():
    """parallel.ParallelInference replicates an unplaced model onto its
    mesh; an output executable warmed before that move is forgotten
    (the lazy jit re-specialises), not dispatched and rejected."""
    from deeplearning4j_tpu.parallel import DeviceMesh, ParallelInference
    net = _net()
    x = np.random.default_rng(0).normal(size=(8, N_IN)).astype(np.float32)
    want = np.asarray(net.output(x).to_numpy())
    net._sd_infer.precompile_output({"input": (8, N_IN)}, ["output"])
    pi = ParallelInference(
        net, mesh=DeviceMesh.create(devices=jax.devices()[:4]))
    np.testing.assert_allclose(np.asarray(pi.output(x).to_numpy()), want,
                               rtol=1e-5, atol=1e-6)


def test_precompile_needs_resolvable_batch_dims():
    sd = _mlp(fused_steps=2)
    with pytest.raises(ValueError, match="batch"):
        sd.precompile()                 # -1 dims and no batch_size


def test_graph_mutation_invalidates_precompiled_programs():
    sd = _mlp(fused_steps=2)
    sd.precompile(batch_size=8)
    assert len(sd.make_train_window(accum_steps=1).aot) > 0
    sd.training_config = sd.training_config     # reassign = mutation
    assert len(sd.make_train_window(accum_steps=1).aot) == 0


# ---------------------------------------------------------------------------
# AOT precompile: serving warmup

def _net(seed=7):
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration,
                                       OutputLayer)
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(1e-3)).list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=N_OUT, loss_function="MCXENT"))
            .set_input_type(InputType.feed_forward(N_IN))
            .build())
    return MultiLayerNetwork(conf).init()


def test_serving_warmup_mixed_traffic_zero_compiles():
    from deeplearning4j_tpu.serving import InferenceMode, ParallelInference
    net = _net()
    pi = ParallelInference(net, mode=InferenceMode.BATCHED,
                           max_batch_size=8, max_delay_ms=1.0,
                           warmup_buckets=True)
    try:
        assert pi.warmup_report["buckets"] == [1, 2, 4, 8]
        assert pi.metrics.counters["warmup_compiles"] == 4
        rng = np.random.default_rng(0)
        for rows in (1, 3, 5, 8, 2, 7, 4, 6):
            x = rng.normal(size=(rows, N_IN)).astype(np.float32)
            got = np.asarray(pi.output(x))
            want = np.asarray(net.output(x).to_numpy())
            assert np.array_equal(got, want)    # bit-identical to lazy
        assert pi.metrics.counters["compiles"] == 0
        assert "(4 prewarmed)" in pi.metrics.stats()
    finally:
        pi.shutdown()


def test_serving_warmup_explicit_buckets_inplace_mode():
    from deeplearning4j_tpu.serving import InferenceMode, ParallelInference
    net = _net()
    pi = ParallelInference(net, mode=InferenceMode.INPLACE,
                           max_batch_size=16, warmup_buckets=(2, 16))
    assert pi.warmup_report["buckets"] == [2, 16]
    rng = np.random.default_rng(1)
    for rows in (2, 16):
        x = rng.normal(size=(rows, N_IN)).astype(np.float32)
        assert np.array_equal(np.asarray(pi.output(x)),
                              np.asarray(net.output(x).to_numpy()))
    assert pi.metrics.counters["compiles"] == 0
    pi.shutdown()


def test_precompile_output_idempotent():
    sd = _mlp()
    c1 = sd.precompile_output({"x": (4, N_IN)}, outputs=["logits"])
    c2 = sd.precompile_output({"x": (4, N_IN)}, outputs=["logits"])
    assert c1 is c2


# ---------------------------------------------------------------------------
# window executor satellite: sharding specs built once, not per window

def test_window_sharding_spec_construction_hoisted():
    calls = []
    spec = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    class It:
        def window_sharding(self, ndim):
            calls.append(ndim)
            return spec

        def __iter__(self):
            return iter(_data(n=96, batch=8))   # 12 batches → 3 windows

        def reset(self):
            pass

    sd = _mlp(fused_steps=4)
    sd.fit(It(), epochs=2, listeners=[_quiet_listener()])
    # one construction per distinct rank (x is rank 2, labels rank 2 →
    # stacked rank 3), not windows × tensors × epochs
    assert len(calls) == 1, f"window_sharding called {len(calls)} times"


# ---------------------------------------------------------------------------
# faults rail: a retraced retry re-precompiles during recovery

def test_rollback_reprecompiles_after_lr_rescale(tmp_path):
    from deeplearning4j_tpu.checkpoint import CheckpointManager
    from deeplearning4j_tpu.faults import FaultTolerantFit, RetryPolicy
    sd = _mlp(fused_steps=2)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    ftf = FaultTolerantFit(sd, mgr,
                           policy=RetryPolicy(lr_rescale=0.5,
                                              backoff_base=0.0))
    sd.precompile(batch_size=8)         # after FTF armed the sentinel
    mgr.save(0, model=sd, blocking=True)
    ftf._rollback(RuntimeError("injected"))
    assert any(e["event"] == "precompile" for e in ftf.events)
    # the retraced (rescaled-LR) dispatcher is AOT-warm again
    assert len(sd.make_train_window(accum_steps=1, sentinel=True).aot) > 0
    mgr.close() if hasattr(mgr, "close") else None


# ---------------------------------------------------------------------------
# observability plumbing

def test_compile_record_folds_and_renders():
    from deeplearning4j_tpu.monitor import MetricsRegistry
    from deeplearning4j_tpu.ui.report import render_report
    from deeplearning4j_tpu.ui.stats import StatsStorage
    storage = StatsStorage()
    rec = COMPILE_STATS.publish(storage)
    assert rec["type"] == "compile"
    assert rec["miss_compiles"] == max(
        0, rec["backend_compiles"] - rec["cache_hits"])
    reg = MetricsRegistry()
    reg.fold_storage(storage)
    assert reg.get("compile_backend_compiles_total") == \
        rec["backend_compiles"]
    text = reg.to_prometheus_text()
    assert "dl4j_compile_cache_hits_total" in text
    html = render_report(storage)
    assert "Compilation" in html
    assert "unrendered record types" not in html


def test_monitored_fit_publishes_compile_record():
    """A monitored run surfaces the cache-hit/miss split by itself:
    MonitorListener emits the ``{"type": "compile"}`` record and the
    ``compile_*`` gauges at its epoch cadence — no manual
    ``COMPILE_STATS.publish()`` required."""
    from deeplearning4j_tpu.monitor import MetricsRegistry, MonitorListener
    from deeplearning4j_tpu.ui.stats import StatsStorage
    storage = StatsStorage()
    reg = MetricsRegistry()
    sd = _mlp(fused_steps=4)
    sd.fit(_data(), epochs=1,
           listeners=[MonitorListener(storage, registry=reg),
                      _quiet_listener()])
    recs = storage.of_type("compile")
    assert recs, "monitored fit emitted no compile record"
    snap = COMPILE_STATS.snapshot()
    assert recs[-1]["backend_compiles"] <= snap["backend_compiles"]
    assert reg.get("compile_backend_compiles_total") == \
        recs[-1]["backend_compiles"]


def test_ph_shape_sig_matches_window_accounting():
    import jax.numpy as jnp
    ph = {"b": jnp.zeros((4, 2)), "a": jnp.zeros((4, 3))}
    assert ph_shape_sig(ph) == (("a", (4, 3)), ("b", (4, 2)))


# ---------------------------------------------------------------------------
# the real thing: a fresh-process warm restart

#: what each fresh process runs: the 784-512-256-10 SameDiff MLP fitted
#: one step, then the process's compile accounting and its seconds
_RESTART_PROBE = """
import json, time
t0 = time.perf_counter()
import numpy as np
from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
from deeplearning4j_tpu.compilecache import (COMPILE_STATS,
                                             install_compile_watcher)
from deeplearning4j_tpu.learning.updaters import Adam
install_compile_watcher()
rng = np.random.default_rng(0)
sd = SameDiff()
cur, n_in = sd.placeholder("x", shape=(-1, 784)), 784
for i, h in enumerate((512, 256, 10)):
    w = sd.var(f"w{i}", value=rng.normal(0, .05, (n_in, h)).astype("f4"))
    cur = cur.mmul(w).add(sd.var(f"b{i}", value=np.zeros(h, "f4")))
    cur, n_in = (sd.nn.relu(cur) if h != 10 else cur), h
labels = sd.placeholder("labels", shape=(-1, 10))
sd.loss.softmax_cross_entropy(cur, labels, name="loss")
sd.set_loss_variables(["loss"])
sd.training_config = (TrainingConfig.builder().updater(Adam(1e-3))
                      .data_set_feature_mapping("x")
                      .data_set_label_mapping("labels").build())
X = rng.normal(size=(128, 784)).astype("f4")
sd.fit([(X, np.eye(10, dtype="f4")[rng.integers(0, 10, 128)])], epochs=1)
print(json.dumps({**COMPILE_STATS.snapshot(),
                  "restart_to_first_step_s": time.perf_counter() - t0}))
"""


@pytest.mark.slow
def test_cold_vs_warm_restart_subprocess(tmp_path):
    """A restart in two fresh processes sharing a cache placed from
    outside through $JAX_COMPILATION_CACHE_DIR: the second compiles
    nothing anew."""
    import json
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "restart_cache"),
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    runs = {}
    for phase in ("cold", "warm"):
        proc = subprocess.run(
            [sys.executable, "-c", _RESTART_PROBE],
            capture_output=True, text=True, timeout=600, cwd=repo, env=env)
        assert proc.returncode == 0, proc.stderr[-800:]
        runs[phase] = json.loads(proc.stdout.strip().splitlines()[-1])
    assert runs["cold"]["cache_hits"] == 0
    assert runs["warm"]["cache_hits"] >= 1
    # a warm restart performs ZERO miss compiles — the acceptance bar
    # behind "warm-restart compile time ≈ 0"
    assert runs["warm"]["backend_compiles"] - runs["warm"]["cache_hits"] \
        == 0
    assert runs["warm"]["restart_to_first_step_s"] < \
        runs["cold"]["restart_to_first_step_s"]
