"""The fit boundary's staging (autodiff/staging.py, ISSUE 38): a fit's
working copies of the parameters, the state variables and the updater
state are made by ONE compiled program over the whole tree.

- every tier (scanned epoch, fused windows, per step,
  ``MultiLayerNetwork.fit_tbptt``) reports ``stage_programs == 1`` and
  the leaves it copied; the arrays the graph held before a fit are alive
  after it with the values they had (what was donated are the staged
  buffers); a graph that has fitted stages again without compiling;
- two fits give losses, parameters and updater state BIT FOR BIT those
  of the staging that stood before, one eager ``copy`` a leaf, kept here
  as the plain reference; also where the trainable set changed between
  the fits and the state starts anew;
- on a mesh every staged leaf has its source's sharding and the program
  holds no collective.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.autodiff import (SameDiff, ScoreIterationListener,
                                         TrainingConfig)
from deeplearning4j_tpu.autodiff import samediff as samediff_mod
from deeplearning4j_tpu.autodiff import staging
from deeplearning4j_tpu.autodiff import window as window_mod
from deeplearning4j_tpu.compilecache import (COMPILE_STATS,
                                             install_compile_watcher)
from deeplearning4j_tpu.dataset import DeviceCachedIterator
from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.monitor import TRACER, disable_tracing, \
    enable_tracing
from deeplearning4j_tpu.nn import (InputType, LSTMLayer, MultiLayerNetwork,
                                   NeuralNetConfiguration, RnnOutputLayer)
from deeplearning4j_tpu.nn import multilayer as multilayer_mod

install_compile_watcher()

N_IN, N_OUT = 16, 4
TIERS = ("scanned", "windowed", "per_step", "tbptt")


def per_leaf_staging(sd, tc):
    """The staging as it stood before ISSUE 38, the plain reference:
    one eager ``jnp.copy`` a leaf, outside any ``jit``."""
    params = jax.tree_util.tree_map(jnp.copy, sd.trainable_params())
    svars = jax.tree_util.tree_map(jnp.copy, sd.state_vars_map())
    if sd._updater_state is not None and \
            set(sd._updater_state.keys()) == set(params.keys()):
        state = jax.tree_util.tree_map(jnp.copy, sd._updater_state)
    else:
        state = tc.updater.init(params)
    leaves = len(jax.tree_util.tree_leaves((params, svars, state)))
    return params, svars, state, {"stage_programs": leaves,
                                  "stage_leaves": leaves}


@pytest.fixture()
def reference_staging(monkeypatch):
    """Switches every caller to the per-leaf reference."""
    def switch():
        for mod in (samediff_mod, window_mod, multilayer_mod):
            monkeypatch.setattr(mod, "stage_fit_state", per_leaf_staging)
    return switch


def mlp(fused_steps=1, sharding=None):
    """Three parameters and a state variable that follows the hidden
    layer's mean, so that all three trees hold something."""
    rng = np.random.default_rng(0)
    sd = SameDiff()
    x = sd.placeholder("x", shape=(-1, N_IN))
    w0 = sd.var("w0", value=rng.normal(0, 0.1, (N_IN, 8))
                .astype(np.float32))
    b0 = sd.var("b0", value=np.zeros(8, np.float32))
    h = sd.nn.relu(x.mmul(w0).add(b0), name="h")
    seen = sd.state_var("seen", value=np.zeros(8, np.float32))
    sd.update_state(seen, seen.mul(0.5).add(h.mean(0).mul(0.5),
                                            name="seen_next"))
    w1 = sd.var("w1", value=rng.normal(0, 0.1, (8, N_OUT))
                .astype(np.float32))
    logits = h.mmul(w1, name="logits")
    labels = sd.placeholder("labels", shape=(-1, N_OUT))
    sd.loss.softmax_cross_entropy(logits, labels, name="loss")
    sd.set_loss_variables(["loss"])
    tc = (TrainingConfig.builder().updater(Adam(1e-2))
          .data_set_feature_mapping("x").data_set_label_mapping("labels")
          .fused_steps(fused_steps).build())
    tc.sharding = sharding
    sd.training_config = tc
    return sd


def arrays(n=64, seed=1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, N_IN)).astype(np.float32)
    Y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, n)]
    return X, Y


class Fitter:
    """One tier's graph and the call that fits it once more."""

    def __init__(self, tier: str):
        self.tier = tier
        if tier == "tbptt":
            conf = (NeuralNetConfiguration.builder().seed(3)
                    .updater(Adam(1e-2)).list()
                    .layer(LSTMLayer(n_out=8))
                    .layer(RnnOutputLayer(n_out=2, loss_function="MCXENT"))
                    .set_input_type(InputType.recurrent(3, 12)).build())
            self.net = MultiLayerNetwork(conf).init()
            rng = np.random.RandomState(1)
            self.X = rng.randn(16, 12, 3).astype(np.float32)
            y = (np.cumsum(self.X[:, :, 0], axis=1) > 0).astype(int)
            self.Y = np.eye(2, dtype=np.float32)[y]
            self.sd = None          # the TBPTT graph: built by a first fit
            return
        self.sd = mlp(fused_steps=4 if tier == "windowed" else 1)
        X, Y = arrays()
        if tier == "scanned":
            self.data = DeviceCachedIterator(X, Y, batch_size=8)
        else:
            self.data = [(X[i:i + 8], Y[i:i + 8])
                         for i in range(0, len(X), 8)]

    def fit(self):
        if self.tier == "tbptt":
            h = self.net.fit_tbptt(self.X, self.Y, tbptt_length=4,
                                   epochs=2, batch_size=16)
            (self.sd, _), = self.net._tbptt_graphs.values()
            return h
        listeners = [ScoreIterationListener(print_every=10 ** 9,
                                            print_fn=lambda *a: None)] \
            if self.tier == "windowed" else []
        return self.sd.fit(self.data, epochs=2, listeners=listeners)

    def held(self):
        """Everything the graph holds that a fit stages, by name."""
        sd = self.sd
        return (dict(sd.trainable_params()), dict(sd.state_vars_map()),
                sd._updater_state)

    def host(self):
        return jax.tree_util.tree_map(np.asarray, self.held())


def assert_trees_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


# ----------------------------------------------------------------------
# every tier: one program, the graph's arrays live on, nothing recompiled

@pytest.mark.parametrize("tier", TIERS)
def test_a_fit_stages_in_one_program_and_leaves_the_graphs_arrays(tier):
    f = Fitter(tier)
    f.fit()
    stats = f.sd.last_fit_stats
    assert stats["stage_programs"] == 1 and "dispatches_per_epoch" in stats
    # (b) what the graph held before a fit is alive after it, unchanged:
    # the buffers the step donated were the staged ones
    before, before_host = f.held(), f.host()
    assert before[2] is not None
    n_leaves = len(jax.tree_util.tree_leaves(before))
    programs = staging._copy_tree._cache_size()
    f.fit()
    assert not any(a.is_deleted() for a in
                   jax.tree_util.tree_leaves(before))
    assert_trees_equal(jax.tree_util.tree_map(np.asarray, before),
                       before_host)
    after = jax.tree_util.tree_leaves(f.host())
    assert any(not np.array_equal(x, y) for x, y in
               zip(after, jax.tree_util.tree_leaves(before_host)))
    # (a) the counter: parameters, state variables and updater leaves
    stats = f.sd.last_fit_stats
    assert stats["stage_programs"] == 1
    assert stats["stage_leaves"] == n_leaves
    # (c) a third fit compiles nothing at all, and the staging program
    # is the one the graph's FIRST fit built (a fresh updater state goes
    # through it like a kept one)
    mark = COMPILE_STATS.mark()
    f.fit()
    assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
    assert staging._copy_tree._cache_size() == programs


def test_the_first_fit_counts_its_fresh_updater_state_among_the_leaves():
    f = Fitter("scanned")
    assert f.sd._updater_state is None
    f.fit()
    assert f.sd.last_fit_stats["stage_leaves"] == \
        len(jax.tree_util.tree_leaves(f.held())) == 3 + 1 + 2 * 3


def test_the_stage_span_says_how_many_programs_and_leaves():
    f = Fitter("scanned")
    enable_tracing(reset=True)
    try:
        f.fit()
        (span,) = [sp for sp in TRACER.spans() if sp.name == "fit.stage"]
    finally:
        disable_tracing()
    assert span.args == {"programs": 1,
                         "leaves": f.sd.last_fit_stats["stage_leaves"]}


def test_a_failed_fit_leaves_the_graph_as_it_was():
    f = Fitter("per_step")
    f.fit()
    before, before_host = f.held(), f.host()
    X, Y = arrays()
    f.data = [(X[:8], Y[:8]), (X[:8, :3], Y[:8])]      # a batch that fails
    with pytest.raises(Exception):
        f.fit()
    held = f.held()
    assert all(x is y for x, y in zip(jax.tree_util.tree_leaves(held),
                                      jax.tree_util.tree_leaves(before)))
    assert_trees_equal(f.host(), before_host)


# ----------------------------------------------------------------------
# bit for bit the per-leaf staging's

def two_fits(tier, between=None):
    f = Fitter(tier)
    losses = list(f.fit().loss_curve.losses)
    if between is not None:
        between(f.sd)
    losses += f.fit().loss_curve.losses
    return losses, f.host(), f.sd.last_fit_stats


@pytest.mark.parametrize("tier", TIERS)
def test_two_fits_equal_the_per_leaf_stagings_bit_for_bit(
        tier, reference_staging):
    losses, held, stats = two_fits(tier)
    reference_staging()
    ref_losses, ref_held, ref_stats = two_fits(tier)
    assert ref_stats["stage_programs"] == stats["stage_leaves"] > 1
    assert losses == ref_losses and np.isfinite(losses).all()
    assert_trees_equal(held, ref_held)


@pytest.mark.parametrize("tier", ("scanned", "windowed", "per_step"))
def test_a_changed_trainable_set_starts_the_state_anew_as_before(
        tier, reference_staging):
    def freeze(sd):
        sd.convert_to_constant(sd._vars["b0"])

    losses, held, stats = two_fits(tier, between=freeze)
    assert set(held[0]) == set(held[2]) == {"w0", "w1"}
    assert stats["stage_leaves"] == 2 + 1 + 2 * 2
    reference_staging()
    ref_losses, ref_held, _ = two_fits(tier, between=freeze)
    assert losses == ref_losses
    assert_trees_equal(held, ref_held)


# ----------------------------------------------------------------------
# a mesh

def tensor_parallel():
    """The 2 x 2 mesh the parallel tests use, the graph's kernels split
    over ``model`` (column, row) and the first bias with its kernel."""
    from deeplearning4j_tpu.parallel import ShardingRule, ShardingSpec
    if len(jax.devices()) < 4:
        pytest.skip("needs the 4-device CPU mesh of tests/conftest.py")
    return ShardingSpec(axes={"data": 2, "model": 2},
                        rules=[ShardingRule(r"^w0$", (None, "model")),
                               ShardingRule(r"^b0$", ("model",)),
                               ShardingRule(r"^w1$", ("model", None))])


def test_on_a_mesh_every_staged_leaf_has_its_sources_sharding():
    from deeplearning4j_tpu.parallel.trainer import (resolve_strategy,
                                                     shard_model)
    sd = mlp(sharding=tensor_parallel())
    sd.fit(DeviceCachedIterator(*arrays(), batch_size=8), epochs=1)
    shard_model(sd, resolve_strategy(sd, sd.training_config.sharding))
    source = (sd.trainable_params(), sd.state_vars_map(),
              sd._updater_state)
    leaves = jax.tree_util.tree_leaves(source)
    assert any(len({s.device for s in a.addressable_shards}) == 4 and
               not a.sharding.is_fully_replicated for a in leaves)
    *staged, stats = staging.stage_fit_state(sd, sd.training_config)
    staged_leaves = jax.tree_util.tree_leaves(tuple(staged))
    assert stats == {"stage_programs": 1, "stage_leaves": len(leaves)}
    for src, got in zip(leaves, staged_leaves):
        assert got.sharding.is_equivalent_to(src.sharding, src.ndim)
        assert got.committed == src.committed
        assert [s.data.unsafe_buffer_pointer() for s in
                got.addressable_shards] != \
            [s.data.unsafe_buffer_pointer() for s in src.addressable_shards]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(src))
    # nothing is resharded or gathered to make the copies
    text = staging._copy_tree.lower(source).compile().as_text()
    assert text.count(" copy(") >= len(leaves)
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text


def test_a_sharded_fit_equals_the_per_leaf_stagings(reference_staging):
    def run():
        sd = mlp(sharding=tensor_parallel())
        it = DeviceCachedIterator(*arrays(), batch_size=8)
        losses = [sd.fit(it, epochs=2).loss_curve.losses for _ in range(2)]
        held = (sd.trainable_params(), sd.state_vars_map(),
                sd._updater_state)
        return losses, held

    losses, held = run()
    reference_staging()
    ref_losses, ref_held = run()
    assert losses == ref_losses
    for got, ref in zip(jax.tree_util.tree_leaves(held),
                        jax.tree_util.tree_leaves(ref_held)):
        assert got.sharding.is_equivalent_to(ref.sharding, ref.ndim)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ----------------------------------------------------------------------
# the copy itself

def test_a_staged_buffer_is_never_its_source_forwarded():
    tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": jnp.float32(2.0),
            "none": None}
    staged = staging._copy_tree(tree)
    assert staged["none"] is None
    for k in ("a", "b"):
        assert staged[k].unsafe_buffer_pointer() != \
            tree[k].unsafe_buffer_pointer()
        assert staged[k].dtype == tree[k].dtype
        assert staged[k].weak_type == jnp.copy(tree[k]).weak_type
    # donating the copies to a next program leaves the sources alive
    bump = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x + 1, t),
                   donate_argnums=0)
    bump({k: staged[k] for k in ("a", "b")})
    assert staged["a"].is_deleted() and not tree["a"].is_deleted()
    np.testing.assert_array_equal(np.asarray(tree["a"]),
                                  np.arange(12.0).reshape(3, 4))
