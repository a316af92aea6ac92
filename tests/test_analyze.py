"""analyze/ — pre-compile static analysis (docs/static_analysis.md).

Structure mirrors the acceptance contract:
- a seeded-defect corpus: one deliberately broken graph/config per
  cataloged rule, each caught with the RIGHT rule_id and variable/op
  provenance (and the corpus keys are asserted == the catalog, so a
  new rule without a seeded defect fails here);
- a zero-false-positive sweep over the zoo/bench model families
  (no error- or warn-severity findings on healthy models);
- strict mode raises GraphAnalysisError BEFORE any XLA compile
  (asserted via the compilecache COMPILE_STATS counters);
- integration: fit()/precompile() caching, ParallelInference, the CLI,
  the {"type": "analysis"} record (render + registry fold), and the
  PR-12 satellites (loss f32 accumulators, ShardingSpec.validate).
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analyze import (RULES, AnalysisReport,
                                        GraphAnalysisError,
                                        GraphAnalysisWarning,
                                        analyze_inference,
                                        analyze_training)
from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
from deeplearning4j_tpu.autodiff.training import MixedPrecision
from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.ops import registry as op_registry

rng = np.random.default_rng(0)


def _tc(**kw):
    kw.setdefault("updater", Adam(learning_rate=1e-3))
    kw.setdefault("data_set_feature_mapping", ["x"])
    kw.setdefault("data_set_label_mapping", ["labels"])
    return TrainingConfig(**kw)


def _mlp(sd=None, n_in=20, hidden=8, n_out=4, w0_rows=None,
         batch=(-1,)):
    """A small healthy MLP graph; ``w0_rows`` seeds a shape defect."""
    sd = sd or SameDiff()
    x = sd.placeholder("x", shape=tuple(batch) + (n_in,))
    w0 = sd.var("w0", value=rng.normal(
        0, 0.1, (w0_rows or n_in, hidden)).astype(np.float32))
    b0 = sd.var("b0", value=np.zeros(hidden, np.float32))
    h = sd.nn.relu(x.mmul(w0, name="h0_mm").add(b0), name="h0")
    w1 = sd.var("w1", value=rng.normal(
        0, 0.1, (hidden, n_out)).astype(np.float32))
    logits = h.mmul(w1, name="logits")
    labels = sd.placeholder("labels", shape=tuple(batch) + (n_out,))
    sd.loss.softmax_cross_entropy(logits, labels, name="loss")
    sd.set_loss_variables(["loss"])
    sd.training_config = _tc()
    return sd


class _lowp_loss_op:
    """Context manager registering a deliberately-broken loss op whose
    scalar accumulates in the input dtype (the defect the ops/loss.py
    satellite removed from the real loss ops) — and UNREGISTERING it
    after, so the op-coverage ledger (test_op_ledger) never sees a
    test-only op in the global registry."""

    NAME = "_test_lowp_accum_loss"

    def __enter__(self):
        if not op_registry.has_op(self.NAME):
            @op_registry.op(self.NAME, "loss")
            def _test_lowp_accum_loss(predictions, labels):
                return jnp.sum(jnp.abs(predictions - labels))
        return self.NAME

    def __exit__(self, *exc):
        op_registry._REGISTRY.pop(self.NAME, None)


# ---------------------------------------------------------------------------
# seeded-defect corpus: rule_id -> builder returning
# (report, expected-subject substring, expected-message substring)

def _seed_shape_mismatch():
    sd = _mlp(w0_rows=13)
    return analyze_training(sd), "h0_mm", "cannot compose"


def _seed_undefined_input():
    sd = _mlp()
    sd._ops["logits"].inputs[0] = "ghost"   # serde-corruption analogue
    return analyze_training(sd), "logits", "ghost"


def _seed_invalid_loss():
    sd = _mlp()
    sd.set_loss_variables(["not_a_var"])
    return analyze_training(sd), "not_a_var", "does not exist"


def _seed_unused_placeholder():
    sd = _mlp()
    sd.placeholder("extra_feature", shape=(-1, 3))
    return analyze_training(sd), "extra_feature", "not consumed"


def _seed_name_shadowing():
    sd = SameDiff()
    a = sd.placeholder("x", shape=(-1, 4))
    b = sd.placeholder("x", shape=(-1, 4))      # auto-renamed to x_1
    sd.loss.mean_sqerr_loss(a, b, name="loss")
    sd.set_loss_variables(["loss"])
    return analyze_training(sd), "x_1", "auto-renamed"


def _seed_dead_op():
    sd = _mlp()
    # a recorded penalty the user forgot to add to loss_variables
    sd.loss.l2_loss(sd.get_variable("w0"), name="l2_penalty")
    return analyze_training(sd), "l2_penalty", "trains nothing"


def _seed_state_alias():
    sd = _mlp()
    sv = sd.state_var("running_mean", np.zeros(8, np.float32))
    sd._state_updates[sv.name] = "missing_src"   # update_state analogue
    return analyze_training(sd), "running_mean", "does not exist"


def _seed_lowp_loss_accum():
    with _lowp_loss_op() as op_name:
        sd = SameDiff()
        p = sd.placeholder("x", shape=(-1, 16), dtype="bfloat16")
        l = sd.placeholder("labels", shape=(-1, 16), dtype="bfloat16")
        sd.invoke(op_name, [p, l], name="loss")
        sd.set_loss_variables(["loss"])
        return analyze_training(sd), "loss", "scalar"


def _seed_lowp_reduction():
    sd = SameDiff()
    x = sd.placeholder("x", shape=(4, 8192), dtype="bfloat16")
    s = x.sum(dims=(1,), name="big_sum")
    s.mean(name="loss")
    sd.set_loss_variables(["loss"])
    return analyze_training(sd), "big_sum", "8192"


def _seed_unguarded_log():
    sd = SameDiff()
    x = sd.placeholder("x", shape=(-1, 4))
    x.log(name="raw_log").mean(name="loss")
    sd.set_loss_variables(["loss"])
    return analyze_training(sd), "raw_log", "positivity"


def _seed_unguarded_div():
    sd = SameDiff()
    x = sd.placeholder("x", shape=(-1, 4))
    d = sd.placeholder("denom", shape=(-1, 4))
    x.div(d, name="raw_div").mean(name="loss")
    sd.set_loss_variables(["loss"])
    return analyze_training(sd), "raw_div", "zero guard"


def _seed_ce_tail_f32():
    sd = _mlp()
    sd.training_config = _tc(mixed_precision=MixedPrecision())
    return analyze_training(sd), "loss", "f32 under bf16"


def _seed_mapping_unknown():
    sd = _mlp()
    sd.training_config = _tc(data_set_feature_mapping=["nope"])
    return analyze_training(sd), "nope", "not in the graph"


def _seed_mapping_incomplete():
    sd = _mlp()
    sd.training_config = _tc(data_set_feature_mapping=["x"],
                             data_set_label_mapping=[])
    return analyze_training(sd), "labels", "neither feature nor label"


def _seed_cadence_misalignment():
    sd = _mlp()
    sd.training_config = _tc(fused_steps=6, accum_steps=4)
    return analyze_training(sd), "fused_steps=6", "not a multiple"


def _seed_donation_conflict():
    sd = _mlp()
    sd.set_loss_variables(["w0"])
    return analyze_training(sd), "w0", "no gradient"


def _seed_sharding_invalid():
    from deeplearning4j_tpu.parallel.sharding import ShardingSpec
    sd = _mlp()
    sd.training_config = _tc(
        sharding=ShardingSpec(axes={"data": -1, "model": 5}))
    return (analyze_training(sd, device_count=8),
            "TrainingConfig.sharding", "multiple of 5")


def _seed_sharding_unmatched_rule():
    from deeplearning4j_tpu.parallel.sharding import (ShardingRule,
                                                      ShardingSpec)
    sd = _mlp()
    sd.training_config = _tc(sharding=ShardingSpec(
        axes={"data": -1},
        rules=[ShardingRule(r"^transformer_block_.*$", (None,))]))
    return (analyze_training(sd, device_count=1),
            "transformer_block", "zero")


def _seed_chaos_armed():
    from types import SimpleNamespace
    sd = _mlp()
    sd.training_config._chaos_spec = SimpleNamespace(nan_grads_at=5)
    return analyze_training(sd), "_chaos_spec", "chaos"


def _seed_tensorstats_unobserved():
    sd = _mlp()
    sd.training_config = _tc(tensorstats=True)
    return (analyze_training(sd, has_listeners=False),
            "tensorstats", "no listeners")


def _seed_dense_kv_exceeds_headroom():
    from deeplearning4j_tpu.analyze import analyze_generative_config
    from deeplearning4j_tpu.serving.generative import GenerativeSpec
    spec = GenerativeSpec(
        params=dict, prefill=None, decode=None,
        kv_shape=lambda slots, seq: (2, slots, 2, seq, 16),
        vocab_size=64, max_seq_len=4096)
    # 64 slots x 4096 positions of f32 KV = 128 MiB vs a 64 MiB budget
    rep = analyze_generative_config(spec, max_slots=64,
                                    headroom_bytes=64 * 2**20)
    assert rep.context == "serving_config" and rep.rules_run == 1
    # the same plan under a roomy budget is clean, and CPU (no device
    # limit -> headroom None) is a no-op like the construction guard
    assert not analyze_generative_config(
        spec, max_slots=64, headroom_bytes=1 << 40).findings
    f = [x for x in rep.findings
         if x.rule_id == "serving.dense_kv_exceeds_headroom"][0]
    assert "paged" in f.fix_hint         # the hint IS the point
    return rep, "kv_slab[64x4096]", "headroom guard"


def _seed_fleet_slo_unreachable():
    from deeplearning4j_tpu.analyze import analyze_fleet_config
    # 100 req/s x 16 tokens x 20ms step = 32 concurrent slots needed,
    # but 2 replicas x 4 slots = 8 -> saturated, queues diverge
    rep = analyze_fleet_config(replicas=2, max_slots=4,
                               p99_decode_step_ms=20.0,
                               ttft_slo_ms=200.0,
                               arrival_rate_rps=100.0)
    assert rep.context == "serving_config" and rep.rules_run == 1
    f = [x for x in rep.findings
         if x.rule_id == "serving.fleet_slo_unreachable"][0]
    assert "replicas" in f.fix_hint      # the hint IS the point
    # a feasible plan (8 replicas x 8 slots = 64 >= 32 needed) is clean
    assert not analyze_fleet_config(
        replicas=8, max_slots=8, p99_decode_step_ms=20.0,
        ttft_slo_ms=200.0, arrival_rate_rps=100.0).findings
    # the floor variant: one decode step longer than the whole SLO
    floor = analyze_fleet_config(replicas=64, max_slots=64,
                                 p99_decode_step_ms=250.0,
                                 ttft_slo_ms=200.0,
                                 arrival_rate_rps=1.0)
    assert any("no replica count" in x.message for x in floor.findings)
    return rep, "fleet[2x4]", "concurrent slots"


def _seed_speculation_misconfig():
    from deeplearning4j_tpu.analyze import analyze_speculation_config
    from deeplearning4j_tpu.serving.generative import GenerativeSpec

    def _fake(vocab, msl, n_params):
        return GenerativeSpec(
            params=lambda: {"w": np.zeros((n_params,), np.float32)},
            prefill=None, decode=None,
            kv_shape=lambda slots, seq: (2, slots, 2, seq, 16),
            vocab_size=vocab, max_seq_len=msl)

    target = _fake(64, 128, 1000)
    # vocab mismatch: the error variant (the server refuses the pairing
    # at construction; the lint names it without building anything)
    rep = analyze_speculation_config(target, _fake(48, 128, 10))
    assert rep.context == "serving_config" and rep.rules_run == 1
    # a too-short draft window is the other error variant
    short = analyze_speculation_config(target, _fake(64, 64, 10))
    assert any(x.severity == "error" and "max_seq_len" in x.subject
               for x in short.findings)
    # a draft as LARGE as its target constructs fine and still emits
    # the target's exact tokens -> DEMOTED to warn, hint names a
    # smaller config
    big = analyze_speculation_config(target, _fake(64, 128, 1000))
    f = [x for x in big.findings
         if x.rule_id == "serving.speculation_misconfig"][0]
    assert f.severity == "warn" and "smaller" in f.fix_hint
    assert not big.errors()
    # a sane pairing is clean
    assert not analyze_speculation_config(target,
                                          _fake(64, 128, 10)).findings
    return rep, "draft_spec.vocab_size", "embedding table"


CORPUS = {
    "graph.shape_mismatch": _seed_shape_mismatch,
    "graph.undefined_input": _seed_undefined_input,
    "graph.invalid_loss": _seed_invalid_loss,
    "graph.unused_placeholder": _seed_unused_placeholder,
    "graph.name_shadowing": _seed_name_shadowing,
    "graph.dead_op": _seed_dead_op,
    "graph.state_alias": _seed_state_alias,
    "numerics.lowp_loss_accum": _seed_lowp_loss_accum,
    "numerics.lowp_reduction": _seed_lowp_reduction,
    "numerics.unguarded_log": _seed_unguarded_log,
    "numerics.unguarded_div": _seed_unguarded_div,
    "numerics.ce_tail_f32": _seed_ce_tail_f32,
    "config.mapping_unknown": _seed_mapping_unknown,
    "config.mapping_incomplete": _seed_mapping_incomplete,
    "config.cadence_misalignment": _seed_cadence_misalignment,
    "config.donation_conflict": _seed_donation_conflict,
    "config.sharding_invalid": _seed_sharding_invalid,
    "config.sharding_unmatched_rule": _seed_sharding_unmatched_rule,
    "config.chaos_armed": _seed_chaos_armed,
    "config.tensorstats_unobserved": _seed_tensorstats_unobserved,
    "serving.dense_kv_exceeds_headroom": _seed_dense_kv_exceeds_headroom,
    "serving.fleet_slo_unreachable": _seed_fleet_slo_unreachable,
    "serving.speculation_misconfig": _seed_speculation_misconfig,
}


class TestSeededDefects:
    def test_corpus_covers_catalog(self):
        """Every cataloged rule has a seeded defect — a rule added
        without one fails HERE, not in production."""
        assert set(CORPUS) == set(RULES)

    @pytest.mark.parametrize("rule_id", sorted(CORPUS))
    def test_rule_catches_seeded_defect(self, rule_id):
        report, subject_sub, message_sub = CORPUS[rule_id]()
        hits = [f for f in report.findings if f.rule_id == rule_id]
        assert hits, (f"{rule_id} not raised; got "
                      f"{[f.rule_id for f in report.findings]}")
        f = hits[0]
        assert f.severity == RULES[rule_id].severity
        assert subject_sub in f.subject, (f.subject, subject_sub)
        assert message_sub in f.message, (f.message, message_sub)

    def test_severity_override_is_demote_only(self):
        """finding(severity=...) may demote a dual-severity rule's hit
        below the catalog, never escalate past it."""
        from deeplearning4j_tpu.analyze.findings import finding
        with pytest.raises(ValueError, match="bad severity"):
            finding("serving.speculation_misconfig", "s", "m",
                    severity="bogus")
        with pytest.raises(ValueError, match="escalates"):
            # the fleet rule is cataloged warn — error would escalate
            finding("serving.fleet_slo_unreachable", "s", "m",
                    severity="error")
        f = finding("serving.speculation_misconfig", "s", "m",
                    severity="warn")
        assert f.severity == "warn"

    def test_shape_mismatch_provenance_names_producers(self):
        report, _, _ = CORPUS["graph.shape_mismatch"]()
        f = [x for x in report.findings
             if x.rule_id == "graph.shape_mismatch"][0]
        prov = "\n".join(f.provenance)
        # the chain names the user's placeholder AND the bad kernel
        # with their inferred shapes — not an XLA frame in sight
        assert "x" in prov and "w0" in prov
        assert "PLACEHOLDER" in prov and "VARIABLE" in prov
        assert "(13, 8)" in prov

    def test_batch_dim_artifacts_are_suppressed(self):
        """A graph valid at ANY batch extent produces no
        shape findings even though -1 dims were substituted."""
        report = analyze_training(_mlp())
        assert not [f for f in report.findings
                    if f.rule_id == "graph.shape_mismatch"]

    def test_weak_typed_constants_do_not_promote(self):
        """Regression (found by the inception-resnet sweep under the
        suite's x64 mode): ``sd.constant(0.17)`` stores a WEAKLY-typed
        scalar that promotes to its partner's dtype at runtime — the
        abstract walk must preserve weak_type, or the scaled-residual
        pattern reports a phantom f64/f32 conv mismatch."""
        sd = SameDiff()
        x = sd.placeholder("x", shape=(-1, 8))
        w = sd.var("w", value=rng.normal(0, 0.1, (8, 8))
                   .astype(np.float32))
        h = x.mmul(w, name="h")
        scaled = h.mul(sd.constant(0.17, "scale_c"), name="scaled")
        res = x.add(scaled, name="residual")       # f32 + scaled
        sd.loss.mean_sqerr_loss(res, x, name="loss")
        sd.set_loss_variables(["loss"])
        report = analyze_training(sd)
        assert not report.errors(), [f.render() for f in report.errors()]


# ---------------------------------------------------------------------------
# zero-false-positive sweep

def _assert_clean(report: AnalysisReport, name: str):
    bad = report.errors() + report.warnings()
    assert not bad, (name, [f.render() for f in bad])


class TestModelSweep:
    """Healthy zoo/bench models must produce ZERO error- or
    warn-severity findings (info hints are allowed). The examples/
    sweep rides test_examples: every example runs with
    GraphAnalysisWarning escalated to an error."""

    def test_bench_mlp(self):
        _assert_clean(analyze_training(_mlp(), has_listeners=True),
                      "bench-style mlp")

    def test_bench_mlp_fused_sentinel_tensorstats(self):
        sd = _mlp()
        sd.training_config = _tc(fused_steps=8, accum_steps=2,
                                 sentinel=True, tensorstats=True)
        _assert_clean(analyze_training(sd, has_listeners=True),
                      "mlp fused+sentinel+tensorstats")

    def test_zoo_lenet(self):
        from deeplearning4j_tpu.zoo import LeNet
        net = LeNet(height=28, width=28, channels=1).build()
        _assert_clean(analyze_training(net.samediff,
                                       has_listeners=True), "lenet")

    def test_zoo_resnet50(self):
        from deeplearning4j_tpu.zoo import ResNet50
        net = ResNet50(height=32, width=32, channels=3,
                       num_classes=4).build()
        _assert_clean(analyze_training(net.samediff,
                                       has_listeners=True),
                      "resnet50 (small input)")

    def test_zoo_lstm_and_transformer(self):
        from deeplearning4j_tpu.zoo import TextGenLSTM, TransformerEncoder
        net = TextGenLSTM(vocab_size=12, timesteps=6, units=8).build()
        _assert_clean(analyze_training(net.samediff,
                                       has_listeners=True), "lstm")
        net = TransformerEncoder(vocab_size=50, max_len=8, d_model=16,
                                 n_layers=2, n_heads=2, d_ff=32,
                                 num_classes=3).build()
        _assert_clean(analyze_training(net.samediff,
                                       has_listeners=True),
                      "transformer encoder")

    def test_zoo_gpt(self):
        from deeplearning4j_tpu.zoo.gpt import GPT_TINY, build_gpt
        sd = build_gpt(GPT_TINY, batch=4, seq_len=16)
        sd.training_config = (
            TrainingConfig.builder().updater(Adam(1e-4))
            .data_set_feature_mapping("input_ids")
            .data_set_label_mapping("targets")
            .mixed_precision(MixedPrecision(softmax_dtype="bfloat16"))
            .build())
        _assert_clean(analyze_training(sd, has_listeners=True),
                      "gpt_tiny bf16")

    def test_zoo_bert(self):
        from deeplearning4j_tpu.zoo.bert import BERT_TINY, bert_base
        sd = bert_base(BERT_TINY, batch=2, seq_len=8, num_labels=2,
                       seed=7)
        _assert_clean(analyze_training(sd, has_listeners=True),
                      "bert_tiny classifier")

    @pytest.mark.slow
    def test_bench_flagship_models_full_size(self):
        """The BENCH-config architectures at their real parameter
        sizes: resnet50@224/1000, bert_base, gpt_medium."""
        from deeplearning4j_tpu.zoo import ResNet50
        from deeplearning4j_tpu.zoo.bert import BERT_BASE, bert_base
        from deeplearning4j_tpu.zoo.gpt import GPT_MEDIUM, build_gpt
        net = ResNet50(height=224, width=224, channels=3,
                       num_classes=1000).build()
        _assert_clean(analyze_training(net.samediff,
                                       has_listeners=True),
                      "resnet50 imagenet")
        sd = bert_base(BERT_BASE, batch=2, seq_len=32, num_labels=2)
        _assert_clean(analyze_training(sd, has_listeners=True),
                      "bert_base")
        sd = build_gpt(GPT_MEDIUM, batch=2, seq_len=64)
        sd.training_config = (
            TrainingConfig.builder().updater(Adam(1e-4))
            .data_set_feature_mapping("input_ids")
            .data_set_label_mapping("targets")
            .mixed_precision(MixedPrecision(softmax_dtype="bfloat16"))
            .build())
        _assert_clean(analyze_training(sd, has_listeners=True),
                      "gpt_medium")

    def test_serving_graph_sweep(self):
        from deeplearning4j_tpu.zoo import LeNet
        net = LeNet(height=28, width=28, channels=1).build()
        sd, ins, outs, sync = net.serving_spec()
        rep = analyze_inference(sd, outputs=outs, inputs=ins)
        _assert_clean(rep, "lenet serving graph")
        assert rep.context == "serving"
        # rules_run counts EXECUTED rules: no config/loss/CE-tail/
        # dead-loss checks on the serving path (review regression)
        from deeplearning4j_tpu.analyze import _INFERENCE_RULES
        assert rep.rules_run == len(_INFERENCE_RULES) == 9
        # ... and a config-less training analysis skips config rules
        # (and the serving-capacity rules, which only run under
        # analyze_generative_config / analyze_fleet_config)
        from deeplearning4j_tpu.analyze import _SERVING_RULES
        bare = SameDiff()
        p = bare.placeholder("p", shape=(-1, 4))
        p.mean(name="loss")
        bare.set_loss_variables(["loss"])
        assert (analyze_training(bare).rules_run
                == len(RULES) - 8 - len(_SERVING_RULES))
        assert len(_SERVING_RULES) == 3


# ---------------------------------------------------------------------------
# integration: fit / precompile / serving / CLI / records

def _iterator(sd, n=32, batch=8, n_in=20, n_out=4):
    X = rng.normal(size=(n, n_in)).astype(np.float32)
    Y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, n)]
    return [(X[i:i + batch], Y[i:i + batch])
            for i in range(0, n, batch)]


class TestFitIntegration:
    def test_strict_raises_before_any_compile(self):
        """The acceptance bar: strict=True fails with named
        diagnostics and ZERO backend compiles (PR-6 counters)."""
        from deeplearning4j_tpu.compilecache import (
            COMPILE_STATS, install_compile_watcher)
        install_compile_watcher()
        sd = _mlp(w0_rows=13)
        sd.training_config.analyze = "strict"
        it = _iterator(sd)
        # warm the tiny eager kernels analysis itself touches
        # (random key construction) so the delta isolates fit()
        analyze_training(_mlp())
        mark = COMPILE_STATS.mark()
        with pytest.raises(GraphAnalysisError) as ei:
            sd.fit(it, epochs=1)
        assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
        assert "graph.shape_mismatch" in str(ei.value)
        assert sd.last_analysis.errors()

    def test_precompile_strict_raises_before_any_compile(self):
        from deeplearning4j_tpu.compilecache import (
            COMPILE_STATS, install_compile_watcher)
        install_compile_watcher()
        sd = _mlp(w0_rows=13)
        sd.training_config.analyze = "strict"
        analyze_training(_mlp())
        mark = COMPILE_STATS.mark()
        with pytest.raises(GraphAnalysisError):
            sd.precompile(batch_size=8)
        assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
        # a precompile-triggered analysis stamps its entry point
        assert sd.last_analysis.context == "precompile"

    def test_default_mode_warns_and_proceeds(self):
        sd = _mlp(w0_rows=13)
        it = _iterator(sd)
        with pytest.warns(GraphAnalysisWarning, match="shape_mismatch"):
            with pytest.raises(Exception):
                sd.fit(it, epochs=1)      # XLA still fails, later

    def test_analyze_false_disables(self):
        sd = _mlp(w0_rows=13)
        sd.training_config.analyze = False
        it = _iterator(sd)
        with warnings.catch_warnings():
            warnings.simplefilter("error", GraphAnalysisWarning)
            with pytest.raises(Exception) as ei:
                sd.fit(it, epochs=1)
        assert not isinstance(ei.value, GraphAnalysisError)
        assert sd.last_analysis is None

    def test_analysis_cached_per_graph_version(self):
        """Warm fits pay a dict lookup, not a re-analysis."""
        sd = _mlp()
        it = _iterator(sd)
        sd.fit(it, epochs=1)
        first = sd.last_analysis
        assert first is not None and not first.errors()
        sd.fit(it, epochs=1)
        assert sd.last_analysis is first       # same report object
        sd.constant(1.0, "poke")               # graph mutation
        sd.fit(it, epochs=1)
        assert sd.last_analysis is not first

    def test_strict_keeps_refusing_on_repeat_fits(self):
        """Review regression: the cached report must re-enforce
        strict mode — a retry loop around a broken graph cannot slip
        past analysis into the compile on its second attempt."""
        sd = _mlp(w0_rows=13)
        sd.training_config.analyze = "strict"
        it = _iterator(sd)
        with pytest.raises(GraphAnalysisError):
            sd.fit(it, epochs=1)
        first = sd.last_analysis
        with pytest.raises(GraphAnalysisError):
            sd.fit(it, epochs=1)          # cache hit, same refusal
        assert sd.last_analysis is first

    def test_config_mutation_invalidates_analysis_cache(self):
        """Review regression: in-place TrainingConfig mutation (the
        common pattern) must re-analyze — the key is a content
        fingerprint, not the config object's identity."""
        from deeplearning4j_tpu.parallel.sharding import ShardingSpec
        sd = _mlp()
        it = _iterator(sd)
        sd.fit(it, epochs=1)
        assert not sd.last_analysis.errors()
        sd.training_config.sharding = ShardingSpec(
            axes={"data": -1, "model": 5})      # cannot bind
        sd.training_config.analyze = "strict"
        with pytest.raises(GraphAnalysisError) as ei:
            sd.fit(it, epochs=1)
        assert any(f.rule_id == "config.sharding_invalid"
                   for f in ei.value.report.errors())
        # loss_variables changes don't bump the graph version either
        sd2 = _mlp()
        sd2.fit(_iterator(sd2), epochs=1)
        sd2.set_loss_variables(["w0"])
        sd2.training_config.analyze = "strict"
        with pytest.raises(GraphAnalysisError):
            sd2.fit(_iterator(sd2), epochs=1)

    def test_clean_fit_trains_and_is_clean(self):
        sd = _mlp()
        it = _iterator(sd)
        with warnings.catch_warnings():
            warnings.simplefilter("error", GraphAnalysisWarning)
            h = sd.fit(it, epochs=2)
        assert np.isfinite(h.final_loss())
        assert sd.last_analysis is not None
        assert not sd.last_analysis.errors()


class TestServingIntegration:
    def _net(self):
        from deeplearning4j_tpu.zoo import LeNet
        return LeNet(height=8, width=8, channels=1).build()

    def test_parallel_inference_runs_analyzer(self):
        from deeplearning4j_tpu.serving import ParallelInference
        from deeplearning4j_tpu.ui.stats import StatsStorage
        storage = StatsStorage()
        pi = ParallelInference(self._net(), stats_storage=storage,
                               workers=1)
        try:
            assert pi.analysis is not None
            assert not pi.analysis.errors()
            recs = storage.of_type("analysis")
            assert len(recs) == 1
            assert recs[0]["context"] == "serving"
        finally:
            pi.shutdown()

    def test_parallel_inference_strict_raises(self):
        from deeplearning4j_tpu.serving import InferenceMode, \
            ParallelInference

        broken = SameDiff()
        x = broken.placeholder("input", shape=(-1, 6))
        w = broken.var("w", value=np.zeros((5, 2), np.float32))
        x.mmul(w, name="output")

        class FakeModel:
            def serving_spec(self):
                return broken, ["input"], ["output"], lambda: None

        with pytest.raises(GraphAnalysisError):
            ParallelInference(FakeModel(), analyze="strict",
                              mode=InferenceMode.INPLACE)
        with pytest.warns(GraphAnalysisWarning):
            pi = ParallelInference(FakeModel(),
                                   mode=InferenceMode.INPLACE)
            pi.shutdown()


class TestCLI:
    def _save(self, sd, tmp_path, name):
        path = str(tmp_path / name)
        sd.save(path)
        return path

    def test_cli_clean_model_exits_zero(self, tmp_path, capsys):
        from deeplearning4j_tpu.analyze.__main__ import main
        rc = main([self._save(_mlp(), tmp_path, "clean.zip")])
        out = capsys.readouterr().out
        assert rc == 0
        assert "static analysis" in out

    def test_cli_broken_model_exits_one_with_named_finding(
            self, tmp_path, capsys):
        from deeplearning4j_tpu.analyze.__main__ import main
        rc = main([self._save(_mlp(w0_rows=13), tmp_path, "bad.zip")])
        out = capsys.readouterr().out
        assert rc == 1
        assert "graph.shape_mismatch" in out and "h0_mm" in out

    def test_cli_json_record(self, tmp_path, capsys):
        from deeplearning4j_tpu.analyze.__main__ import main
        rc = main([self._save(_mlp(w0_rows=13), tmp_path, "bad.zip"),
                   "--json"])
        rec = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert rec["type"] == "analysis" and rec["context"] == "cli"
        assert rec["counts"]["error"] >= 1
        assert any(f["rule_id"] == "graph.shape_mismatch"
                   for f in rec["findings"])

    def test_cli_strict_fails_on_warns(self, tmp_path):
        from deeplearning4j_tpu.analyze.__main__ import main
        sd = _mlp()
        sd.placeholder("extra", shape=(-1, 2))    # warn-severity only
        path = self._save(sd, tmp_path, "warn.zip")
        assert main([path]) == 0
        assert main([path, "--strict"]) == 1

    def test_cli_rules_catalog(self, capsys):
        from deeplearning4j_tpu.analyze.__main__ import main
        assert main(["--rules"]) == 0
        out = capsys.readouterr().out
        for rid in RULES:
            assert rid in out

    def test_cli_missing_model_usage_error(self, capsys):
        from deeplearning4j_tpu.analyze.__main__ import main
        assert main([]) == 2


class TestRecordsAndReport:
    def test_record_renders_no_footer_leak(self):
        from deeplearning4j_tpu.ui.report import render_report
        from deeplearning4j_tpu.ui.stats import StatsStorage
        report, _, _ = CORPUS["graph.shape_mismatch"]()
        storage = StatsStorage()
        storage.put(report.to_record())
        html = render_report(storage)
        assert "Static analysis" in html
        assert "graph.shape_mismatch" in html
        assert "unrendered record types" not in html

    def test_registry_fold(self):
        from deeplearning4j_tpu.monitor import MetricsRegistry
        report, _, _ = CORPUS["graph.shape_mismatch"]()
        reg = MetricsRegistry()
        reg.fold_analysis(report.to_record())
        text = reg.to_prometheus_text()
        assert 'dl4j_analysis_findings{severity="error"}' in text
        assert "dl4j_analysis_rules_run" in text

    def test_monitor_listener_publishes_once(self):
        from deeplearning4j_tpu.monitor import MonitorListener
        from deeplearning4j_tpu.ui.stats import StatsStorage
        sd = _mlp()
        sd.training_config.fused_steps = 4
        storage = StatsStorage()
        mon = MonitorListener(storage)
        it = _iterator(sd)
        sd.fit(it, epochs=1, listeners=[mon])
        assert len(storage.of_type("analysis")) == 1
        sd.fit(it, epochs=1, listeners=[mon])    # same graph version
        assert len(storage.of_type("analysis")) == 1
        assert 'severity="error"' in \
            mon.registry.to_prometheus_text().replace("'", '"')


class TestSatellites:
    def test_weighted_loss_reductions_f32_accumulator(self):
        """ops/loss.py satellite: the weighted-reduction tails force
        an f32 accumulator under bf16 inputs (PR 6 fixed only the
        dense softmax-CE vocab sum)."""
        from deeplearning4j_tpu.ops.loss import (absolute_difference_loss,
                                                 hinge_loss,
                                                 mean_sqerr_loss)
        p = jnp.linspace(0, 1, 512, dtype=jnp.bfloat16).reshape(64, 8)
        l = jnp.zeros((64, 8), jnp.bfloat16)
        for fn in (absolute_difference_loss, hinge_loss):
            for reduction in ("sum", "mean", "mean_by_weight"):
                out = fn(p, l, reduction=reduction)
                assert out.dtype == jnp.float32, (fn.__name__, reduction)
        # reference value: the f32 accumulation matches a full-f32 run
        # to bf16 input precision
        lo = absolute_difference_loss(p, l, reduction="sum")
        hi = absolute_difference_loss(p.astype(jnp.float32),
                                      l.astype(jnp.float32),
                                      reduction="sum")
        np.testing.assert_allclose(float(lo), float(hi), rtol=1e-2)
        # "none" stays per-element in the compute dtype
        assert absolute_difference_loss(
            p, l, reduction="none").dtype == jnp.bfloat16

    def test_analyzer_reports_builtin_losses_clean_under_bf16(self):
        """The satellite's acceptance: after the f32-accumulator fix,
        the numerics pass reports the real loss ops clean."""
        for loss_op in ("absolute_difference_loss", "mean_sqerr_loss",
                        "hinge_loss", "huber_loss",
                        "softmax_cross_entropy"):
            sd = SameDiff()
            p = sd.placeholder("x", shape=(-1, 16), dtype="bfloat16")
            l = sd.placeholder("labels", shape=(-1, 16),
                               dtype="bfloat16")
            sd.invoke(loss_op, [p, l], name="loss")
            sd.set_loss_variables(["loss"])
            rep = analyze_training(sd)
            assert not [f for f in rep.findings
                        if f.rule_id == "numerics.lowp_loss_accum"], \
                loss_op

    def test_sharding_validate_matches_build_errors(self):
        """ShardingSpec.validate raises the SAME errors build() does,
        without constructing a mesh."""
        from deeplearning4j_tpu.parallel.sharding import (ShardingRule,
                                                          ShardingSpec)
        spec = ShardingSpec(axes={"data": -1, "model": -1})
        with pytest.raises(ValueError, match="one -1"):
            spec.validate(device_count=8)
        with pytest.raises(ValueError, match="one -1"):
            spec.build()
        spec = ShardingSpec(axes={"data": 0})
        with pytest.raises(ValueError, match="positive"):
            spec.validate(device_count=8)
        spec = ShardingSpec(axes={"data": -1}, preset="warp_drive")
        with pytest.raises(ValueError, match="unknown sharding preset"):
            spec.validate()
        with pytest.raises(ValueError, match="unknown sharding preset"):
            spec.build()
        spec = ShardingSpec(axes={"data": -1, "model": 5})
        with pytest.raises(ValueError, match="multiple of 5"):
            spec.validate(device_count=8)
        spec = ShardingSpec(axes={"data": -1}, batch_axes=("warp",))
        with pytest.raises(ValueError, match="batch axis"):
            spec.validate(device_count=8)
        # review regression: a FIXED (fill-free) product exceeding the
        # device count raises DeviceMesh.create's error pre-mesh
        spec = ShardingSpec(axes={"data": 16}, batch_axes=("data",))
        with pytest.raises(ValueError, match="needs 16 devices"):
            spec.validate(device_count=8)
        spec.validate(device_count=16)    # enough devices: fine

    def test_sharding_validate_param_divisibility(self):
        from deeplearning4j_tpu.parallel.sharding import (ShardingRule,
                                                          ShardingSpec)
        spec = ShardingSpec(
            axes={"data": -1, "model": 4},
            rules=[ShardingRule(r"_dense_W$", (None, "model"))])
        # dim 8 % 4 == 0: fine
        spec.validate(params={"l0_dense_W": (16, 8)}, device_count=8)
        with pytest.raises(ValueError, match="not.*divisible|divisible"):
            spec.validate(params={"l0_dense_W": (16, 10)},
                          device_count=8)
        # unmatched params are never constrained
        spec.validate(params={"something_else": (7, 13)},
                      device_count=8)

    def test_docs_catalog_in_sync(self):
        """docs/static_analysis.md documents every cataloged rule."""
        import pathlib
        doc = (pathlib.Path(__file__).resolve().parents[1]
               / "docs" / "static_analysis.md").read_text()
        missing = [rid for rid in RULES if rid not in doc]
        assert not missing, missing
