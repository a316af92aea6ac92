"""Static analysis before the first compile (analyze/,
docs/static_analysis.md).

Builds a small model, breaks it four different ways, and shows how the
analyzer turns each break into a NAMED diagnostic — the variable, the
op, the producer chain, the fix — instead of an XLA traceback. Then
demonstrates strict mode (fail before any compile), the warm-path cost
(analysis runs once per graph version), and the CLI.
"""
import numpy as np

from deeplearning4j_tpu.analyze import (GraphAnalysisError,
                                        analyze_training)
from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
from deeplearning4j_tpu.learning.updaters import Adam

rng = np.random.default_rng(0)


def build_mlp(w0_rows=20, fused_steps=1, accum_steps=1,
              feature_mapping=("x",)):
    sd = SameDiff()
    x = sd.placeholder("x", shape=(-1, 20))
    w0 = sd.var("w0", value=rng.normal(0, 0.1, (w0_rows, 16))
                .astype(np.float32))
    b0 = sd.var("b0", value=np.zeros(16, np.float32))
    h = sd.nn.relu(x.mmul(w0).add(b0), name="h0")
    w1 = sd.var("w1", value=rng.normal(0, 0.1, (16, 4))
                .astype(np.float32))
    logits = h.mmul(w1, name="logits")
    labels = sd.placeholder("labels", shape=(-1, 4))
    sd.loss.softmax_cross_entropy(logits, labels, name="loss")
    sd.set_loss_variables(["loss"])
    sd.training_config = (
        TrainingConfig.builder().updater(Adam(learning_rate=1e-3))
        .data_set_feature_mapping(*feature_mapping)
        .data_set_label_mapping("labels")
        .fused_steps(fused_steps).accum_steps(accum_steps).build())
    return sd


# -- 1. a healthy model is clean -------------------------------------------
clean = analyze_training(build_mlp(), has_listeners=True)
print(f"clean model: {clean.counts()} in {clean.seconds:.3f}s "
      f"({clean.rules_run} rules)")
assert not clean.errors() and not clean.warnings()

# -- 2. four seeded defects, four named diagnostics ------------------------
print("\n--- shape mismatch (wrong kernel rows) ---")
rep = analyze_training(build_mlp(w0_rows=13))
print(rep.findings[0].render())

print("\n--- config lint: mapping names a ghost placeholder ---")
rep = analyze_training(build_mlp(feature_mapping=("features",)))
print([f.rule_id for f in rep.findings])

print("\n--- cadence: fused_steps not a multiple of accum_steps ---")
rep = analyze_training(build_mlp(fused_steps=6, accum_steps=4))
print([f.rule_id for f in rep.findings])

print("\n--- numerics: an unguarded log ---")
sd = build_mlp()
sd.get_variable("w1")  # keep graph healthy; add a hazardous branch
bad = SameDiff()
p = bad.placeholder("p", shape=(-1, 4))
bad_loss = p.log(name="raw_log").mean(name="loss")
bad.set_loss_variables(["loss"])
rep = analyze_training(bad)
print([f"{f.rule_id}@{f.subject}" for f in rep.findings])

# -- 3. strict mode: fail BEFORE any XLA compile ---------------------------
sd = build_mlp(w0_rows=13)
sd.training_config.analyze = "strict"
X = rng.normal(size=(32, 20)).astype(np.float32)
Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 32)]
batches = [(X[i:i + 8], Y[i:i + 8]) for i in range(0, 32, 8)]
try:
    sd.fit(batches, epochs=1)
    raise SystemExit("strict mode should have raised")
except GraphAnalysisError as e:
    print(f"\nstrict fit refused pre-compile: "
          f"{len(e.report.errors())} error(s), first rule "
          f"{e.report.errors()[0].rule_id}")

# -- 4. warm path: analysis runs once per graph version --------------------
sd = build_mlp()
sd.fit(batches, epochs=1)
first = sd.last_analysis
sd.fit(batches, epochs=1)
assert sd.last_analysis is first
print("\nwarm fit reused the cached report "
      f"(one-time cost {first.seconds:.3f}s, ~0 per-fit after)")

# -- 5. the CLI runs the same rules on a saved artifact --------------------
import os
import subprocess
import sys
import tempfile

with tempfile.TemporaryDirectory() as d:
    path = f"{d}/model.zip"
    build_mlp(w0_rows=13).save(path)
    # this process has trained on the accelerator and holds it (a chip
    # belongs to one process); the analyzer compiles nothing, so its
    # child is pinned to the CPU instead of reaching for the same chip
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analyze", path],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    print(f"\nCLI exit code {proc.returncode} (1 = error findings):")
    print(proc.stdout.splitlines()[0])
print("done.")
