"""Training configuration, history and listener API for SameDiff.

Reference parity:
- TrainingConfig (org.nd4j.autodiff.samediff.TrainingConfig.java:42):
  updater + L1/L2 + dataSetFeatureMapping/dataSetLabelMapping.
- Listener (org.nd4j.autodiff.listeners.Listener) and the History/LossCurve
  records (org.nd4j.autodiff.listeners.records).
- ScoreIterationListener / PerformanceListener
  (deeplearning4j optimize/listeners/) — throughput metrics use the same
  samples/sec & batches/sec definitions (PerformanceListener.java:46-118).

The listener surface is host-side: it observes per-iteration scalars after
the compiled step returns. It can NOT inject code into the XLA computation
(the reference's listeners run between per-op JNI dispatches; here there is
nothing between ops — that is the point).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

from deeplearning4j_tpu.learning.updaters import IUpdater
from deeplearning4j_tpu.learning.regularization import Regularization


def _env():
    from deeplearning4j_tpu.environment import environment
    return environment()


@dataclasses.dataclass
class MixedPrecision:
    """Mixed-precision training policy: compute in ``compute_dtype``
    (bf16 → the MXU's native input format), keep float32 master params.

    The reference has no analogue (its DataType plumbing switches the
    whole net's dtype); this is the TPU-native design: the train step
    casts params + inputs to the compute dtype at the top of the
    forward trace, XLA fuses the casts into the producing/consuming
    ops, gradients flow back through the casts as float32 into the
    updater, and loss-sensitive reductions (loss ops, BN statistics)
    stay float32 internally. ``loss_scale`` is optional static loss
    scaling (rarely needed with bf16 — same exponent range as f32).

    ``softmax_dtype`` (alias ``ce_tail_dtype``) relaxes the one upcast
    that dominates LM steps: by default the softmax-CE losses run their
    log-softmax tail in f32 even under bf16 compute, which on a 32k
    vocab materializes the largest f32 tensor in the step. Setting
    ``softmax_dtype="bfloat16"`` keeps that [batch..., vocab] tail in
    bf16 — the per-example losses still reduce to the scalar loss in
    f32, so the training signal accumulates at full precision. Default
    ``None`` preserves the f32 tail bit-exactly
    (docs/training_performance.md).
    """
    compute_dtype: str = "bfloat16"
    loss_scale: Optional[float] = None
    softmax_dtype: Optional[str] = None
    ce_tail_dtype: dataclasses.InitVar[Optional[str]] = None

    def __post_init__(self, ce_tail_dtype: Optional[str]) -> None:
        if ce_tail_dtype is not None:
            if (self.softmax_dtype is not None
                    and self.softmax_dtype != ce_tail_dtype):
                raise ValueError(
                    f"softmax_dtype={self.softmax_dtype!r} and its alias "
                    f"ce_tail_dtype={ce_tail_dtype!r} disagree — pass one")
            self.softmax_dtype = ce_tail_dtype

    def to_json(self) -> dict:
        return {"compute_dtype": self.compute_dtype,
                "loss_scale": self.loss_scale,
                "softmax_dtype": self.softmax_dtype}

    @staticmethod
    def from_json(d) -> "Optional[MixedPrecision]":
        if d is None:
            return None
        return MixedPrecision(compute_dtype=d.get("compute_dtype", "bfloat16"),
                              loss_scale=d.get("loss_scale"),
                              softmax_dtype=d.get("softmax_dtype",
                                                  d.get("ce_tail_dtype")))


# ce_tail_dtype is BOTH a constructor alias (the InitVar above) and a
# read alias of softmax_dtype; the property is attached after class
# creation because defining it in the body would shadow the InitVar's
# class-attribute default and feed the property object to __post_init__
MixedPrecision.ce_tail_dtype = property(lambda self: self.softmax_dtype)


@dataclasses.dataclass
class TrainingConfig:
    updater: IUpdater
    data_set_feature_mapping: Sequence[str] = ()
    data_set_label_mapping: Sequence[str] = ()
    regularization: Sequence[Regularization] = ()
    grad_clip_value: Optional[float] = None
    minibatch: bool = True
    iteration_count: int = 0
    epoch_count: int = 0
    mixed_precision: Optional[MixedPrecision] = None
    # gradient normalization mode (reference:
    # BaseMultiLayerUpdater.preApply :395 / GradientNormalization enum):
    # None | "clip_element_wise_absolute_value" | "clip_l2_per_layer" |
    # "clip_l2_global" | "renormalize_l2_per_layer"
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    # unroll factor for the scanned whole-epoch fit path (compile-time
    # cost vs fewer while-loop iterations; runtime-tuning knob, not serde)
    scan_unroll: int = 1
    # fused training windows (autodiff/window.py): K consecutive train
    # steps execute as ONE compiled lax.scan dispatch, with per-step
    # losses buffered on device and flushed to listeners at window
    # boundaries. 1 = per-step dispatch (the legacy tier). Works with
    # listeners AND host-streaming iterators — unlike the scanned
    # whole-epoch tier, which needs neither.
    fused_steps: int = 1
    # gradient accumulation: micro-batch grads accumulate in the window
    # scan carry and the updater applies every ``accum_steps``-th
    # micro-step (grads averaged — an effective batch of
    # accum_steps * batch). 1 = update every step.
    accum_steps: int = 1
    # NaN/Inf panic (reference: DefaultOpExecutioner ProfilingMode
    # NAN_PANIC/INF_PANIC): fit() checks fetched losses and raises
    # NumericsException naming the iteration; localize the producing op
    # with sd.exec_debug(). Step-internal per-op checks are impossible
    # under whole-graph jit, so the check granularity is the loss fetch.
    # Defaults from the runtime Environment ($DL4J_TPU_NAN_PANIC /
    # $DL4J_TPU_DEBUG, reference: Environment.h debug mode).
    nan_panic: bool = dataclasses.field(default_factory=lambda: bool(
        _env().get("nan_panic") or _env().get("debug")))
    # device-side divergence sentinel (faults/sentinels.py): the compiled
    # step additionally emits isfinite(loss) AND an isfinite check over
    # EVERY gradient leaf (SameDiff._sentinel_ok — deliberately not a
    # sampled leaf); fused windows fold it into the scan carry (one
    # extra scalar per window, no per-step host sync) and the fit tiers
    # raise a structured faults.TrainingDivergedError naming
    # step/epoch/batch. Parameter math is untouched — sentinel-on
    # training is bit-identical.
    sentinel: bool = False
    # declarative mesh sharding (parallel.ShardingSpec, serde'd like
    # every other field): when set, SameDiff.fit places params/state on
    # the spec's device mesh and shards input batches before tier
    # selection, so DP/TP training composes with fused windows, the
    # sentinel carry and AOT precompile without the ParallelTrainer
    # front end. The spec carries INTENT (axis sizes with one -1 fill,
    # rule preset, per-layer rules); the strategy binds to whatever
    # devices the process has — the elastic-resume contract
    # (docs/elastic_training.md).
    sharding: Optional[Any] = None
    # in-graph per-layer tensor statistics (monitor/tensorstats.py):
    # True (defaults) or a TensorStatsConfig. The compiled step
    # additionally summarizes gradients/updates/params per layer (L2,
    # mean|x|, min/max, nonfinite count, fixed log2-magnitude
    # histogram) every Nth step, folded into the scan carry like the
    # sentinel and fetched at the flush boundaries the host already
    # syncs on. Requires the listener rail (per-step or fused-window
    # tier with listeners) to deliver {"type": "tensorstats"} records;
    # parameter math is untouched — stats-on training is bit-identical.
    tensorstats: Optional[Any] = None
    # bitwise state fingerprints (integrity/fingerprint.py): the
    # compiled window additionally emits one uint32 digest of
    # params + state vars + optimizer state (a word-sum folded in
    # like the sentinel — one extra int per window), read at the
    # flush boundaries the host already syncs on. Checkpoint captures
    # compare it against the host bytes and stamp the snapshot;
    # restores re-verify the stamp; mismatch raises a typed
    # faults.SilentCorruptionError. Parameter math is untouched —
    # fingerprints-on training is bit-identical.
    fingerprints: bool = False
    # replay probe cadence (windows): every Nth window is re-dispatched
    # from a stashed carry and the two digests compared — genuine
    # in-dispatch SDC/nondeterminism disagrees. Costs 1/N extra
    # compute; 0 = off.
    fingerprint_replay_every: int = 0
    # cross-replica agreement cadence (flushes): every Nth listener
    # flush compares per-replica digests of DP-sharded params bitwise
    # (integrity.check_replica_agreement). 0 = off.
    fingerprint_replica_every: int = 0
    # pre-compile static analysis (analyze/, docs/static_analysis.md):
    # fit()/precompile() walk the graph + this config WITHOUT compiling
    # and surface structured findings (shape mismatches with producer
    # chains, numerics hazards, sharding/cadence/mapping lint). True =
    # error-severity findings warn (GraphAnalysisWarning) and the fit
    # proceeds; "strict" = raise GraphAnalysisError BEFORE any XLA
    # compile; False = off. Analysis runs once per graph version, so
    # its cost never touches the warm dispatch path.
    analyze: Any = True

    def __post_init__(self):
        if self.tensorstats is not None:
            from deeplearning4j_tpu.monitor.tensorstats import normalize
            self.tensorstats = normalize(self.tensorstats)

    def clip_gradients(self, grads):
        """Apply elementwise clip + the configured normalization mode to a
        gradient pytree (traced inside the compiled train step)."""
        import jax
        import jax.numpy as jnp
        if self.grad_clip_value is not None:
            c = self.grad_clip_value
            grads = jax.tree_util.tree_map(lambda g: jnp.clip(g, -c, c),
                                           grads)
        mode = (self.gradient_normalization or "none").lower()
        if mode in ("none", ""):
            return grads
        t = self.gradient_normalization_threshold
        eps = 1e-8
        if mode == "clip_element_wise_absolute_value":
            return jax.tree_util.tree_map(lambda g: jnp.clip(g, -t, t), grads)
        if mode == "clip_l2_per_layer":
            def _clip(g):
                n = jnp.sqrt(jnp.sum(jnp.square(g)))
                return g * jnp.minimum(1.0, t / (n + eps))
            return jax.tree_util.tree_map(_clip, grads)
        if mode in ("clip_l2_global", "clip_by_global_norm"):
            leaves = jax.tree_util.tree_leaves(grads)
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
            scale = jnp.minimum(1.0, t / (gn + eps))
            return jax.tree_util.tree_map(lambda g: g * scale, grads)
        if mode == "renormalize_l2_per_layer":
            return jax.tree_util.tree_map(
                lambda g: g / (jnp.sqrt(jnp.sum(jnp.square(g))) + eps), grads)
        raise ValueError(f"unknown gradient_normalization {mode!r}")

    def to_json(self) -> dict:
        return {
            "updater": self.updater.to_json(),
            "data_set_feature_mapping": list(self.data_set_feature_mapping),
            "data_set_label_mapping": list(self.data_set_label_mapping),
            "regularization": [r.to_json() for r in self.regularization],
            "grad_clip_value": self.grad_clip_value,
            "minibatch": self.minibatch,
            "iteration_count": self.iteration_count,
            "epoch_count": self.epoch_count,
            "mixed_precision": (self.mixed_precision.to_json()
                                if self.mixed_precision else None),
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold":
                self.gradient_normalization_threshold,
            "fused_steps": self.fused_steps,
            "accum_steps": self.accum_steps,
            "sentinel": self.sentinel,
            # the fit path also accepts a live ShardingStrategy here;
            # serialize it through its declarative to_spec() form
            "sharding": (None if self.sharding is None
                         else (self.sharding
                               if hasattr(self.sharding, "to_json")
                               else self.sharding.to_spec()).to_json()),
            "tensorstats": (None if self.tensorstats is None
                            else self.tensorstats.to_json()),
            "fingerprints": self.fingerprints,
            "fingerprint_replay_every": self.fingerprint_replay_every,
            "fingerprint_replica_every": self.fingerprint_replica_every,
            "analyze": (self.analyze if isinstance(self.analyze,
                                                   (bool, str))
                        else bool(self.analyze)),
        }

    @staticmethod
    def from_json(d: dict) -> "TrainingConfig":
        sharding = None
        if d.get("sharding") is not None:
            from deeplearning4j_tpu.parallel.sharding import ShardingSpec
            sharding = ShardingSpec.from_json(d["sharding"])
        tensorstats = None
        if d.get("tensorstats") is not None:
            from deeplearning4j_tpu.monitor.tensorstats import \
                TensorStatsConfig
            tensorstats = TensorStatsConfig.from_json(d["tensorstats"])
        return TrainingConfig(
            updater=IUpdater.from_json(d["updater"]),
            data_set_feature_mapping=d.get("data_set_feature_mapping", []),
            data_set_label_mapping=d.get("data_set_label_mapping", []),
            regularization=[Regularization.from_json(r)
                            for r in d.get("regularization", [])],
            grad_clip_value=d.get("grad_clip_value"),
            minibatch=d.get("minibatch", True),
            iteration_count=d.get("iteration_count", 0),
            epoch_count=d.get("epoch_count", 0),
            mixed_precision=MixedPrecision.from_json(d.get("mixed_precision")),
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get(
                "gradient_normalization_threshold", 1.0),
            fused_steps=d.get("fused_steps", 1),
            accum_steps=d.get("accum_steps", 1),
            sentinel=d.get("sentinel", False),
            sharding=sharding,
            tensorstats=tensorstats,
            fingerprints=d.get("fingerprints", False),
            fingerprint_replay_every=d.get("fingerprint_replay_every", 0),
            fingerprint_replica_every=d.get("fingerprint_replica_every",
                                            0),
            analyze=d.get("analyze", True),
        )

    class Builder:
        """Fluent builder matching the reference's TrainingConfig.Builder."""

        def __init__(self):
            self._kw: Dict[str, Any] = {}

        def updater(self, u):             self._kw["updater"] = u; return self
        def data_set_feature_mapping(self, *names):
            self._kw["data_set_feature_mapping"] = list(names); return self
        def data_set_label_mapping(self, *names):
            self._kw["data_set_label_mapping"] = list(names); return self
        def regularization(self, *regs):  self._kw["regularization"] = list(regs); return self
        def grad_clip_value(self, v):     self._kw["grad_clip_value"] = v; return self
        def minibatch(self, b):           self._kw["minibatch"] = b; return self
        def mixed_precision(self, mp):
            if mp is True:
                mp = MixedPrecision()
            self._kw["mixed_precision"] = mp; return self
        def gradient_normalization(self, mode, threshold: float = 1.0):
            self._kw["gradient_normalization"] = mode
            self._kw["gradient_normalization_threshold"] = threshold
            return self
        def fused_steps(self, k: int):
            self._kw["fused_steps"] = int(k); return self
        def accum_steps(self, n: int):
            self._kw["accum_steps"] = int(n); return self
        def sentinel(self, on: bool = True):
            self._kw["sentinel"] = bool(on); return self
        def sharding(self, spec):
            self._kw["sharding"] = spec; return self
        def tensorstats(self, cfg=True):
            self._kw["tensorstats"] = cfg; return self
        def fingerprints(self, on: bool = True, replay_every: int = 0,
                         replica_every: int = 0):
            """Bitwise state fingerprints (integrity/): capture/restore
            verification plus the optional replay-probe and
            cross-replica-agreement cadences."""
            self._kw["fingerprints"] = bool(on)
            self._kw["fingerprint_replay_every"] = int(replay_every)
            self._kw["fingerprint_replica_every"] = int(replica_every)
            return self
        def analyze(self, mode=True):
            """Pre-compile static analysis: True (warn), "strict"
            (raise GraphAnalysisError before any compile), False."""
            self._kw["analyze"] = mode; return self
        def build(self) -> "TrainingConfig":
            return TrainingConfig(**self._kw)

    @staticmethod
    def builder() -> "TrainingConfig.Builder":
        return TrainingConfig.Builder()


class LossCurve:
    """Per-epoch mean loss (reference: listeners.records.LossCurve)."""

    def __init__(self):
        self.epochs: List[int] = []
        self.losses: List[float] = []

    def add(self, epoch: int, loss: float):
        self.epochs.append(epoch)
        self.losses.append(loss)

    def mean_loss(self, epoch: int) -> float:
        return self.losses[self.epochs.index(epoch)]

    def last(self) -> float:
        return self.losses[-1] if self.losses else float("nan")


class History:
    """Training run record (reference: listeners.records.History)."""

    def __init__(self):
        self.loss_curve = LossCurve()

    def add_epoch(self, epoch: int, mean_loss: float):
        self.loss_curve.add(epoch, mean_loss)

    def final_loss(self) -> float:
        return self.loss_curve.last()


class Listener:
    """Training listener (reference: autodiff.listeners.Listener /
    dl4j TrainingListener). Return False from on_epoch_end to stop.

    Loss scalars live on device; forcing one to a python float costs a
    device round-trip that serializes the dispatch pipeline. fit()
    therefore buffers per-step losses and delivers them in bursts via
    ``iterations_done`` every ``frequency`` steps (ONE transfer per
    burst). The default implementation replays ``iteration_done`` per
    step, so simple listeners just implement that."""

    #: how often (in iterations) this listener needs scalars delivered
    frequency: int = 10

    def on_training_start(self, sd): ...
    def on_training_end(self, sd): ...
    def on_epoch_start(self, sd, epoch: int): ...
    def on_epoch_end(self, sd, epoch: int, mean_loss: float): ...
    def iteration_done(self, sd, epoch: int, iteration: int, loss: float): ...

    def iterations_done(self, sd, epoch: int, iterations: Sequence[int],
                        losses: Sequence[float]):
        for it, lo in zip(iterations, losses):
            self.iteration_done(sd, epoch, it, lo)

    def tensorstats_done(self, sd, epoch: int,
                         records: Sequence[dict]):
        """Per-layer tensor-statistics delivery (``TrainingConfig.
        tensorstats``, monitor/tensorstats.py): fit() calls this right
        after ``iterations_done`` at each flush whose burst contained
        sampled stats, with the fetched ``{"type": "tensorstats"}``
        records. Default: ignore."""


class ScoreIterationListener(Listener):
    """Print score every N iterations (reference:
    optimize/listeners/ScoreIterationListener)."""

    def __init__(self, print_every: int = 10, print_fn=print):
        self.print_every = print_every
        self.frequency = print_every
        self.print_fn = print_fn

    def iteration_done(self, sd, epoch, iteration, loss):
        if iteration % self.print_every == 0:
            self.print_fn(f"Score at iteration {iteration} is {loss}")


class PerformanceListener(Listener):
    """Throughput metrics: samples/sec, batches/sec (reference:
    optimize/listeners/PerformanceListener.java:46-118)."""

    def __init__(self, frequency: int = 10, print_fn=print):
        self.frequency = frequency
        self.print_fn = print_fn
        self.batch_size = None  # auto-filled by fit() from the first batch
        self._last_time = None
        self._last_iter = None
        self._last_print_iter = None
        self.samples_per_sec = float("nan")
        self.batches_per_sec = float("nan")

    def iteration_done(self, sd, epoch, iteration, loss):
        self.iterations_done(sd, epoch, [iteration], [loss])

    def iterations_done(self, sd, epoch, iterations, losses):
        # burst delivery: timing spans the whole burst, so rates stay
        # honest — and the listener no longer forces per-step syncs
        now = time.perf_counter()
        iteration = iterations[-1]
        if self._last_time is not None and iteration > self._last_iter:
            dt = now - self._last_time
            n_batches = iteration - self._last_iter
            self.batches_per_sec = n_batches / dt
            if self.batch_size:
                self.samples_per_sec = self.batch_size * self.batches_per_sec
            # bursts may arrive more often than this listener's frequency
            # (the fit loop flushes at the MIN frequency across listeners) —
            # keep printing on our own cadence
            if self._last_print_iter is None or \
                    iteration - self._last_print_iter >= self.frequency:
                self._last_print_iter = iteration
                self.print_fn(
                    f"iteration {iteration}: {self.batches_per_sec:.1f} batches/sec"
                    + (f", {self.samples_per_sec:.1f} samples/sec"
                       if self.batch_size else ""))
        self._last_time = now
        self._last_iter = iteration


class CheckpointListener(Listener):
    """Periodic model save (reference: optimize/listeners/CheckpointListener
    + autodiff/listeners/checkpoint/CheckpointListener): keep-last-N,
    every-N-epochs.

    Legacy whole-model-zip variant. Production checkpointing lives in
    ``deeplearning4j_tpu.checkpoint`` (``checkpoint.CheckpointListener``):
    asynchronous writes, atomic commits with integrity manifests,
    iteration/seconds cadences, retention policies, and bit-exact
    resume including updater/RNG state."""

    def __init__(self, save_dir, every_n_epochs: int = 1, keep_last: int = 3):
        import os
        self.save_dir = str(save_dir)
        self.every_n_epochs = every_n_epochs
        self.keep_last = keep_last
        self._saved: List[str] = []
        os.makedirs(self.save_dir, exist_ok=True)

    def on_epoch_end(self, sd, epoch, mean_loss):
        import os
        if (epoch + 1) % self.every_n_epochs != 0:
            return
        path = os.path.join(self.save_dir, f"checkpoint_epoch_{epoch}.zip")
        sd.save(path, include_updater_state=True)
        self._saved.append(path)
        while len(self._saved) > self.keep_last:
            old = self._saved.pop(0)
            if os.path.exists(old):
                os.remove(old)

    def last_checkpoint(self) -> Optional[str]:
        return self._saved[-1] if self._saved else None


class EarlyStoppingListener(Listener):
    """Stop when the score stops improving (reference: earlystopping/
    EarlyStoppingTrainer + termination conditions, compressed into a
    listener since fit() owns the loop here)."""

    def __init__(self, patience: int = 5, min_delta: float = 0.0,
                 max_epochs: Optional[int] = None):
        self.patience = patience
        self.min_delta = min_delta
        self.max_epochs = max_epochs
        self.best_loss = float("inf")
        self.best_epoch = -1
        self.stopped_epoch = None

    def on_epoch_end(self, sd, epoch, mean_loss):
        if mean_loss < self.best_loss - self.min_delta:
            self.best_loss = mean_loss
            self.best_epoch = epoch
            return None
        if epoch - self.best_epoch >= self.patience or \
                (self.max_epochs is not None and epoch + 1 >= self.max_epochs):
            self.stopped_epoch = epoch
            return False
        return None


class FailureTestingListener(Listener):
    """Fault injection for robustness testing (reference:
    optimize/listeners/FailureTestingListener.java:19 — FailureMode
    {OOM, SYSTEM_EXIT_1, ILLEGAL_STATE, INFINITE_SLEEP} x CallType
    trigger points). TPU-native subset: raising and sleeping; process
    exit/OOM are not simulated in-process (the elastic-restart test
    kills training with the EXCEPTION mode instead, see
    parallel/multihost.ElasticTrainer).

    failure_mode: "exception" | "illegal_state" | "sleep"
    trigger: "epoch_start" | "epoch_end" | "iteration" | "training_start"
    at: epoch or iteration number that fires the fault (-1 = first call)
    sleep_seconds: used by the sleep mode
    """

    class InjectedFailure(RuntimeError):
        pass

    #: deliver scalars every iteration — a fault at iteration N must fire
    #: before N+1 trains, not at the next burst flush
    frequency = 1

    def __init__(self, failure_mode: str = "exception",
                 trigger: str = "iteration", at: int = -1,
                 sleep_seconds: float = 0.1):
        self.failure_mode = failure_mode.lower()
        self.trigger = trigger.lower()
        self.at = at
        self.sleep_seconds = sleep_seconds
        self.fired = False

    def _fire(self, where: str):
        self.fired = True
        if self.failure_mode == "sleep":
            time.sleep(self.sleep_seconds)
            return
        if self.failure_mode == "illegal_state":
            raise RuntimeError(
                f"FailureTestingListener: injected illegal state at {where}")
        raise FailureTestingListener.InjectedFailure(
            f"FailureTestingListener: injected failure at {where}")

    def _should(self, n: int) -> bool:
        return not self.fired and (self.at < 0 or n == self.at)

    def on_training_start(self, sd):
        if self.trigger == "training_start" and self._should(0):
            self._fire("training start")

    def on_epoch_start(self, sd, epoch):
        if self.trigger == "epoch_start" and self._should(epoch):
            self._fire(f"epoch {epoch} start")

    def on_epoch_end(self, sd, epoch, mean_loss):
        if self.trigger == "epoch_end" and self._should(epoch):
            self._fire(f"epoch {epoch} end")

    def iteration_done(self, sd, epoch, iteration, loss):
        if self.trigger == "iteration" and self._should(iteration):
            self._fire(f"iteration {iteration}")
