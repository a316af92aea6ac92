"""Device-side per-layer tensor statistics — the in-graph half of the
DL4J ``BaseStatsListener`` parity story.

The reference streams per-layer parameter/gradient/update histograms and
update:param ratios to its web UI from *inside* the per-op interpreter
(ui-model/.../stats/BaseStatsListener.java). Our port could only diff
host copies of parameters at epoch boundaries (``ui/stats.StatsListener``)
— under the fused-window tier gradients never reach the host at all, so
the single most diagnostic training-health signal (per-layer grad norms,
dead/exploding-layer detection) was invisible.

This module computes those summaries *inside* the jitted train step:

- **stat families** (``TensorStatsConfig.families``): ``grads`` (the raw
  per-step gradients, pre-clip — the diagnostic signal), ``updates``
  (the post-clip/post-updater update tensor ``u`` the step SUBTRACTS,
  ``new_params = params - u`` — the DL4J StatsListener convention, so
  its sign follows the gradient, not the parameter movement; the
  applied delta is ``-u``) and ``params`` (the post-update parameters);
- **per-layer summary vector**: L2 norm, mean |x|, min, max, nonfinite
  count, zero count (``SCALAR_FIELDS`` order) — every leaf reduces to
  the same fixed-size vector regardless of its shape, so the per-family
  result stacks to ``(layers, 6)``;
- **fixed log2-magnitude histogram**: ``hist_bins`` bins over
  ``floor(log2|x|)`` clipped to ``[hist_min_exp, hist_min_exp +
  hist_bins)`` — a dtype-health view (how much of a tensor sits near
  underflow / overflow) whose bin edges never move, so histograms are
  comparable across steps, layers and runs (unlike the reference's
  data-dependent bin ranges).

Sampling is **in-graph**: the step body evaluates the summaries under a
``lax.cond`` only on steps where :func:`sample_mask` fires (every
``every_n``-th step; with gradient accumulation, every ``every_n``-th
*update* so the ``updates`` family always describes a real apply). The
fused-window tier folds the sampled stats into the ``lax.scan`` carry
exactly like the divergence sentinel (faults/sentinels.py): a K-step
window returns ONE stats pytree (the last sampled step's) plus the
int32 iteration it was sampled at (``-1`` = no sample point in this
window), and the host fetches it at the flush boundaries it already
syncs on — in the same ``device_get`` burst as losses and sentinel
verdicts. Parameter math is untouched: stats-on training is
bit-identical to stats-off (tested).

Host side, :func:`build_record` turns a fetched stats pytree into one
``{"type": "tensorstats"}`` record (ui/stats.py schema), delivered to
listeners through the ``tensorstats_done`` rail; :class:`MonitorListener
<deeplearning4j_tpu.monitor.steptime.MonitorListener>` persists + folds
them (``dl4j_layer_*``) and :class:`LayerHealthWatcher` turns a dead or
exploding layer into a structured, recoverable fault.

See docs/observability.md ("Tensor statistics").
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

#: per-leaf summary vector layout (the (layers, 6) scalar stack)
SCALAR_FIELDS = ("l2", "mean_abs", "min", "max", "nonfinite", "zeros")

#: family name -> record field prefix ("grads" -> "grad_l2", ...)
FAMILY_PREFIX = {"grads": "grad", "updates": "update", "params": "param"}

#: canonical family order (configs normalize to this, cache keys are
#: stable under permuted user input)
_FAMILY_ORDER = ("grads", "updates", "params")


@dataclasses.dataclass(frozen=True)
class TensorStatsConfig:
    """Sampling cadence + stat shape for the in-graph tensor statistics.

    ``every_n``: sample every Nth step (absolute iterations; with
    ``accum_steps > 1`` every Nth *update*, aligned to apply
    boundaries). The overhead tier: stats cost is paid only on sampled
    steps (``lax.cond``), so the amortized cost scales as 1/every_n;
    cost on the chip: not measured (ROADMAP D5).
    ``families``: which of grads/updates/params to summarize.
    ``hist_bins``/``hist_min_exp``: the fixed log2-magnitude histogram
    covers exponents ``[hist_min_exp, hist_min_exp + hist_bins)``;
    values outside clip to the edge bins.
    ``sample_cap``: distribution stats (mean |x|, min/max, zero count,
    the histogram, the sampled nonfinite count) are computed over a
    deterministic strided subsample of at most this many elements per
    leaf (0 = exact full-tensor stats). The L2 norm is ALWAYS exact —
    it feeds ``update_ratio``, the layer-health signal — and its
    full-tensor accumulator also lower-bounds the nonfinite count (a
    NaN/Inf anywhere poisons the sum even when the subsample missed
    it). The config is frozen (it is baked into compiled-program cache
    keys via :meth:`key`).
    """
    every_n: int = 25
    families: Tuple[str, ...] = _FAMILY_ORDER
    hist_bins: int = 20
    hist_min_exp: int = -16
    sample_cap: int = 16384

    def __post_init__(self):
        if int(self.every_n) < 1:
            raise ValueError("tensorstats every_n must be >= 1")
        if int(self.hist_bins) < 1:
            raise ValueError("tensorstats hist_bins must be >= 1")
        if int(self.sample_cap) < 0:
            raise ValueError("tensorstats sample_cap must be >= 0 "
                             "(0 = exact)")
        fams = tuple(f for f in _FAMILY_ORDER if f in tuple(self.families))
        unknown = set(self.families) - set(_FAMILY_ORDER)
        if unknown or not fams:
            raise ValueError(
                f"tensorstats families must be a non-empty subset of "
                f"{_FAMILY_ORDER}, got {tuple(self.families)}")
        object.__setattr__(self, "every_n", int(self.every_n))
        object.__setattr__(self, "families", fams)
        object.__setattr__(self, "hist_bins", int(self.hist_bins))
        object.__setattr__(self, "hist_min_exp", int(self.hist_min_exp))
        object.__setattr__(self, "sample_cap", int(self.sample_cap))

    def key(self) -> tuple:
        """Hashable identity for compiled-program cache keys: two
        configs with equal keys trace to identical programs."""
        return (self.every_n, self.families, self.hist_bins,
                self.hist_min_exp, self.sample_cap)

    def to_json(self) -> dict:
        return {"every_n": self.every_n, "families": list(self.families),
                "hist_bins": self.hist_bins,
                "hist_min_exp": self.hist_min_exp,
                "sample_cap": self.sample_cap}

    @staticmethod
    def from_json(d) -> "Optional[TensorStatsConfig]":
        if d is None or d is False:
            return None
        if d is True:
            return TensorStatsConfig()
        return TensorStatsConfig(
            every_n=d.get("every_n", 25),
            families=tuple(d.get("families", _FAMILY_ORDER)),
            hist_bins=d.get("hist_bins", 20),
            hist_min_exp=d.get("hist_min_exp", -16),
            sample_cap=d.get("sample_cap", 16384))


def layer_names(params: Dict[str, object]) -> Tuple[str, ...]:
    """THE canonical layer order: sorted trainable-param names. The
    device-side stat rows (``summarize_tree``/``compute_stats``) and
    the host-side record labels (``build_record``) must agree
    element-for-element — every call site goes through this ONE
    helper, because a silent ordering drift would attribute every
    layer's stats to the wrong name with no error (the same
    single-key-construction rule as ``window_trace_set``)."""
    return tuple(sorted(params.keys()))


def normalize(cfg) -> Optional[TensorStatsConfig]:
    """``TrainingConfig.tensorstats`` accepts ``True`` (defaults), a
    :class:`TensorStatsConfig`, or a serde dict — canonicalize."""
    if cfg is None or cfg is False:     # False = disabled, like sentinel
        return None
    if isinstance(cfg, TensorStatsConfig):
        return cfg
    if cfg is True:
        return TensorStatsConfig()
    if isinstance(cfg, dict):
        return TensorStatsConfig.from_json(cfg)
    raise TypeError(f"tensorstats must be True, a TensorStatsConfig or "
                    f"a dict, got {type(cfg).__name__}")


# ---------------------------------------------------------------------------
# traced (device-side) summaries — called only inside jit traces

def summarize_leaf(x, cfg: TensorStatsConfig):
    """One leaf -> ``((6,) float32 scalars, (hist_bins,) int32 hist)``.

    Engineered for the in-scan hot path (the naive full-tensor
    formulation cost ~10x a train step per sampled step on CPU):

    - ``l2`` is EXACT, via one dot-product over the full tensor (the
      one reduction backends run at memory bandwidth) — it feeds
      ``update_ratio``, the layer-health signal. Nonfinite entries
      propagate into it: a poisoned layer has no meaningful norm, and
      a NaN l2 is itself diagnostic.
    - the distribution stats (mean |x|, min/max over finite entries,
      zero count, sampled nonfinite count, histogram) run over a
      deterministic strided subsample of ≤ ``sample_cap`` elements
      (exact when the leaf is smaller). ``nonfinite`` is
      lower-bounded by the full-tensor norm accumulator: any NaN/Inf
      poisons the dot even when the subsample misses it, reporting at
      least 1. (An f32-overflowing norm reads the same way — by the
      time ``sum(x^2)`` exceeds f32 range the layer IS exploding.)
    - histogram binning reads ``floor(log2|x|)`` straight from the
      float32 exponent bits (no transcendental per element); denormals
      clip into the lowest bin, zeros and nonfinites are excluded.
    """
    import jax
    import jax.numpy as jnp
    xf = jnp.ravel(x).astype(jnp.float32)
    n = xf.size
    sumsq = jnp.vdot(xf, xf)
    l2 = jnp.sqrt(sumsq)
    cap = cfg.sample_cap
    stride = max(1, -(-n // cap)) if cap else 1
    xs = xf[::stride]
    m = max(1, xs.size)
    finite = jnp.isfinite(xs)
    xz = jnp.where(finite, xs, 0.0)
    bits = jax.lax.bitcast_convert_type(xs, jnp.int32)
    biased_exp = (bits >> 23) & 0xFF
    nonzero = (bits & 0x7FFFFFFF) != 0
    nonfinite = jnp.maximum(
        jnp.sum(jnp.logical_not(finite)),
        jnp.logical_not(jnp.isfinite(sumsq)).astype(jnp.int32))
    scalars = jnp.stack([
        l2, jnp.sum(jnp.abs(xz)) / m,
        jnp.min(jnp.where(finite, xs, jnp.inf)),
        jnp.max(jnp.where(finite, xs, -jnp.inf)),
        nonfinite.astype(jnp.float32),
        jnp.sum(finite & jnp.logical_not(nonzero)).astype(jnp.float32)])
    # floor(log2|x|) == biased_exp - 127 for normal floats
    idx = jnp.clip(biased_exp - 127 - cfg.hist_min_exp, 0,
                   cfg.hist_bins - 1)
    mask = finite & nonzero
    # one-hot sum, not scatter-add: B small vectorized passes over the
    # subsample beat XLA-CPU's serial scatter by ~10x
    onehot = (idx[:, None] == jnp.arange(cfg.hist_bins)[None, :]) \
        & mask[:, None]
    hist = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    return scalars, hist


def summarize_tree(tree: Dict[str, object], names: Sequence[str],
                   cfg: TensorStatsConfig):
    """Stack per-leaf summaries over ``names`` (the canonical sorted
    layer order) -> ``((L, 6) scalars, (L, hist_bins) hist)``."""
    import jax.numpy as jnp
    scalars, hists = [], []
    for n in names:
        s, h = summarize_leaf(tree[n], cfg)
        scalars.append(s)
        hists.append(h)
    return jnp.stack(scalars), jnp.stack(hists)


def compute_stats(cfg: TensorStatsConfig, names: Sequence[str],
                  grads=None, updates=None, params=None):
    """The sampled-branch payload: ``{family: (scalars, hist)}`` for
    every configured family (callers pass the trees the step already
    produced)."""
    trees = {"grads": grads, "updates": updates, "params": params}
    out = {}
    for fam in cfg.families:
        tree = trees[fam]
        if tree is None:
            raise ValueError(f"tensorstats family {fam!r} configured but "
                             f"no tree passed")
        out[fam] = summarize_tree(tree, names, cfg)
    return out


def zeros_stats(n_layers: int, cfg: TensorStatsConfig):
    """The not-sampled-branch payload: the same pytree structure, all
    zeros (shape-stable across the ``lax.cond``)."""
    import jax.numpy as jnp
    return {fam: (jnp.zeros((n_layers, len(SCALAR_FIELDS)), jnp.float32),
                  jnp.zeros((n_layers, cfg.hist_bins), jnp.int32))
            for fam in cfg.families}


def sample_mask(iteration, cfg: TensorStatsConfig, accum_steps: int = 1):
    """Traced sampling predicate for the absolute ``iteration``.

    Plain training samples every ``every_n``-th step. With gradient
    accumulation the cadence counts *updates* and aligns to apply
    boundaries — a mid-cycle micro-step has a zero ``updates`` delta
    that would read as a dead layer, so sampling there is banned by
    construction."""
    if accum_steps <= 1:
        return iteration % cfg.every_n == 0
    nxt = iteration + 1
    return (nxt % accum_steps == 0) & \
        ((nxt // accum_steps) % cfg.every_n == 0)


# ---------------------------------------------------------------------------
# host side: fetched stats -> {"type": "tensorstats"} records

def build_record(names: Sequence[str], stats: Dict[str, tuple],
                 iteration: int, epoch: int,
                 cfg: TensorStatsConfig) -> dict:
    """One fetched stats pytree (host numpy after ``device_get``) ->
    one ``{"type": "tensorstats"}`` record (schema: ui/stats.py).

    Non-finite float stats serialize as ``None``, never NaN/Infinity —
    ``json.dumps`` would emit the non-RFC ``NaN`` token and corrupt the
    JSONL file and the /stats NDJSON for strict parsers. No signal is
    lost: the ``*_nonfinite`` counts (exact-lower-bounded by the norm
    accumulator) are what carry the poison diagnostic."""
    import math

    import numpy as np

    def _clean(v: float):
        return v if math.isfinite(v) else None

    layers: Dict[str, dict] = {}
    for li, name in enumerate(names):
        ent: Dict[str, object] = {}
        for fam, (scalars, hist) in stats.items():
            pfx = FAMILY_PREFIX[fam]
            row = np.asarray(scalars)[li]
            for fi, field in enumerate(SCALAR_FIELDS):
                v = float(row[fi])
                ent[f"{pfx}_{field}"] = int(v) \
                    if field in ("nonfinite", "zeros") else _clean(v)
            ent[f"{pfx}_hist"] = [int(c) for c in np.asarray(hist)[li]]
        if ent.get("update_l2") is not None and \
                ent.get("param_l2") is not None:
            ent["update_ratio"] = ent["update_l2"] / \
                (ent["param_l2"] + 1e-12)
        elif "update_l2" in ent and "param_l2" in ent:
            ent["update_ratio"] = None      # poisoned norm -> no ratio
        layers[name] = ent
    return {"type": "tensorstats", "iter": int(iteration),
            "epoch": int(epoch), "t": time.time(),
            "every_n": cfg.every_n, "hist_min_exp": cfg.hist_min_exp,
            "layers": layers}


class LayerHealthWatcher:
    """Listener-rail watcher over ``tensorstats`` records: raises a
    structured :class:`~deeplearning4j_tpu.faults.errors.
    TrainingDivergedError` when a layer goes **dead** (update:param
    ratio below ``dead_ratio`` for ``patience`` consecutive samples —
    the optimizer has stopped moving it) or **exploding** (ratio above
    ``explode_ratio`` — the update is rewriting the parameter
    wholesale). The per-layer counterpart of
    :class:`~deeplearning4j_tpu.faults.sentinels.LossSpikeWatcher`:
    riding the same listener rail, it makes ``FaultTolerantFit`` roll
    back on layer-level pathologies a healthy-looking loss curve hides
    (docs/fault_tolerance.md).

    A **poisoned** layer (any family's nonfinite count > 0 — the
    record's ratio is ``None`` because the norms are meaningless) is
    flagged immediately, warmup included (``flag_nonfinite=True``):
    this is the listener-rail backstop for runs without the device
    sentinel, and a NaN ratio must never slip through the threshold
    comparisons unflagged.

    ``warmup`` samples per layer are observed before dead/exploding
    verdicts fire (init transients routinely look dead or hot).
    ``reset()`` forgets all state — FaultTolerantFit calls it on
    rollback so replayed timelines are judged fresh. Decisions are
    appended to ``events`` and published as ``{"type": "faults",
    "event": "layer_health"}`` records when a storage is attached.
    """

    #: epoch-only cadence ask: never forces extra mid-epoch flushes
    #: (same huge-frequency idiom as PlateauWatcher) — the watcher
    #: rides whatever tensorstats cadence the run already has
    frequency = 1_000_000_000

    def __init__(self, dead_ratio: float = 1e-9,
                 explode_ratio: float = 1.0, patience: int = 3,
                 warmup: int = 2, storage=None,
                 flag_nonfinite: bool = True):
        if explode_ratio <= dead_ratio:
            raise ValueError("explode_ratio must exceed dead_ratio")
        self.dead_ratio = float(dead_ratio)
        self.explode_ratio = float(explode_ratio)
        self.patience = max(1, int(patience))
        self.warmup = max(0, int(warmup))
        self.storage = storage
        self.flag_nonfinite = bool(flag_nonfinite)
        self.events: List[dict] = []
        self.reset()

    def reset(self) -> None:
        """Forget per-layer sample counts and dead-streaks (the
        rollback listener-reset hook, faults/recovery.py)."""
        self._seen: Dict[str, int] = {}
        self._dead_streak: Dict[str, int] = {}

    def _flag(self, cause: str, layer: str, ratio: float, record: dict):
        import math
        ev = {"type": "faults", "event": "layer_health", "cause": cause,
              "layer": layer,
              "ratio": ratio if math.isfinite(ratio) else None,
              "iter": record.get("iter"), "t": time.time()}
        self.events.append(ev)
        if self.storage is not None:
            self.storage.put(ev)
        from deeplearning4j_tpu.faults.errors import TrainingDivergedError
        raise TrainingDivergedError(
            f"layer {layer!r} {cause.replace('_', ' ')}: update:param "
            f"ratio {ratio:.3g} at iteration {record.get('iter')} "
            f"(dead < {self.dead_ratio:.3g}, exploding > "
            f"{self.explode_ratio:.3g})",
            step=record.get("iter"), epoch=record.get("epoch"),
            cause=cause, value=ratio)

    # -- listener rail (duck-typed: the only callback that matters is
    # tensorstats_done; the rest of the protocol is no-op) --------------
    def on_training_start(self, sd) -> None: ...
    def on_training_end(self, sd) -> None: ...
    def on_epoch_start(self, sd, epoch: int) -> None: ...
    def on_epoch_end(self, sd, epoch: int, mean_loss) -> None: ...
    def iterations_done(self, sd, epoch: int, iterations, losses) -> None:
        ...

    def tensorstats_done(self, sd, epoch: int,
                         records: Sequence[dict]) -> None:
        for rec in records:
            for layer, ent in rec.get("layers", {}).items():
                if self.flag_nonfinite and any(
                        ent.get(f"{p}_nonfinite", 0)
                        for p in FAMILY_PREFIX.values()):
                    # poisoned layer: the ratio is None/meaningless and
                    # would otherwise sail past both threshold checks —
                    # flag regardless of warmup (categorical, not a
                    # transient)
                    self._flag("poisoned_layer", layer,
                               float("nan"), rec)
                ratio = ent.get("update_ratio")
                if ratio is None:
                    continue
                seen = self._seen.get(layer, 0)
                self._seen[layer] = seen + 1
                if seen < self.warmup:
                    continue
                if ratio > self.explode_ratio:
                    self._flag("exploding_layer", layer, float(ratio),
                               rec)
                if ratio < self.dead_ratio:
                    streak = self._dead_streak.get(layer, 0) + 1
                    self._dead_streak[layer] = streak
                    if streak >= self.patience:
                        self._flag("dead_layer", layer, float(ratio),
                                   rec)
                else:
                    self._dead_streak[layer] = 0


__all__ = ["TensorStatsConfig", "LayerHealthWatcher", "SCALAR_FIELDS",
           "FAMILY_PREFIX", "summarize_leaf", "summarize_tree",
           "compute_stats", "zeros_stats", "sample_mask", "build_record",
           "normalize", "layer_names"]
