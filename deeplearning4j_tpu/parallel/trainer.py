"""Parallel training and serving over a DeviceMesh.

Reference parity:
- Training: NEW capability (the reference's ParallelWrapper/
  GradientsAccumulator data-parallel training was removed upstream,
  SURVEY.md §2.5). TPU-native design: place params/batch with
  NamedShardings and jit the SAME whole-graph train step SameDiff already
  compiles — GSPMD propagates shardings and inserts AllReduce over ICI for
  gradients; there is no separate "gradient sharing" code path to write.
- Serving: ParallelInference (deeplearning4j-parallelwrapper
  ParallelInference.java:54) ran N model replicas on N GPUs with
  host-thread affinity + dynamic batching; here a batch sharded over the
  'data' axis runs on all chips inside one compiled computation.
"""
from __future__ import annotations

import time
from typing import Optional, Sequence

import jax
import numpy as np

from deeplearning4j_tpu.monitor.trace import TRACER as _tracer
from deeplearning4j_tpu.parallel.mesh import DeviceMesh
from deeplearning4j_tpu.parallel.sharding import (
    ShardingSpec, ShardingStrategy, data_parallel)


def shard_model(model_or_sd, strategy: ShardingStrategy) -> None:
    """Commit a model's parameter/state/constant arrays to the
    strategy's mesh shardings (the placement half of ParallelTrainer,
    shared with the ``TrainingConfig.sharding`` fit path and the
    resharded-restore path in checkpoint/reshard.py)."""
    sd = getattr(model_or_sd, "samediff", model_or_sd)
    st = strategy
    moved = False

    def _place(v, sharding):
        nonlocal moved
        moved = moved or not v.sharding.is_equivalent_to(sharding, v.ndim)
        return jax.device_put(v, sharding)

    for n, v in sd.trainable_params().items():
        sd._arrays[n] = _place(v, st.param_sharding(n, v.ndim))
    for n, v in sd.state_vars_map().items():
        sd._arrays[n] = _place(v, st.param_sharding(n, v.ndim))
    for n, v in sd.constants_map().items():
        sd._arrays[n] = _place(v, st.replicated())
    if moved:
        # executables precompiled for the old placement would reject
        # the re-placed arrays (compilecache/aot.py)
        sd._drop_aot_executables()
    # precompile() reads each array's placement off the array; what it
    # cannot see there is how fit() will shard the BATCHES
    sd._placement_strategy = st
    if sd._updater_state is not None:
        # updater state leaves mirror their parameter's sharding
        new_state = {}
        for pname, leaves in sd._updater_state.items():
            sh = st.param_sharding(pname, np.ndim(
                sd._arrays[pname]) if pname in sd._arrays else 0)
            new_state[pname] = tuple(jax.device_put(l, sh) for l in leaves) \
                if isinstance(leaves, tuple) else jax.device_put(leaves, sh)
        sd._updater_state = new_state


def resolve_strategy(sd, spec_or_strategy) -> ShardingStrategy:
    """A live ShardingStrategy from either a strategy (as-is) or a
    declarative ShardingSpec, cached on the SameDiff per (spec json,
    device count) so repeated fits reuse one mesh."""
    if isinstance(spec_or_strategy, ShardingStrategy):
        return spec_or_strategy
    spec: ShardingSpec = spec_or_strategy
    import json
    key = (json.dumps(spec.to_json(), sort_keys=True), len(jax.devices()))
    cache = sd.__dict__.setdefault("_sharding_strategies", {})
    strat = cache.get(key)
    if strat is None:
        strat = cache[key] = spec.build(model=sd)
    return strat


def ensure_sharded(sd, spec_or_strategy, dataset_iterator):
    """The ``TrainingConfig.sharding`` fit hook: place the model on the
    spec's mesh and wrap the input iterator so batches land pre-sharded.
    A no-op when the iterator is already a _ShardedIterator (e.g. the
    fit was routed through ParallelTrainer, whose explicit strategy
    wins over the config spec)."""
    if isinstance(dataset_iterator, _ShardedIterator):
        return dataset_iterator
    strategy = resolve_strategy(sd, spec_or_strategy)
    shard_model(sd, strategy)
    return _ShardedIterator(dataset_iterator, strategy)


class _ShardedIterator:
    """Wraps a dataset iterator, placing each batch with the strategy's
    batch sharding (host→HBM transfer lands pre-sharded; the analogue of
    the reference's AsyncDataSetIterator device feed)."""

    def __init__(self, it, strategy: ShardingStrategy):
        self._it = it
        self._strategy = strategy
        # expose stacked_batches ONLY when the wrapped source has it, so
        # the scanned/cached-window fast tiers (which route on a hasattr
        # probe) survive the wrap: stacked (steps, batch, ...) arrays
        # land with the steps axis replicated and batch axes sharded —
        # wrapping a device-cached source no longer demotes the fit to
        # the streaming tier
        if callable(getattr(it, "stacked_batches", None)):
            self.stacked_batches = self._stacked_batches

    def reset(self):
        if hasattr(self._it, "reset"):
            self._it.reset()

    def _place(self, a):
        a = np.asarray(a)
        return jax.device_put(a, self._strategy.batch_sharding(a.ndim))

    def _place_stacked(self, a):
        import jax.numpy as jnp
        a = jnp.asarray(a)
        return jax.device_put(a, self._strategy.window_sharding(a.ndim))

    def _stacked_batches(self):
        feats, labels = self._it.stacked_batches()
        return ([self._place_stacked(f) for f in feats],
                [self._place_stacked(l) for l in labels])

    def window_sharding(self, ndim: int):
        """Fused-window placement hook (autodiff/window.py probes for
        this): stacked (K, batch, ...) windows land with the steps axis
        replicated and the batch axes sharded as usual."""
        return self._strategy.window_sharding(ndim)

    def __iter__(self):
        for batch in self._it:
            if isinstance(batch, dict):
                yield {k: self._place(v) for k, v in batch.items()}
            elif hasattr(batch, "features") and hasattr(batch, "labels"):
                yield (self._place(batch.features), self._place(batch.labels))
            elif isinstance(batch, (tuple, list)) and len(batch) == 2:
                f, l = batch
                fs = [self._place(x) for x in (f if isinstance(f, (list, tuple)) else [f])]
                ls = [self._place(x) for x in (l if isinstance(l, (list, tuple)) else [l])]
                yield (fs if len(fs) > 1 else fs[0],
                       ls if len(ls) > 1 else ls[0])
            else:
                yield batch


class ParallelTrainer:
    """Trains a SameDiff (or MultiLayerNetwork) across a mesh.

    Params are committed to their strategy shardings; the already-compiled
    train step follows input shardings (GSPMD), so DP/TP need no new
    step code — collectives appear in the compiled computation.
    """

    def __init__(self, model, strategy: Optional[ShardingStrategy] = None,
                 mesh: Optional[DeviceMesh] = None,
                 stats_storage=None):
        # accept MultiLayerNetwork or SameDiff
        self.sd = getattr(model, "samediff", model)
        self.model = model
        if strategy is None:
            # a declarative TrainingConfig.sharding spec is the next
            # most specific intent; fall back to pure DP over the mesh
            spec = getattr(getattr(self.sd, "training_config", None),
                           "sharding", None)
            if spec is not None and mesh is None:
                strategy = resolve_strategy(self.sd, spec)
            else:
                strategy = data_parallel(mesh or DeviceMesh.create())
        self.strategy = strategy
        self.stats_storage = stats_storage
        #: info dict of the last restore that crossed a topology change
        #: (None when the last restore matched the manifest topology)
        self.last_reshard: Optional[dict] = None

    def shard_params(self) -> None:
        """Commit parameter/state arrays to their mesh shardings."""
        shard_model(self.sd, self.strategy)

    def fit(self, dataset_iterator, epochs: int = 1, listeners: Sequence = ()):
        """Listeners pass through to the underlying SameDiff fit — a
        checkpoint.CheckpointListener here checkpoints sharded training
        exactly like single-device training."""
        self.shard_params()
        return self.sd.fit(_ShardedIterator(dataset_iterator, self.strategy),
                           epochs=epochs, listeners=listeners)

    def restore_latest(self, manager, strict: bool = True,
                       strategy: Optional[ShardingStrategy] = None,
                       verified_only: bool = False):
        """Resume from a checkpoint.CheckpointManager: restore the newest
        committed step into the model (host arrays), then re-commit the
        arrays to their mesh shardings. Returns (step, TrainingState) or
        None when no committed checkpoint exists.

        ``strategy=`` reshards the restored state into a DIFFERENT
        sharding than the trainer was constructed with (elastic resume
        onto a changed mesh; the override becomes the trainer's
        strategy). When the checkpoint's recorded topology differs from
        the target mesh the re-placement is surfaced as a
        ``checkpoint.reshard`` span plus a ``{"type": "reshard"}``
        record, and ``self.last_reshard`` holds the summary.

        ``verified_only`` routes through the manager's fingerprint-
        verified walk (integrity/) while KEEPING the mesh re-commit
        below — the rollback-to-verified path for sharded models."""
        res = manager.restore_latest(model=self.model, strict=strict,
                                     verified_only=verified_only)
        self.last_reshard = None
        if res is not None:
            # adopt the override only once a restore actually landed —
            # swapping before a None/raising restore would leave the
            # trainer's strategy pointing at a mesh its params (still
            # placed under the old one) have never been committed to
            if strategy is not None:
                self.strategy = strategy
            step, state = res
            from_topo = (state.metadata or {}).get("topology") or {}
            to_axes = {str(k): int(v)
                       for k, v in self.strategy.mesh.mesh.shape.items()}
            # compare the SAVED mesh extent against the target mesh —
            # not the process-wide device_count, which stays at e.g. 8
            # while a sub-mesh trainer legitimately runs on 4 of them
            # (an unsharded save has mesh_axes None, which != any mesh)
            changed = bool(from_topo) and \
                from_topo.get("mesh_axes") != to_axes
            if changed:
                t0 = time.perf_counter()
                with _tracer.span("checkpoint.reshard", cat="checkpoint",
                                  step=int(step)):
                    self.shard_params()
                self.last_reshard = {
                    "step": int(step),
                    "arrays": len(state.arrays),
                    "bytes": int(state.nbytes()),
                    "seconds": round(time.perf_counter() - t0, 6),
                    "from_mesh": from_topo.get("mesh_axes"),
                    "to_mesh": to_axes,
                    "from_devices": from_topo.get("device_count"),
                    "to_devices": self.strategy.mesh.n_devices}
                if self.stats_storage is not None:
                    self.stats_storage.put({"type": "reshard",
                                            "t": time.time(),
                                            **self.last_reshard})
            else:
                self.shard_params()
        return res


class ParallelInference:
    """Mesh-wide batched inference (reference:
    parallelism/ParallelInference.java:54 — replica-per-device workers,
    BATCHED mode). One compiled computation with the batch sharded over
    'data' replaces worker threads + affinity + observable queues."""

    def __init__(self, model, strategy: Optional[ShardingStrategy] = None,
                 mesh: Optional[DeviceMesh] = None):
        self.model = model
        self.sd = getattr(model, "_sd_infer", None) or getattr(
            model, "samediff", model)
        if strategy is None:
            strategy = data_parallel(mesh or DeviceMesh.create())
        self.strategy = strategy

    def _ensure_on_mesh(self):
        """Place arrays on the mesh ONLY if they are not already there —
        existing mesh shardings (e.g. tensor-parallel params) are kept, so
        a sharded-to-fit model is never forcibly replicated."""
        sd, st = self.sd, self.strategy
        mesh_devices = frozenset(self.strategy.mesh.mesh.devices.flat)
        moved = False
        for n, v in {**sd.trainable_params(), **sd.state_vars_map(),
                     **sd.constants_map()}.items():
            if frozenset(v.sharding.device_set) != mesh_devices:
                sd._arrays[n] = jax.device_put(v, st.replicated())
                moved = True
        if moved:
            # as in shard_model: executables lowered for the old
            # placement would reject the re-placed arrays
            sd._drop_aot_executables()

    def output(self, x, output_names: Optional[Sequence[str]] = None):
        if hasattr(self.model, "_sync_infer"):
            self.model._sync_infer()
        sd, st = self.sd, self.strategy
        self._ensure_on_mesh()
        x = np.asarray(x)
        x = jax.device_put(x, st.batch_sharding(x.ndim))
        if output_names:
            names = list(output_names)
        elif sd.has_variable("output"):
            names = ["output"]                 # MultiLayerNetwork contract
        else:
            # ComputationGraph: resolve declared outputs via its name map
            conf = getattr(self.model, "conf", None)
            name_map = getattr(self.model, "_map_infer", None) or \
                getattr(self.model, "_map_train", None)
            if conf is not None and name_map is not None:
                names = [name_map[o] for o in conf.outputs]
            else:
                names = ["output"]
        ph_name = "input" if sd.has_variable("input") else sd.placeholders()[0]
        res = sd.output({ph_name: x}, names)
        return res[names[0]] if len(names) == 1 else res


class BatchedParallelInference:
    """Dynamic-batching serving mode (reference: ParallelInference
    InferenceMode.BATCHED + observers/BatchedInferenceObservable.java —
    concurrent observe() calls coalesce into one model invocation).

    TPU-native design: requests enqueue from any thread; a single
    dispatcher thread drains the queue, concatenates up to
    ``max_batch_size`` rows (waiting at most ``max_wait_ms`` after the
    first request), runs ONE compiled forward over the mesh, and scatters
    row slices back to per-request futures. One XLA computation per
    coalesced batch replaces the reference's worker threads + device
    affinity."""

    def __init__(self, model, strategy: Optional[ShardingStrategy] = None,
                 mesh: Optional[DeviceMesh] = None,
                 max_batch_size: int = 32, max_wait_ms: float = 5.0):
        import queue as _queue
        import threading
        self._inner = ParallelInference(model, strategy=strategy, mesh=mesh)
        self.max_batch_size = int(max_batch_size)
        self.max_wait_ms = float(max_wait_ms)
        self._q: "_queue.Queue" = _queue.Queue()
        self._closed = False
        self._lock = threading.Lock()     # submit/close atomicity
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()
        self.batches_dispatched = 0       # observability (reference:
        self.requests_served = 0          # observer counts)

    # -- client side ----------------------------------------------------
    def submit(self, x):
        """Enqueue one request (features (b, ...)); returns a Future whose
        result is the model output rows for exactly this request."""
        from concurrent.futures import Future
        fut: Future = Future()
        with self._lock:
            if self._closed:
                raise RuntimeError("BatchedParallelInference is closed")
            self._q.put((np.asarray(x), fut))
        return fut

    def output(self, x):
        """Synchronous convenience (single request)."""
        return self.submit(x).result()

    def close(self):
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._q.put(None)
        self._worker.join(timeout=5)
        # fail any request that raced past the sentinel rather than
        # leaving its Future unresolved forever
        import queue as _queue
        while True:
            try:
                item = self._q.get_nowait()
            except _queue.Empty:
                break
            if item is not None and not item[1].done():
                item[1].set_exception(
                    RuntimeError("BatchedParallelInference closed"))

    # -- dispatcher -----------------------------------------------------
    def _loop(self):
        import queue as _queue
        import time as _time
        while True:
            item = self._q.get()
            if item is None:
                return
            batch = [item]
            rows = item[0].shape[0]
            deadline = _time.monotonic() + self.max_wait_ms / 1000.0
            while rows < self.max_batch_size:
                timeout = deadline - _time.monotonic()
                if timeout <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=timeout)
                except _queue.Empty:
                    break
                if nxt is None:
                    self._q.put(None)     # propagate shutdown
                    break
                batch.append(nxt)
                rows += nxt[0].shape[0]
            try:
                X = np.concatenate([b[0] for b in batch], axis=0)
                # EVERY dispatch is exactly max_batch_size rows: requests
                # larger than the cap (or coalescing overshoot) are sliced
                # into max-sized dispatches, and the tail pads up — so the
                # serving hot path only ever sees ONE compiled shape
                # (per-row-count or multiple-of-max shapes would recompile)
                n_real = X.shape[0]
                m = self.max_batch_size
                outs = []
                for start in range(0, n_real, m):
                    sl = X[start:start + m]
                    if sl.shape[0] < m:
                        sl = np.concatenate(
                            [sl, np.repeat(sl[-1:], m - sl.shape[0], 0)], 0)
                    out = self._inner.output(sl)
                    out = out[0] if isinstance(out, list) else out
                    outs.append(np.asarray(out.data))
                    self.batches_dispatched += 1
                arr = np.concatenate(outs, axis=0)[:n_real]
                off = 0
                for feats, fut in batch:
                    n = feats.shape[0]
                    fut.set_result(arr[off:off + n])
                    off += n
                    self.requests_served += 1
            except Exception as e:       # pragma: no cover - error path
                for _, fut in batch:
                    if not fut.done():
                        fut.set_exception(e)
