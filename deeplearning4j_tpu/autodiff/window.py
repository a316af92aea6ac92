"""Fused multi-step training windows: K train steps per compiled dispatch.

On small models the per-step fit tier pays one host dispatch per step
for a step the device finishes quickly (how much of the step that is
on the current chip is not measured yet — PERF.md), and the scanned
whole-epoch tier that fixes this was only reachable with zero listeners
and a fully device-cached dataset. This module makes the fused path work
under PRODUCTION constraints:

- **K steps, one dispatch** — ``SameDiff.make_train_window`` scans the
  train-step body over a ``(K, batch, ...)`` stacked window, so the
  per-epoch dispatch count drops from ``steps`` to ``ceil(steps / K)``.
- **listeners keep working** — per-step losses accumulate in the scan's
  device-side ``(K,)`` output buffer; the burst-flush machinery from the
  per-step tier delivers them via ``Listener.iterations_done`` at window
  boundaries (one device→host transfer per flush). Checkpoint flushes
  stay bit-exact: params + updater state + the iteration counter sync at
  window boundaries, which is exactly the granularity the checkpoint/
  listener contract records (a saved step is always a window boundary).
  Exception: the gradient-accumulation carry is NOT part of the
  checkpoint schema — with ``accum_steps > 1`` use a checkpoint cadence
  that is a multiple of ``accum_steps`` (docs/training_performance.md).
- **streaming data keeps working** — a background ``WindowStager`` thread
  stacks the NEXT window's batches and enqueues its host→HBM transfer
  while the current window computes (double buffering, queue depth 2).
- **ragged final windows stay fused** — a tail of ``r < K`` steps is
  decomposed into power-of-two buckets (serving-style shape bucketing:
  at most ``log2(K)+1`` compiled window lengths EVER, vs one compile per
  distinct tail if dispatched raw, vs per-step dispatch if not fused).
- **gradient accumulation rides along** — ``TrainingConfig.accum_steps``
  accumulates micro-batch grads in the scan carry and applies the
  updater every N-th micro-step (see ``make_train_window``); the accum
  carry threads BETWEEN windows, so accumulation cycles may span window
  boundaries.

The reference has no analogue: DL4J dispatched per-op, its
GradientsAccumulator shared grads across workers but never fused steps.
This is the lax.scan generalization of the whole-epoch tier (SURVEY
L3/L4) to the listener + streaming-ETL workloads production runs have.
"""
from __future__ import annotations

import queue
import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.autodiff.staging import stage_fit_state
from deeplearning4j_tpu.compilecache.aot import ph_shape_sig
from deeplearning4j_tpu.integrity.watchdog import guard as _wd_guard
from deeplearning4j_tpu.monitor import memstats
from deeplearning4j_tpu.monitor.trace import TRACER as _tracer


def pow2_buckets(r: int) -> List[int]:
    """Binary decomposition of a ragged tail length into descending
    powers of two — the bounded compiled-shape set (serving/ bucketing
    idiom applied to window lengths). ``pow2_buckets(13) == [8, 4, 1]``."""
    out = []
    b = 1
    while r > 0:
        if r & 1:
            out.append(b)
        r >>= 1
        b <<= 1
    return out[::-1]


class WindowStager:
    """Background double-buffering window stager.

    Pulls raw ``{placeholder: array}`` batch dicts from ``source``,
    stacks ``window`` of them on a new leading axis, finalizes the stack
    (dtype coercion + device placement — this is where the host→HBM
    transfer of the NEXT window is enqueued while the CURRENT one
    computes), and hands ``(k, stacked)`` pairs to the consumer through
    a bounded queue (``depth=2`` → classic double buffering).

    Stacking happens host-side (one ``np.stack`` + ONE transfer per
    window) when the batches are host arrays, and device-side
    (``jnp.stack`` of resident slices) when they already live in HBM
    (DeviceCachedIterator, pre-sharded batches).

    Shutdown is leak-proof: ``close()`` (also called by ``__iter__``'s
    ``finally``) sets a stop flag, drains the queue to unblock the
    worker's bounded put, and joins the thread — abandoning the
    iterator mid-epoch cannot strand a blocked thread.
    """

    _END = object()

    def __init__(self, source, window: int, finalize=None, depth: int = 2):
        self._source = source
        self._window = max(1, int(window))
        self._finalize = finalize or (lambda d: d)
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    # -- worker side ----------------------------------------------------
    def _put(self, item) -> bool:
        """Bounded put that aborts when the consumer is gone."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _stack(self, batches: List[Dict[str, object]]):
        # the H2D stage of the window pipeline: stacking + the enqueue
        # of the next window's host→HBM transfer, on the stager thread
        # (its own swimlane in the chrome trace — overlap with the
        # consumer's dispatch lane is the double-buffering working)
        with _tracer.span("h2d_stage", cat="train", k=len(batches)):
            names = batches[0].keys()
            stacked = {}
            h2d_bytes = 0
            for n in names:
                items = [b[n] for b in batches]
                if all(isinstance(a, np.ndarray) for a in items):
                    stacked[n] = np.stack(items)
                    h2d_bytes += stacked[n].nbytes
                else:
                    stacked[n] = jnp.stack([jnp.asarray(a) for a in items])
            if h2d_bytes:
                # tagged host→HBM transfer accounting: the staging
                # bytes surface in {"type": "memory"} records
                # (memory.AllocationsTracker is thread-safe — this runs
                # on the stager thread)
                from deeplearning4j_tpu.memory import AllocationsTracker
                AllocationsTracker.get_instance().allocate(
                    "h2d_stage", h2d_bytes)
            return len(batches), self._finalize(stacked)

    def _emit_bucketed(self, buf) -> bool:
        i = 0
        for k in pow2_buckets(len(buf)):
            if not self._put(self._stack(buf[i:i + k])):
                return False
            i += k
        return True

    @staticmethod
    def _sig(batch) -> tuple:
        return tuple(sorted((n, tuple(np.shape(v)))
                            for n, v in batch.items()))

    def _worker(self):
        try:
            buf: List[Dict[str, object]] = []
            sig = None
            for b in self._source:
                if self._stop.is_set():
                    return
                # only same-shaped batches stack into one window: a
                # ragged final BATCH (fewer rows than the rest) flushes
                # the current buffer and forms its own (smaller-shape)
                # window — the same extra compiled shape the per-step
                # tier pays for it
                bsig = self._sig(b)
                if buf and bsig != sig:
                    if not self._emit_bucketed(buf):
                        return
                    buf = []
                if not buf:
                    sig = bsig
                buf.append(b)
                if len(buf) == self._window:
                    if not self._put(self._stack(buf)):
                        return
                    buf = []
            # ragged tail → bounded power-of-two buckets
            if buf and not self._emit_bucketed(buf):
                return
        except BaseException as e:     # propagate to the consumer
            self._err = e
        finally:
            # close a closeable source (generators) from THIS thread —
            # the one that iterated it: an abandoned mid-epoch stager
            # then deterministically releases whatever the source holds
            # (a streaming pipeline's prefetch workers, datapipe/)
            # instead of waiting for GC to run its finally
            close = getattr(self._source, "close", None)
            if callable(close):
                try:
                    close()
                except Exception:      # noqa: BLE001 — shutdown path;
                    pass               # the consumer's error (if any)
                #                        is already in self._err
            self._put(self._END)

    # -- consumer side --------------------------------------------------
    def __iter__(self):
        try:
            while True:
                item = self._q.get()
                if item is self._END:
                    break
                yield item
        finally:
            self.close()
        if self._err is not None:
            raise self._err

    def close(self):
        self._stop.set()
        while True:                    # unblock a worker stuck on put
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5)


def window_trace_set(sd, accum_steps: int, sentinel: bool,
                     ts_key=None, fingerprint: bool = False) -> set:
    """The per-(graph version, accum, sentinel, tensorstats,
    fingerprint) set of window trace signatures already compiled. This
    is the ONE key construction, shared by the executor's compile
    accounting below and ``SameDiff.precompile()``'s pre-registration —
    if the key shape changed in only one place, precompiled sigs would
    land in a set fit never reads and ``window_compiles`` would
    silently report nonzero after a precompile (the same drift
    ``ph_shape_sig`` was unified to prevent for the signature itself).
    ``ts_key`` is ``TensorStatsConfig.key()`` or None (stats-free)."""
    return sd.__dict__.setdefault("_window_traces", {}) \
        .setdefault((sd._version, accum_steps, sentinel, ts_key,
                     bool(fingerprint)), set())


def fit_windowed(sd, dataset_iterator, epochs: int = 1, listeners=()):
    """The fused-window fit tier (``TrainingConfig.fused_steps`` /
    ``accum_steps``). Called by ``SameDiff.fit`` — see its docstring for
    the tier contract. Structure mirrors the per-step loop; the unit of
    dispatch is a window instead of a step."""
    from deeplearning4j_tpu.autodiff.samediff import (NumericsException,
                                                      _split_batch)
    from deeplearning4j_tpu.autodiff.training import History

    tc = sd.training_config
    K = max(1, int(getattr(tc, "fused_steps", 1) or 1))
    A = max(1, int(getattr(tc, "accum_steps", 1) or 1))
    use_sentinel = bool(getattr(tc, "sentinel", False))
    # bitwise state fingerprints (integrity/fingerprint.py): one extra
    # uint32 output per window, read only at flush boundaries; the
    # optional replay probe re-dispatches every Nth window from a
    # stashed carry and compares digests, and the optional replica
    # check compares per-replica digests every Nth flush
    fp_on = bool(getattr(tc, "fingerprints", False))
    probe_every = int(getattr(tc, "fingerprint_replay_every", 0) or 0) \
        if fp_on else 0
    replica_every = int(getattr(tc, "fingerprint_replica_every", 0) or 0) \
        if fp_on else 0
    sd._device_fingerprint = None
    if fp_on:
        from deeplearning4j_tpu.integrity.fingerprint import (
            check_probes, check_replica_agreement)
    # in-graph tensor statistics (monitor/tensorstats.py): only with
    # listeners — the records ride the listener rail; a listener-free
    # fit dispatches the stats-free window
    ts_cfg = getattr(tc, "tensorstats", None) if listeners else None
    window_fn = sd.make_train_window(accum_steps=A, sentinel=use_sentinel,
                                     tensorstats=ts_cfg,
                                     fingerprint=fp_on)
    # window_fn donates param/state buffers; work on copies so the
    # graph's stored arrays stay valid for output()/save() mid-fit
    params, svars, state, staged = stage_fit_state(sd, tc)
    constants = sd.constants_map()
    iteration = int(getattr(tc, "iteration_count", 0))
    it_dev = jnp.asarray(iteration, jnp.int32)
    accum = None
    if A > 1:
        # resume a mid-cycle accumulation from the previous fit: the
        # apply phase is (iteration+1) % A on the ABSOLUTE iteration, so
        # a fit ending mid-cycle leaves partial grads that the next fit
        # must continue from (otherwise those micro-batches are lost)
        prev = getattr(sd, "_grad_accum", None)
        if prev is not None and set(prev.keys()) == set(params.keys()) \
                and iteration % A != 0:
            accum = jax.tree_util.tree_map(jnp.copy, prev)
        else:
            accum = jax.tree_util.tree_map(jnp.zeros_like, params)
    # resumable RNG contract (checkpoint/state.py): per-step keys are
    # fold_in(key(base_seed), absolute_iteration)
    sd._fit_base_seed = sd._seed
    base_key = jax.random.key(sd._seed)
    sd._seed += 1
    history = History()
    deferred_means = []                # device scalars, fetched at fit end
    panic = sd._nan_panic_active(tc)
    for l in listeners:
        l.on_training_start(sd)
    flush_every = min((max(1, int(getattr(l, "frequency", 10)))
                       for l in listeners), default=0)
    # next absolute iteration whose crossing triggers a listener flush
    next_flush = (iteration // flush_every + 1) * flush_every \
        if flush_every else 0
    sync_params_on_flush = any(getattr(l, "needs_params", False)
                               for l in listeners)
    # compiled window lengths (jit retraces per leading-dim K): tracked
    # per (graph version, accum) so stats report real compile counts
    seen_sizes = window_trace_set(
        sd, A, use_sentinel, ts_cfg.key() if ts_cfg is not None else None,
        fp_on)
    # last window's device digest (a device scalar until fetched at a
    # flush / fit end) + probe/replica bookkeeping shared across epochs
    last_fp_box: List[Optional[jax.Array]] = [None]
    replica_mark = [0]
    win_count = 0
    probes_total = 0
    if ts_cfg is not None:
        from deeplearning4j_tpu.monitor.tensorstats import layer_names
        ts_names = layer_names(params)
    else:
        ts_names = ()

    def _name_batch(batch):
        if isinstance(batch, dict):
            # dict keys may be SDVariables (same contract as the
            # per-step tier's _prep_placeholders)
            from deeplearning4j_tpu.autodiff.variable import SDVariable
            return {k.name if isinstance(k, SDVariable) else k: v
                    for k, v in batch.items()}
        feats, labels = _split_batch(batch)
        ph = dict(zip(tc.data_set_feature_mapping, feats))
        ph.update(zip(tc.data_set_label_mapping, labels))
        return ph

    window_sharding = getattr(dataset_iterator, "window_sharding", None)
    # sharding specs are a pure function of rank: build each ONCE here
    # (stager setup) instead of per window per tensor — at post-fusion
    # window times the repeated PartitionSpec/NamedSharding construction
    # was measurable host work between dispatches (monitor/ steptime
    # attributes it to data_wait)
    _sharding_by_rank: Dict[int, object] = {}

    def _window_spec(ndim):
        spec = _sharding_by_rank.get(ndim)
        if spec is None:
            spec = _sharding_by_rank[ndim] = window_sharding(ndim)
        return spec

    def _finalize(stacked):
        ph = sd._prep_placeholders(stacked)
        if window_sharding is not None:
            ph = {k: jax.device_put(v, _window_spec(v.ndim))
                  for k, v in ph.items()}
        return ph

    # device-cached source (stacked_batches): the window content is
    # identical every epoch, so build the window list ONCE as device
    # slices of the pre-stacked arrays and reuse it — no stager thread,
    # no per-epoch re-stack/re-upload churn
    cached_windows = None
    if hasattr(dataset_iterator, "stacked_batches"):
        feats, labels = dataset_iterator.stacked_batches()
        stacked = _finalize(dict(
            list(zip(tc.data_set_feature_mapping, feats)) +
            list(zip(tc.data_set_label_mapping, labels))))
        n_steps = next(iter(stacked.values())).shape[0]
        parts, i = [], 0
        while n_steps - i >= K:
            parts.append((i, K))
            i += K
        for k in pow2_buckets(n_steps - i):
            parts.append((i, k))
            i += k
        cached_windows = [(k, {nm: a[j:j + k] for nm, a in stacked.items()})
                          for j, k in parts]

    stop = False
    for epoch in range(epochs):
        epoch_losses: List[float] = []       # floats (listener path)
        epoch_loss_bufs: List[jax.Array] = []  # device (K,) buffers
        pending = []                         # (start_iter, k, (k,) losses)
        pending_bads: List[jax.Array] = []   # sentinel scalars, device
        epoch_bads: List[jax.Array] = []     # ... for the listener-free path
        pending_stats: List[tuple] = []      # (stats pytree, at) device
        pending_probes: List[tuple] = []     # (start_iter, fp, fp_replay)
        epoch_probes: List[tuple] = []       # ... listener-free variant
        epoch_start_iter = iteration
        dispatches = 0
        compiles = 0
        sizes: Dict[int, int] = {}     # window length -> dispatch count

        def _check_bads(bads):
            """Device-sentinel verdicts for a burst of windows: ONE
            stacked fetch; the first non-negative entry is the absolute
            iteration of the diverged step (faults/sentinels.py)."""
            if not bads:
                return
            from deeplearning4j_tpu.faults.sentinels import check_bad_steps
            fetched = np.asarray(jnp.stack(bads))
            bads.clear()
            check_bad_steps(fetched, epoch, epoch_start_iter)

        def _fetch_flush():
            """The device-sync half of a listener flush: fetch the loss
            burst (+ sentinel verdicts), sync training state. Returns
            the (iters, vals) burst for :func:`_deliver`, or None. Split
            from delivery so the ``flush`` span records the WINDOW
            boundary's device wait (as a child of the window span that
            triggered it) while listener callbacks run outside it."""
            if not pending:
                return None
            iters: List[int] = []
            for start, k, _ in pending:
                iters.extend(range(start, start + k))
            ts_recs: List[dict] = []
            with _tracer.span("flush", cat="train", steps=len(iters)):
                losses_cat = jnp.concatenate([lv for _, _, lv in pending])
                # losses + sentinel verdicts + sampled tensorstats +
                # fingerprints/probe digests in ONE device→host
                # transfer; poisoned windows must not feed listeners/
                # checkpoints, so verdicts are checked (and may raise)
                # before the burst is delivered
                bads_stack = jnp.stack(pending_bads) if pending_bads \
                    else None
                stats_burst = list(pending_stats)
                pending_stats.clear()
                probes = list(pending_probes)
                pending_probes.clear()
                probes_stack = jnp.stack(
                    [jnp.stack((a, b)) for _, a, b in probes]) \
                    if probes else None
                fp_dev = last_fp_box[0] if fp_on else None
                try:
                    with _wd_guard("flush"):
                        vals_arr, bads, stats_host, fp_host, probes_host \
                            = jax.device_get(
                                (losses_cat, bads_stack, stats_burst,
                                 fp_dev, probes_stack))
                except Exception as e:
                    # async dispatch: an allocation failure inside a
                    # window often surfaces HERE, at the first sync
                    memstats.reraise_oom(e, step=iters[-1] if iters
                                         else None, epoch=epoch)
                    raise
                if bads is not None:
                    from deeplearning4j_tpu.faults.sentinels import \
                        check_bad_steps
                    pending_bads.clear()
                    check_bad_steps(np.asarray(bads), epoch,
                                    epoch_start_iter)
                if fp_host is not None:
                    # the boundary digest a checkpoint capture at this
                    # flush verifies its host bytes against
                    sd._device_fingerprint = {"iteration": iters[-1] + 1,
                                              "fp": int(fp_host)}
                if probes:
                    # replay-probe verdicts gate delivery like the
                    # sentinel: a corrupted window's losses must not
                    # reach listeners/checkpoints
                    check_probes(np.asarray(probes_host),
                                 [s for s, _, _ in probes])
                if replica_every:
                    replica_mark[0] += 1
                    if replica_mark[0] % replica_every == 0:
                        check_replica_agreement({**params, **svars})
                if stats_burst:
                    # windows with no sample point carry at = -1 (zeros
                    # payload) and are dropped here
                    from deeplearning4j_tpu.monitor.tensorstats import \
                        build_record
                    ts_recs = [build_record(ts_names, s, int(at), epoch,
                                            ts_cfg)
                               for s, at in stats_host if int(at) >= 0]
            vals = [float(v) for v in vals_arr]
            epoch_losses.extend(vals)
            if sync_params_on_flush:
                # the FULL training state at the window boundary: a
                # checkpoint taken at this flush captures params, updater
                # state and the iteration counter of the LAST completed
                # window — bit-exact resume (checkpoint/listener.py)
                for n, p in {**params, **svars}.items():
                    sd._arrays[n] = jnp.copy(p)
                sd._updater_state = jax.tree_util.tree_map(jnp.copy, state)
                tc.iteration_count = iters[-1] + 1
            if panic:
                for it, v in zip(iters, vals):
                    if not np.isfinite(v):
                        raise NumericsException(
                            f"non-finite loss {v} at iteration {it} "
                            f"(nan_panic); localize the producing op with "
                            f"sd.exec_debug(placeholders)")
            pending.clear()
            return iters, vals, ts_recs

        def _deliver(flushed):
            if flushed is None:
                return
            iters, vals, ts_recs = flushed
            for l in listeners:
                l.iterations_done(sd, epoch, iters, vals)
            if ts_recs:
                for l in listeners:
                    hook = getattr(l, "tensorstats_done", None)
                    if hook is not None:
                        hook(sd, epoch, ts_recs)

        def _flush():
            _deliver(_fetch_flush())

        for l in listeners:
            l.on_epoch_start(sd, epoch)
        if cached_windows is not None:
            stager, source = None, cached_windows
        else:
            if hasattr(dataset_iterator, "reset"):
                dataset_iterator.reset()
            # a real generator expression (not map()): the stager closes
            # its source on shutdown, and generator .close() propagates
            # GeneratorExit into a streaming pipeline's generator —
            # releasing its prefetch workers deterministically
            # (map objects have no close())
            stager = WindowStager(
                (_name_batch(b) for b in iter(dataset_iterator)),
                K, finalize=_finalize)
            source = stager
        _END_OF_DATA = object()
        src_iter = iter(source)
        try:
            while True:
                # one "window" span per dispatch unit, with data_wait /
                # dispatch (and, when this window crosses a listener
                # cadence, flush) children — the trace rows ui/report's
                # step-time breakdown and monitor/steptime.py attribute
                flushed = None
                with _tracer.span("window", cat="train") as wspan:
                    with _tracer.span("data_wait", cat="train"):
                        item = next(src_iter, _END_OF_DATA)
                    if item is _END_OF_DATA:
                        wspan.discard()
                        break
                    k, win = item
                    wspan.set(k=k, iteration=iteration)
                    for l in listeners:
                        if getattr(l, "batch_size", -1) is None:
                            l.batch_size = next(iter(win.values())).shape[1]
                    # jit retraces per full placeholder shape set (a
                    # ragged final BATCH recompiles even at an
                    # already-seen k); the signature is the same key
                    # AOT dispatch uses, so shapes prebuilt by
                    # sd.precompile() count as already-seen
                    trace_sig = ph_shape_sig(win)
                    first_dispatch = trace_sig not in seen_sizes
                    if first_dispatch:
                        seen_sizes.add(trace_sig)
                        compiles += 1
                        sd._verbose_log(f"fit: compiling window length {k}")
                    bad = None
                    with _tracer.span("dispatch", cat="train", k=k):
                        # positional output layout (make_train_window):
                        # p, sv, st, [accum], it, losses, [bad],
                        # [stats, at], [fp]
                        if A > 1:
                            args = (params, svars, state, accum, it_dev,
                                    constants, win, base_key)
                        else:
                            args = (params, svars, state, it_dev,
                                    constants, win, base_key)
                        # replay probe (integrity/fingerprint.py): stash
                        # copies of the donated carry BEFORE the main
                        # dispatch so the window can be re-dispatched
                        # from identical inputs and the two digests
                        # compared at the next flush
                        probe_this = probe_every and \
                            win_count % probe_every == probe_every - 1
                        if probe_this:
                            stash = jax.tree_util.tree_map(
                                jnp.copy, args[:5 if A > 1 else 4])
                        win_count += 1
                        if first_dispatch:
                            # with plan capture armed (MonitorListener),
                            # a new shape compiles through the AOT path
                            # so its memory plan is captured — same
                            # lowering, one compile either way, outputs
                            # bit-identical (tests/test_memory_obs.py)
                            memstats.promote_dispatch(
                                window_fn, args, trace_sig,
                                f"window_k{k}", steps=k, graph=sd)
                        try:
                            with _wd_guard("window_dispatch",
                                           first=first_dispatch):
                                out = window_fn(*args)
                        except Exception as e:
                            memstats.reraise_oom(e,
                                                 program=f"window_k{k}",
                                                 step=iteration,
                                                 epoch=epoch)
                            raise
                        memstats.note_dispatch(trace_sig, steps=k)
                        if A > 1:
                            params, svars, state, accum = out[:4]
                            i = 4
                        else:
                            params, svars, state = out[:3]
                            i = 3
                        it_dev = out[i]
                        losses = out[i + 1]
                        i += 2
                        if use_sentinel:
                            bad = out[i]
                            i += 1
                        if ts_cfg is not None:
                            pending_stats.append((out[i], out[i + 1]))
                            i += 2
                        if fp_on:
                            last_fp_box[0] = out[i]
                            i += 1
                        if probe_this:
                            # second dispatch of the SAME window from
                            # the stash (which it donates); only its
                            # digest is kept — compared at the flush
                            with _tracer.span("integrity.replay_probe",
                                              cat="integrity", k=k), \
                                    _wd_guard("window_dispatch"):
                                out2 = window_fn(*stash, constants, win,
                                                 base_key)
                            probes_total += 1
                            # fp is the LAST window output by layout
                            (pending_probes if listeners
                             else epoch_probes).append(
                                (iteration, out[-1], out2[-1]))
                    dispatches += 1
                    sizes[k] = sizes.get(k, 0) + 1
                    if bad is not None:
                        (pending_bads if listeners
                         else epoch_bads).append(bad)
                    if listeners:
                        pending.append((iteration, k, losses))
                        iteration += k
                        # flush at the FIRST window boundary at-or-after
                        # each multiple of the listener cadence (absolute
                        # iterations), so an every-N listener sees its
                        # burst as soon as a boundary crosses N — not
                        # only when a full N steps have buffered
                        # (docs/checkpointing.md)
                        if iteration >= next_flush:
                            flushed = _fetch_flush()
                            next_flush = (iteration // flush_every + 1) \
                                * flush_every
                    else:
                        epoch_loss_bufs.append(losses)
                        iteration += k
                # listener callbacks run OUTSIDE the window span: their
                # cost is user code, not executor time
                _deliver(flushed)
        finally:
            if stager is not None:
                stager.close()
        # listener-free sentinel path: one stacked verdict fetch per epoch
        _check_bads(epoch_bads)
        if epoch_probes:
            # listener-free replay probes: one stacked digest fetch
            fetched = np.asarray(jnp.stack(
                [jnp.stack((a, b)) for _, a, b in epoch_probes]))
            starts = [s for s, _, _ in epoch_probes]
            epoch_probes.clear()
            check_probes(fetched, starts)
        if listeners:
            _flush()
            if flush_every:
                next_flush = (iteration // flush_every + 1) * flush_every
            mean_loss = float(np.mean(epoch_losses)) \
                if epoch_losses else float("nan")
        elif panic:
            mean_loss = float(jnp.mean(jnp.concatenate(epoch_loss_bufs))) \
                if epoch_loss_bufs else float("nan")
            if epoch_loss_bufs and not np.isfinite(mean_loss):
                raise NumericsException(
                    f"non-finite epoch-{epoch} mean loss {mean_loss} "
                    f"(nan_panic); localize with sd.exec_debug()")
        else:
            # mean on device, fetch deferred to fit end (one transfer)
            mean_loss = None
            deferred_means.append(
                jnp.mean(jnp.concatenate(epoch_loss_bufs))
                if epoch_loss_bufs else jnp.asarray(float("nan")))
        history.add_epoch(epoch, mean_loss)
        tc.epoch_count = getattr(tc, "epoch_count", 0) + 1
        sd.last_fit_stats = {
            "tier": "windowed", "fused_steps": K, "accum_steps": A,
            "steps_per_epoch": iteration - epoch_start_iter,
            "dispatches_per_epoch": dispatches,
            "window_sizes": sizes, "window_compiles": compiles,
            "sentinel": use_sentinel, "fingerprints": fp_on,
            "replay_probes": probes_total, **staged}
        if listeners:
            # sync current training state into the graph (copies — the
            # next window donates the working buffers)
            for n, p in {**params, **svars}.items():
                sd._arrays[n] = jnp.copy(p)
            sd._updater_state = jax.tree_util.tree_map(jnp.copy, state)
            tc.iteration_count = iteration
        for l in listeners:
            if l.on_epoch_end(sd, epoch, mean_loss) is False:
                stop = True
        if stop:
            break
    if deferred_means:
        fetched = np.asarray(jnp.stack(deferred_means))
        history.loss_curve.losses = [float(v) for v in fetched]
    # write trained params back into the graph
    for n, p in {**params, **svars}.items():
        sd._arrays[n] = p
    sd._updater_state = state
    sd._grad_accum = accum         # partial accumulation survives the fit
    tc.iteration_count = iteration
    if fp_on and last_fp_box[0] is not None:
        cur = sd._device_fingerprint
        if cur is None or cur.get("iteration") != iteration:
            # listener-free (or post-final-flush) boundary digest for
            # checkpoint captures taken after this fit
            sd._device_fingerprint = {
                "iteration": int(iteration),
                "fp": int(jax.device_get(last_fp_box[0]))}
    for l in listeners:
        l.on_training_end(sd)
    return history
