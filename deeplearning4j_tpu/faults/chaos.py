"""Deterministic, seed-driven fault injection for the training stack.

Every injector is reproducible: given the same seed and the same
training run, the same fault fires at the same place — which is what
makes the chaos suite a *regression* suite rather than a flake
generator. Faults on offer (the ones the recovery rail must survive):

- ``nan_gradients(sd, at_step)`` — device-side: the compiled train step
  replaces every gradient leaf with NaN at absolute iteration
  ``at_step`` (traced into the XLA program, so it works inside fused
  windows and scans). Arms via ``TrainingConfig`` and retraces; exiting
  the context disarms and retraces back to the clean program.
- ``poison_batches(it, at_step)`` — host-side one-shot: the batch
  feeding absolute step ``at_step`` has its features replaced with NaN.
  One-shot means a rolled-back retry passes cleanly — the
  self-healing end-to-end test's fault of choice.
- ``flaky_iterator(it, fail_at_batch)`` — the loader raises a transient
  ``IOError`` at a chosen batch index, a limited number of times.
- ``torn_shard(directory, shard_index)`` — datapipe IO fault: bit-flip
  or truncate a committed shard file on disk (restored on exit). With
  ``heal_after_failures=N`` the original bytes return after the reader
  has failed N verifications — transient bit-rot, the self-heal e2e's
  fault of choice; without it the damage is permanent and drives the
  shard-quarantine path.
- ``flaky_read(times, every)`` / ``slow_reader(delay_s)`` — patch the
  ONE shard-IO seam (``datapipe.reader._read_file_bytes``): transient
  ``IOError`` every Nth read / injected latency (straggler drills for
  the read-timeout backup path).
- ``worker_killer(at_batch)`` — a prefetch worker crashes while
  holding the claimed batch: drives the supervisor's exactly-once
  requeue + bounded-backoff respawn (and, at ``times=2``, the
  twice-lost typed failure).
- ``failing_os_replace(times)`` / ``failing_fsync(times)`` — the next
  ``times`` checkpoint commit renames / durability fsyncs raise
  ``OSError``, leaving exactly the torn ``step_N.tmp`` state a killed
  writer leaves.
- ``stalled_dispatch(delay_s, at_call)`` — a train dispatch blocks for
  ``delay_s`` before returning real results: the recoverable-stall
  drill for ``integrity.StallWatchdog`` (typed ``TrainingStalledError``
  + forensics + /healthz 503, then a clean rollback-retry).
- ``bitflip_param(at_call)`` — silent data corruption: one bit of the
  dispatched window's returned params flips, finite-in finite-out;
  ``refingerprint=True`` keeps the corruption self-consistent (SDC
  inside the dispatch — the replay probe's case), ``False`` leaves the
  device digest intact (a corrupted D2H copy — the capture check's
  case). With fingerprints off the flip is genuinely silent.
- ``rot_checkpoint(dir, step)`` — flip/truncate a committed checkpoint
  payload on disk without touching its manifest: the bit-rot
  ``restore_latest`` must skip and ``checkpoint.Scrubber``
  quarantines (``step_N.rotten``).
- ``sigterm_listener(at_iteration)`` — delivers SIGTERM to this process
  at a training iteration, mid-window (drives PreemptionHook drills).
- ``failing_exec(server, n, every)`` — serving-side: every ``every``-th
  ``ParallelInference`` exec raises a transient device error, ``n``
  times total (counter-deterministic; bisection retries count too) —
  drives the serving self-heal / circuit-breaker e2e tests.
- ``poison_request(template)`` — a NaN-rows request payload shaped like
  ``template``: the poisoned-batch-isolation e2e's fault of choice
  (XLA does not raise on NaN; the resilient dispatcher must detect the
  non-finite output rows and quarantine exactly this request).
- ``resource_exhausted(at_call)`` / ``oom_serving(server, at_call)`` —
  synthetic device OOM (a real ``JaxRuntimeError`` with the
  ``RESOURCE_EXHAUSTED:`` status) from the training dispatch / serving
  exec path: drives the OOM-forensics e2e — the exec paths must
  convert it to a structured ``memory.MemoryExhaustedError`` and the
  recovery rail must diagnose-and-abort, not retry
  (docs/observability.md "OOM forensics").
- ``host_loss(trainer, surviving_strategy, at_iteration)`` — elastic
  topology drill: the trainer's mesh shrinks mid-fit and a retryable
  ``host_loss`` fault fires; FaultTolerantFit resumes RESHARDED on the
  surviving devices (docs/elastic_training.md).
- ``host_killer(at_iteration)`` / ``FileBarrier`` — multi-process
  host-death drills: one process of a multihost dryrun ``os._exit``s
  mid-window (no cleanup, no barrier release); peers see a barrier
  timeout, the job dies, and the relaunched smaller job restores
  through ``checkpoint.reshard`` (ShardCountMismatchError).

Reference parity: optimize/listeners/FailureTestingListener.java
injected OOM/exit/exception at listener trigger points; this harness
additionally reaches INSIDE the compiled step (NaN grads), the data
pipeline, and the checkpoint commit protocol.
"""
from __future__ import annotations

import contextlib
import os
import signal as _signal
import threading
import time
from typing import Iterator, List, Optional

import numpy as np

from deeplearning4j_tpu.autodiff.training import Listener
from deeplearning4j_tpu.dataset.iterators import DataSetIterator
from deeplearning4j_tpu.faults.errors import TransientDeviceError


def _synthetic_resource_exhausted(nbytes: int) -> BaseException:
    """The backend's allocation-failure error, synthesized: a real
    ``jax.errors.JaxRuntimeError`` with the ``RESOURCE_EXHAUSTED:``
    status, so the exec paths' detection — type AND message —
    exercises exactly the production code path."""
    from jax.errors import JaxRuntimeError
    return JaxRuntimeError(
        f"RESOURCE_EXHAUSTED: chaos: out of memory while trying to "
        f"allocate {int(nbytes)} bytes")


class ChaosSpec:
    """Device-side injection knobs read by the train-step tracer
    (``SameDiff._build_step_parts``). Attached as
    ``TrainingConfig._chaos_spec``; a None spec (the default) leaves the
    compiled program untouched."""

    def __init__(self, nan_grads_at: Optional[int] = None):
        self.nan_grads_at = nan_grads_at


class FlakyIterator(DataSetIterator):
    """Raises a transient loader error at batch ``fail_at_batch``
    (index within the pass), ``times`` times total across passes."""

    def __init__(self, wrapped: DataSetIterator, fail_at_batch: int,
                 times: int = 1, exc_factory=None, log: Optional[List] = None):
        self._wrapped = wrapped
        self.fail_at_batch = int(fail_at_batch)
        self.times_left = int(times)
        self._exc_factory = exc_factory or (
            lambda i: IOError(f"chaos: injected loader failure at "
                              f"batch {i}"))
        self._log = log if log is not None else []

    def reset(self):
        if hasattr(self._wrapped, "reset"):
            self._wrapped.reset()

    def __iter__(self):
        for i, batch in enumerate(self._wrapped):
            if i == self.fail_at_batch and self.times_left > 0:
                self.times_left -= 1
                self._log.append({"event": "loader_exception",
                                  "batch_index": i, "t": time.time()})
                raise self._exc_factory(i)
            yield batch


class BatchPoisoner(DataSetIterator):
    """Replaces the batch at yield-count ``at_step`` with NaN features,
    ``times`` times total (default one-shot). The counter is batches
    yielded BY THIS WRAPPER across passes/epochs — equal to the absolute
    training iteration only while nothing upstream replays batches. An
    outer RetryingIterator's reset-and-fast-forward (or quarantine
    skips) re-consume earlier batches and shift the firing point
    relative to training iterations, so tests needing an EXACT step
    should assert on the sentinel's reported provenance (or use
    ``ChaosMonkey.nan_gradients``, which is iteration-exact by
    construction); ``at_step`` here chooses roughly-where, one-shot —
    which is all the self-heal drills need."""

    def __init__(self, wrapped: DataSetIterator, at_step: int,
                 times: int = 1, log: Optional[List] = None):
        self._wrapped = wrapped
        self.at_step = int(at_step)
        self.times_left = int(times)
        self._step = 0                  # absolute batches yielded ever
        self._log = log if log is not None else []

    def reset(self):
        if hasattr(self._wrapped, "reset"):
            self._wrapped.reset()

    @staticmethod
    def _poison(part):
        if isinstance(part, (tuple, list)):
            return type(part)(BatchPoisoner._poison(p) for p in part)
        a = np.array(part, copy=True)
        if np.issubdtype(a.dtype, np.floating):
            a[...] = np.nan
        return a

    def __iter__(self):
        for batch in self._wrapped:
            if self._step == self.at_step and self.times_left > 0:
                self.times_left -= 1
                self._log.append({"event": "batch_poisoned",
                                  "step": self._step, "t": time.time()})
                if isinstance(batch, dict):
                    batch = {k: self._poison(v) for k, v in batch.items()}
                elif hasattr(batch, "features") and hasattr(batch, "labels"):
                    batch = (self._poison(batch.features), batch.labels)
                else:
                    f, l = batch
                    batch = (self._poison(f), l)
            self._step += 1
            yield batch


class TornShard:
    """Deterministic on-disk shard corruption (datapipe/): ``inject()``
    damages the committed shard file (``bitflip`` one payload byte, or
    ``truncate`` to half) while keeping the original bytes in memory;
    ``heal()`` restores them. As a context manager the shard is
    corrupted for the body and restored on exit.

    ``heal_after_failures=N`` makes the damage TRANSIENT: subscribed to
    a pipeline's event stream (``pipeline.subscribe(ts.observe)`` —
    done by ``ChaosMonkey.torn_shard(pipeline=...)``), the original
    bytes come back after the reader has failed N verification
    attempts on this shard — so the reader's retry budget heals the
    fault (flaky-NFS bit-rot), which is what the zero-dropped-samples
    self-heal e2e needs. Without it the corruption is permanent and
    the bounded budget quarantines the shard."""

    def __init__(self, directory: str, shard_index: int = 0,
                 mode: str = "bitflip",
                 heal_after_failures: Optional[int] = None,
                 log: Optional[List] = None):
        from deeplearning4j_tpu.datapipe.manifest import SHARD_FMT
        if mode not in ("bitflip", "truncate"):
            raise ValueError(f"mode {mode!r}: use 'bitflip'|'truncate'")
        self.shard_file = SHARD_FMT.format(i=int(shard_index))
        self.path = os.path.join(os.fspath(directory), self.shard_file)
        self.mode = mode
        self.heal_after = heal_after_failures
        self._log = log if log is not None else []
        with open(self.path, "rb") as fh:
            self._orig = fh.read()
        self._failures = 0
        self.healed = False

    def inject(self) -> "TornShard":
        if self.mode == "truncate":
            data = self._orig[: len(self._orig) // 2]
        else:
            buf = bytearray(self._orig)
            buf[len(buf) // 2] ^= 0xFF
            data = bytes(buf)
        with open(self.path, "wb") as fh:
            fh.write(data)
        self.healed = False
        self._log.append({"event": "shard_torn", "shard": self.shard_file,
                          "mode": self.mode, "t": time.time()})
        return self

    def heal(self) -> None:
        if self.healed:
            return
        with open(self.path, "wb") as fh:
            fh.write(self._orig)
        self.healed = True
        self._log.append({"event": "shard_healed",
                          "shard": self.shard_file, "t": time.time()})

    def observe(self, ev: dict) -> None:
        """Pipeline-event hook: count this shard's read failures and
        heal once ``heal_after_failures`` is reached (the restore runs
        on the worker thread, BETWEEN its retry attempts — so the next
        attempt reads good bytes)."""
        if self.healed or self.heal_after is None:
            return
        if ev.get("event") in ("read_retry", "shard_quarantined") and \
                ev.get("shard") == self.shard_file:
            self._failures += 1
            if self._failures >= self.heal_after:
                self.heal()

    def __enter__(self) -> "TornShard":
        return self.inject()

    def __exit__(self, *exc) -> None:
        self.heal()


class HostLossInjector(Listener):
    """Deterministic in-process host-loss drill: at training iteration
    ``at_iteration`` the trainer's world shrinks to
    ``surviving_strategy`` (the mesh a preemption would leave behind)
    and a structured :class:`TransientDeviceError` (cause
    ``"host_loss"``) aborts the fit — exactly what a lost slice looks
    like from the training loop. ``faults.FaultTolerantFit``'s rollback
    then restores the last committed checkpoint RESHARDED onto the
    surviving mesh (ParallelTrainer records ``last_reshard``) and the
    run continues on the shrunken topology.

    One-shot; the strategy swap persists (the host stays dead)."""

    frequency = 1

    def __init__(self, trainer, surviving_strategy, at_iteration: int,
                 log: Optional[List] = None):
        self.trainer = trainer
        self.surviving_strategy = surviving_strategy
        self.at_iteration = int(at_iteration)
        self.fired = False
        self._log = log if log is not None else []

    def iteration_done(self, sd, epoch, iteration, loss):
        if not self.fired and iteration >= self.at_iteration:
            self.fired = True
            lost = (self.trainer.strategy.mesh.n_devices
                    - self.surviving_strategy.mesh.n_devices)
            self._log.append({"event": "host_loss", "iteration": iteration,
                              "devices_lost": lost, "t": time.time()})
            self.trainer.strategy = self.surviving_strategy
            raise TransientDeviceError(
                f"chaos: injected host loss at iteration {iteration} "
                f"({lost} device(s) gone; surviving mesh "
                f"{dict(self.surviving_strategy.mesh.mesh.shape)})",
                step=int(iteration), epoch=int(epoch), cause="host_loss")


class HostKiller(Listener):
    """SIGKILL-grade host death for multi-process drills: at training
    iteration ``at_iteration`` the process exits immediately via
    ``os._exit`` — no atexit hooks, no final checkpoint, no barrier
    release; surviving peers discover the death as a barrier timeout.
    The piece :class:`SigtermListener` (graceful preemption) cannot
    simulate."""

    frequency = 1

    def __init__(self, at_iteration: int, exit_code: int = 137):
        self.at_iteration = int(at_iteration)
        self.exit_code = int(exit_code)

    def iteration_done(self, sd, epoch, iteration, loss):
        if iteration >= self.at_iteration:
            os._exit(self.exit_code)


class FileBarrier:
    """Cross-process barrier over a shared directory (marker files) —
    the CheckpointManager ``barrier=`` hook for multi-process chaos
    drills without ``jax.distributed``. Each arrival writes
    ``<run_id>.<tag>.g<generation>.<index>`` and spins until all
    ``count`` markers exist; a peer that dies mid-protocol surfaces as
    a ``TimeoutError`` here, which fails the save — the whole job dies,
    and the relaunched job recovers through the elastic restore path.

    Markers persist on disk, so a RELAUNCHED job reusing the same
    barrier directory must pass a fresh ``run_id`` (every peer of a
    launch the same one — e.g. an attempt counter from the launcher):
    otherwise the dead job's markers would satisfy the new job's waits
    instantly, letting a commit race an in-flight shard."""

    def __init__(self, directory: str, index: int, count: int,
                 timeout: float = 60.0, poll: float = 0.01,
                 run_id: str = "r0"):
        self.directory = os.fspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.index = int(index)
        self.count = int(count)
        self.timeout = float(timeout)
        self.poll = float(poll)
        self.run_id = "".join(c if c.isalnum() or c in "._-" else "_"
                              for c in str(run_id))
        self._generations: dict = {}

    def __call__(self, tag: str) -> None:
        safe = "".join(c if c.isalnum() or c in "._-" else "_"
                       for c in str(tag))
        # a tag recurs when the same step is re-saved (rollback-retry);
        # stale markers from the earlier arrival would satisfy the wait
        # instantly and let a commit race an in-flight shard, so each
        # recurrence gets its own generation (peers agree because
        # multihost cadences are deterministic across processes)
        gen = self._generations.get(safe, 0)
        self._generations[safe] = gen + 1
        safe = f"{self.run_id}.{safe}.g{gen}"
        mine = os.path.join(self.directory, f"{safe}.{self.index}")
        with open(mine, "w", encoding="utf-8") as fh:
            fh.write("here\n")
        deadline = time.monotonic() + self.timeout
        want = [os.path.join(self.directory, f"{safe}.{i}")
                for i in range(self.count)]
        while True:
            if all(os.path.exists(p) for p in want):
                return
            if time.monotonic() > deadline:
                missing = [p for p in want if not os.path.exists(p)]
                raise TimeoutError(
                    f"chaos barrier {tag!r}: peer(s) never arrived "
                    f"within {self.timeout}s ({missing}) — a host is "
                    f"dead; the job should abort and relaunch elastic")
            time.sleep(self.poll)


class SigtermListener(Listener):
    """Delivers SIGTERM to this process at a chosen training iteration
    (one-shot) — mid-window under the fused tier, since flushes happen
    at window boundaries. Pair with checkpoint.PreemptionHook."""

    frequency = 1

    def __init__(self, at_iteration: int, log: Optional[List] = None):
        self.at_iteration = int(at_iteration)
        self.fired = False
        self._log = log if log is not None else []

    def iteration_done(self, sd, epoch, iteration, loss):
        if not self.fired and iteration >= self.at_iteration:
            self.fired = True
            self._log.append({"event": "sigterm", "iteration": iteration,
                              "t": time.time()})
            os.kill(os.getpid(), _signal.SIGTERM)


class MidStreamKiller:
    """Serving chaos: kill a fleet replica after it emits ``n`` more
    tokens — the mid-stream death the durable-request drill needs
    (``shutdown(drain=False)`` only fails QUEUED work; this aborts the
    in-flight generations too, typed ``ServerClosedError``, exactly
    what a SIGKILL looks like to clients holding handles).

    Deterministic: the count is over the server's own ``_emit`` calls,
    so the same trace kills at the same token every run. The emit hook
    runs ON the decode worker, which cannot join itself — so it trips
    the server's ``_killed`` flag (the worker aborts in-flight at its
    next step boundary) and finishes the kill (``replica.kill()`` →
    ``server.abort()``) from a side thread. ``fired.wait()`` to
    synchronize a drill on the kill having landed."""

    def __init__(self, replica, after_tokens: int,
                 log: Optional[List] = None):
        self.replica = replica
        self.after_tokens = int(after_tokens)
        self.fired = threading.Event()
        self._remaining = int(after_tokens)
        self._log = log if log is not None else []

    def arm(self) -> "MidStreamKiller":
        server = getattr(self.replica, "server", self.replica)
        orig = server._emit

        def emit(s, req, tok, _orig=orig, _server=server):
            _orig(s, req, tok)
            self._remaining -= 1
            if self._remaining == 0:
                self._log.append({"event": "kill_mid_stream",
                                  "replica": getattr(self.replica,
                                                     "name", "?"),
                                  "t": time.time()})
                _server._killed = True
                threading.Thread(target=self._finish,
                                 daemon=True).start()

        server._emit = emit
        return self

    def _finish(self) -> None:
        kill = getattr(self.replica, "kill", None)
        if kill is not None:
            kill()
        else:
            self.replica.abort()
        self.fired.set()


class ChaosMonkey:
    """Deterministic fault-injection front end. All randomness flows
    from the constructor seed; every injection is appended to ``log``.

    ::

        chaos = ChaosMonkey(seed=7)
        it = chaos.poison_batches(it, at_step=12)       # NaN at step 12
        it = chaos.flaky_iterator(it, fail_at_batch=3)  # loader IOError
        with chaos.failing_os_replace(times=1):
            mgr.save(step, state, blocking=True)        # torn commit
    """

    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        self.rng = np.random.default_rng(self.seed)
        self.log: List[dict] = []

    def draw_step(self, lo: int, hi: int) -> int:
        """A seed-deterministic step/batch index in [lo, hi)."""
        return int(self.rng.integers(lo, hi))

    # -- data-pipeline faults -------------------------------------------
    def flaky_iterator(self, wrapped, fail_at_batch: Optional[int] = None,
                       n_batches: Optional[int] = None,
                       times: int = 1) -> FlakyIterator:
        if fail_at_batch is None:
            if n_batches is None:
                raise ValueError("pass fail_at_batch= or n_batches= to "
                                 "draw one from the seed")
            fail_at_batch = self.draw_step(0, n_batches)
        return FlakyIterator(wrapped, fail_at_batch, times=times,
                             log=self.log)

    def poison_batches(self, wrapped, at_step: Optional[int] = None,
                       n_steps: Optional[int] = None,
                       times: int = 1) -> BatchPoisoner:
        if at_step is None:
            if n_steps is None:
                raise ValueError("pass at_step= or n_steps= to draw one "
                                 "from the seed")
            at_step = self.draw_step(0, n_steps)
        return BatchPoisoner(wrapped, at_step, times=times, log=self.log)

    def torn_shard(self, directory, shard_index: Optional[int] = None,
                   n_shards: Optional[int] = None, mode: str = "bitflip",
                   heal_after_failures: Optional[int] = None,
                   pipeline=None) -> TornShard:
        """Corrupt a committed datapipe shard on disk (see
        :class:`TornShard`). ``pipeline=`` subscribes the healer to the
        pipeline's event stream so ``heal_after_failures`` counts real
        reader verdicts. Draws the shard from the seed when only
        ``n_shards`` is given. Use as a context manager (restores the
        bytes on exit) or call ``.inject()`` for permanent damage."""
        if shard_index is None:
            if n_shards is None:
                raise ValueError("pass shard_index= or n_shards= to draw "
                                 "one from the seed")
            shard_index = self.draw_step(0, n_shards)
        ts = TornShard(directory, shard_index, mode=mode,
                       heal_after_failures=heal_after_failures,
                       log=self.log)
        if pipeline is not None:
            pipeline.subscribe(ts.observe)
        return ts

    @contextlib.contextmanager
    def flaky_read(self, times: int = 1, every: int = 1,
                   match: Optional[str] = None) -> Iterator[dict]:
        """Transient IO at the shard-read seam: every ``every``-th
        ``datapipe.reader._read_file_bytes`` call (optionally filtered
        to paths containing ``match``) raises ``IOError``, ``times``
        times total — the reader's transient-retry budget must absorb
        it. Yields the mutable ``{"calls", "left"}`` state."""
        from deeplearning4j_tpu.datapipe import reader as _reader
        state = {"calls": 0, "left": int(times)}
        orig = _reader._read_file_bytes

        def chaotic_read(path):
            if match is None or match in os.path.basename(str(path)):
                state["calls"] += 1
                if state["left"] > 0 and state["calls"] % int(every) == 0:
                    state["left"] -= 1
                    self.log.append({"event": "read_failed",
                                     "path": str(path),
                                     "call": state["calls"],
                                     "t": time.time()})
                    raise IOError(f"chaos: injected read failure "
                                  f"({os.path.basename(str(path))})")
            return orig(path)

        _reader._read_file_bytes = chaotic_read
        try:
            yield state
        finally:
            _reader._read_file_bytes = orig

    @contextlib.contextmanager
    def slow_reader(self, delay_s: float, times: int = 1, every: int = 1,
                    match: Optional[str] = None) -> Iterator[dict]:
        """Latency injection at the shard-read seam: every ``every``-th
        read sleeps ``delay_s`` before returning real bytes, ``times``
        times total — the straggler drill for the prefetch pool's
        read-timeout backup requests."""
        from deeplearning4j_tpu.datapipe import reader as _reader
        state = {"calls": 0, "left": int(times)}
        orig = _reader._read_file_bytes

        def slow_read(path):
            if match is None or match in os.path.basename(str(path)):
                state["calls"] += 1
                if state["left"] > 0 and state["calls"] % int(every) == 0:
                    state["left"] -= 1
                    self.log.append({"event": "slow_read_injected",
                                     "path": str(path),
                                     "delay_s": float(delay_s),
                                     "t": time.time()})
                    time.sleep(float(delay_s))
            return orig(path)

        _reader._read_file_bytes = slow_read
        try:
            yield state
        finally:
            _reader._read_file_bytes = orig

    @contextlib.contextmanager
    def worker_killer(self, at_batch: int, times: int = 1
                      ) -> Iterator[dict]:
        """Kill the prefetch worker that claims plan batch ``at_batch``
        (an unstructured crash while HOLDING the claim), ``times``
        times total: the supervisor must requeue the batch exactly
        once and respawn the worker; at ``times=2`` the twice-lost
        batch fails typed instead of ping-ponging."""
        from deeplearning4j_tpu.datapipe import prefetch as _prefetch
        state = {"at_index": int(at_batch), "left": int(times),
                 "log": self.log}
        prev = _prefetch._CHAOS_KILL
        _prefetch._CHAOS_KILL = state
        try:
            yield state
        finally:
            _prefetch._CHAOS_KILL = prev

    # -- device faults --------------------------------------------------
    @contextlib.contextmanager
    def nan_gradients(self, sd, at_step: int) -> Iterator[None]:
        """Arm device-side NaN-gradient injection at absolute iteration
        ``at_step`` for the duration of the context. Retraces the train
        step on entry and exit (the injection is part of the compiled
        program)."""
        tc = sd.training_config
        if tc is None:
            raise ValueError("set sd.training_config first")
        prev = getattr(tc, "_chaos_spec", None)
        tc._chaos_spec = ChaosSpec(nan_grads_at=int(at_step))
        sd._mutated()
        self.log.append({"event": "nan_gradients_armed",
                         "step": int(at_step), "t": time.time()})
        try:
            yield
        finally:
            tc._chaos_spec = prev
            sd._mutated()

    @contextlib.contextmanager
    def transient_device_error(self, sd, at_call: int = 0) -> Iterator[None]:
        """Make the model's next fit attempt fail host-side with a
        :class:`TransientDeviceError` (simulates a lost device /
        preempted slice surfacing as a runtime error)."""
        raise_at = {"n": int(at_call)}
        orig = sd.fit

        def flaky_fit(*a, **kw):
            if raise_at["n"] == 0:
                raise_at["n"] = -1
                self.log.append({"event": "transient_device_error",
                                 "t": time.time()})
                raise TransientDeviceError(
                    "chaos: injected transient device loss",
                    cause="device")
            if raise_at["n"] > 0:
                raise_at["n"] -= 1
            return orig(*a, **kw)

        sd.fit = flaky_fit
        try:
            yield
        finally:
            sd.fit = orig

    @contextlib.contextmanager
    def bitflip_param(self, at_call: int = 1, times: int = 1,
                      bit: int = 17, leaf: Optional[str] = None,
                      refingerprint: bool = True) -> Iterator[dict]:
        """Silent data corruption: the ``at_call``-th train dispatch's
        RETURNED params have one bit flipped (``times`` times total) —
        finite-in, finite-out, so the isfinite sentinel never fires;
        only the integrity rail (integrity/fingerprint.py) can see it.

        Two flavors, matching the two real failure modes:

        - ``refingerprint=True`` (default) also recomputes the
          window's fingerprint output over the flipped state — the
          corruption is SELF-CONSISTENT, exactly what SDC inside the
          dispatch looks like (device state and its digest agree but
          differ from a correct replay). Detected by the REPLAY PROBE
          (``TrainingConfig.fingerprint_replay_every``) or a
          cross-replica check, NOT by the capture check.
        - ``refingerprint=False`` leaves the in-program digest intact —
          the corruption happened AFTER the device computed it (a bad
          device→host copy, host memory rot). Detected by the CAPTURE
          check at the next checkpoint.

        ``bit`` indexes into the flattened first float leaf (or
        ``leaf``, by name); with fingerprints off the flip is genuinely
        silent — the negative control the docs warn about. Yields the
        mutable ``{"calls", "left", "flips"}`` state."""
        from deeplearning4j_tpu.compilecache.aot import AOTDispatch
        state = {"calls": 0, "left": int(times), "flips": []}
        orig = AOTDispatch.__call__
        monkey = self

        def _flip_leaf(arr):
            import jax
            host = np.asarray(arr).copy()
            words = host.view(np.uint8).reshape(-1)
            pos = int(bit) % (words.size * 8)
            words[pos // 8] ^= np.uint8(1 << (pos % 8))
            return jax.device_put(host), pos

        def chaotic_call(disp, *args):
            out = orig(disp, *args)
            state["calls"] += 1
            if state["left"] <= 0 or state["calls"] < int(at_call) or \
                    not (isinstance(out, tuple) and out
                         and isinstance(out[0], dict)):
                return out
            state["left"] -= 1
            params = dict(out[0])
            name = leaf if leaf is not None else sorted(
                n for n, a in params.items()
                if np.issubdtype(np.asarray(a).dtype, np.floating))[0]
            params[name], pos = _flip_leaf(params[name])
            rest = list(out[1:])
            import jax
            fp_i = None
            if rest:
                last = rest[-1]
                if getattr(last, "dtype", None) is not None and \
                        getattr(last, "shape", None) == () and \
                        str(last.dtype) == "uint32":
                    fp_i = len(rest) - 1
            if refingerprint and fp_i is not None:
                # self-consistent SDC: re-digest the FLIPPED state
                # (params + svars + updater state — the same leaf set
                # the in-program digest covers)
                from deeplearning4j_tpu.integrity.fingerprint import \
                    np_fingerprint
                leaves = list(params.values()) \
                    + jax.tree_util.tree_leaves(rest[0]) \
                    + jax.tree_util.tree_leaves(rest[1])
                rest[fp_i] = jax.device_put(
                    np.uint32(np_fingerprint(leaves)))
            monkey.log.append({"event": "param_bit_flipped",
                               "call": state["calls"], "leaf": name,
                               "bit": pos,
                               "refingerprint": bool(refingerprint
                                                     and fp_i is not None),
                               "t": time.time()})
            state["flips"].append((name, pos))
            return (params, *rest)

        AOTDispatch.__call__ = chaotic_call
        try:
            yield state
        finally:
            AOTDispatch.__call__ = orig

    @contextlib.contextmanager
    def stalled_dispatch(self, delay_s: float, at_call: int = 1,
                         times: int = 1) -> Iterator[dict]:
        """Wedged-dispatch drill: the ``at_call``-th train dispatch
        blocks ``delay_s`` seconds before returning real results,
        ``times`` times total — a RECOVERABLE stall (the call
        eventually un-wedges). With a ``StallWatchdog`` armed past its
        deadline this drives the full stall path: forensics dump,
        ``{"type": "faults", "event": "stall"}``, /healthz 503, a typed
        ``TrainingStalledError`` at the boundary's exit, and a
        FaultTolerantFit rollback-retry that passes cleanly (one-shot).
        Yields the mutable ``{"calls", "left"}`` state."""
        from deeplearning4j_tpu.compilecache.aot import AOTDispatch
        state = {"calls": 0, "left": int(times)}
        orig = AOTDispatch.__call__
        monkey = self

        def chaotic_call(disp, *args):
            state["calls"] += 1
            if state["left"] > 0 and state["calls"] >= int(at_call):
                state["left"] -= 1
                monkey.log.append({"event": "dispatch_stalled",
                                   "call": state["calls"],
                                   "delay_s": float(delay_s),
                                   "t": time.time()})
                time.sleep(float(delay_s))
            return orig(disp, *args)

        AOTDispatch.__call__ = chaotic_call
        try:
            yield state
        finally:
            AOTDispatch.__call__ = orig

    # -- checkpoint/storage faults --------------------------------------
    def rot_checkpoint(self, directory, step: Optional[int] = None,
                       mode: str = "bitflip") -> dict:
        """Checkpoint bit-rot: damage the payload bytes of a COMMITTED
        step dir on disk (newest by default) without touching its
        manifest — the classic cold-storage rot ``restore_latest``'s
        verification must skip and the ``checkpoint.Scrubber``
        quarantines. ``mode='bitflip'`` flips one payload byte;
        ``'truncate'`` halves the largest payload file. Permanent (no
        heal — rot does not heal). Returns ``{step, file, mode}``."""
        from deeplearning4j_tpu.checkpoint.scrub import _STEP_RE
        directory = os.fspath(getattr(directory, "directory", directory))
        steps = sorted(int(m.group(1))
                       for m in (_STEP_RE.match(n)
                                 for n in os.listdir(directory)) if m)
        if not steps:
            raise ValueError(f"no committed steps under {directory!r}")
        step = steps[-1] if step is None else int(step)
        d = os.path.join(directory, f"step_{step:08d}")
        payloads = [n for n in sorted(os.listdir(d))
                    if n not in ("MANIFEST.json", "COMMIT")
                    and os.path.isfile(os.path.join(d, n))]
        target = max(payloads,
                     key=lambda n: os.path.getsize(os.path.join(d, n)))
        p = os.path.join(d, target)
        with open(p, "rb") as fh:
            data = fh.read()
        if mode == "truncate":
            data = data[: len(data) // 2]
        else:
            buf = bytearray(data)
            buf[len(buf) // 2] ^= 0xFF
            data = bytes(buf)
        with open(p, "wb") as fh:
            fh.write(data)
        self.log.append({"event": "checkpoint_rotted", "step": step,
                         "file": target, "mode": mode, "t": time.time()})
        return {"step": step, "file": target, "mode": mode}

    @contextlib.contextmanager
    def resource_exhausted(self, at_call: int = 1, times: int = 1,
                           nbytes: int = 1 << 30) -> Iterator[dict]:
        """Synthetic device OOM in the TRAINING exec path: the
        ``at_call``-th train dispatch (every ``AOTDispatch`` call —
        per-step steps, fused windows, scanned epochs — counts) raises
        ``RESOURCE_EXHAUSTED``, ``times`` times total. The fit tiers
        convert it into a structured
        :class:`~deeplearning4j_tpu.memory.MemoryExhaustedError` with
        forensics attached, and ``FaultTolerantFit`` publishes the
        ``{"type": "faults", "event": "oom"}`` diagnosis instead of
        burning its retry budget — the OOM-forensics e2e's fault of
        choice (docs/fault_tolerance.md). Yields the mutable
        ``{"calls", "left"}`` state."""
        from deeplearning4j_tpu.compilecache.aot import AOTDispatch
        state = {"calls": 0, "left": int(times)}
        orig = AOTDispatch.__call__

        def chaotic_call(disp, *args):
            state["calls"] += 1
            if state["left"] > 0 and state["calls"] >= int(at_call):
                state["left"] -= 1
                self.log.append({"event": "resource_exhausted",
                                 "call": state["calls"],
                                 "t": time.time()})
                raise _synthetic_resource_exhausted(nbytes)
            return orig(disp, *args)

        AOTDispatch.__call__ = chaotic_call
        try:
            yield state
        finally:
            AOTDispatch.__call__ = orig

    @contextlib.contextmanager
    def oom_serving(self, server, at_call: int = 1, times: int = 1,
                    nbytes: int = 1 << 30) -> Iterator[dict]:
        """Synthetic device OOM in the SERVING exec path: the
        ``at_call``-th graph execution under
        ``ParallelInference._execute`` raises ``RESOURCE_EXHAUSTED``
        from inside ``sd.output`` — so the server's own conversion
        (structured OOM + ``oom`` fault record + 503 /healthz) is what
        the test exercises, not a replaced ``_execute``."""
        state = {"calls": 0, "left": int(times)}
        sd = server._spec.sd
        orig = sd.output

        def chaotic_output(*args, **kw):
            state["calls"] += 1
            if state["left"] > 0 and state["calls"] >= int(at_call):
                state["left"] -= 1
                self.log.append({"event": "resource_exhausted",
                                 "call": state["calls"],
                                 "t": time.time()})
                raise _synthetic_resource_exhausted(nbytes)
            return orig(*args, **kw)

        sd.output = chaotic_output
        try:
            yield state
        finally:
            sd.output = orig

    # -- checkpoint/storage faults --------------------------------------
    @contextlib.contextmanager
    def failing_os_replace(self, times: int = 1,
                           match: str = "step_") -> Iterator[None]:
        """The next ``times`` ``os.replace`` calls whose source path
        contains ``match`` raise OSError — exactly the crash point the
        commit protocol's atomic publish must tolerate (everything is
        staged; the rename never lands)."""
        state = {"left": int(times)}
        orig = os.replace

        def chaotic_replace(src, dst, *a, **kw):
            if state["left"] > 0 and match in os.path.basename(str(src)):
                state["left"] -= 1
                self.log.append({"event": "os_replace_failed",
                                 "path": str(dst), "t": time.time()})
                raise OSError(f"chaos: injected os.replace failure "
                              f"publishing {dst}")
            return orig(src, dst, *a, **kw)

        os.replace = chaotic_replace
        try:
            yield
        finally:
            os.replace = orig

    @contextlib.contextmanager
    def failing_fsync(self, times: int = 1) -> Iterator[None]:
        """The next ``times`` ``os.fsync`` calls raise OSError (a dying
        disk / full quota during checkpoint staging)."""
        state = {"left": int(times)}
        orig = os.fsync

        def chaotic_fsync(fd):
            if state["left"] > 0:
                state["left"] -= 1
                self.log.append({"event": "fsync_failed", "t": time.time()})
                raise OSError("chaos: injected fsync failure")
            return orig(fd)

        os.fsync = chaotic_fsync
        try:
            yield
        finally:
            os.fsync = orig

    # -- serving faults -------------------------------------------------
    @contextlib.contextmanager
    def failing_exec(self, server, n: int = 1, every: int = 1,
                     exc_factory=None) -> Iterator[dict]:
        """Deterministic transient exec failures on a
        ``serving.ParallelInference``: every ``every``-th ``_execute``
        call raises (default :class:`TransientDeviceError`, cause
        ``"exec"``), ``n`` times total. The counter covers EVERY exec —
        including the bisection/probe retries the resilience rail
        issues — so a test can reason exactly about which dispatch
        fails. Yields the mutable ``{"calls", "left"}`` state."""
        state = {"calls": 0, "left": int(n)}
        factory = exc_factory or (lambda i: TransientDeviceError(
            f"chaos: injected exec failure (call {i})", cause="exec"))
        orig = server._execute

        def chaotic_execute(features, real_rows=None):
            state["calls"] += 1
            if state["left"] > 0 and state["calls"] % int(every) == 0:
                state["left"] -= 1
                self.log.append({"event": "exec_failed",
                                 "call": state["calls"], "t": time.time()})
                raise factory(state["calls"])
            return orig(features, real_rows=real_rows)

        server._execute = chaotic_execute
        try:
            yield state
        finally:
            server._execute = orig

    def poison_request(self, template) -> np.ndarray:
        """A request payload shaped like ``template`` with every
        floating value replaced by NaN — the poisoned request the
        bisecting dispatcher must quarantine while its co-batched
        neighbours still serve bit-identically."""
        a = np.array(template, copy=True)
        if np.issubdtype(a.dtype, np.floating):
            a[...] = np.nan
        self.log.append({"event": "request_poisoned",
                         "shape": list(a.shape), "t": time.time()})
        return a

    # -- process faults -------------------------------------------------
    def sigterm_listener(self, at_iteration: int) -> SigtermListener:
        return SigtermListener(at_iteration, log=self.log)

    # -- topology faults ------------------------------------------------
    def host_loss(self, trainer, surviving_strategy,
                  at_iteration: Optional[int] = None,
                  n_steps: Optional[int] = None) -> HostLossInjector:
        """In-process host-loss drill (see :class:`HostLossInjector`):
        mid-fit, the trainer's mesh shrinks to ``surviving_strategy``
        and a retryable ``host_loss`` fault fires — the elastic e2e's
        fault of choice. Draws the iteration from the seed when only
        ``n_steps`` is given."""
        if at_iteration is None:
            if n_steps is None:
                raise ValueError("pass at_iteration= or n_steps= to draw "
                                 "one from the seed")
            at_iteration = self.draw_step(1, n_steps)
        return HostLossInjector(trainer, surviving_strategy, at_iteration,
                                log=self.log)

    def host_killer(self, at_iteration: int, exit_code: int = 137
                    ) -> HostKiller:
        """SIGKILL-grade process death at an iteration (multi-process
        dryrun drills; see :class:`HostKiller`)."""
        return HostKiller(at_iteration, exit_code=exit_code)

    def kill_mid_stream(self, replica, after_tokens: int
                        ) -> MidStreamKiller:
        """Kill a serving replica after ``after_tokens`` more emitted
        tokens — in-flight generations fail typed mid-stream (the
        fleet durability drill; see :class:`MidStreamKiller`). Armed
        immediately."""
        return MidStreamKiller(replica, after_tokens,
                               log=self.log).arm()
