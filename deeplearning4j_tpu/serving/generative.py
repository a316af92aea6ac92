"""Continuous-batching generative serving: slotted KV caches,
step-boundary admission, streaming decode.

The decoder-LM serving tier (ROADMAP item 1): ``ParallelInference``
batches fixed-shape forwards, but an autoregressive request is a LOOP —
one token per model invocation, sequence lengths unknown in advance. A
static batcher ("wait for a full batch, run it to completion") lets one
long generation hold every co-batched short request hostage and leaves
finished slots idle; the mechanism proven by Orca's iteration-level
scheduling (Yu et al., OSDI '22) and vLLM's slot-based KV memory (Kwon
et al., SOSP '23) is to keep the decode batch full by admitting new
requests **at step boundaries** into preallocated KV slots:

- **KV slabs** — two HBM arrays (K and V), shaped
  ``[layers, max_slots, heads, max_seq, head_dim]``, allocated ONCE at
  construction (headroom-guarded via ``monitor/memstats``) and donated
  through every dispatch so the cache is updated in place — no
  per-request allocation, no fragmentation.
- **ONE decode program** — a single jitted step advances *all* active
  slots per dispatch (active-slot mask + per-slot position indices);
  its shapes never change, so the decode path compiles exactly once.
- **pow2 prefill buckets** — a new request's prompt runs through a
  bucket-padded prefill program that fills its slot's KV rows and emits
  the first token (TTFT = queue wait + one prefill); the bucket ladder
  reuses ``serving/batching.py``'s machinery, so mixed prompt lengths
  cost ≤ log2(max_seq) compiled shapes.
- **continuous batching** — the scheduler admits queued requests into
  free slots at every step boundary, streams each token to its
  request's iterator/callback as it resolves, and retires finished
  slots (EOS / ``max_new_tokens`` / deadline / cancel / sequence
  capacity) immediately, so the next queued request starts on the very
  next step.
- **one step ahead** — at a boundary where every lane is greedy, no
  slot is free and no lane ends by something the host knows, step n+1
  is launched, fed step n's next tokens where they lie on the device,
  before the host reads them: token n is handed out while the device
  runs step n+1 (``_decode_once``; docs/serving.md "One step ahead").
- **SLO admission** — a rolling p99 of decode-step time
  (``serving/resilience.AdmissionController``) turns queue depth into a
  TTFT estimate; a deadline-carrying request that cannot make it is
  shed typed (``ServerOverloadedError(retry_after_s=...)``) before it
  occupies a slot.
- **crash recovery** — the decode worker runs under the PR-9
  ``WorkerSupervisor``: a crashed worker's in-flight generations are
  requeued at the FRONT exactly once and re-enter at prefill with
  ``prompt + tokens-generated-so-far`` (greedy decode is deterministic,
  so the continuation matches; already-streamed tokens are not
  re-streamed), and the respawned worker starts from fresh slabs.

Correctness contract (tests/test_generative.py): greedy tokens are
identical to :func:`greedy_decode` (the unbatched single-request
reference) for every request in a mixed-length run; a retired slot's
cache — even poisoned with NaNs — can never influence its successor
(masked positions have their V rows zeroed *under the mask*, see
``zoo/gpt.py gpt_decode_fns``), so slot reuse is bit-exact vs a fresh
server. See docs/serving.md "Generative serving".
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from queue import SimpleQueue
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np

from deeplearning4j_tpu.compilecache.aot import AOTDispatch, ph_shape_sig
from deeplearning4j_tpu.compilecache.cache import COMPILE_STATS
from deeplearning4j_tpu.monitor.trace import TRACER as _tracer
from deeplearning4j_tpu.serving.batching import BucketSpec, pow2_buckets
from deeplearning4j_tpu.serving.metrics import (LatencyHistogram,
                                                ServingMetrics, safe_ratio)
from deeplearning4j_tpu.serving.sampling import sample_token
from deeplearning4j_tpu.serving.queue import (
    InferenceRequest, RequestQueue, ServerClosedError, ServerOverloadedError,
    ServingError, ServingTimeoutError)
from deeplearning4j_tpu.serving.resilience import (AdmissionController,
                                                   InflightSlot,
                                                   ResilienceConfig,
                                                   WorkerSupervisor)


class GenerationCancelled(ServingError):
    """The request was cancelled by its client; ``tokens`` holds what
    was generated before the cancel took effect at a step boundary."""

    def __init__(self, message: str, tokens: Optional[List[int]] = None):
        super().__init__(message)
        self.tokens = list(tokens or [])


@dataclass
class GenerativeSpec:
    """A model's generative-serving contract — the decode-mode analogue
    of :class:`~deeplearning4j_tpu.serving.inference.ServingSpec`
    (produced by e.g. ``zoo.gpt.gpt_generative_spec``).

    - ``params()`` pulls the current trained parameter arrays (by-name
      sync from the training graph; ``GenerativeServer.update_model()``
      re-pulls).
    - ``prefill(params, kc, vc, io)`` with ``io = {"tokens": [L] int32,
      "length": (), "slot": ()}`` fills slot ``io["slot"]``'s KV rows
      from a bucket-padded prompt and returns
      ``(kc, vc, next_token, last_logits)``.
    - ``decode(params, kc, vc, io)`` with ``io = {"tokens": [S],
      "positions": [S], "active": [S] bool}`` advances every active
      slot one token and returns ``(kc, vc, next_tokens, logits)``.
    - ``kv_shape(max_slots, max_seq)`` is the shape of ONE slab (K and
      V are two arrays of this shape).
    - ``verify`` (optional) scores a K-token window per slot in one
      dispatch for speculative decoding: ``io = {"tokens": [S, W],
      "positions": [S], "active": [S] bool}`` returns ``(kc, vc,
      out_tokens [S, W], logits [S, W, vocab])`` where ``out[s, j]`` is
      the greedy token after consuming window columns ``0..j`` —
      column 0 is the slot's last emitted token, so ``out[s, 0]`` is
      bit-identical to what ``decode`` would have produced.

    All functions must be pure and shape-static so the server can jit
    them with donated slabs and AOT-precompile every shape it will ever
    dispatch (docs/cold_start.md).
    """

    params: Callable[[], Dict[str, object]]
    prefill: Callable
    decode: Callable
    kv_shape: Callable[[int, int], tuple]
    vocab_size: int
    max_seq_len: int
    kv_dtype: str = "float32"
    eos_id: Optional[int] = None
    verify: Optional[Callable] = None


class SlotAllocator:
    """Free-list allocator over ``n`` KV slots. ``free()`` of a slot
    that is not currently allocated raises — the slot-lifecycle
    invariant ("freed exactly once") is enforced here, not hoped for."""

    def __init__(self, n: int):
        if n <= 0:
            raise ValueError("need at least one slot")
        self.n = int(n)
        self._free = list(range(self.n - 1, -1, -1))   # pop() -> slot 0 first
        self._inuse: set = set()

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("no free slots")
        s = self._free.pop()
        self._inuse.add(s)
        return s

    def free(self, s: int) -> None:
        if s not in self._inuse:
            raise RuntimeError(f"slot {s} freed twice (or never allocated)")
        self._inuse.discard(s)
        self._free.append(s)

    def free_count(self) -> int:
        return len(self._free)

    def in_use(self) -> set:
        return set(self._inuse)

    def reset(self) -> None:
        self._free = list(range(self.n - 1, -1, -1))
        self._inuse.clear()


_STREAM_DONE = object()


class _Flight(NamedTuple):
    """One decode step that is launched and whose tokens the host has
    not read yet: what the program gave back (still on the device), the
    lanes it ran and who held them at the launch, and what its
    ``serving.decode`` span and counters will say of it."""

    nxt: object
    logits: object
    active: np.ndarray
    reqs: list
    launch_ms: float
    attrs: dict


def _trace_args(req: "GenerationRequest") -> dict:
    """The span args tying a per-request serving span to its fleet
    trace — empty for untraced requests, so local (non-fleet) traffic
    records byte-identical spans to the pre-tracing tier."""
    if req.trace_id is None:
        return {}
    return {"trace_id": req.trace_id, "segment": req.trace_seg}


@dataclass
class GenerationRequest(InferenceRequest):
    """One queued generation: prompt + budget + the per-token stream.
    Rides the existing :class:`RequestQueue` (deadlines expire queued
    requests, ``requeue`` puts crash-recovered ones back at the front)
    and the :class:`WorkerSupervisor`'s exactly-once requeue contract
    (``requeues``)."""

    prompt: np.ndarray = None
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    on_token: Optional[Callable[[int], None]] = None
    # sampling knobs: temperature 0 = exact greedy (device argmax);
    # otherwise serving/sampling.py draws from the target logits with
    # the (seed, absolute-token-index) fold — reproducible per request
    # whatever shares the batch, including after a crash requeue
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: Optional[int] = None
    # request tracing (monitor/reqtrace.py): the fleet-wide trace id +
    # segment this attempt serves under, snapshotted at submit; tags
    # every serving.* span the request touches. None = untraced (the
    # spans carry no trace args, exactly the pre-tracing shape)
    trace_id: Optional[int] = None
    trace_seg: int = 0
    generated: List[int] = field(default_factory=list)
    cancelled: bool = False
    # when _admit first gave the request a slot (time.monotonic, like
    # enqueue_t): queue wait is admit_t - enqueue_t
    admit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    last_token_t: Optional[float] = None
    _stream: SimpleQueue = field(default_factory=SimpleQueue)

    def prefix(self) -> np.ndarray:
        """Prompt + tokens generated so far — what a crash-requeued
        request re-prefills with (greedy decode is deterministic, so
        the continuation is the one the dead worker would have
        produced; already-streamed tokens are not re-emitted)."""
        if not self.generated:
            return np.asarray(self.prompt, np.int32)
        return np.concatenate([np.asarray(self.prompt, np.int32),
                               np.asarray(self.generated, np.int32)])

    # stream closure rides every resolution path (success, failure,
    # queued-deadline expiry) so a consumer iterating tokens() can
    # never hang on a finished request
    def close_stream(self, error: Optional[BaseException] = None) -> None:
        self._stream.put((_STREAM_DONE, error))

    def emit(self, token: int) -> None:
        self.generated.append(int(token))
        self._stream.put((int(token), None))

    def succeed(self) -> None:
        if not self.future.done():
            self.future.set_result(list(self.generated))
        self.close_stream()

    def fail(self, exc: BaseException) -> None:
        super().fail(exc)
        self.close_stream(exc)

    def time_out(self) -> None:
        super().time_out()
        self.close_stream(self.future.exception()
                          if self.future.done() else None)


class GenerationHandle:
    """Client view of one generation: a Future of the full token list
    plus a streaming iterator of tokens as they resolve."""

    def __init__(self, req: GenerationRequest):
        self._req = req
        self.future = req.future

    @property
    def id(self) -> int:
        return self._req.id

    def result(self, timeout: Optional[float] = None) -> List[int]:
        return self.future.result(timeout)

    def partial(self) -> List[int]:
        """Tokens generated so far (snapshot)."""
        return list(self._req.generated)

    def cancel(self) -> None:
        """Request cancellation; takes effect at the next step boundary
        (the slot is freed, the future resolves to the partial token
        list, the stream closes cleanly)."""
        self._req.cancelled = True

    def tokens(self, timeout: Optional[float] = None):
        """Iterate tokens as they are generated. Raises the request's
        failure (deadline, crash, ...) at the point the stream closed
        on it; a clean finish (EOS/max_new_tokens/cancel) just ends
        the iteration. ``timeout`` bounds the wait for EACH token: a
        gap longer than that raises the builtin :class:`TimeoutError`
        (the generation itself is unaffected — iterating again resumes
        from the next undelivered token)."""
        from queue import Empty
        while True:
            try:
                token, err = self._req._stream.get(timeout=timeout)
            except Empty:
                raise TimeoutError(
                    f"no token from generation {self._req.id} within "
                    f"{timeout}s (the request is still in flight; "
                    f"re-iterate to resume the stream)") from None
            if token is _STREAM_DONE:
                if err is not None and \
                        not isinstance(err, GenerationCancelled):
                    raise err
                return
            yield token

    def __iter__(self):
        return self.tokens()


class GenerativeMetrics(ServingMetrics):
    """ServingMetrics plus the generative lanes: TTFT (submit → first
    streamed token), inter-token latency, prefill time, token/step
    counters and slot occupancy. The extra counters/lanes export
    through the existing generic folds (``fold_serving`` →
    ``dl4j_serving_*``) without new record types."""

    def __init__(self, max_slots: int = 0):
        super().__init__()
        self.max_slots = int(max_slots)
        self.ttft_ms = LatencyHistogram()
        self.intertoken_ms = LatencyHistogram()
        self.prefill_ms = LatencyHistogram()
        for c in ("tokens_generated", "prefills", "decode_steps",
                  "slots_active_sum", "requests_cancelled",
                  "spec_rounds", "draft_tokens", "draft_accepted",
                  "draft_rejected", "requests_admitted",
                  # runs of the prefill program: a prompt longer than
                  # the largest bucket takes several
                  "prefill_runs",
                  # decode steps launched before the tokens of the step
                  # before them were on the host (of decode_steps)
                  "decode_ahead_steps"):
            self.counters[c] = 0
        # exact sums in milliseconds, taken on the worker thread where
        # the work happens (PERF.md section 3 names the metric each is for)
        for c in ("sched_host_ms_sum", "decode_launch_ms_sum",
                  "queue_wait_ms_sum"):
            self.counters[c] = 0.0

    def observe_ttft(self, ms: float) -> None:
        with self._lock:
            self.ttft_ms.record(ms)

    def observe_intertoken(self, ms: float) -> None:
        with self._lock:
            self.intertoken_ms.record(ms)

    def observe_admit(self, queue_wait_ms: float) -> None:
        """One request placed in a slot, ``queue_wait_ms`` after it was
        enqueued."""
        with self._lock:
            self.counters["requests_admitted"] += 1
            self.counters["queue_wait_ms_sum"] += queue_wait_ms

    def observe_step(self, host_ms: float) -> None:
        """One scheduler step that did work: its wall time outside any
        dispatch-to-sync interval, which is the host's own bookkeeping."""
        with self._lock:
            self.counters["sched_host_ms_sum"] += host_ms

    def observe_prefill(self, ms: float, runs: int) -> None:
        """One prompt prefilled in ``runs`` runs of the program, ``ms``
        from before the first launch to after the first token."""
        with self._lock:
            self.counters["prefills"] += 1
            self.counters["prefill_runs"] += int(runs)
            self.prefill_ms.record(ms)

    def observe_program(self, names: Sequence[str], counted) -> None:
        """What a decode program counted on the device in one step,
        added to the counters ``names`` (a spec's
        ``program_counters``, registered by the server)."""
        with self._lock:
            for name, n in zip(names, counted):
                self.counters[name] += int(n)

    def observe_spec_round(self, drafted: int, accepted: int) -> None:
        """One speculative round: ``drafted`` proposals across the
        batch, ``accepted`` of them matched by the target. Every
        EMITTED token (accepted drafts included) is counted in
        ``tokens_generated`` by the emission path exactly once;
        rejected drafts only ever land here — they never inflate
        throughput."""
        with self._lock:
            self.counters["spec_rounds"] += 1
            self.counters["draft_tokens"] += int(drafted)
            self.counters["draft_accepted"] += int(accepted)
            self.counters["draft_rejected"] += int(drafted) - int(accepted)

    def observe_decode_step(self, active: int, ms: float,
                            launch_ms: float, ahead: bool = False) -> None:
        """One decode step (or speculative round): ``ms`` from before
        the dispatch to after the host sync, of which ``launch_ms`` went
        into the target's launch, when the device can do nothing. A
        step launched ``ahead``, while the step before it was still
        unread, is counted from that step's sync to its own (its launch
        lies in the interval before)."""
        with self._lock:
            self.counters["decode_steps"] += 1
            self.counters["decode_ahead_steps"] += bool(ahead)
            self.counters["decode_launch_ms_sum"] += launch_ms
            self.counters["slots_active_sum"] += int(active)
            self.counters["batches_dispatched"] += 1
            self.counters["rows_served"] += int(active)
            self.counters["rows_padded"] += max(0, self.max_slots
                                                - int(active))
            self.batch_sizes[int(active)] = \
                self.batch_sizes.get(int(active), 0) + 1
            self.exec_ms.record(ms)

    def to_record(self) -> dict:
        rec = super().to_record()
        with self._lock:
            rec["latency_ms"]["ttft"] = self.ttft_ms.summary()
            rec["latency_ms"]["intertoken"] = self.intertoken_ms.summary()
            rec["latency_ms"]["prefill"] = self.prefill_ms.summary()
            steps = self.counters["decode_steps"]
            occ = (self.counters["slots_active_sum"]
                   / (steps * self.max_slots)) \
                if steps and self.max_slots else 0.0
            uptime = max(time.time() - self._start_t, 1e-9)
            rec["generative"] = {
                "max_slots": self.max_slots,
                "tokens_generated": self.counters["tokens_generated"],
                "prefills": self.counters["prefills"],
                "decode_steps": steps,
                "slot_occupancy": round(occ, 4),
                "tokens_per_sec": round(
                    self.counters["tokens_generated"] / uptime, 3),
                "spec_rounds": self.counters["spec_rounds"],
                "draft_tokens": self.counters["draft_tokens"],
                "draft_accepted": self.counters["draft_accepted"],
                "draft_rejected": self.counters["draft_rejected"],
                "draft_acceptance_rate": round(safe_ratio(
                    self.counters["draft_accepted"],
                    self.counters["draft_tokens"]), 4)}
        return rec

    def stats(self) -> str:
        rec = self.to_record()
        g = rec["generative"]
        lines = [super().stats(),
                 f"  generative: {g['tokens_generated']} tokens "
                 f"({g['tokens_per_sec']} tok/s lifetime), "
                 f"{g['prefills']} prefills, {g['decode_steps']} decode "
                 f"steps, slot occupancy {g['slot_occupancy']:.1%} of "
                 f"{g['max_slots']} slots"]
        if g["spec_rounds"]:
            lines.append(
                f"  speculative: {g['spec_rounds']} rounds, acceptance "
                f"{g['draft_acceptance_rate']:.1%} "
                f"({g['draft_accepted']}/{g['draft_tokens']} drafts)")
        for name in ("ttft", "intertoken", "prefill"):
            s = rec["latency_ms"][name]
            lines.append(f"  {name:<10} p50 {s['p50']:.3f} ms  "
                         f"p95 {s['p95']:.3f} ms  p99 {s['p99']:.3f} ms  "
                         f"max {s['max']:.3f} ms  (n={s['count']})")
        return "\n".join(lines)


def _spec_dispatchers(spec: GenerativeSpec,
                      kv_shape: tuple) -> Dict[str, AOTDispatch]:
    """One (decode, prefill) dispatcher pair per (spec, KV slab shape),
    memoized on the spec object: every consumer of the same model AND
    slab geometry — servers, restarts, the :func:`greedy_decode`
    reference — shares one compile set. Keyed by the slab shape, not
    just the spec: AOT executables are looked up by the io-dict shape
    signature alone, so two servers differing only in ``max_seq_len``
    would otherwise collide on the same decode signature and the
    second would silently fall off the warmed path onto lazy compiles
    (the aval-mismatch fallback) under live traffic."""
    cache = getattr(spec, "_disp_cache", None)
    if cache is None:
        cache = {}
        spec._disp_cache = cache
    key = tuple(int(d) for d in kv_shape)
    pair = cache.get(key)
    if pair is None:
        import jax
        pair = {
            "decode": AOTDispatch(
                jax.jit(spec.decode, donate_argnums=(1, 2)), ph_arg=3),
            "prefill": AOTDispatch(
                jax.jit(spec.prefill, donate_argnums=(1, 2)), ph_arg=3)}
        if getattr(spec, "verify", None) is not None:
            pair["verify"] = AOTDispatch(
                jax.jit(spec.verify, donate_argnums=(1, 2)), ph_arg=3)
        cache[key] = pair
    return pair


class GenerativeServer:
    """Continuous-batching autoregressive model server.

    ::

        spec = zoo.gpt.gpt_generative_spec(sd, cfg)
        srv = GenerativeServer(spec, max_slots=8, max_seq_len=128)
        handle = srv.submit([1, 2, 3], max_new_tokens=32)
        for tok in handle.tokens():      # streams as decoded
            ...
        tokens = handle.result()         # or the full list
        srv.shutdown()

    Admission is continuous: free slots are filled from the queue at
    every step boundary.

    ``warmup=True`` AOT-precompiles the decode program and every
    prefill bucket before the worker starts (compiles stay 0 under
    traffic; with a persistent compilation cache a warm restart serves
    with 0 backend compiles — docs/cold_start.md). ``resilience=True``
    arms SLO admission (p99 decode-step TTFT estimates) and worker
    supervision (crash requeue at prefill, exactly once).
    """

    def __init__(self, spec, max_slots: int = 8,
                 max_seq_len: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 max_queue_len: int = 256,
                 default_timeout_ms: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 stats_storage=None,
                 telemetry_port: Optional[int] = None,
                 resilience=True,
                 warmup: bool = True,
                 memory_sample_every: Optional[int] = 64,
                 draft_spec=None,
                 speculate_k: int = 4,
                 start: bool = True):
        # everything up to the worker's start is one phase of the start
        # (compilecache/cache.py): its self time is build_seconds
        with COMPILE_STATS.span("serving.build", cat="serving"):
            spec = self._coerce_spec(spec)
            self.spec = spec
            self.max_slots = int(max_slots)
            self.max_seq_len = int(max_seq_len or spec.max_seq_len)
            if self.max_seq_len > spec.max_seq_len:
                raise ValueError(
                    f"max_seq_len {self.max_seq_len} exceeds the model's "
                    f"positional capacity {spec.max_seq_len}")
            # speculative decoding: a small DRAFT model proposes K-1 tokens
            # per slot per round, the target verifies the whole window in
            # one dispatch. The draft always runs DENSE (its slabs are tiny)
            # even under a paged target. Misconfigurations that can never
            # work fail here, not mid-decode (analyze/servingpass.py lints
            # the same contract statically)
            self.speculate_k = int(speculate_k)
            self.draft_spec = None
            self.draft_slab_bytes = 0
            if draft_spec is not None:
                if not isinstance(draft_spec, GenerativeSpec):
                    if hasattr(draft_spec, "generative_spec"):
                        draft_spec = draft_spec.generative_spec()
                    else:
                        raise TypeError(
                            f"{type(draft_spec).__name__} is not usable as "
                            f"a draft: pass a dense GenerativeSpec (the "
                            f"draft always runs dense, even under a paged "
                            f"target)")
                if int(draft_spec.vocab_size) != int(spec.vocab_size):
                    raise ValueError(
                        f"draft vocab_size {draft_spec.vocab_size} != "
                        f"target vocab_size {spec.vocab_size}: speculation "
                        f"compares token ids, the vocabularies must match")
                if int(draft_spec.max_seq_len) < self.max_seq_len:
                    raise ValueError(
                        f"draft max_seq_len {draft_spec.max_seq_len} < "
                        f"served max_seq_len {self.max_seq_len}: the draft "
                        f"must cover every position the target can reach")
                if self.speculate_k < 2:
                    raise ValueError(
                        f"speculate_k must be >= 2, got {self.speculate_k} "
                        f"(a window of 1 holds only the already-emitted "
                        f"token and drafts nothing)")
                self.draft_spec = draft_spec
            self.eos_id = eos_id if eos_id is not None else spec.eos_id
            self.default_timeout_ms = default_timeout_ms
            self.max_queue_len = int(max_queue_len)
            self.stats_storage = stats_storage
            self.metrics = self._make_metrics()
            # pow2 prefill bucket ladder (serving/batching.py machinery):
            # halving down from max_seq_len to 1 — ≤ log2(max_seq)+1
            # compiled prefill shapes for ANY prompt-length mix
            self._buckets = BucketSpec(
                buckets if buckets is not None
                else pow2_buckets(
                    self.max_seq_len,
                    n_buckets=int(self.max_seq_len).bit_length()))
            if self._buckets.max_rows > self.max_seq_len:
                raise ValueError(
                    f"largest prefill bucket {self._buckets.max_rows} exceeds "
                    f"max_seq_len {self.max_seq_len}: its KV rows would not "
                    f"fit the slab")
            # resilience (serving/resilience.py): the generative tier uses
            # p99 decode-step time for TTFT estimates (ISSUE 15 / Orca-style
            # step scheduling makes tail steps the binding constraint)
            if resilience is True:
                resilience = ResilienceConfig(percentile=99.0)
            self.resilience = ResilienceConfig.normalize(resilience)
            self.admission: Optional[AdmissionController] = None
            if self.resilience is not None and self.resilience.admission:
                self.admission = AdmissionController(
                    window=self.resilience.window,
                    percentile=self.resilience.percentile,
                    min_samples=self.resilience.min_exec_samples)
            self._queue = RequestQueue(
                self.max_queue_len,
                on_timeout=lambda req: self.metrics.record_timeout("deadline"))
            self._exec_lock = threading.Lock()
            self._state_lock = threading.Lock()
            self._shapes_seen: set = set()
            self._req_id = 0
            self._id_lock = threading.Lock()
            self._closed = False
            self._killed = False         # abort(): fail in-flight, no drain
            # dispatch-to-sync ms inside the current _step (worker thread)
            self._step_busy_ms = 0.0
            # the decode loop one step ahead (worker thread): the step that
            # is launched and unread when a pass ends, and the step before
            # it, read and not yet handed out (_decode_once)
            self._ahead: Optional[_Flight] = None
            self._unemitted: Optional[Tuple[_Flight, np.ndarray]] = None
            self._dirty = False          # a respawned worker must reset state
            self._mem_every = (max(1, int(memory_sample_every))
                               if memory_sample_every else None)
            # parameters: by-name sync from the training graph, cached as
            # one dict so every dispatch shares the same device arrays
            with COMPILE_STATS.span("serving.build.params", cat="serving"):
                self._params = dict(spec.params())
            # KV slabs + host scheduler state + dispatchers — the memory
            # tier. Overridden by serving/paged's PagedGenerativeServer,
            # which replaces the dense per-slot slabs with a block pool and
            # admits on free BLOCKS rather than free slots
            with COMPILE_STATS.span("serving.build.pool", cat="serving"):
                self._init_kv()
                self._init_draft()
            self.telemetry = None
            if telemetry_port is not None:
                from deeplearning4j_tpu.monitor.server import TelemetryServer
                self.telemetry = TelemetryServer(storage=stats_storage,
                                                 port=telemetry_port)
                self.telemetry.add_scrape_hook(
                    lambda reg: reg.fold_serving(self.metrics))
                self.telemetry.add_health_provider("generative",
                                                   self._telemetry_health)
            self.warmup_report: Optional[dict] = None
            if warmup:
                self.warmup()
            self._workers: List[threading.Thread] = []
            self._supervisor: Optional[WorkerSupervisor] = None
            # gate on the CONFIG, not self._supervisor: the supervisor's
            # constructor spawns the worker before the attribute assignment
            # completes (the PR-9 construction race)
            self._supervised = (self.resilience is not None
                                and self.resilience.supervise)
            self._cur_slot: Optional[InflightSlot] = None
            self._started = False
        if start:
            self.start()

    # -- subclass hooks (serving/paged/server.py overrides) -------------
    #: counters a spec's decode program feeds (the paged tier reads its
    #: spec's ``program_counters``)
    _program_counters: Tuple[str, ...] = ()
    #: whether the decode program takes its own next tokens as its next
    #: ``tokens`` where they lie on the device (the paged tier reads it
    #: off the compiled programs of a mesh)
    _feed_on_device = True

    def _coerce_spec(self, spec):
        if not isinstance(spec, GenerativeSpec):
            if hasattr(spec, "generative_spec"):
                spec = spec.generative_spec()
            else:
                raise TypeError(
                    f"{type(spec).__name__} is not generatively servable: "
                    f"pass a GenerativeSpec (e.g. from "
                    f"zoo.gpt.gpt_generative_spec)")
        return spec

    def _make_metrics(self) -> GenerativeMetrics:
        return GenerativeMetrics(self.max_slots)

    def _init_kv(self) -> None:
        """Allocate the KV memory tier + host scheduler state.

        Dense layout: two ``[layers, max_slots, heads, max_seq,
        head_dim]`` slabs allocated ONCE, headroom-guarded, donated
        through every dispatch (docs/serving.md "Generative serving").
        """
        spec = self.spec
        shape = tuple(spec.kv_shape(self.max_slots, self.max_seq_len))
        import jax.numpy as jnp
        from deeplearning4j_tpu.memory import AllocationsTracker
        from deeplearning4j_tpu.monitor import memstats
        from deeplearning4j_tpu.ndarray.dtype import DataType
        self._kv_dtype = DataType.from_any(spec.kv_dtype).jnp
        itemsize = jnp.zeros((), self._kv_dtype).dtype.itemsize
        self.kv_slab_bytes = 2 * int(np.prod(shape)) * itemsize
        memstats.check_headroom(
            self.kv_slab_bytes,
            f"generative KV slabs ({self.max_slots} slots x "
            f"{self.max_seq_len} positions)")
        self._kc = jnp.zeros(shape, self._kv_dtype)
        self._vc = jnp.zeros(shape, self._kv_dtype)
        AllocationsTracker.get_instance().allocate("kv_slab",
                                                   self.kv_slab_bytes)
        # host-side slot state (the worker thread owns mutation)
        self._slots = SlotAllocator(self.max_slots)
        self._slot_reqs: List[Optional[GenerationRequest]] = \
            [None] * self.max_slots
        self._tokens = np.zeros(self.max_slots, np.int32)
        self._positions = np.zeros(self.max_slots, np.int32)
        self._active = np.zeros(self.max_slots, bool)
        # dispatchers: lazy jit + AOT executables keyed by io shapes;
        # slabs (args 1, 2) donated so KV updates are in place. Shared
        # per (spec, slab shape): a second server over the same model
        # and geometry — a restart, a canary — reuses every compiled
        # program instead of paying XLA again
        disp = _spec_dispatchers(spec, shape)
        self._decode_disp = disp["decode"]
        self._prefill_disp = disp["prefill"]
        self._verify_disp = disp.get("verify")

    def _init_draft(self) -> None:
        """Speculative-decoding memory + dispatchers: the draft model
        gets its own DENSE per-slot KV slabs (one row per target slot,
        kept position-synced with the target through partial
        acceptance) and its own decode/prefill dispatcher pair. A
        no-op without ``draft_spec``."""
        ds = self.draft_spec
        self._draft_decode_disp = None
        self._draft_prefill_disp = None
        self._draft_params = None
        self._dkc = self._dvc = None
        if ds is None:
            return
        if self._verify_disp is None:
            raise ValueError(
                "speculative decoding needs a target spec exposing a "
                "verify program — rebuild the spec with a current "
                "zoo.gpt.gpt_generative_spec / gpt_paged_spec")
        import jax.numpy as jnp

        from deeplearning4j_tpu.memory import AllocationsTracker
        from deeplearning4j_tpu.monitor import memstats
        from deeplearning4j_tpu.ndarray.dtype import DataType
        shape = tuple(ds.kv_shape(self.max_slots, self.max_seq_len))
        self._draft_kv_dtype = DataType.from_any(ds.kv_dtype).jnp
        itemsize = jnp.zeros((), self._draft_kv_dtype).dtype.itemsize
        self.draft_slab_bytes = 2 * int(np.prod(shape)) * itemsize
        memstats.check_headroom(
            self.draft_slab_bytes,
            f"draft KV slabs (speculative decoding, {self.max_slots} "
            f"slots x {self.max_seq_len} positions)")
        self._dkc = jnp.zeros(shape, self._draft_kv_dtype)
        self._dvc = jnp.zeros(shape, self._draft_kv_dtype)
        AllocationsTracker.get_instance().allocate("kv_slab",
                                                   self.draft_slab_bytes)
        ddisp = _spec_dispatchers(ds, shape)
        self._draft_decode_disp = ddisp["decode"]
        self._draft_prefill_disp = ddisp["prefill"]
        self._draft_params = dict(ds.params())

    def _reset_draft_slabs(self) -> None:
        if self.draft_spec is None:
            return
        import jax.numpy as jnp
        shape = tuple(self.draft_spec.kv_shape(self.max_slots,
                                               self.max_seq_len))
        self._dkc = jnp.zeros(shape, self._draft_kv_dtype)
        self._dvc = jnp.zeros(shape, self._draft_kv_dtype)

    def _refresh_draft_params(self) -> None:
        if self.draft_spec is None:
            return
        fresh = dict(self.draft_spec.params())
        with self._exec_lock:
            self._draft_params = fresh

    def _can_place(self, req: GenerationRequest) -> bool:
        """Whether the memory tier can hold ``req``'s prefill right
        now. Dense slabs: a free slot IS the capacity (the ``_admit``
        loop already gates on one). The paged subclass gates on free
        KV *blocks* — a request it cannot place goes back to the front
        of the queue until a retirement frees blocks."""
        return True

    # ------------------------------------------------------------------
    def start(self) -> None:
        """Start the decode worker (a no-op when already started).
        ``GenerativeServer(..., start=False)`` + queued submits + a late
        ``start()`` makes admission order deterministic for tests."""
        if self._started or self._closed:
            return
        self._started = True
        if self._supervised:
            self._supervisor = WorkerSupervisor(
                spawn=self._spawn_worker, n_workers=1, queue=self._queue,
                metrics=self.metrics,
                backoff_base_s=self.resilience.worker_backoff_base_s,
                backoff_max_s=self.resilience.worker_backoff_max_s,
                publish=self._publish_fault)
        else:
            self._workers.append(self._spawn_worker(0, InflightSlot()))

    def _next_id(self) -> int:
        with self._id_lock:
            self._req_id += 1
            return self._req_id

    # -- AOT warmup (compilecache/, docs/cold_start.md) -----------------
    def warmup(self, buckets: Optional[Sequence[int]] = None) -> dict:
        """AOT-precompile the decode program and every prefill bucket so
        live traffic never waits on XLA: one decode shape + ≤
        log2(max_seq)+1 prefill shapes. With a persistent compilation
        cache configured every entry is a cache hit on a warm restart
        and warmup is ~free. Returns (and stores as ``warmup_report``)
        the shape list, the compile/cache-hit deltas, ``seconds`` (the
        length of the ``serving.warmup`` span) and ``programs``: one row
        a program built by this call, in order (``label, trace_s,
        lower_s, backend_s, cache_hit, plan_analyze_s``), which sum to
        what the call added to ``COMPILE_STATS``."""
        with COMPILE_STATS.span("serving.warmup", cat="serving") as phase:
            report = self._warmup(buckets)
        report["seconds"] = round(phase.dur, 4)
        self.warmup_report = report
        return report

    def _warmup(self, buckets: Optional[Sequence[int]]) -> dict:
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.compilecache import install_compile_watcher
        from deeplearning4j_tpu.environment import environment
        from deeplearning4j_tpu.monitor import memstats
        environment().apply_compilation_cache()
        install_compile_watcher()
        bucket_list = sorted({int(b) for b in buckets}) \
            if buckets is not None else list(self._buckets.buckets)
        params_abs = {n: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
                      for n, a in self._params.items()}
        kv_abs = jax.ShapeDtypeStruct(tuple(self._kc.shape),
                                      self._kc.dtype)
        S = self.max_slots
        mark = COMPILE_STATS.mark()
        programs: List[dict] = []

        def _build(disp, io_abs, label, params_abs=params_abs,
                   kv_abs=kv_abs, role="target"):
            sig = ph_shape_sig(io_abs)
            with self._exec_lock:
                if sig not in disp.aot:
                    at = COMPILE_STATS.mark()
                    with COMPILE_STATS.precompile(label):
                        disp.aot[sig] = disp.lower(
                            params_abs, kv_abs, kv_abs, io_abs).compile()
                    memstats.capture_plan(label, sig,
                                          compiled=disp.aot[sig])
                    programs.append(COMPILE_STATS.program_row(label, at))
                # mark INSIDE the lock hold: a live dispatch between
                # compile and mark must not count a spurious lazy
                # compile for a just-warmed shape (PR-6 round-6 rule).
                # Keyed by role: the draft's decode/prefill signatures
                # are identical to the target's
                if (role, sig) not in self._shapes_seen:
                    self._shapes_seen.add((role, sig))
                    self.metrics.inc("warmup_compiles")

        _build(self._decode_disp,
               {"tokens": jax.ShapeDtypeStruct((S,), jnp.int32),
                "positions": jax.ShapeDtypeStruct((S,), jnp.int32),
                "active": jax.ShapeDtypeStruct((S,), jnp.bool_)},
               f"generative_decode_s{S}")
        for b in bucket_list:
            _build(self._prefill_disp,
                   {"tokens": jax.ShapeDtypeStruct((int(b),), jnp.int32),
                    "length": jax.ShapeDtypeStruct((), jnp.int32),
                    "slot": jax.ShapeDtypeStruct((), jnp.int32)},
                   f"generative_prefill_b{int(b)}")
        if self.draft_spec is not None:
            W = self.speculate_k
            _build(self._verify_disp,
                   {"tokens": jax.ShapeDtypeStruct((S, W), jnp.int32),
                    "positions": jax.ShapeDtypeStruct((S,), jnp.int32),
                    "active": jax.ShapeDtypeStruct((S,), jnp.bool_)},
                   f"generative_verify_s{S}w{W}")
            dparams_abs = {n: jax.ShapeDtypeStruct(tuple(np.shape(a)),
                                                   np.asarray(a).dtype)
                           for n, a in self._draft_params.items()}
            dkv_abs = jax.ShapeDtypeStruct(tuple(self._dkc.shape),
                                           self._dkc.dtype)
            _build(self._draft_decode_disp,
                   {"tokens": jax.ShapeDtypeStruct((S,), jnp.int32),
                    "positions": jax.ShapeDtypeStruct((S,), jnp.int32),
                    "active": jax.ShapeDtypeStruct((S,), jnp.bool_)},
                   f"draft_decode_s{S}", params_abs=dparams_abs,
                   kv_abs=dkv_abs, role="draft")
            for b in bucket_list:
                _build(self._draft_prefill_disp,
                       {"tokens": jax.ShapeDtypeStruct((int(b),),
                                                       jnp.int32),
                        "length": jax.ShapeDtypeStruct((), jnp.int32),
                        "slot": jax.ShapeDtypeStruct((), jnp.int32)},
                       f"draft_prefill_b{int(b)}", params_abs=dparams_abs,
                       kv_abs=dkv_abs, role="draft")
        return {
            "decode_slots": S,
            "prefill_buckets": bucket_list,
            "speculative": self.draft_spec is not None,
            "programs": programs,
            **{k: v for k, v in COMPILE_STATS.delta(mark).items()
               if k in ("backend_compiles", "cache_hits",
                        "cache_misses")}}

    # -- client API -----------------------------------------------------
    def _validate_submit(self, prompt, max_new_tokens: int) -> np.ndarray:
        """The cheap permanent-error checks every submit path runs
        BEFORE any capacity accounting, returning the coerced prompt.
        Shared so the paged subclass can validate ahead of its block
        commitment: an invalid request must surface its ValueError (a
        permanent rejection) even under pool pressure, never a
        retryable overload shed."""
        if self._closed:
            raise ServerClosedError("GenerativeServer is shut down")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if prompt.size > self.max_seq_len - 1:
            raise ValueError(
                f"prompt of {prompt.size} tokens leaves no room to "
                f"generate within max_seq_len {self.max_seq_len}")
        if prompt.min() < 0 or prompt.max() >= self.spec.vocab_size:
            raise ValueError(
                f"prompt token ids must be in [0, {self.spec.vocab_size})")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        return prompt

    def submit(self, prompt, max_new_tokens: int = 16,
               timeout_ms: Optional[float] = None,
               on_token: Optional[Callable[[int], None]] = None,
               eos_id: Optional[int] = None,
               temperature: float = 0.0,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               seed: Optional[int] = None,
               trace=None) -> GenerationHandle:
        """Enqueue one generation; returns a :class:`GenerationHandle`
        streaming tokens as they decode. Sheds typed at the call site:
        :class:`ServerOverloadedError` when the queue is full or the
        estimated TTFT (queue depth × rolling p99 decode-step time)
        already exceeds the deadline.

        ``temperature`` 0 (default) is exact greedy; > 0 samples from
        the target logits with optional ``top_k``/``top_p`` truncation,
        seeded by ``(seed, absolute token index)`` so the continuation
        is reproducible per request regardless of co-batching or a
        crash requeue. ``seed`` defaults to the request id (stable for
        the request's whole lifetime, including requeues).

        ``trace`` is an optional request-trace context (anything with
        ``trace_id``/``segment`` ints — the fleet router passes a
        ``monitor.reqtrace.TraceContext``); its identity is snapshotted
        onto the request and tags every span it touches. Purely
        observational: tokens are bit-identical with or without it."""
        prompt = self._validate_submit(prompt, max_new_tokens)
        temperature = float(temperature)
        if not np.isfinite(temperature) or temperature < 0.0:
            raise ValueError(
                f"temperature must be a finite float >= 0, "
                f"got {temperature}")
        if top_k is not None and int(top_k) < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if top_p is not None and not 0.0 < float(top_p) <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        self.metrics.inc("requests_submitted")
        timeout_ms = timeout_ms if timeout_ms is not None \
            else self.default_timeout_ms
        self._admit_check(timeout_ms)
        deadline = time.monotonic() + timeout_ms / 1000.0 \
            if timeout_ms is not None else None
        from concurrent.futures import Future
        rid = self._next_id()
        req = GenerationRequest(
            x=[prompt], future=Future(), rows=1, deadline=deadline,
            id=rid, prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            eos_id=eos_id if eos_id is not None else self.eos_id,
            on_token=on_token,
            temperature=temperature,
            top_k=int(top_k) if top_k is not None else None,
            top_p=float(top_p) if top_p is not None else None,
            seed=int(seed) if seed is not None else rid,
            trace_id=(int(trace.trace_id) if trace is not None
                      else None),
            trace_seg=(int(trace.segment) if trace is not None else 0))
        with _tracer.span("serving.enqueue", cat="serving", id=req.id,
                          prompt=int(prompt.size), **_trace_args(req)):
            try:
                self._queue.put(req)
            except ServerOverloadedError:
                self.metrics.inc("requests_rejected")
                raise
        return GenerationHandle(req)

    def submit_continuation(self, prompt, emitted,
                            max_new_tokens: int = 16,
                            timeout_ms: Optional[float] = None,
                            on_token: Optional[Callable[[int], None]]
                            = None,
                            eos_id: Optional[int] = None,
                            temperature: float = 0.0,
                            top_k: Optional[int] = None,
                            top_p: Optional[float] = None,
                            seed: Optional[int] = None,
                            trace=None) -> GenerationHandle:
        """Resume a generation from its already-emitted prefix — the
        fleet's failover/replay primitive. ``prompt + emitted`` becomes
        the prefill (on the paged server that span hits the prefix
        cache), the token budget is decremented by ``len(emitted)``,
        and the handle streams/returns only the REMAINING tokens.

        Bit-identity contract: sampling keys on ``(seed, absolute
        token index)`` and the index is prompt length + generated
        ordinal, so a continuation prefilled with the emitted prefix
        lands every remaining draw on exactly the indices the
        uninterrupted run would have used. That only holds if the seed
        crosses the hop — a sampled continuation therefore REQUIRES an
        explicit ``seed`` (the original request's), because the
        server-local default (the request id) differs per replica.

        A continuation that is already finished (budget spent, EOS
        emitted, or context full) resolves immediately to an empty
        token list without occupying a slot."""
        temperature = float(temperature)
        if temperature > 0.0 and seed is None:
            raise ValueError(
                "a sampled continuation needs the original request's "
                "seed — without it the remaining draws cannot land on "
                "the same (seed, index) stream and bit-identity is "
                "lost")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        emitted = [int(t) for t in
                   np.asarray(emitted, np.int64).reshape(-1)]
        remaining = int(max_new_tokens) - len(emitted)
        eos = eos_id if eos_id is not None else self.eos_id
        prefix = (np.concatenate([prompt,
                                  np.asarray(emitted, np.int32)])
                  if emitted else prompt)
        done = (remaining < 1
                or (eos is not None and emitted and emitted[-1] == eos)
                or int(prefix.size) >= self.max_seq_len)
        if done:
            # nothing left to decode: the interrupted generation had in
            # fact finished — resolve without queueing (an empty-result
            # handle; the caller stitches it onto the emitted prefix)
            if self._closed:
                raise ServerClosedError(
                    "GenerativeServer is shut down")
            from concurrent.futures import Future
            req = GenerationRequest(
                x=[prefix], future=Future(), rows=1,
                id=self._next_id(), prompt=prefix,
                max_new_tokens=max(1, remaining),
                eos_id=eos, temperature=temperature,
                top_k=top_k, top_p=top_p, seed=seed,
                trace_id=(int(trace.trace_id) if trace is not None
                          else None),
                trace_seg=(int(trace.segment) if trace is not None
                           else 0))
            req.succeed()
            return GenerationHandle(req)
        return self.submit(prefix, remaining, timeout_ms=timeout_ms,
                           on_token=on_token, eos_id=eos_id,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, seed=seed, trace=trace)

    def generate(self, prompt, max_new_tokens: int = 16,
                 timeout_ms: Optional[float] = None) -> List[int]:
        """Blocking convenience around :meth:`submit`."""
        return self.submit(prompt, max_new_tokens,
                           timeout_ms=timeout_ms).result()

    def _admit_check(self, timeout_ms: Optional[float]) -> None:
        """SLO admission: TTFT estimate = (queue depth + 1) × rolling
        p99 decode-step time. A deadline the estimate already exceeds
        is shed typed, with the estimate as the backoff hint."""
        if self.admission is None or timeout_ms is None:
            return
        est = self.admission.estimate_wait_ms(self._queue.pending() + 1, 1)
        if est is not None and est > timeout_ms:
            self.metrics.inc("requests_shed")
            raise ServerOverloadedError(
                f"estimated TTFT {est:.1f} ms exceeds the "
                f"{timeout_ms:.1f} ms deadline — shed at admission "
                f"(queue depth x p{self.admission.percentile:g} "
                f"decode-step time)", retry_after_s=round(est / 1000.0, 3))

    def update_model(self) -> None:
        """Re-pull trained parameters from the spec's source graph
        between dispatches (the ``ParallelInference.update_model``
        analogue)."""
        fresh = dict(self.spec.params())
        with self._exec_lock:
            self._params = fresh
        self._refresh_draft_params()

    def params_snapshot(self) -> dict:
        """The currently-installed serving parameters — the rollback
        token a canaried fleet deploy takes BEFORE ``update_model`` so
        a failed gate can restore exactly what served before."""
        with self._exec_lock:
            return self._params

    def restore_params(self, params: dict) -> None:
        """Install a :meth:`params_snapshot` between dispatches — the
        fleet-deploy rollback path (same in-flight staleness contract
        as ``update_model``)."""
        with self._exec_lock:
            self._params = dict(params)

    # -- worker ---------------------------------------------------------
    def _spawn_worker(self, index: int, slot: InflightSlot
                      ) -> threading.Thread:
        t = threading.Thread(target=self._worker_main, args=(slot,),
                             name=f"GenerativeServer-{index}", daemon=True)
        t.start()
        return t

    def _worker_main(self, slot: InflightSlot) -> None:
        self._cur_slot = slot
        try:
            if self._dirty:
                # a respawned worker after a crash: the in-flight
                # requests were requeued (they re-enter at prefill) and
                # the donated slabs may be mid-dispatch garbage — start
                # from fresh slabs + a clean slot table
                self._reset_state()
            self._dirty = True
            self._worker_loop(slot)
            slot.exited = True
        except BaseException as e:      # noqa: BLE001 — supervisor's cue
            slot.crashed = e
            if not self._supervised:
                # no supervisor to requeue them: in-flight generations
                # must not hang their clients forever
                for r in list(slot.requests or []):
                    r.fail(e)
                self.metrics.record_failure(
                    e, cause="worker_crash",
                    n=max(1, len(slot.requests or [])))

    def _reset_state(self) -> None:
        import jax.numpy as jnp
        shape = tuple(self.spec.kv_shape(self.max_slots, self.max_seq_len))
        self._kc = jnp.zeros(shape, self._kv_dtype)
        self._vc = jnp.zeros(shape, self._kv_dtype)
        self._reset_draft_slabs()
        self._reset_slots()

    def _reset_slots(self) -> None:
        """A clean slot table, and nothing launched or unread."""
        self._slots.reset()
        self._slot_reqs = [None] * self.max_slots
        self._tokens[:] = 0
        self._positions[:] = 0
        self._active[:] = False
        self._ahead = self._unemitted = None

    def _worker_loop(self, slot: InflightSlot) -> None:
        while True:
            if self._killed:
                # abort(): a killed process completes nothing — fail
                # the in-flight generations typed at this step boundary
                # and exit CLEANLY (the supervisor must not respawn or
                # requeue: the futures are already resolved)
                self._abort_inflight()
                return
            progressed = self._step(slot)
            if progressed:
                slot.progressed = True
            elif self._queue.finished and not self._active.any():
                return

    def _abort_inflight(self) -> None:
        # a step launched ahead, and tokens read and not yet handed
        # out, go with their lanes
        self._ahead = self._unemitted = None
        for s in range(self.max_slots):
            req = self._slot_reqs[s]
            if req is not None:
                self._retire(s, error=ServerClosedError(
                    f"server killed with generation {req.id} in "
                    f"flight after {len(req.generated)} tokens"))

    def _n_active(self) -> int:
        return int(self._active.sum())

    def _sync_inflight(self, slot: InflightSlot) -> None:
        """Keep the supervisor's crash-requeue window exact: every
        popped-but-unresolved generation, at all times."""
        reqs = [r for r in self._slot_reqs if r is not None]
        slot.requests = reqs or None

    def _step(self, slot: InflightSlot) -> bool:
        """One pass of the scheduler: admission, then one decode step or
        speculative round; with a step launched ahead by the pass before
        (no slot was free then and none has freed since), that step
        alone. An idle server waits for work HERE, before the
        ``serving.step`` span and the step's clock open, so it traces
        nothing and its wait is nobody's host time."""
        first = None
        if self._ahead is None and not self._active.any():
            first = self._take(timeout=0.05)
            if first is None:
                return False
        with _tracer.span("serving.step", cat="serving"):
            t0 = time.perf_counter()
            self._step_busy_ms = 0.0
            if self._ahead is not None:
                self._decode_once(slot)
                progressed = True
            else:
                progressed = self._admit(slot, first)
                if self._active.any():
                    if self._spec_ready():
                        self._speculate_once(slot)
                    else:
                        self._decode_once(slot)
                    progressed = True
            self.metrics.observe_step(
                (time.perf_counter() - t0) * 1000.0 - self._step_busy_ms)
        return progressed

    def _take(self, timeout: float = 0.0) -> Optional[GenerationRequest]:
        """The queue's next live request, if a slot is free for it."""
        if self._slots.free_count() == 0:
            return None
        return next(iter(self._queue.take(1, timeout=timeout)), None)

    def _admit(self, slot: InflightSlot,
               first: Optional[GenerationRequest] = None) -> bool:
        """Step-boundary admission: fill free slots from the queue
        (continuous batching), starting with ``first`` where an idle
        :meth:`_step` already took one. Never waits: an active decode
        batch must not stall at the boundary for new work."""
        req = first if first is not None else self._take()
        if req is None:
            return False
        admitted = 0
        with _tracer.span("serving.admit", cat="serving") as sp:
            while req is not None:
                if req.cancelled:
                    # same accounting as a slot-occupying cancel
                    # (_retire): cancelled, not served
                    req.future.set_result(list(req.generated))
                    req.close_stream()
                    self.metrics.inc("requests_cancelled")
                elif not self._can_place(req):
                    # memory-tier backpressure (paged: not enough free
                    # KV blocks): back to the FRONT — it keeps its place
                    # in line — and stop admitting until a retirement
                    # frees capacity. Does not consume the crash-requeue
                    # budget
                    self._queue.requeue(req)
                    break
                else:
                    s = self._slots.alloc()
                    self._slot_reqs[s] = req
                    self._sync_inflight(slot)
                    if req.admit_t is None:
                        # a crash-requeued request re-enters here, but
                        # it waited in the queue once
                        req.admit_t = time.monotonic()
                        self.metrics.observe_admit(
                            (req.admit_t - req.enqueue_t) * 1000.0)
                    try:
                        self._prefill(s, req)
                        admitted += 1
                    except Exception as e:  # noqa: BLE001 — per-request
                        # already OOM-wrapped by _dispatch; a failing
                        # prompt fails ITS request, not the decode worker
                        self._retire(s, error=e)
                req = self._take()
            sp.set(requests=admitted)
        return admitted > 0

    def _pad_to_bucket(self, tokens: np.ndarray):
        """``(bucket, tokens zero-padded to it)``."""
        bucket = self._buckets.bucket_for(int(tokens.size))
        padded = np.zeros(bucket, np.int32)
        padded[:tokens.size] = tokens
        return bucket, padded

    def _prefill_runs(self, s: int, prefix: np.ndarray, L: int):
        """The runs of the prefill program that fill slot ``s``, as
        ``(start, stop)`` positions of the prefix: one, here (a prompt
        past the largest bucket is refused when it is padded). The paged
        tier starts after what its prefix cache holds and cuts the rest
        into chunks."""
        return [(0, L)]

    def _prefill_io(self, s: int, prefix: np.ndarray, L: int,
                    start: int, stop: int):
        """What the prefill program of slot ``s`` is given for the run
        over ``[start, stop)`` of the prefix (all of it, here): ``(io,
        span args, filled)``; ``filled()`` runs once the run is launched
        (the paged tier registers prefix blocks there)."""
        bucket, padded = self._pad_to_bucket(prefix)
        return ({"tokens": padded, "length": np.int32(L),
                 "slot": np.int32(s)}, {"bucket": bucket}, lambda: None)

    def _prefill(self, s: int, req: GenerationRequest) -> None:
        prefix = req.prefix()
        L = int(prefix.size)
        if L > self.max_seq_len - 1:
            # a crash-requeued request whose prefix already fills the
            # sequence: nothing left to decode — finish with what it has
            self._retire(s)
            return
        runs = self._prefill_runs(s, prefix, L)

        def resolve(nxt, logits):
            return self._resolve_token(req, int(nxt), logits)

        if len(runs) == 1:
            io, attrs, filled = self._prefill_io(s, prefix, L, *runs[0])
            tok, _, ms, _ = self._dispatch(
                self._prefill_disp, io, "serving.prefill", resolve=resolve,
                slot=s, **attrs, **_trace_args(req))
            filled()
        else:
            # a prompt in chunks: the runs are launched one behind the
            # other, each on the pool the last one leaves, and the host
            # waits for the last alone, which gives the first token
            with _tracer.span("serving.prefill", cat="serving", slot=s,
                              hist=runs[0][0], **_trace_args(req)):
                t0 = time.perf_counter()
                for k, (start, stop) in enumerate(runs):
                    last = k == len(runs) - 1
                    io, attrs, filled = self._prefill_io(s, prefix, L,
                                                         start, stop)
                    tok = self._dispatch(
                        self._prefill_disp, io, "serving.prefill_chunk",
                        sync=last, resolve=resolve if last else None,
                        index=k, of=len(runs), **attrs)[0]
                    filled()
                ms = (time.perf_counter() - t0) * 1000.0
        self.metrics.observe_prefill(ms, runs=len(runs))
        self._step_busy_ms += ms
        self._positions[s] = L
        self._tokens[s] = tok
        self._active[s] = True
        with _tracer.span("serving.emit", cat="serving", tokens=1):
            self._emit(s, req, tok)
        self._draft_prefill(s, prefix, L)

    def _draft_prefill(self, s: int, prefix: np.ndarray, L: int) -> None:
        """Fill the DRAFT model's KV rows for a freshly admitted slot
        — always the FULL prefix from scratch (the draft has no prefix
        cache, even under a paged target). Its first-token output is
        discarded: the target's prefill already emitted the real one,
        and the draft only needs its cache position-synced before the
        first speculative round."""
        if self.draft_spec is None or not self._active[s]:
            return
        bucket, padded = self._pad_to_bucket(prefix)
        io = {"tokens": padded, "length": np.int32(L),
              "slot": np.int32(s)}
        self._dispatch(self._draft_prefill_disp, io, "serving.draft",
                       draft=True, sync=False, phase="prefill",
                       bucket=bucket, slot=s)

    def _resolve_token(self, req: GenerationRequest, device_tok: int,
                       logits_row) -> int:
        """The target's own next token for one slot: the device argmax
        at temperature 0 (bit-identical to the greedy-only path),
        otherwise a seeded host sample from the target logits at this
        request's absolute token index. The (seed, index) fold makes
        the draw independent of co-batching, admission order and
        crash-requeue re-entry; under speculation the emitted token is
        ALWAYS the target's own, so output never depends on draft
        quality — only throughput does."""
        if not req.temperature or req.temperature <= 0.0:
            return int(device_tok)
        seed = req.seed if req.seed is not None else req.id
        return sample_token(np.asarray(logits_row),
                            temperature=req.temperature,
                            top_k=req.top_k, top_p=req.top_p,
                            seed=seed,
                            index=int(np.asarray(req.prompt).size)
                            + len(req.generated))

    def _sampled_active(self) -> bool:
        return any(r is not None and r.temperature > 0
                   for r in self._slot_reqs)

    def _trace_slots(self) -> dict:
        """The slot -> trace_id occupancy map a batch-level dispatch
        span records: ONE decode dispatch serves every active slot at
        once, so per-request attribution needs to know who shared it
        (``monitor.reqtrace.assemble`` divides the span's duration by
        the map size). Only traced requests appear; call sites attach
        the map only while the tracer is recording."""
        out = {}
        for s, r in enumerate(self._slot_reqs):
            if r is not None and r.trace_id is not None:
                out[s] = r.trace_id
        return out

    def _batch_span_args(self, n_active: int, **extra) -> dict:
        attrs = dict(extra, active=n_active)
        if _tracer.enabled:
            slots = self._trace_slots()
            if slots:
                attrs["slots"] = slots
        return attrs

    def _decode_io(self, lead: int = 0) -> Optional[dict]:
        """What the decode program is given this step; ``None`` when no
        lane is left to decode (the paged tier can retire lanes while it
        grows their block tables). ``lead`` is 1 while the step before
        is launched and unread: the books hold the positions of the
        tokens handed out, and that step has each active lane's next
        row."""
        positions = self._positions.copy()
        positions[self._active] += lead
        return {"tokens": self._tokens.copy(), "positions": positions,
                "active": self._active.copy()}

    def _decode_span_args(self, io: dict) -> dict:
        """What the ``serving.decode`` span says of this step's io beside
        the lanes (paged: the width of the tables sent)."""
        return {}

    def _sample_pool(self) -> None:
        """Memory-tier occupancy sample, once per decode step or round
        (paged: the block pool)."""

    def _check_leaks(self) -> None:
        """Memory-tier leak invariant at the end of a step or round
        (paged, under ``debug_leaks``)."""

    def _observe_decode(self, n_active: int, ms: float,
                        launch_ms: float, ahead: bool = False) -> None:
        self.metrics.observe_decode_step(n_active, ms, launch_ms, ahead)
        self._step_busy_ms += ms
        if self.admission is not None:
            self.admission.observe(ms)
        self._maybe_memory_record()

    def _decode_once(self, slot: InflightSlot) -> None:
        """One decode step, from its launch to its tokens on the host
        and out to their requests, with the loop ONE STEP AHEAD where
        the books allow (:meth:`_may_run_ahead`): the step after this
        one is launched, fed this step's next tokens as they lie on the
        device, before the host waits for them. This step's tokens then
        wait until the next pass, which hands them out first thing,
        inside the ``serving.decode`` span and clock of the step in the
        air: while a decode program is launched and unread, the time is
        decode's. Where the books say no, the boundary is synchronous:
        sync, emit, and the next pass admits and launches."""
        fl, self._ahead = self._ahead, None
        if fl is None:
            io = self._decode_io()
            if io is None:
                return
            attrs = self._decode_attrs(io)
        else:
            io, attrs = None, fl.attrs
        with _tracer.span("serving.decode", cat="serving", **attrs):
            t0 = time.perf_counter()
            if fl is None:
                fl = self._launch_decode(io, attrs)
            else:
                self._emit_step(*self._unemitted)
                self._unemitted = None
            # the memory tier as this step runs on it, before the step
            # after it takes its blocks
            self._sample_pool()
            nfl = self._launch_ahead(fl)
            with _tracer.span("serving.sync", cat="serving"):
                nxt = np.asarray(fl.nxt)
            ms = (time.perf_counter() - t0) * 1000.0
        self._observe_decode(attrs["active"], ms, fl.launch_ms,
                             ahead="ahead" in attrs)
        if self._program_counters:
            # what the program counted rides behind its next tokens, in
            # the one array the sync brings over
            self.metrics.observe_program(self._program_counters,
                                         nxt[self.max_slots:])
        if nfl is not None:
            self._ahead, self._unemitted = nfl, (fl, nxt)
        else:
            self._emit_step(fl, nxt)
        self._check_leaks()

    def _decode_attrs(self, io: dict) -> dict:
        return self._batch_span_args(int(io["active"].sum()),
                                     **self._decode_span_args(io))

    def _launch_decode(self, io: dict, attrs: dict) -> _Flight:
        nxt, logits, launch_ms = self._launch(self._decode_disp, io,
                                              "serving.decode")
        return _Flight(nxt, logits, io["active"], list(self._slot_reqs),
                       launch_ms, attrs)

    def _may_run_ahead(self) -> bool:
        """Whether the step after the one in the air may be launched
        before that one's tokens are read, by the server's own books:
        every lane greedy (its token is the device's argmax and needs
        nothing from the host) and no draft armed; no slot free, and no
        lane that ends at the step in the air by something known
        beforehand (its budget, the sequence's end, a cancel), so that
        no admission can follow it: a request is only ever placed at a
        boundary with nothing in the air, on an idle device."""
        if (self._draft_decode_disp is not None or not self._feed_on_device
                or self._slots.free_count()):
            return False
        for s, req in enumerate(self._slot_reqs):
            if (req is None or not self._active[s] or req.temperature > 0
                    or req.cancelled
                    or len(req.generated) + 1 >= req.max_new_tokens
                    or int(self._positions[s]) + 2 >= self.max_seq_len):
                return False
        return True

    def _next_tokens(self, nxt):
        """The decode program's next tokens as its next run's ``tokens``
        input, on the device (the paged tier cuts them out of a packed
        array)."""
        return nxt

    def _launch_ahead(self, fl: _Flight) -> Optional[_Flight]:
        """Launch the step after ``fl`` while ``fl`` is unread, if the
        books allow: its io follows from positions alone (a lane is one
        row further whatever its token is) and its tokens are ``fl``'s
        next tokens where they lie."""
        if not self._may_run_ahead():
            return None
        io = self._decode_io(1)
        if io is None:
            return None
        io["tokens"] = self._next_tokens(fl.nxt)
        return self._launch_decode(io, dict(self._decode_attrs(io), ahead=1))

    def _emit_step(self, fl: _Flight, nxt: np.ndarray) -> None:
        """Hand a decode step's tokens to the lanes that ran it. A lane
        that has ended since the launch (an EOS, a cancel, a deadline or
        a failing ``on_token`` at the step before, with this one already
        in the air) drops its token: its books are those of the tokens
        it was handed."""
        with _tracer.span("serving.emit", cat="serving",
                          tokens=fl.attrs["active"]):
            lg = np.asarray(fl.logits) if self._sampled_active() else None
            for s in np.flatnonzero(fl.active):
                s = int(s)
                req = self._slot_reqs[s]
                if req is None or req is not fl.reqs[s]:
                    continue
                tok = self._resolve_token(
                    req, int(nxt[s]), lg[s] if lg is not None else None)
                self._positions[s] += 1
                self._tokens[s] = tok
                self._emit(s, req, tok)

    # -- speculative decoding (draft K, verify once) --------------------
    def _spec_ready(self) -> bool:
        """Whether the next round can run speculatively: a draft is
        armed and every active slot has a full verify window of
        positions left in the slab. The paged subclass additionally
        grows block tables to cover the window up front, falling back
        to a plain step when the pool cannot."""
        if self._draft_decode_disp is None:
            return False
        act = np.flatnonzero(self._active)
        if act.size == 0:
            return False
        return bool(np.all(self._positions[act].astype(np.int64)
                           + self.speculate_k <= self.max_seq_len))

    def _verify_io(self, window: np.ndarray, positions: np.ndarray,
                   active: np.ndarray) -> dict:
        return {"tokens": window, "positions": positions.copy(),
                "active": active.copy()}

    def _speculate_once(self, slot: InflightSlot) -> None:
        """One draft-K / verify-once speculative round (Leviathan et
        al., "Fast Inference from Transformers via Speculative
        Decoding"): K sequential DRAFT decode dispatches propose a
        token window per active slot, then the TARGET scores the whole
        window in ONE batched verify dispatch — one read of the target
        weights for up to K emitted tokens. Acceptance is exact: every
        emitted token is the target's own (:meth:`_resolve_token`), so
        output is independent of draft quality; the draft only decides
        how many positions the single verify dispatch resolves. A
        rejected tail needs no KV rollback — positions simply never
        advance over it, and rows above a slot's position are masked
        until overwritten (the same discipline that makes slot reuse
        safe). The draft's own KV stays row-synced because dispatch m
        feeds window column m-1 (the token that, if the round reaches
        that column, is exactly what was accepted there)."""
        W = self.speculate_k
        active = self._active.copy()
        positions = self._positions.copy()
        n_active = int(active.sum())
        window = np.zeros((self.max_slots, W), np.int32)
        window[:, 0] = self._tokens
        reqs = list(self._slot_reqs)
        act_idx = [int(s) for s in np.flatnonzero(active)
                   if reqs[int(s)] is not None]
        sampled = any(reqs[s].temperature > 0 for s in act_idx)
        t0 = time.perf_counter()
        # draft loop: dispatch m feeds window column m-1 at position
        # pos0+m-1, writing that draft-KV row and proposing column m.
        # The W-th dispatch exists only for its KV write (the draft
        # cache must cover the full window before the NEXT round); its
        # proposal is discarded
        d_tokens = window[:, 0].copy()
        for m in range(1, W + 1):
            dio = {"tokens": d_tokens.copy(),
                   "positions": (positions + np.int32(m - 1)
                                 * active).astype(np.int32),
                   "active": active.copy()}
            dnxt, dlg, _, _ = self._dispatch(
                self._draft_decode_disp, dio, "serving.draft",
                draft=True, sync=m < W,
                **self._batch_span_args(n_active, step=m))
            if m >= W:
                break
            dlg_h = np.asarray(dlg) if sampled else None
            for s in act_idx:
                req = reqs[s]
                d = int(dnxt[s])
                if req.temperature and req.temperature > 0:
                    # the draft proposal consumes the SAME (seed,
                    # index) draw the target will use to resolve this
                    # position — close distributions then agree on the
                    # sampled token, maximizing acceptance, while the
                    # emitted token remains the target's own
                    d = sample_token(
                        dlg_h[s], temperature=req.temperature,
                        top_k=req.top_k, top_p=req.top_p,
                        seed=req.seed if req.seed is not None
                        else req.id,
                        index=int(np.asarray(req.prompt).size)
                        + len(req.generated) + m - 1)
                window[s, m] = d
            d_tokens = window[:, m].copy()
        vio = self._verify_io(window, positions, active)
        out, vlg_d, _, launch_ms = self._dispatch(
            self._verify_disp, vio, "serving.verify",
            **self._batch_span_args(n_active, window=W))
        self._observe_decode(
            n_active, (time.perf_counter() - t0) * 1000.0, launch_ms)
        drafted = accepted = 0
        with _tracer.span("serving.emit", cat="serving", window=W):
            lg = np.asarray(vlg_d) if sampled else None
            for s in act_idx:
                req = reqs[s]
                drafted += W - 1
                pos0 = int(positions[s])
                for j in range(W):
                    tok = self._resolve_token(
                        req, int(out[s, j]),
                        lg[s, j] if lg is not None else None)
                    self._positions[s] = pos0 + j + 1
                    self._tokens[s] = tok
                    self._emit(s, req, tok)
                    if not self._active[s]:
                        break  # retired: EOS / budget / deadline / cancel
                    if j + 1 >= W:
                        break
                    if int(window[s, j + 1]) != tok:
                        break  # draft rejected: the window tail is invalid
                    accepted += 1
        self.metrics.observe_spec_round(drafted, accepted)
        self._sample_pool()
        self._check_leaks()

    def _dispatch(self, disp: AOTDispatch, io: dict, span: str,
                  draft: bool = False, sync: bool = True, resolve=None,
                  **attrs):
        """One device dispatch of prefill/decode/verify, from before
        the launch until its result is on the host, with the shared
        plumbing: the ``span`` and the clock that ``GenerativeMetrics``
        is fed from (the same two edges), exec lock, stall-watchdog
        guard, compile accounting, OOM forensics, and slab rebinding
        (the old slab buffers are donated into the call).

        Under ``span`` sit ``serving.launch`` (the call that enqueues
        the program; the device can do nothing until it returns) and,
        with ``sync``, ``serving.sync`` (the host's wait for the next
        tokens). ``resolve(next_tokens, logits)``, where given, runs
        after the sync and inside both span and clock, and its result
        stands for the next tokens (a prefill's first token is only
        known once it is resolved). ``draft=True`` routes to the draft
        model's params + slabs; the shapes-seen key carries the role
        because draft and target share io signatures.

        Returns ``(next tokens, logits on the device, ms, launch ms)``;
        the next tokens stay on the device without ``sync``."""
        with _tracer.span(span, cat="serving", **attrs):
            t0 = time.perf_counter()
            nxt, logits, launch_ms = self._launch(disp, io, span, draft)
            if sync:
                with _tracer.span("serving.sync", cat="serving"):
                    nxt = np.asarray(nxt)
            if resolve is not None:
                nxt = resolve(nxt, logits)
            ms = (time.perf_counter() - t0) * 1000.0
        return nxt, logits, ms, launch_ms

    def _launch(self, disp: AOTDispatch, io: dict, what: str,
                draft: bool = False):
        """Enqueue one program (``serving.launch``): exec lock,
        stall-watchdog guard, compile accounting, OOM forensics, and
        slab rebinding. Returns ``(next tokens, logits, launch ms)``,
        both arrays on the device."""
        sig = ("draft" if draft else "target", ph_shape_sig(io))
        with self._exec_lock, \
                _tracer.span("serving.launch", cat="serving"):
            t_launch = time.perf_counter()
            first = sig not in self._shapes_seen
            if first:
                self._shapes_seen.add(sig)
                self.metrics.inc("compiles")
            from deeplearning4j_tpu.integrity.watchdog import \
                guard as _wd_guard
            try:
                with _wd_guard("generative_step", first=first):
                    if draft:
                        kc, vc, nxt, logits = disp(
                            self._draft_params, self._dkc, self._dvc, io)
                    else:
                        kc, vc, nxt, logits = disp(
                            self._params, self._kc, self._vc, io)
            except Exception as e:
                raise self._wrap_exec_error(e, what) from e
            if draft:
                self._dkc, self._dvc = kc, vc
            else:
                self._kc, self._vc = kc, vc
            launch_ms = (time.perf_counter() - t_launch) * 1000.0
        return nxt, logits, launch_ms

    def _wrap_exec_error(self, e: BaseException, what: str):
        from deeplearning4j_tpu.monitor import memstats
        if memstats.is_resource_exhausted(e):
            err = memstats.oom_error(e, program=f"generative_{what}")
            self._publish_fault("oom", program=f"generative_{what}",
                                error=repr(e))
            return err
        return e

    def _maybe_memory_record(self) -> None:
        if self._mem_every is None or self.stats_storage is None:
            return
        if self.metrics.counters["decode_steps"] % self._mem_every != 0:
            return
        from deeplearning4j_tpu.monitor import memstats
        try:
            self.stats_storage.put(memstats.memory_record(source="serving"))
        except Exception:
            pass            # a broken stats sink must not fail requests

    # -- token delivery + retirement ------------------------------------
    def _emit(self, s: int, req: GenerationRequest, tok: int) -> None:
        """Deliver one decoded token to its request's stream at the
        step boundary it resolved, then retire the slot if this token
        finished the generation (EOS / budget / capacity / deadline /
        cancel) — a freed slot is admissible on the very next step."""
        now = time.monotonic()
        # deadline re-checked at DELIVERY time (the serving tier's
        # reply-time deadline rule): a generation that outlived its
        # deadline mid-decode surfaces as a timeout, not a stale stream
        if req.expired(now):
            err = ServingTimeoutError(
                f"generation {req.id} missed its deadline after "
                f"{len(req.generated)} tokens")
            err.tokens = list(req.generated)
            self.metrics.record_timeout("deadline")
            self._retire(s, error=err, timed_out=True)
            return
        if req.cancelled:
            self._retire(s, cancelled=True)
            return
        with _tracer.span("serving.reply", cat="serving", id=req.id,
                          **_trace_args(req)):
            req.emit(tok)
        self.metrics.inc("tokens_generated")
        if req.first_token_t is None:
            req.first_token_t = now
            self.metrics.observe_ttft((now - req.enqueue_t) * 1000.0)
        else:
            self.metrics.observe_intertoken(
                (now - req.last_token_t) * 1000.0)
        req.last_token_t = now
        if req.on_token is not None:
            try:
                req.on_token(tok)
            except Exception as e:      # noqa: BLE001 — user callback
                self._retire(s, error=e)
                return
        done = (len(req.generated) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)
                or int(self._positions[s]) + 1 >= self.max_seq_len)
        if done:
            self._retire(s)

    def _retire(self, s: int, error: Optional[BaseException] = None,
                timed_out: bool = False, cancelled: bool = False) -> None:
        """Free slot ``s`` exactly once and resolve its request."""
        req = self._slot_reqs[s]
        self._slot_reqs[s] = None
        self._active[s] = False
        self._slots.free(s)
        if req is not None:
            now = time.monotonic()
            if error is not None:
                req.fail(error)
                if not timed_out:
                    self.metrics.record_failure(error)
            elif cancelled:
                # resolve the future BEFORE closing the stream: a
                # consumer that sees the stream end must find the
                # result already set (no result(timeout=0) race)
                if not req.future.done():
                    req.future.set_result(list(req.generated))
                req.close_stream(GenerationCancelled(
                    f"generation {req.id} cancelled",
                    tokens=req.generated))
                self.metrics.inc("requests_cancelled")
            else:
                req.succeed()
                self.metrics.observe_request(
                    queue_wait_ms=((req.admit_t or now)
                                   - req.enqueue_t) * 1000.0,
                    e2e_ms=(now - req.enqueue_t) * 1000.0)
        # keep the supervisor's crash-requeue window exact
        if self._cur_slot is not None:
            self._sync_inflight(self._cur_slot)

    # -- observability --------------------------------------------------
    def memory_report(self) -> dict:
        """KV slab accounting for /memory + capacity planning."""
        per_slot = self.kv_slab_bytes // max(1, self.max_slots)
        return {"kv_slab_bytes": self.kv_slab_bytes,
                "kv_slab_shape": list(self._kc.shape),
                "kv_bytes_per_slot": per_slot,
                "max_slots": self.max_slots,
                "max_seq_len": self.max_seq_len,
                "active_slots": self._n_active()}

    def _publish_fault(self, event: str, **fields) -> None:
        if self.stats_storage is None:
            return
        try:
            self.stats_storage.put({"type": "faults", "event": event,
                                    "t": time.time(), "origin": "serving",
                                    **fields})
        except Exception:
            pass        # a broken stats sink must not take a worker down

    def _telemetry_health(self) -> dict:
        depth = self._queue.pending()
        active = self._n_active()
        healthy = not self._closed
        return {"queue_depth": depth,
                "queue_capacity": self.max_queue_len,
                "active_slots": active,
                "max_slots": self.max_slots,
                "ready": healthy and depth < self.max_queue_len,
                "healthy": healthy,
                # the one-scrape routing signal: health_snapshot merges
                # this sub-dict into /readyz's top-level "load" key
                "load": self._telemetry_load(depth, active)}

    def _telemetry_load(self, depth: int, active: int) -> dict:
        step_ms = 0.0
        if self.admission is not None:
            try:
                step_ms = float(self.admission.exec_ms())
            except Exception:
                step_ms = 0.0           # cold controller: no samples yet
        return {"queue_depth": depth,
                "slot_occupancy": (active / self.max_slots)
                if self.max_slots else 0.0,
                "p99_decode_step_ms": round(step_ms, 3)}

    # -- lifecycle ------------------------------------------------------
    def abort(self, timeout: Optional[float] = None) -> None:
        """The chaos kill switch: fail queued AND in-flight generations
        with :class:`ServerClosedError` instead of letting active slots
        finish — what a SIGKILL looks like to clients holding handles
        (``shutdown(drain=False)`` only fails the QUEUE; in-flight work
        still completes). The in-flight failure lands at the worker's
        next step boundary; tokens already emitted stay emitted — the
        fleet's continuation failover resumes from exactly those. Must
        be called from outside the decode worker (it joins the worker
        thread); the mid-stream chaos injector trips ``_killed`` from
        the emit hook and calls this from a side thread."""
        self._killed = True
        self.shutdown(drain=False, timeout=timeout)

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop intake; with ``drain`` (default) finish queued and
        in-flight generations, otherwise fail queued futures
        immediately (in-flight slots still finish their current
        generation). Idempotent."""
        if self._closed:
            return
        self._closed = True
        # a server that was never start()ed has no worker to drain —
        # leaving queued futures pending would hang their clients
        # forever, so they fail typed instead
        self._queue.close(drain=drain and self._started)
        if self._supervisor is not None:
            self._supervisor.stop(timeout=timeout)
        for t in self._workers:
            t.join(timeout=timeout)
        from deeplearning4j_tpu.memory import AllocationsTracker
        AllocationsTracker.get_instance().release("kv_slab",
                                                  self.kv_slab_bytes)
        if self.draft_slab_bytes:
            AllocationsTracker.get_instance().release(
                "kv_slab", self.draft_slab_bytes)
        if self.stats_storage is not None:
            self.metrics.publish(self.stats_storage)
        if self.telemetry is not None:
            self.telemetry.close()

    def __enter__(self) -> "GenerativeServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.shutdown(drain=exc_type is None)


def greedy_decode(spec: GenerativeSpec, prompt, max_new_tokens: int = 16,
                  eos_id: Optional[int] = None,
                  max_seq_len: Optional[int] = None,
                  buckets: Optional[Sequence[int]] = None) -> List[int]:
    """Unbatched single-request greedy decode — the REFERENCE the
    continuous-batching server is pinned against: fresh one-slot slabs,
    the same pow2 prefill bucketing (bucket choice is a deterministic
    function of the prompt length, so both paths run the same prefill
    program), then one decode step per token. Greedy tokens from the
    server match this for every request in a mixed run
    (tests/test_generative.py)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.ndarray.dtype import DataType
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    msl = int(max_seq_len or spec.max_seq_len)
    bspec = BucketSpec(buckets if buckets is not None
                       else pow2_buckets(msl, n_buckets=msl.bit_length()))
    dt = DataType.from_any(spec.kv_dtype).jnp
    kc = jnp.zeros(spec.kv_shape(1, msl), dt)
    vc = jnp.zeros(spec.kv_shape(1, msl), dt)
    params = dict(spec.params())
    disp = _spec_dispatchers(spec, tuple(spec.kv_shape(1, msl)))
    prefill_j, decode_j = disp["prefill"], disp["decode"]
    L = int(prompt.size)
    if not 1 <= L <= msl - 1:
        raise ValueError(f"prompt length {L} not in [1, {msl - 1}]")
    bucket = bspec.bucket_for(L)
    padded = np.zeros(bucket, np.int32)
    padded[:L] = prompt
    kc, vc, nxt, _ = prefill_j(params, kc, vc,
                               {"tokens": padded, "length": np.int32(L),
                                "slot": np.int32(0)})
    out = [int(nxt)]
    pos = L
    while (len(out) < int(max_new_tokens)
           and not (eos_id is not None and out[-1] == eos_id)
           and pos + 1 < msl):
        io = {"tokens": np.asarray([out[-1]], np.int32),
              "positions": np.asarray([pos], np.int32),
              "active": np.asarray([True])}
        kc, vc, nxt, _ = decode_j(params, kc, vc, io)
        pos += 1
        out.append(int(np.asarray(nxt)[0]))
    return out


__all__ = ["GenerativeSpec", "GenerativeServer", "GenerativeMetrics",
           "GenerationHandle", "GenerationRequest", "GenerationCancelled",
           "SlotAllocator", "greedy_decode"]
