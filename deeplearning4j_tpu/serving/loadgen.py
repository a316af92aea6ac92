"""Closed- and open-loop load generation for the serving stack.

No reference analogue (the reference ships no load tool); this is the
standard serving-benchmark pair:

- **closed loop**: N client threads, each issuing its next request only
  after the previous one completes — measures latency under a fixed
  concurrency, throughput is an OUTPUT;
- **open loop**: requests submitted on a fixed-rate clock regardless of
  completion — the arrival process a real fleet produces; exposes
  queueing collapse (rejections/timeouts) that closed loops hide.

:class:`GenerativeLoadGenerator` is the autoregressive twin over a
``serving.generative.GenerativeServer``: mixed prompt/output lengths
sampled from a **seeded per-request distribution** (request ``i`` is
identical across runs and concurrency settings, so two servers can
be compared on the SAME trace), optional per-request deadlines, and
TTFT + inter-token percentiles on :class:`LoadResult` — the driver of
the acceptance tests (tests/test_generative.py).

:class:`FleetLoadGenerator` is the multi-target replay: it drives a
**callable front door** (``serving.fleet.FleetRouter.generate``, or
any ``fn(prompt, max_new_tokens, timeout_ms)`` returning a
``FleetResult``-shaped object) instead of one server, tags every
``LoadResult`` row with the replica that served it and the retries it
took, and reports fleet-wide TTFT / inter-token percentiles. Request
``i`` stays a pure function of ``(seed, i)`` — identical traces
against one replica, a fleet of three, or affinity-vs-random routing.
An optional ``prefix_pool`` mixes shared prompt prefixes into the
trace (the repeated-prefix traffic that prefix-affinity routing and
prefix caching exist for).

Used by tests/test_serving.py, tests/test_fleet.py and
examples/serving_mnist.py / examples/fleet_serving.py.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from deeplearning4j_tpu.serving.queue import (
    RequestTimeoutError, ServerClosedError, ServerOverloadedError)
from deeplearning4j_tpu.serving.resilience import RetryableServingError


@dataclass
class LoadResult:
    """Outcome of one load run."""

    n_ok: int = 0
    n_rejected: int = 0             # ServerOverloadedError at submit
    n_timed_out: int = 0            # RequestTimeoutError from the future
    n_failed: int = 0               # anything else
    duration_s: float = 0.0
    latencies_ms: List[float] = field(default_factory=list)
    # generative traffic (GenerativeLoadGenerator): per-request time to
    # first streamed token, per-gap inter-token latencies, token total
    ttft_ms: List[float] = field(default_factory=list)
    intertoken_ms: List[float] = field(default_factory=list)
    tokens_total: int = 0
    # fleet traffic (FleetLoadGenerator): one row per request —
    # ``{"i", "outcome", "replica", "retries", "routed", "ttft_ms",
    # "e2e_ms", "resumes", "tokens_salvaged", "ttft_breakdown"}`` — so
    # a run can be sliced per replica, per retry count, per durability
    # resume, and (when the request's trace was sampled) per TTFT phase
    rows: List[dict] = field(default_factory=list)

    @property
    def resumes_total(self) -> int:
        """Mid-stream failovers resumed from the emitted prefix across
        the run (fleet rows; 0 without the durability rail)."""
        return sum(int(r.get("resumes") or 0) for r in self.rows)

    @property
    def tokens_salvaged_total(self) -> int:
        return sum(int(r.get("tokens_salvaged") or 0) for r in self.rows)

    @property
    def n_issued(self) -> int:
        return self.n_ok + self.n_rejected + self.n_timed_out + self.n_failed

    @property
    def retries_total(self) -> int:
        return sum(int(r.get("retries") or 0) for r in self.rows)

    def by_replica(self) -> dict:
        """``{replica: n_ok}`` over the tagged rows (fleet runs)."""
        out: dict = {}
        for r in self.rows:
            if r.get("outcome") == "ok" and r.get("replica"):
                out[r["replica"]] = out.get(r["replica"], 0) + 1
        return out

    @property
    def throughput_rps(self) -> float:
        return self.n_ok / self.duration_s if self.duration_s > 0 else 0.0

    @property
    def tokens_per_sec(self) -> float:
        return self.tokens_total / self.duration_s \
            if self.duration_s > 0 else 0.0

    @staticmethod
    def _pct(values: List[float], p: float) -> float:
        if not values:
            return 0.0
        return float(np.percentile(np.asarray(values), p))

    def percentile(self, p: float) -> float:
        return self._pct(self.latencies_ms, p)

    def ttft_percentile(self, p: float) -> float:
        return self._pct(self.ttft_ms, p)

    def intertoken_percentile(self, p: float) -> float:
        return self._pct(self.intertoken_ms, p)

    def slo_attainment(self, slo_ms: float, lane: str = "ttft_ms") -> float:
        """Fraction of issued requests that met ``slo_ms`` on ``lane``
        (``"ttft_ms"`` or ``"e2e_ms"``) — the SAME definition the
        router-side ``monitor.reqtrace.SLOTracker`` applies, so these
        rows and the fleet record's ``slo`` sub-dict can't disagree:
        non-ok outcomes are misses, ok rows without a measurement are
        excluded."""
        from deeplearning4j_tpu.monitor.reqtrace import slo_attainment
        return slo_attainment(
            ((("ok" if r.get("outcome") == "ok"
               else (r.get("outcome") or "failed")), r.get(lane))
             for r in self.rows), slo_ms)

    def stats(self) -> str:
        s = (f"LoadResult: {self.n_ok}/{self.n_issued} ok "
             f"({self.n_rejected} rejected, {self.n_timed_out} timed "
             f"out, {self.n_failed} failed) in {self.duration_s:.2f}s "
             f"-> {self.throughput_rps:.1f} req/s; latency p50 "
             f"{self.percentile(50):.2f} ms, p95 "
             f"{self.percentile(95):.2f} ms, p99 "
             f"{self.percentile(99):.2f} ms")
        if self.tokens_total:
            s += (f"; {self.tokens_total} tokens -> "
                  f"{self.tokens_per_sec:.1f} tok/s; TTFT p50 "
                  f"{self.ttft_percentile(50):.2f} ms, p99 "
                  f"{self.ttft_percentile(99):.2f} ms; inter-token p50 "
                  f"{self.intertoken_percentile(50):.2f} ms")
        if self.rows:
            s += (f"; fleet: {self.retries_total} retries across "
                  f"{len(self.by_replica())} serving replicas")
        return s


class LoadGenerator:
    """Drives a :class:`~deeplearning4j_tpu.serving.ParallelInference`.

    ``request_fn(rng, i)`` builds the i-th request payload (a
    (rows, *features) array); each worker thread gets an independent
    seeded Generator so runs are reproducible.
    """

    def __init__(self, server,
                 request_fn: Callable[[np.random.Generator, int], object],
                 seed: int = 0):
        self.server = server
        self.request_fn = request_fn
        self.seed = int(seed)

    # -- closed loop ----------------------------------------------------
    def run_closed(self, n_requests: int = 256, concurrency: int = 4,
                   timeout_ms: Optional[float] = None) -> LoadResult:
        result = LoadResult()
        lock = threading.Lock()
        counter = {"next": 0}

        def worker(wid: int):
            rng = np.random.default_rng(self.seed + wid)
            while True:
                with lock:
                    i = counter["next"]
                    if i >= n_requests:
                        return
                    counter["next"] = i + 1
                x = self.request_fn(rng, i)
                t0 = time.monotonic()
                try:
                    self.server.output(x, timeout_ms=timeout_ms)
                except ServerOverloadedError:
                    with lock:
                        result.n_rejected += 1
                    continue
                except RequestTimeoutError:
                    with lock:
                        result.n_timed_out += 1
                    continue
                except Exception:
                    with lock:
                        result.n_failed += 1
                    continue
                ms = (time.monotonic() - t0) * 1000.0
                with lock:
                    result.n_ok += 1
                    result.latencies_ms.append(ms)

        t_start = time.monotonic()
        threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                   for w in range(max(1, int(concurrency)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        result.duration_s = time.monotonic() - t_start
        return result

    # -- open loop ------------------------------------------------------
    def run_open(self, n_requests: int = 256, rate_rps: float = 200.0,
                 timeout_ms: Optional[float] = None) -> LoadResult:
        result = LoadResult()
        lock = threading.Lock()
        rng = np.random.default_rng(self.seed)
        interval = 1.0 / max(rate_rps, 1e-9)
        pending = []
        t_start = time.monotonic()
        for i in range(n_requests):
            target = t_start + i * interval
            delay = target - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            x = self.request_fn(rng, i)
            t0 = time.monotonic()
            try:
                fut = self.server.submit(x, timeout_ms=timeout_ms)
            except ServerOverloadedError:
                with lock:              # callbacks also mutate result
                    result.n_rejected += 1
                continue
            except ServerClosedError:
                with lock:
                    result.n_failed += 1
                continue

            def _done(f, t0=t0):
                with lock:
                    try:
                        f.result()
                    except RequestTimeoutError:
                        result.n_timed_out += 1
                    except Exception:
                        result.n_failed += 1
                    else:
                        result.n_ok += 1
                        result.latencies_ms.append(
                            (time.monotonic() - t0) * 1000.0)

            fut.add_done_callback(_done)
            pending.append(fut)
        for fut in pending:
            try:
                fut.exception()     # wait for completion; counted above
            except Exception:
                pass
        result.duration_s = time.monotonic() - t_start
        return result


class GenerativeLoadGenerator:
    """Drives a ``serving.generative.GenerativeServer`` with a seeded
    mixed-length autoregressive trace.

    Request ``i`` is a pure function of ``(seed, i)`` — prompt tokens,
    prompt length (uniform in ``prompt_len``), output budget (uniform
    in ``new_tokens``), optional deadline (uniform in ``deadline_ms``),
    and a per-request sampling ``(temperature, seed)`` pair (uniform in
    ``temperature`` when given as a range; 0.0 = greedy) — regardless
    of loop mode or concurrency, so two
    servers (e.g. continuous vs static admission) can be benchmarked on
    the SAME trace. Per-token timings land on the LoadResult as
    ``ttft_ms`` / ``intertoken_ms``; ``tokens_total``/``tokens_per_sec``
    are the generative throughput."""

    def __init__(self, server, seed: int = 0,
                 prompt_len=(1, 16), new_tokens=(4, 32),
                 deadline_ms=None, vocab_size: Optional[int] = None,
                 temperature=0.0):
        self.server = server
        self.seed = int(seed)
        # (lo, hi) = uniform inclusive; a callable(rng) -> int models
        # the long-tailed output lengths real LLM traffic has (the
        # distribution continuous batching exists for)
        self.prompt_len = prompt_len
        self.new_tokens = new_tokens
        self.deadline_ms = deadline_ms
        # scalar (every request) or (lo, hi) uniform range; 0.0 keeps
        # the trace greedy — the historical behaviour
        self.temperature = temperature
        self.vocab_size = int(vocab_size if vocab_size is not None
                              else server.spec.vocab_size)

    @staticmethod
    def _sample_len(spec, rng) -> int:
        if callable(spec):
            return max(1, int(spec(rng)))
        lo, hi = spec
        return int(rng.integers(int(lo), int(hi) + 1))

    @staticmethod
    def _sample_temperature(spec, rng) -> float:
        if isinstance(spec, (tuple, list)):
            lo, hi = spec
            return float(rng.uniform(float(lo), float(hi)))
        return float(spec)

    def request(self, i: int):
        """The i-th trace entry: ``(prompt, max_new_tokens,
        deadline_ms, temperature, sample_seed)`` — deterministic in
        ``(seed, i)``, so a sampled trace replays token-identically
        whatever the concurrency or admission order."""
        rng = np.random.default_rng((self.seed, int(i)))
        plen = self._sample_len(self.prompt_len, rng)
        prompt = rng.integers(0, self.vocab_size, plen).astype(np.int32)
        n_new = self._sample_len(self.new_tokens, rng)
        deadline = None
        if self.deadline_ms is not None:
            dlo, dhi = (self.deadline_ms
                        if isinstance(self.deadline_ms, (tuple, list))
                        else (self.deadline_ms, self.deadline_ms))
            deadline = float(rng.uniform(dlo, dhi))
        temp = self._sample_temperature(self.temperature, rng)
        sample_seed = int(rng.integers(0, 2 ** 63))
        return prompt, n_new, deadline, temp, sample_seed

    def _consume(self, handle, t0: float, result: LoadResult,
                 lock: threading.Lock) -> None:
        """Drain one generation's token stream, recording TTFT and
        inter-token gaps; classify the outcome like the fixed-shape
        loops do."""
        ttft = None
        gaps: List[float] = []
        n_tokens = 0
        last = t0
        try:
            for _tok in handle.tokens():
                now = time.monotonic()
                if ttft is None:
                    ttft = (now - t0) * 1000.0
                else:
                    gaps.append((now - last) * 1000.0)
                last = now
                n_tokens += 1
            handle.result(timeout=0)   # surfaces a non-stream failure
        except RequestTimeoutError:
            with lock:
                result.n_timed_out += 1
                result.tokens_total += n_tokens
                if ttft is not None:
                    result.ttft_ms.append(ttft)
                result.intertoken_ms.extend(gaps)
            return
        except Exception:
            with lock:
                result.n_failed += 1
                result.tokens_total += n_tokens
            return
        with lock:
            result.n_ok += 1
            result.tokens_total += n_tokens
            result.latencies_ms.append((last - t0) * 1000.0)
            if ttft is not None:
                result.ttft_ms.append(ttft)
            result.intertoken_ms.extend(gaps)

    # -- closed loop ----------------------------------------------------
    def run_closed(self, n_requests: int = 64,
                   concurrency: int = 4) -> LoadResult:
        result = LoadResult()
        lock = threading.Lock()
        counter = {"next": 0}

        def worker():
            while True:
                with lock:
                    i = counter["next"]
                    if i >= n_requests:
                        return
                    counter["next"] = i + 1
                prompt, n_new, deadline, temp, sseed = self.request(i)
                t0 = time.monotonic()
                try:
                    handle = self.server.submit(prompt, n_new,
                                                timeout_ms=deadline,
                                                temperature=temp,
                                                seed=sseed)
                except ServerOverloadedError:
                    with lock:
                        result.n_rejected += 1
                    continue
                except ServerClosedError:
                    with lock:
                        result.n_failed += 1
                    continue
                self._consume(handle, t0, result, lock)

        t_start = time.monotonic()
        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(max(1, int(concurrency)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        result.duration_s = time.monotonic() - t_start
        return result

    # -- open loop ------------------------------------------------------
    def run_open(self, n_requests: int = 64,
                 rate_rps: float = 50.0) -> LoadResult:
        result = LoadResult()
        lock = threading.Lock()
        interval = 1.0 / max(rate_rps, 1e-9)
        consumers: List[threading.Thread] = []
        t_start = time.monotonic()
        for i in range(n_requests):
            target = t_start + i * interval
            delay = target - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            prompt, n_new, deadline, temp, sseed = self.request(i)
            t0 = time.monotonic()
            try:
                handle = self.server.submit(prompt, n_new,
                                            timeout_ms=deadline,
                                            temperature=temp,
                                            seed=sseed)
            except ServerOverloadedError:
                with lock:
                    result.n_rejected += 1
                continue
            except ServerClosedError:
                with lock:
                    result.n_failed += 1
                continue
            t = threading.Thread(target=self._consume,
                                 args=(handle, t0, result, lock),
                                 daemon=True)
            t.start()
            consumers.append(t)
        for t in consumers:
            t.join()
        result.duration_s = time.monotonic() - t_start
        return result


class FleetLoadGenerator:
    """Open-loop replay against a callable front door (the fleet
    router) — N servers behind one function.

    ``front_door(prompt, max_new_tokens=..., timeout_ms=...)`` must
    BLOCK until the generation completes and return an object with
    ``tokens`` / ``replica`` / ``retries`` / ``routed`` / ``ttft_ms`` /
    ``intertoken_ms`` (``serving.fleet.FleetResult``). Typed sheds the
    router gave up on (``RetryableServingError``) count as rejected;
    deadline misses as timed out; anything else as failed. Every
    request lands one tagged row on ``LoadResult.rows``.

    Request ``i`` is a pure function of ``(seed, i)`` — and of the
    fixed ``prefix_pool``, when given: with probability ``prefix_p``
    request ``i`` prepends pool entry ``rng.integers(len(pool))`` to
    its random tail, producing the repeated-prefix traffic that makes
    affinity routing measurable (same trace under any routing policy).
    """

    def __init__(self, front_door: Callable, *, vocab_size: int,
                 seed: int = 0, prompt_len=(1, 16), new_tokens=(4, 32),
                 deadline_ms=None, prefix_pool=None,
                 prefix_p: float = 0.75, temperature=0.0):
        self.front_door = front_door
        self.vocab_size = int(vocab_size)
        self.seed = int(seed)
        self.prompt_len = prompt_len
        self.new_tokens = new_tokens
        self.deadline_ms = deadline_ms
        self.prefix_pool = None if prefix_pool is None else [
            np.asarray(p, np.int32).reshape(-1) for p in prefix_pool]
        self.prefix_p = float(prefix_p)
        # scalar or (lo, hi); nonzero traces forward temperature+seed
        # to the front door (FleetRouter.generate passes them through
        # to replica submit) — 0.0 keeps the plain greedy contract
        self.temperature = temperature

    def request(self, i: int):
        """The i-th trace entry ``(prompt, max_new_tokens,
        deadline_ms, temperature, sample_seed)`` — deterministic in
        ``(seed, i)``."""
        rng = np.random.default_rng((self.seed, int(i)))
        plen = GenerativeLoadGenerator._sample_len(self.prompt_len, rng)
        tail = rng.integers(0, self.vocab_size, plen).astype(np.int32)
        prompt = tail
        if self.prefix_pool and rng.random() < self.prefix_p:
            prefix = self.prefix_pool[
                int(rng.integers(len(self.prefix_pool)))]
            prompt = np.concatenate([prefix, tail])
        n_new = GenerativeLoadGenerator._sample_len(self.new_tokens, rng)
        deadline = None
        if self.deadline_ms is not None:
            dlo, dhi = (self.deadline_ms
                        if isinstance(self.deadline_ms, (tuple, list))
                        else (self.deadline_ms, self.deadline_ms))
            deadline = float(rng.uniform(dlo, dhi))
        temp = GenerativeLoadGenerator._sample_temperature(
            self.temperature, rng)
        sample_seed = int(rng.integers(0, 2 ** 63))
        return prompt, n_new, deadline, temp, sample_seed

    def _issue(self, i: int, result: LoadResult,
               lock: threading.Lock) -> None:
        prompt, n_new, deadline, temp, sseed = self.request(i)
        t0 = time.monotonic()
        row = {"i": int(i), "outcome": None, "replica": None,
               "retries": 0, "routed": None, "ttft_ms": None,
               "e2e_ms": None, "resumes": 0, "tokens_salvaged": 0,
               "ttft_breakdown": None}
        # sampling kwargs only on sampled traces: plain front doors
        # keep the documented (prompt, max_new_tokens, timeout_ms)
        # signature working unchanged
        kw = {"temperature": temp, "seed": sseed} if temp > 0.0 else {}
        try:
            res = self.front_door(prompt, max_new_tokens=n_new,
                                  timeout_ms=deadline, **kw)
        except RetryableServingError:
            row["outcome"] = "rejected"     # typed give-up: budget spent
            with lock:
                result.n_rejected += 1
                result.rows.append(row)
            return
        except RequestTimeoutError:
            row["outcome"] = "timed_out"
            with lock:
                result.n_timed_out += 1
                result.rows.append(row)
            return
        except Exception as e:              # noqa: BLE001 — tally + tag
            row["outcome"] = f"failed:{type(e).__name__}"
            with lock:
                result.n_failed += 1
                result.rows.append(row)
            return
        ms = (time.monotonic() - t0) * 1000.0
        row.update(outcome="ok",
                   replica=getattr(res, "replica", None),
                   retries=int(getattr(res, "retries", 0) or 0),
                   routed=getattr(res, "routed", None),
                   ttft_ms=getattr(res, "ttft_ms", None),
                   e2e_ms=ms,
                   resumes=int(getattr(res, "resumes", 0) or 0),
                   tokens_salvaged=int(
                       getattr(res, "tokens_salvaged", 0) or 0),
                   # populated when the request's trace was sampled
                   # (FleetResult.ttft_breakdown from the assembled
                   # waterfall): queue_wait/prefill/first_decode ms
                   ttft_breakdown=getattr(res, "ttft_breakdown", None))
        with lock:
            result.n_ok += 1
            result.latencies_ms.append(ms)
            result.tokens_total += len(getattr(res, "tokens", ()) or ())
            if row["ttft_ms"] is not None:
                result.ttft_ms.append(float(row["ttft_ms"]))
            result.intertoken_ms.extend(
                getattr(res, "intertoken_ms", ()) or ())
            result.rows.append(row)

    def run_closed(self, n_requests: int = 64,
                   concurrency: int = 4) -> LoadResult:
        """Fixed-concurrency closed loop over the front door: each of
        ``concurrency`` workers issues its next request only after the
        previous one returned (same trace as :meth:`run_open` — request
        ``i`` is a pure function of ``(seed, i)``)."""
        result = LoadResult()
        lock = threading.Lock()
        counter = {"next": 0}

        def worker():
            while True:
                with lock:
                    i = counter["next"]
                    if i >= n_requests:
                        return
                    counter["next"] = i + 1
                self._issue(i, result, lock)

        t_start = time.monotonic()
        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(max(1, int(concurrency)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        result.duration_s = time.monotonic() - t_start
        return result

    def run_open(self, n_requests: int = 64,
                 rate_rps: float = 50.0) -> LoadResult:
        """Fixed-rate open-loop replay: request ``i`` is issued at
        ``i / rate_rps`` regardless of completions (each in its own
        thread — the front door blocks per request)."""
        result = LoadResult()
        lock = threading.Lock()
        interval = 1.0 / max(rate_rps, 1e-9)
        workers: List[threading.Thread] = []
        t_start = time.monotonic()
        for i in range(n_requests):
            target = t_start + i * interval
            delay = target - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=self._issue,
                                 args=(i, result, lock), daemon=True)
            t.start()
            workers.append(t)
        for t in workers:
            t.join()
        result.duration_s = time.monotonic() - t_start
        result.rows.sort(key=lambda r: r["i"])
        return result
