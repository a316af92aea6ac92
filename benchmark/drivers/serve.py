"""One run of a serving cell: stand the server up, offer the closed-loop
traffic for the window, keep every token's time as the server's own
thread saw it, then hold a sample of what was served against the plain
reference."""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmark import harness
from benchmark.harness import BenchFailure, say
from benchmark.readers import requests as request_reader

#: how long past the window's close a request may take to finish
DRAIN_S = 60.0
#: the traced part of a ``--trace 1`` window: starts this long after it
#: opens and lasts at most this long
TRACE_LEAD_S, TRACE_SPAN_S = 1.0, 8.0


class _Sample:
    """One request as offered and as served: submit time, and the time
    of each token taken in ``on_token``, which the server calls from its
    own thread at the step boundary that produced the token."""

    __slots__ = ("index", "prompt", "budget", "submit_t", "token_t",
                 "tokens", "error", "done_t")

    def __init__(self, index, prompt, budget):
        self.index, self.prompt, self.budget = index, prompt, budget
        self.submit_t = self.done_t = self.error = None
        self.token_t, self.tokens = [], []

    def on_token(self, tok):
        self.token_t.append(time.monotonic())
        self.tokens.append(int(tok))


class ClosedLoop:
    """``clients`` callers over one list of requests. A caller's next
    request goes in from the completion callback of its last one, so it
    is queued before the server's next step boundary whatever the host's
    threads are doing."""

    def __init__(self, server, requests, clients: int):
        self.server, self.requests, self.clients = server, requests, clients
        self.samples, self.lock = [], threading.Lock()
        self.open = True
        self.errors = []

    def _launch(self):
        with self.lock:
            i = len(self.samples)
            if not self.open:
                return
            # the list is whole rounds of one mix: past its end, more
            # of the same
            r = self.requests[i % len(self.requests)]
            s = _Sample(i, r["prompt"], r["max_new_tokens"])
            self.samples.append(s)
        s.submit_t = time.monotonic()
        try:
            h = self.server.submit(s.prompt, max_new_tokens=s.budget,
                                   on_token=s.on_token)
        except Exception as e:                  # noqa: BLE001 — counted
            s.error = e
            self.errors.append(e)
            return
        h.future.add_done_callback(lambda f, s=s: self._done(s, f))

    def _done(self, s, fut):
        s.done_t = time.monotonic()
        s.error = fut.exception()
        try:
            self._launch()
        except Exception as e:                  # noqa: BLE001
            self.errors.append(e)

    def start(self, over_s: float = 0.0):
        """The first request of each caller, spread evenly over
        ``over_s`` seconds so that they do not queue behind each other's
        prefill."""
        t = time.monotonic()
        for k in range(self.clients):
            wait = t + k * over_s / self.clients - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            self._launch()
        wait = t + over_s - time.monotonic()
        if wait > 0:
            time.sleep(wait)

    def close(self):
        with self.lock:
            self.open = False

    def drain(self, timeout: float) -> None:
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            with self.lock:
                busy = [s for s in self.samples
                        if s.done_t is None and s.error is None]
            if not busy:
                return
            time.sleep(0.05)


def pick_sample(samples, n: int, seed: int):
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    done = [s for s in samples
            if s.error is None and s.done_t is not None
            and len(s.tokens) == s.budget]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.prompt) + len(s.tokens))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(take)]


def offer(cell, seed: int, seconds: float, trace: bool, stamp: dict,
          wrap_server=None):
    """The window itself. Returns ``(record, rows, breakdown)`` with the
    server gone and its memory freed; ``rows`` are the sampled
    ``(prompt, served tokens)``. ``wrap_server`` lets a test break the
    timed path underneath."""
    traffic, cfg, adapter = cell.traffic, cell.config, cell.adapter
    requests = cell.generator.generate(traffic, cfg, seed)
    watch = harness.CompileWatch()
    server = adapter.build_server(cfg, traffic["server"], seed)
    if wrap_server is not None:
        server = wrap_server(server)
    tracing = harness.Tracing(cell.root, trace)
    loop = ClosedLoop(server, requests, int(traffic["clients"]))
    try:
        # the callers come in one by one before the window opens, so
        # that it opens on a server in its steady state; what they are
        # sent and served before then counts as set-up
        loop.start(float(traffic.get("warm_in_s", 0.0)))
        c0 = adapter.server_counters(server)
        t0, setup_s, setup_compile = watch.window_opens()
        if trace:
            time.sleep(min(TRACE_LEAD_S, seconds / 4))
            tracing.start()
            time.sleep(min(TRACE_SPAN_S, seconds / 2))
            tracing.stop()
        rest = t0 + seconds - time.monotonic()
        if rest > 0:
            time.sleep(rest)
        t1 = time.monotonic()
        loop.close()
        c1 = adapter.server_counters(server)
        watch.window_closes()
        loop.drain(DRAIN_S)
        memory = harness.memory_peak()
    finally:
        loop.close()
        tracing.stop()
        server.shutdown(drain=False)
    if loop.errors:
        say(f"{len(loop.errors)} request(s) failed: {loop.errors[0]!r}")
    samples = loop.samples
    failed = sum(1 for s in samples
                 if s.error is not None or s.done_t is None)
    picked = pick_sample(samples, int(traffic["check"]["sample"]), seed)
    rows = [(s.prompt, s.tokens) for s in picked]
    del server, loop
    gc.collect()
    trace_rec, breakdown = tracing.reduce()
    record = {
        "cell": cell.name, "config": cfg, "traffic": traffic,
        "counts": cell.counts, "peaks": harness.peaks_for(stamp),
        "setup_s": setup_s, "window": (t0, t1), "window_s": t1 - t0,
        "requests": [{"submit_t": s.submit_t, "token_t": s.token_t,
                      "prompt_len": len(s.prompt)} for s in samples],
        "counters": {k: c1[k] - c0[k] for k in c1},
        "pool": {"num_blocks": c1["num_blocks"]},
        "compile": setup_compile, "memory": memory,
        "attempted": len(samples), "failed": failed,
        "trace": trace_rec,
    }
    if not rows:
        raise BenchFailure("no request finished: nothing to compare")
    return record, rows, breakdown


def run(cell, seed: int, seconds: float, trace: bool, stamp: dict,
        wrap_server=None):
    """Returns ``(record, compared, breakdown)``."""
    record, rows, breakdown = offer(cell, seed, seconds, trace, stamp,
                                    wrap_server)
    # for whoever has to explain a run that reads far off: a stall shows
    # here as one gap of seconds
    gaps = sorted(request_reader.gaps_ms(record))
    say(f"{record['attempted']} requests offered, {len(gaps)} token gaps "
        f"in the window, the longest "
        f"{', '.join(f'{g:.0f}' for g in gaps[-3:])} ms")
    t_ref = time.monotonic()
    got = cell.adapter.check_served(
        cell.config, seed, rows, int(cell.traffic["server"]["max_seq_len"]))
    say(f"reference over {len(rows)} requests, {got['tokens']} served "
        f"tokens, {got['parted']} off the reference's choice, in "
        f"{time.monotonic() - t_ref:.1f}s")
    compared = {"widest_gap": got["widest_gap"],
                "requests_failed": float(record["failed"])}
    return record, compared, breakdown
