"""Continuous-batching generative serving (serving/generative.py,
ISSUE 15 / ROADMAP item 1).

Pinned contracts:
- greedy tokens from the continuous-batching server are IDENTICAL to
  :func:`greedy_decode` (the unbatched single-request reference) for
  every request in a mixed-length concurrent run;
- slot lifecycle: a slot is freed exactly once on each retirement path
  (EOS / max_new_tokens / deadline expiry / cancel / capacity), and a
  retired slot's cache — even poisoned with NaNs — cannot influence its
  successor (bit-identical to a fresh server);
- a crashed decode worker's in-flight generations requeue at prefill
  EXACTLY once and complete with the same tokens; a twice-lost request
  fails typed;
- compiles stay ≤ log2(max_seq)+O(1): ONE decode program + one prefill
  program per pow2 bucket, all AOT-warmable (0 traffic compiles);
- continuous batching does ≥2x the tokens-per-decode-step of static
  wait-for-full-batch batching on the same skewed trace.

ISSUE 18 (fast decode) grows the contract:
- draft-model speculation NEVER changes tokens: temp-0 output is
  bit-identical to the non-speculative server and to greedy_decode —
  the draft only sets how many tokens land per verify round;
- seeded sampling replays exactly per (seed, absolute token index):
  same request, same tokens — whatever shares the batch, whatever the
  admission order, and across a crash-requeue re-entry;
- AOT warmup with speculation + int8 weights still leaves 0 traffic
  compiles (7 plain + verify + draft decode + 6 draft prefill = 15).
"""
import threading
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.serving.generative import (
    GenerationCancelled, GenerativeMetrics, GenerativeServer,
    GenerativeSpec, SlotAllocator, greedy_decode)
from deeplearning4j_tpu.serving.loadgen import GenerativeLoadGenerator
from deeplearning4j_tpu.serving.metrics import LatencyHistogram
from deeplearning4j_tpu.serving.queue import (RequestTimeoutError,
                                              ServerClosedError,
                                              ServerOverloadedError,
                                              ServingError,
                                              ServingTimeoutError)
from deeplearning4j_tpu.serving.resilience import ResilienceConfig
from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                        gpt_generative_spec,
                                        gpt_param_names)

CFG = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                intermediate_size=64, max_seq_len=32)
DRAFT_CFG = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                      num_heads=2, intermediate_size=32, max_seq_len=32)
MSL = 32


@pytest.fixture(scope="module")
def gpt_sd():
    return build_gpt(CFG, batch=2, seq_len=8, seed=0)


@pytest.fixture(scope="module")
def spec(gpt_sd):
    # one spec for the whole module: the jitted decode/prefill programs
    # are memoized on it, so every server here shares one compile set
    return gpt_generative_spec(gpt_sd, CFG)


@pytest.fixture(scope="module")
def draft_spec():
    # an independently-trained smaller model over the SAME vocab: low
    # acceptance (it disagrees with the target a lot) is the point —
    # the rejection/rollback path gets exercised hard
    dsd = build_gpt(DRAFT_CFG, batch=2, seq_len=8, seed=1)
    return gpt_generative_spec(dsd, DRAFT_CFG)


def make_server(spec, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", MSL)
    kw.setdefault("warmup", False)
    return GenerativeServer(spec, **kw)


def ref_tokens(spec, prompt, n, eos_id=None):
    return greedy_decode(spec, prompt, n, eos_id=eos_id, max_seq_len=MSL)


def mixed_prompts(n=6, seed=0, max_len=12):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size,
                         int(rng.integers(1, max_len + 1)))
            .astype(np.int32) for _ in range(n)]


# ----------------------------------------------------------------------
class TestSlotAllocator:
    def test_alloc_free_cycle(self):
        a = SlotAllocator(3)
        s = [a.alloc() for _ in range(3)]
        assert sorted(s) == [0, 1, 2]
        assert a.free_count() == 0
        with pytest.raises(RuntimeError):
            a.alloc()
        for x in s:
            a.free(x)
        assert a.free_count() == 3

    def test_double_free_raises(self):
        a = SlotAllocator(2)
        s = a.alloc()
        a.free(s)
        with pytest.raises(RuntimeError, match="twice"):
            a.free(s)

    def test_free_unallocated_raises(self):
        a = SlotAllocator(2)
        with pytest.raises(RuntimeError):
            a.free(1)

    def test_reset(self):
        a = SlotAllocator(2)
        a.alloc()
        a.reset()
        assert a.free_count() == 2


# ----------------------------------------------------------------------
class TestMetricsGuards:
    """ISSUE 15 satellite: NaN-free zeros on empty/degenerate inputs +
    the low-sample percentile flag."""

    def test_empty_percentile_is_zero(self):
        h = LatencyHistogram()
        for p in (0, 50, 99, 100):
            v = h.percentile(p)
            assert v == 0.0 and np.isfinite(v)
        assert h.mean() == 0.0
        s = h.summary()
        assert s["count"] == 0 and s["low_sample"] is True
        assert all(np.isfinite(v) for k, v in s.items()
                   if isinstance(v, (int, float)))

    def test_nonfinite_sample_records_as_zero(self):
        h = LatencyHistogram()
        h.record(float("nan"))
        h.record(float("inf"))
        s = h.summary()
        assert s["count"] == 2
        assert np.isfinite(s["mean"]) and s["mean"] == 0.0
        assert np.isfinite(s["p99"])

    def test_observe_batch_zero_rows_nan_free(self):
        m = GenerativeMetrics(max_slots=4)
        m.observe_batch(rows=0, padding=0, exec_ms=float("nan"))
        m.observe_batch(rows=-3, padding=-1, exec_ms=1.0)
        rec = m.to_record()
        assert rec["batch"]["mean_size"] == 0.0
        assert rec["batch"]["padding_waste"] == 0.0
        flat = [rec["batch"]["mean_size"], rec["batch"]["padding_waste"],
                *(rec["latency_ms"]["exec"][k]
                  for k in ("mean", "p50", "p99", "max"))]
        assert all(np.isfinite(v) for v in flat)
        assert m.padding_waste() == 0.0 and m.mean_batch_size() == 0.0

    def test_low_sample_flag_clears_at_32(self):
        h = LatencyHistogram()
        for _ in range(31):
            h.record(1.0)
        assert h.summary()["low_sample"] is True
        h.record(1.0)
        assert h.summary()["low_sample"] is False


# ----------------------------------------------------------------------
class TestDecodeMath:
    def test_param_names_cover_graph(self, gpt_sd):
        for n in gpt_param_names(CFG):
            assert n in gpt_sd._arrays, n

    def test_prefill_matches_full_forward(self, gpt_sd, spec):
        """The decode-mode prefill reproduces the training graph's
        logits at the last prompt position — the decode math is the
        same model, not a lookalike."""
        import jax.numpy as jnp
        prompt = np.asarray([5, 17, 40, 2, 33], np.int32)
        L = prompt.size
        # training graph: full forward at the prompt's own length
        sd_full = build_gpt(CFG, batch=1, seq_len=L, seed=0)
        out = sd_full.output({"input_ids": prompt[None],
                              "targets": np.zeros((1, L), np.int32)},
                             ["logits"])
        full_logits = np.asarray(out["logits"].to_numpy())[0, L - 1]
        # decode-mode prefill at the pow2 bucket (8 > 5: padded)
        kc = jnp.zeros(spec.kv_shape(1, MSL), jnp.float32)
        vc = jnp.zeros(spec.kv_shape(1, MSL), jnp.float32)
        padded = np.zeros(8, np.int32)
        padded[:L] = prompt
        _, _, nxt, logits = spec.prefill(
            dict(spec.params()), kc, vc,
            {"tokens": padded, "length": np.int32(L),
             "slot": np.int32(0)})
        np.testing.assert_allclose(np.asarray(logits), full_logits,
                                   rtol=1e-4, atol=1e-5)
        assert int(nxt) == int(np.argmax(full_logits))

    def test_greedy_decode_deterministic(self, spec):
        p = np.asarray([3, 9, 1], np.int32)
        assert ref_tokens(spec, p, 8) == ref_tokens(spec, p, 8)

    def test_greedy_decode_eos_stops(self, spec):
        p = np.asarray([3, 9, 1], np.int32)
        full = ref_tokens(spec, p, 8)
        eos = full[2]
        got = ref_tokens(spec, p, 8, eos_id=eos)
        # stops at the FIRST occurrence of eos (an untrained model may
        # repeat tokens, so that can be earlier than index 2)
        assert got == full[:full.index(eos) + 1]


# ----------------------------------------------------------------------
class TestServer:
    def test_mixed_run_bit_identical_to_unbatched(self, spec):
        """THE acceptance pin: every request in a mixed-length
        concurrent run decodes the same greedy tokens as the unbatched
        single-request reference."""
        prompts = mixed_prompts(8, seed=1)
        with make_server(spec, max_slots=4) as srv:
            handles = [srv.submit(p, max_new_tokens=6 + i % 5)
                       for i, p in enumerate(prompts)]
            results = [h.result(timeout=120) for h in handles]
        for i, (p, got) in enumerate(zip(prompts, results)):
            assert got == ref_tokens(spec, p, 6 + i % 5), f"request {i}"

    def test_streaming_matches_future(self, spec):
        p = np.asarray([1, 2, 3], np.int32)
        with make_server(spec) as srv:
            h = srv.submit(p, max_new_tokens=7)
            streamed = list(h.tokens(timeout=120))
            assert streamed == h.result(timeout=5)
            assert len(streamed) == 7

    def test_on_token_callback(self, spec):
        seen = []
        with make_server(spec) as srv:
            toks = srv.submit(np.asarray([4], np.int32), max_new_tokens=5,
                              on_token=seen.append).result(timeout=120)
        assert seen == toks

    def test_eos_retires_slot_immediately(self, spec):
        p = np.asarray([7, 7], np.int32)
        full = ref_tokens(spec, p, 10)
        eos = full[3]
        with make_server(spec) as srv:
            got = srv.generate(p, max_new_tokens=10)
            # submit with eos -> stops at its FIRST occurrence, slot
            # freed (the follow-up generate proves it)
            got_eos = srv.submit(p, max_new_tokens=10,
                                 eos_id=eos).result(timeout=120)
            assert srv._slots.free_count() == srv.max_slots
        assert got == full
        assert got_eos == full[:full.index(eos) + 1]

    def test_sequence_capacity_retires(self, spec):
        # prompt of MSL-1 leaves exactly one decode position
        p = np.arange(MSL - 1, dtype=np.int32) % CFG.vocab_size
        with make_server(spec) as srv:
            got = srv.generate(p, max_new_tokens=50)
        assert got == ref_tokens(spec, p, 50)
        assert 1 <= len(got) <= 2

    def test_slot_freed_exactly_once_all_paths(self, spec):
        """EOS, max_new_tokens, deadline expiry and cancel each free
        the slot exactly once (SlotAllocator raises on double free, so
        surviving the run IS the invariant; the counter makes it
        explicit)."""
        frees = []
        with make_server(spec, max_slots=2) as srv:
            orig_free = srv._slots.free

            def counting_free(s):
                frees.append(s)
                return orig_free(s)

            srv._slots.free = counting_free
            # max_new_tokens path
            srv.generate(np.asarray([1], np.int32), max_new_tokens=3)
            # eos path
            full = ref_tokens(spec, np.asarray([2], np.int32), 6)
            srv.submit(np.asarray([2], np.int32), max_new_tokens=6,
                       eos_id=full[1]).result(timeout=120)
            # deadline-expiry path (slow consumer via on_token)
            h = srv.submit(np.asarray([3], np.int32), max_new_tokens=50,
                           timeout_ms=150,
                           on_token=lambda t: time.sleep(0.05))
            with pytest.raises(ServingTimeoutError):
                h.result(timeout=120)
            # cancel path
            h2 = srv.submit(np.asarray([4], np.int32), max_new_tokens=400,
                            on_token=lambda t: time.sleep(0.02))
            time.sleep(0.06)
            h2.cancel()
            h2.result(timeout=120)
            deadline = time.monotonic() + 5
            while srv._slots.free_count() < 2 and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
            assert srv._slots.free_count() == 2
        assert len(frees) == 4
        assert sorted(set(frees)) == sorted(frees) or len(frees) == 4

    def test_deadline_mid_generation_typed_with_partial(self, spec):
        with make_server(spec) as srv:
            h = srv.submit(np.asarray([9], np.int32), max_new_tokens=50,
                           timeout_ms=150,
                           on_token=lambda t: time.sleep(0.05))
            with pytest.raises(ServingTimeoutError) as ei:
                h.result(timeout=120)
            assert len(ei.value.tokens) >= 1      # partial tokens attached
            assert ei.value.tokens == h.partial()
            # the stream surfaces the same failure
            with pytest.raises(ServingTimeoutError):
                list(h.tokens(timeout=5))
        assert srv.metrics.counters["requests_timed_out"] >= 1

    def test_cancel_resolves_partial_and_clean_stream(self, spec):
        with make_server(spec) as srv:
            h = srv.submit(np.asarray([8], np.int32), max_new_tokens=400,
                           on_token=lambda t: time.sleep(0.02))
            time.sleep(0.08)
            h.cancel()
            got = h.result(timeout=120)
            assert 1 <= len(got) < 400
            streamed = list(h.tokens(timeout=5))   # ends cleanly, no raise
            assert streamed == got

    def test_queued_deadline_expires_before_prefill(self, spec):
        srv = make_server(spec, start=False)
        try:
            h = srv.submit(np.asarray([5], np.int32), max_new_tokens=4,
                           timeout_ms=1)
            time.sleep(0.05)
            srv.start()
            with pytest.raises(RequestTimeoutError):
                h.result(timeout=60)
            with pytest.raises(RequestTimeoutError):
                list(h.tokens(timeout=5))
        finally:
            srv.shutdown()

    def test_kv_poison_no_bleed_on_slot_reuse(self, spec):
        """Retire a generation, poison the ENTIRE slab with NaNs, then
        serve a new request: its tokens must be bit-identical to a
        fresh server's — the masked-V decode makes slot reuse provably
        independent of retired-cache contents."""
        p2 = np.asarray([11, 3, 7], np.int32)
        with make_server(spec, max_slots=2) as srv:
            srv.generate(np.asarray([1, 2, 3, 4, 5], np.int32),
                         max_new_tokens=8)
            # worker idle at a step boundary: poison between requests
            time.sleep(0.05)
            with srv._exec_lock:
                import jax.numpy as jnp
                srv._kc = jnp.full_like(srv._kc, jnp.nan)
                srv._vc = jnp.full_like(srv._vc, jnp.nan)
            got = srv.generate(p2, max_new_tokens=8)
        with make_server(spec, max_slots=2) as fresh:
            want = fresh.generate(p2, max_new_tokens=8)
        assert got == want
        assert got == ref_tokens(spec, p2, 8)

    def test_compile_budget_and_warm_traffic(self, gpt_sd):
        """ONE decode program + ≤ log2(max_seq)+1 prefill buckets;
        after warmup, mixed traffic compiles NOTHING new."""
        fresh_spec = gpt_generative_spec(gpt_sd, CFG)    # empty compile memo
        with make_server(fresh_spec, max_slots=4, warmup=True) as srv:
            assert srv.warmup_report["prefill_buckets"] == \
                [1, 2, 4, 8, 16, 32]
            assert srv.metrics.counters["warmup_compiles"] == 7
            for i, p in enumerate(mixed_prompts(8, seed=3, max_len=20)):
                srv.generate(p, max_new_tokens=3 + i % 4)
            assert srv.metrics.counters["compiles"] == 0
        # log2(32) + 1 prefill shapes + 1 decode shape
        assert len(srv.warmup_report["prefill_buckets"]) <= \
            int(np.log2(MSL)) + 1

    def test_admission_sheds_typed_on_estimated_ttft(self, spec):
        cfg = ResilienceConfig(min_exec_samples=4, percentile=99.0)
        srv = make_server(spec, resilience=cfg, start=False,
                          max_queue_len=64)
        try:
            for _ in range(8):
                srv.admission.observe(50.0)     # p99 step = 50 ms
            srv.submit(np.asarray([1], np.int32), 4)   # no deadline: kept
            with pytest.raises(ServerOverloadedError) as ei:
                srv.submit(np.asarray([2], np.int32), 4, timeout_ms=20.0)
            assert ei.value.retry_after_s is not None
            assert ei.value.retry_after_s > 0
            assert srv.metrics.counters["requests_shed"] == 1
        finally:
            srv.shutdown(drain=False)

    def test_queue_full_rejects_typed(self, spec):
        srv = make_server(spec, max_queue_len=2, start=False,
                          resilience=False)
        try:
            srv.submit(np.asarray([1], np.int32), 2)
            srv.submit(np.asarray([2], np.int32), 2)
            with pytest.raises(ServerOverloadedError):
                srv.submit(np.asarray([3], np.int32), 2)
            assert srv.metrics.counters["requests_rejected"] == 1
        finally:
            srv.shutdown(drain=False)

    def test_submit_validation(self, spec):
        with make_server(spec, start=False) as srv:
            with pytest.raises(ValueError):
                srv.submit(np.asarray([], np.int32), 4)
            with pytest.raises(ValueError):
                srv.submit(np.arange(MSL, dtype=np.int32), 4)
            with pytest.raises(ValueError):
                srv.submit(np.asarray([CFG.vocab_size], np.int32), 4)
            with pytest.raises(ValueError):
                srv.submit(np.asarray([1], np.int32), 0)
        with pytest.raises(ServerClosedError):
            srv.submit(np.asarray([1], np.int32), 4)

    def test_update_model_serves_new_params(self, spec, gpt_sd):
        import jax.numpy as jnp
        p = np.asarray([6, 6, 6], np.int32)
        with make_server(spec) as srv:
            before = srv.generate(p, max_new_tokens=6)
            old = gpt_sd._arrays["wte"]
            try:
                gpt_sd._arrays["wte"] = old + jnp.asarray(0.5)
                srv.update_model()
                after = srv.generate(p, max_new_tokens=6)
                want = ref_tokens(spec, p, 6)
            finally:
                gpt_sd._arrays["wte"] = old
                srv.update_model()
            assert after == want        # reference reads live params too
            assert srv.generate(p, max_new_tokens=6) == before
        assert before != after or before == after  # smoke: both defined


# ----------------------------------------------------------------------
class TestCrashRecovery:
    @pytest.mark.chaos
    def test_worker_crash_requeues_at_prefill_exactly_once(self, spec):
        """Kill the decode worker mid-generation: in-flight requests
        requeue at the FRONT exactly once, re-enter at prefill with
        prompt+generated-so-far, and finish with the SAME tokens."""
        prompts = mixed_prompts(3, seed=7)
        srv = make_server(spec, max_slots=2, start=False,
                          resilience=ResilienceConfig(
                              worker_backoff_base_s=0.01,
                              worker_backoff_max_s=0.05))
        real = srv._decode_disp
        state = {"calls": 0, "fired": False}

        class CrashOnce:
            def __call__(self, *args):
                state["calls"] += 1
                if not state["fired"] and state["calls"] > 2:
                    state["fired"] = True
                    raise RuntimeError("chaos: decode worker dies")
                return real(*args)

        srv._decode_disp = CrashOnce()
        try:
            srv.start()
            handles = [srv.submit(p, max_new_tokens=8) for p in prompts]
            results = [h.result(timeout=120) for h in handles]
        finally:
            srv.shutdown()
        assert state["fired"]
        for p, got in zip(prompts, results):
            assert got == ref_tokens(spec, p, 8)
        assert srv.metrics.counters["worker_restarts"] >= 1
        assert srv.metrics.counters["requests_requeued"] >= 1
        # streams saw each token exactly once: results == full greedy
        # sequences, nothing duplicated or dropped
        # a requeued request is prefilled again but waited in the queue
        # once: its admission instant is its first placement's
        c = srv.metrics.counters
        assert c["requests_admitted"] == 3 < c["prefills"]
        for h in handles:
            r = h._req
            assert r.enqueue_t <= r.admit_t <= r.first_token_t

    @pytest.mark.chaos
    def test_twice_lost_request_fails_typed(self, spec):
        srv = make_server(spec, max_slots=2, start=False,
                          resilience=ResilienceConfig(
                              worker_backoff_base_s=0.01,
                              worker_backoff_max_s=0.05))
        real = srv._decode_disp

        class AlwaysCrash:
            def __call__(self, *args):
                raise RuntimeError("chaos: decode always dies")

        srv._decode_disp = AlwaysCrash()
        try:
            srv.start()
            h = srv.submit(np.asarray([1, 2], np.int32), max_new_tokens=8)
            with pytest.raises(ServingError, match="twice"):
                h.result(timeout=120)
        finally:
            srv._decode_disp = real
            srv.shutdown(drain=False)

    def test_unsupervised_crash_fails_inflight(self, spec):
        srv = make_server(spec, max_slots=2, start=False, resilience=False)

        class Crash:
            def __call__(self, *args):
                raise RuntimeError("decode crash, no supervisor")

        srv._decode_disp = Crash()
        try:
            srv.start()
            h = srv.submit(np.asarray([1], np.int32), max_new_tokens=8)
            with pytest.raises(RuntimeError, match="no supervisor"):
                h.result(timeout=60)
        finally:
            srv.shutdown(drain=False)


# ----------------------------------------------------------------------
class TestSpeculative:
    """ISSUE 18 tentpole: the draft never changes tokens — it only
    changes how many land per verify dispatch."""

    def test_temp0_bit_identical_to_plain_and_reference(self, spec,
                                                        draft_spec):
        prompts = mixed_prompts(8, seed=11)
        budgets = [6 + i % 5 for i in range(8)]
        with make_server(spec, draft_spec=draft_spec,
                         speculate_k=4) as srv:
            hs = [srv.submit(p, n) for p, n in zip(prompts, budgets)]
            got = [h.result(timeout=120) for h in hs]
            rec = srv.metrics.to_record()["generative"]
        with make_server(spec) as plain:
            want = [plain.submit(p, n).result(timeout=120)
                    for p, n in zip(prompts, budgets)]
        assert got == want
        for p, n, g in zip(prompts, budgets, got):
            assert g == ref_tokens(spec, p, n)
        assert rec["spec_rounds"] >= 1          # speculation actually ran

    def test_round_spans_end_at_their_sync(self, spec, draft_spec):
        """A speculative round in the ring: K draft dispatches (the
        last one unsynced: it only writes the draft's KV) and one
        verify, each holding its launch and, where the host waits, its
        sync; the verify's launch is what ``decode_launch_ms_sum``
        counts, the round what ``sched_host_ms_sum`` leaves out."""
        from deeplearning4j_tpu.monitor.trace import (TRACER,
                                                      disable_tracing,
                                                      enable_tracing)
        with make_server(spec, draft_spec=draft_spec,
                         speculate_k=4) as srv:
            srv.generate(np.asarray([3, 1], np.int32), max_new_tokens=9)
            time.sleep(0.1)
            enable_tracing(reset=True)
            try:
                c0 = dict(srv.metrics.counters)
                srv.generate(np.asarray([5, 2, 4], np.int32),
                             max_new_tokens=9)
                time.sleep(0.1)
                c1 = dict(srv.metrics.counters)
                spans = [s for s in TRACER.spans()
                         if s.thread_name.startswith("GenerativeServer")]
            finally:
                disable_tracing()
        rounds = c1["spec_rounds"] - c0["spec_rounds"]
        assert rounds >= 1
        name = {s.sid: s.name for s in spans}
        kids = {}
        for s in spans:
            kids.setdefault(s.parent, []).append(s.name)
        verifies = [s for s in spans if s.name == "serving.verify"]
        drafts = [s for s in spans if s.name == "serving.draft"
                  and "step" in s.args]
        assert len(verifies) == rounds and len(drafts) == 4 * rounds
        for v in verifies:
            assert name[v.parent] == "serving.step"
            assert kids[v.sid] == ["serving.launch", "serving.sync"]
        for d in drafts:
            want = ["serving.launch"] if d.args["step"] == 4 else \
                ["serving.launch", "serving.sync"]
            assert kids[d.sid] == want
        # the draft's own prefill is a launch and nothing the host
        # waits for
        pre = [s for s in spans if s.name == "serving.draft"
               and s.args.get("phase") == "prefill"]
        assert len(pre) == 1 and kids[pre[0].sid] == ["serving.launch"]
        launch = sum(s.dur for s in spans if s.name == "serving.launch"
                     and name[s.parent] in ("serving.verify",
                                            "serving.decode")) * 1e3
        d = {k: c1[k] - c0[k] for k in c1}
        assert d["decode_launch_ms_sum"] == pytest.approx(launch, rel=0.05)
        steps = [s for s in spans if s.name == "serving.step"]
        wall = sum(s.dur for s in steps) * 1e3
        assert 0 < d["sched_host_ms_sum"] < wall

    def test_metrics_count_tokens_exactly_once(self, spec, draft_spec):
        """Accepted draft tokens and the verify-corrected token land in
        tokens_generated exactly once; the draft ledger balances."""
        with make_server(spec, draft_spec=draft_spec,
                         speculate_k=4) as srv:
            outs = [srv.generate(p, max_new_tokens=6)
                    for p in mixed_prompts(4, seed=13)]
            rec = srv.metrics.to_record()["generative"]
        assert rec["tokens_generated"] == sum(len(o) for o in outs)
        assert rec["draft_tokens"] == \
            rec["draft_accepted"] + rec["draft_rejected"]
        assert rec["draft_tokens"] > 0
        assert 0.0 <= rec["draft_acceptance_rate"] <= 1.0

    def test_acceptance_lane_folds_and_renders(self, spec, draft_spec):
        from deeplearning4j_tpu.monitor.registry import MetricsRegistry
        from deeplearning4j_tpu.ui.report import render_report
        from deeplearning4j_tpu.ui.stats import StatsStorage
        storage = StatsStorage()
        with make_server(spec, draft_spec=draft_spec, speculate_k=4,
                         stats_storage=storage) as srv:
            srv.generate(np.asarray([1, 2, 3], np.int32),
                         max_new_tokens=6)
            rec = srv.metrics.to_record()
        reg = MetricsRegistry()
        reg.fold_serving(rec)
        text = reg.to_prometheus_text()
        assert "dl4j_serving_draft_acceptance_rate" in text
        assert "dl4j_serving_draft_tokens_rejected_total" in text
        html = render_report(storage)
        assert "speculative:" in html
        assert "draft tokens accepted" in html
        # a non-speculative record must NOT grow the lane
        with make_server(spec) as plain:
            plain.generate(np.asarray([1], np.int32), max_new_tokens=3)
            rec2 = plain.metrics.to_record()
        reg2 = MetricsRegistry()
        reg2.fold_serving(rec2)
        assert "draft_acceptance" not in reg2.to_prometheus_text()

    def test_warmup_covers_draft_and_verify_quantized(self, gpt_sd):
        """AOT warmup with speculation AND int8 weights enabled leaves
        0 traffic compiles: 7 plain programs + verify + draft decode +
        6 draft prefill buckets = 15."""
        fresh = gpt_generative_spec(gpt_sd, CFG, quantize_weights=True)
        d_sd = build_gpt(DRAFT_CFG, batch=2, seq_len=8, seed=4)
        fresh_draft = gpt_generative_spec(d_sd, DRAFT_CFG)
        with make_server(fresh, draft_spec=fresh_draft, speculate_k=4,
                         warmup=True) as srv:
            assert srv.warmup_report["speculative"] is True
            assert srv.metrics.counters["warmup_compiles"] == 15
            for i, p in enumerate(mixed_prompts(6, seed=17, max_len=20)):
                srv.generate(p, max_new_tokens=3 + i % 4)
            assert srv.metrics.counters["compiles"] == 0

    def test_pairing_validation(self, spec, draft_spec):
        bad_cfg = GPTConfig(vocab_size=48, hidden_size=16, num_layers=1,
                            num_heads=2, intermediate_size=32,
                            max_seq_len=32)
        bad = gpt_generative_spec(
            build_gpt(bad_cfg, batch=2, seq_len=8, seed=2), bad_cfg)
        with pytest.raises(ValueError, match="vocab"):
            make_server(spec, draft_spec=bad)
        short_cfg = GPTConfig(vocab_size=64, hidden_size=16,
                              num_layers=1, num_heads=2,
                              intermediate_size=32, max_seq_len=16)
        short = gpt_generative_spec(
            build_gpt(short_cfg, batch=2, seq_len=8, seed=2), short_cfg)
        with pytest.raises(ValueError, match="max_seq_len"):
            make_server(spec, draft_spec=short)
        with pytest.raises(ValueError, match="speculate_k"):
            make_server(spec, draft_spec=draft_spec, speculate_k=1)


# ----------------------------------------------------------------------
class TestSeededSampling:
    def test_sample_token_contract(self):
        from deeplearning4j_tpu.serving import sample_token
        r = np.random.default_rng(21)
        logits = r.normal(size=64).astype(np.float32)
        # temp 0 = exact greedy
        assert sample_token(logits, temperature=0.0) == \
            int(np.argmax(logits))
        # pure in (seed, index)
        a = sample_token(logits, temperature=0.8, seed=5, index=3)
        assert a == sample_token(logits, temperature=0.8, seed=5,
                                 index=3)
        assert 0 <= a < 64
        # top-k truncation: the draw is one of the k largest
        t = sample_token(logits, temperature=1.0, top_k=4, seed=9,
                         index=0)
        assert t in set(int(i) for i in np.argsort(logits)[-4:])
        # a vanishing top-p nucleus keeps (at least) the argmax
        assert sample_token(logits, temperature=1.0, top_p=1e-9,
                            seed=11, index=0) == int(np.argmax(logits))
        # NaN-safe: non-finite logits still yield a valid id
        bad = logits.copy()
        bad[::3] = np.nan
        assert 0 <= sample_token(bad, temperature=1.0, seed=1,
                                 index=1) < 64

    def test_sampled_deterministic_under_cobatching(self, spec):
        p = np.asarray([3, 7, 1], np.int32)
        with make_server(spec, max_slots=4) as srv:
            solo = srv.submit(p, max_new_tokens=8, temperature=0.9,
                              seed=7).result(timeout=120)
        with make_server(spec, max_slots=4) as srv:
            # different co-batch mix AND admission order this time
            others = [srv.submit(q, max_new_tokens=10, temperature=0.7,
                                 seed=100 + i)
                      for i, q in enumerate(mixed_prompts(3, seed=23))]
            h = srv.submit(p, max_new_tokens=8, temperature=0.9, seed=7)
            twin = srv.submit(p, max_new_tokens=8, temperature=0.9,
                              seed=7)
            got = h.result(timeout=120)
            assert got == twin.result(timeout=120)
            for o in others:
                o.result(timeout=120)
        assert got == solo
        # a different seed decouples the stream
        with make_server(spec) as srv:
            other = srv.submit(p, max_new_tokens=8, temperature=0.9,
                               seed=8).result(timeout=120)
        assert other != solo

    def test_sampled_identical_with_and_without_speculation(
            self, spec, draft_spec):
        """The emitted token is ALWAYS the target's sample at that
        (seed, index) — the draft cannot perturb a sampled stream."""
        p = np.asarray([5, 9], np.int32)
        with make_server(spec) as plain:
            want = plain.submit(p, max_new_tokens=8, temperature=0.8,
                                seed=42).result(timeout=120)
        with make_server(spec, draft_spec=draft_spec,
                         speculate_k=4) as srv:
            got = srv.submit(p, max_new_tokens=8, temperature=0.8,
                             seed=42).result(timeout=120)
        assert got == want

    @pytest.mark.chaos
    def test_sampled_crash_requeue_replays_identically(self, spec):
        """The (seed, absolute index) fold survives the requeue
        re-entry: prompt+generated-so-far re-prefills, the continuation
        draws land on the SAME indices, the stream is unchanged."""
        p = np.asarray([2, 4, 6], np.int32)
        with make_server(spec) as clean:
            want = clean.submit(p, max_new_tokens=8, temperature=0.9,
                                seed=13).result(timeout=120)
        srv = make_server(spec, max_slots=2, start=False,
                          resilience=ResilienceConfig(
                              worker_backoff_base_s=0.01,
                              worker_backoff_max_s=0.05))
        real = srv._decode_disp
        state = {"calls": 0, "fired": False}

        class CrashOnce:
            def __call__(self, *args):
                state["calls"] += 1
                if not state["fired"] and state["calls"] > 2:
                    state["fired"] = True
                    raise RuntimeError("chaos: decode worker dies")
                return real(*args)

        srv._decode_disp = CrashOnce()
        try:
            srv.start()
            got = srv.submit(p, max_new_tokens=8, temperature=0.9,
                             seed=13).result(timeout=120)
        finally:
            srv.shutdown()
        assert state["fired"]
        assert got == want
        assert srv.metrics.counters["requests_requeued"] >= 1

    def test_sampling_validation(self, spec):
        with make_server(spec, start=False) as srv:
            with pytest.raises(ValueError, match="temperature"):
                srv.submit(np.asarray([1], np.int32), 4,
                           temperature=-0.5)
            with pytest.raises(ValueError, match="temperature"):
                srv.submit(np.asarray([1], np.int32), 4,
                           temperature=float("nan"))
            with pytest.raises(ValueError, match="top_k"):
                srv.submit(np.asarray([1], np.int32), 4,
                           temperature=0.5, top_k=0)
            with pytest.raises(ValueError, match="top_p"):
                srv.submit(np.asarray([1], np.int32), 4,
                           temperature=0.5, top_p=0.0)


# ----------------------------------------------------------------------
def ahead_steps(srv):
    """``(decode steps launched ahead, decode steps)`` so far."""
    c = srv.metrics.counters
    assert 0 <= c["decode_ahead_steps"] <= c["decode_steps"]
    return c["decode_ahead_steps"], c["decode_steps"]


def wait_idle(srv, timeout=10.0):
    """The worker's last pass ends after the last future resolves."""
    end = time.monotonic() + timeout
    while (srv._n_active() or srv._ahead is not None) \
            and time.monotonic() < end:
        time.sleep(0.005)
    time.sleep(0.02)


class TestOneStepAhead:
    """ISSUE 33: the decode loop launches step n+1, fed step n's next
    tokens on the device, before it reads them, at every boundary where
    every lane is greedy, no slot is free and no lane ends at step n by
    something the host knows. Requests are queued on a server that has
    not started, so that they are admitted together and the count of
    steps launched ahead follows from the budgets: with every slot full
    from step 1 and the first lane ending after ``b`` tokens, steps 2 to
    ``b - 1`` run ahead (``b - 2`` of them)."""

    P = [np.asarray(p, np.int32) for p in
         ([3, 1, 4], [1, 5, 9, 2, 6], [5, 3, 5, 8, 9, 7, 9])]

    @pytest.fixture(scope="class")
    def spec(self, gpt_sd, lively):
        # the module's model only repeats its last token: here a row in
        # the wrong place has to change the tokens
        return lively(gpt_generative_spec(gpt_sd, CFG))

    def served(self, spec, jobs, **kw):
        """``jobs``: ``(prompt, budget, submit kwargs)``. Returns the
        handles (resolved or failed), the counts, and the server's
        counters."""
        srv = make_server(spec, start=False, **kw)
        try:
            hs = [srv.submit(p, max_new_tokens=n, **k) for p, n, k in jobs]
            srv.start()
            for h in hs:
                try:
                    h.result(timeout=120)
                except Exception:     # noqa: BLE001 — the test reads it
                    pass
            wait_idle(srv)
            assert srv._slots.free_count() == srv.max_slots
            assert srv._ahead is None and srv._unemitted is None
            return hs, ahead_steps(srv), dict(srv.metrics.counters)
        finally:
            srv.shutdown()

    def test_budgets_that_end_at_different_steps(self, spec):
        budgets = (5, 9, 12)
        hs, (ahead, steps), c = self.served(
            spec, [(p, n, {}) for p, n in zip(self.P, budgets)],
            max_slots=3)
        assert [h.result() for h in hs] == [
            ref_tokens(spec, p, n) for p, n in zip(self.P, budgets)]
        # three lanes for steps 1-4, then a slot is free for good
        assert (ahead, steps) == (budgets[0] - 2, budgets[-1] - 1)
        assert c["tokens_generated"] == sum(budgets)

    def test_a_queued_request_takes_the_freed_slot_and_it_engages_again(
            self, spec):
        budgets = (4, 10, 6)
        hs, (ahead, steps), _ = self.served(
            spec, [(p, n, {}) for p, n in zip(self.P, budgets)],
            max_slots=2)
        assert [h.result() for h in hs] == [
            ref_tokens(spec, p, n) for p, n in zip(self.P, budgets)]
        # steps 2-3 beside the first lane; the third request is placed
        # at the boundary after step 3 with nothing in the air, step 4
        # is launched behind its prefill, steps 5-8 run ahead until it
        # ends at step 8; step 9 has a free slot beside it
        assert (ahead, steps) == (2 + 4, 9)

    def test_the_sequences_end_is_known_beforehand(self, spec):
        ps = [np.arange(26, dtype=np.int32) % CFG.vocab_size,
              (np.arange(26, dtype=np.int32) * 3) % CFG.vocab_size]
        hs, (ahead, steps), _ = self.served(
            spec, [(p, 50, {}) for p in ps], max_slots=2)
        got = [h.result() for h in hs]
        assert got == [ref_tokens(spec, p, 50) for p in ps]
        assert [len(g) for g in got] == [6, 6]
        # 26 positions filled, 32 in all: step 5 writes the last row
        assert (ahead, steps) == (4, 5)

    def test_an_eos_drops_the_token_of_the_step_in_the_air(self, spec):
        full = [ref_tokens(spec, p, 12) for p in self.P[:2]]
        # lane 0 ends on its token j, the first that it has not seen
        j = next(k for k in range(3, 12)
                 if full[0][k] not in full[0][:k]) + 1
        eos = full[0][j - 1]
        seen = []
        hs, (ahead, steps), c = self.served(
            spec, [(self.P[0], 12, {"eos_id": eos,
                                    "on_token": seen.append}),
                   (self.P[1], 12, {})], max_slots=2)
        assert hs[0].result() == seen == full[0][:j]
        assert hs[1].result() == full[1]
        # the host cannot know: step j is in the air when token j ends
        # the lane, and its token for the lane goes nowhere
        assert (ahead, steps) == (j - 1, 11)
        assert c["tokens_generated"] == j + 12

    @pytest.mark.parametrize("ending", ["cancel", "deadline", "raise"])
    def test_a_lane_that_ends_with_a_step_in_the_air(self, spec, ending):
        full = [ref_tokens(spec, p, 12) for p in self.P[:2]]
        seen, box = [], {}

        class Boom(RuntimeError):
            pass

        def on_token(tok):
            seen.append(tok)
            if len(seen) == 3:
                if ending == "cancel":
                    box["h"].cancel()
                elif ending == "deadline":
                    time.sleep(1.1)
                else:
                    raise Boom("client callback fails")

        srv = make_server(spec, start=False, max_slots=2)
        try:
            kw = {"timeout_ms": 1000.0} if ending == "deadline" else {}
            # compiled before the deadline's clock starts
            greedy = [ref_tokens(spec, p, 2) for p in self.P[:2]]
            assert greedy == [f[:2] for f in full]
            box["h"] = h0 = srv.submit(self.P[0], max_new_tokens=12,
                                       on_token=on_token, **kw)
            h1 = srv.submit(self.P[1], max_new_tokens=12)
            srv.start()
            assert h1.result(timeout=120) == full[1]
            if ending == "cancel":
                assert h0.result(timeout=120) == full[0][:3]
            elif ending == "deadline":
                with pytest.raises(ServingTimeoutError) as ei:
                    h0.result(timeout=120)
                assert ei.value.tokens == full[0][:3]
            else:
                with pytest.raises(Boom):
                    h0.result(timeout=120)
            wait_idle(srv)
            ahead, steps = ahead_steps(srv)
            c = dict(srv.metrics.counters)
        finally:
            srv.shutdown()
        # the token of the step in the air never reaches the client
        assert seen == h0.partial() == full[0][:3]
        assert c["tokens_generated"] == 3 + 12
        # a cancel or a failing callback is on the books at the next
        # boundary (steps 2-3 ran ahead); a deadline is only seen at
        # the delivery after it, with step 4 in the air too
        assert (ahead, steps) == (3 if ending == "deadline" else 2, 11)

    def test_a_sampled_lane_joining_holds_the_boundary_back(self, spec):
        jobs = [(self.P[0], 4, {}), (self.P[1], 14, {}),
                (self.P[2], 5, {"temperature": 0.8, "seed": 5})]
        hs, (ahead, steps), _ = self.served(spec, jobs, max_slots=2)
        alone, _, _ = self.served(spec, jobs[2:], max_slots=2)
        assert [h.result() for h in hs[:2]] == [
            ref_tokens(spec, self.P[0], 4), ref_tokens(spec, self.P[1], 14)]
        assert hs[2].result() == alone[0].result()
        # steps 2-3 beside the first lane; none while the sampled lane
        # runs (its token is drawn on the host), none beside a free slot
        assert (ahead, steps) == (2, 13)

    def test_a_free_slot_holds_it_back(self, spec):
        hs, (ahead, steps), _ = self.served(
            spec, [(p, 8, {}) for p in self.P[:2]], max_slots=3)
        assert [h.result() for h in hs] == [
            ref_tokens(spec, p, 8) for p in self.P[:2]]
        assert (ahead, steps) == (0, 7)

    def test_a_draft_armed_leaves_the_rounds_as_they_are(self, spec,
                                                         draft_spec):
        hs, (ahead, _), c = self.served(
            spec, [(p, 10, {}) for p in self.P[:2]], max_slots=2,
            draft_spec=draft_spec, speculate_k=3)
        assert [h.result() for h in hs] == [
            ref_tokens(spec, p, 10) for p in self.P[:2]]
        assert ahead == 0 and c["spec_rounds"] > 0

    def test_no_program_is_built_for_the_tokens_on_the_device(self, gpt_sd,
                                                              lively):
        """A device array in ``tokens``' place takes the warmed decode
        program: nothing compiles under traffic, engaged."""
        from deeplearning4j_tpu.compilecache import COMPILE_STATS
        fresh = lively(gpt_generative_spec(gpt_sd, CFG))
        with GenerativeServer(fresh, max_slots=2, max_seq_len=MSL,
                              warmup=True, start=False) as srv:
            mark = COMPILE_STATS.mark()
            hs = [srv.submit(p, max_new_tokens=9) for p in self.P[:2]]
            srv.start()
            got = [h.result(timeout=120) for h in hs]
            wait_idle(srv)
            assert ahead_steps(srv) == (7, 8)
            assert srv.metrics.counters["compiles"] == 0
            assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
        assert got == [ref_tokens(fresh, p, 9) for p in self.P[:2]]

    def test_a_crash_with_a_step_in_the_air_requeues_and_replays(self,
                                                                 spec):
        """The worker dies in the launch of a step that runs ahead: the
        step before it is unread, its tokens are lost with the worker,
        and both requests re-enter at prefill with what they were
        handed, exactly once."""
        full = [ref_tokens(spec, p, 12) for p in self.P[:2]]
        srv = make_server(spec, start=False, max_slots=2,
                          resilience=ResilienceConfig(
                              worker_backoff_base_s=0.01))
        try:
            real, calls = srv._decode_disp, []

            class CrashOnce:
                def __call__(self, *args):
                    calls.append(type(args[3]["tokens"]).__name__)
                    if len(calls) == 4:
                        raise RuntimeError("chaos: decode worker dies")
                    return real(*args)

            srv._decode_disp = CrashOnce()
            hs = [srv.submit(p, max_new_tokens=12) for p in self.P[:2]]
            srv.start()
            assert [h.result(timeout=120) for h in hs] == full
            wait_idle(srv)
        finally:
            srv.shutdown()
        # the first launch is fed from the host, the ones ahead of their
        # step's tokens from the device; the fourth died in the air
        assert calls[0] == "ndarray" and "ndarray" not in calls[1:4]
        assert srv.metrics.counters["worker_restarts"] == 1
        assert all(h._req.requeues == 1 for h in hs)


class TestContinuousVsStatic:
    def test_continuous_2x_tokens_per_step_on_skewed_trace(self, spec):
        """The perf mechanism, pinned deterministically: on a trace of
        mostly-short generations with a long tail, continuous batching
        produces ≥1.9x the tokens per decode step of wait-for-full-batch
        static batching. The static schedule is arithmetic: waves of
        ``max_slots`` requests in queue order, each wave as many decode
        steps as its longest budget needs after the prefill's token."""
        slots, budgets = 4, [2, 2, 2, 24] * 3
        prompts = mixed_prompts(len(budgets), seed=5, max_len=6)
        srv = make_server(spec, max_slots=slots, start=False,
                          max_queue_len=64)
        try:
            hs = [srv.submit(p, n) for p, n in zip(prompts, budgets)]
            srv.start()
            results = [h.result(timeout=120) for h in hs]
        finally:
            srv.shutdown()
        rec = srv.metrics.to_record()["generative"]
        assert results == [ref_tokens(spec, p, n)
                           for p, n in zip(prompts, budgets)]
        assert rec["tokens_generated"] == sum(budgets)
        static_steps = sum(max(budgets[w:w + slots]) - 1
                           for w in range(0, len(budgets), slots))
        static_occupancy = sum(n - 1 for n in budgets) \
            / (static_steps * slots)
        assert rec["tokens_generated"] / rec["decode_steps"] >= \
            1.9 * sum(budgets) / static_steps, rec
        assert rec["slot_occupancy"] > static_occupancy

    def test_loadgen_trace_shared_between_modes(self, spec):
        with make_server(spec, start=False) as srv:
            lg1 = GenerativeLoadGenerator(srv, seed=3, prompt_len=(1, 8),
                                          new_tokens=(2, 6))
            lg2 = GenerativeLoadGenerator(srv, seed=3, prompt_len=(1, 8),
                                          new_tokens=(2, 6))
            for i in range(10):
                p1, n1, d1, t1, s1 = lg1.request(i)
                p2, n2, d2, t2, s2 = lg2.request(i)
                assert np.array_equal(p1, p2) and n1 == n2 and d1 == d2
                assert t1 == t2 and s1 == s2


# ----------------------------------------------------------------------
class TestLoadgenGenerative:
    def test_closed_loop_records_token_percentiles(self, spec):
        with make_server(spec, max_slots=4) as srv:
            lg = GenerativeLoadGenerator(srv, seed=2, prompt_len=(1, 10),
                                         new_tokens=(2, 8))
            res = lg.run_closed(n_requests=12, concurrency=4)
        assert res.n_ok == 12
        assert res.tokens_total > 0
        assert len(res.ttft_ms) == 12
        assert len(res.intertoken_ms) == res.tokens_total - 12
        assert res.ttft_percentile(50) > 0
        assert res.tokens_per_sec > 0
        assert "TTFT" in res.stats()

    def test_open_loop_with_deadlines(self, spec):
        with make_server(spec, max_slots=2) as srv:
            lg = GenerativeLoadGenerator(srv, seed=4, prompt_len=(1, 6),
                                         new_tokens=(2, 6),
                                         deadline_ms=(5000, 8000))
            res = lg.run_open(n_requests=8, rate_rps=200.0)
        assert res.n_issued == 8
        assert res.n_ok + res.n_timed_out + res.n_rejected \
            + res.n_failed == 8
        assert res.n_ok >= 6            # generous SLO: most complete

    def test_callable_length_sampler(self, spec):
        with make_server(spec, start=False) as srv:
            lg = GenerativeLoadGenerator(
                srv, seed=1,
                prompt_len=lambda rng: 3,
                new_tokens=lambda rng: 2 + int(rng.integers(0, 3)))
            for i in range(5):
                p, n, _, _, _ = lg.request(i)
                assert p.size == 3 and 2 <= n <= 4

    def test_request_carries_pure_sampling_fields(self, spec):
        with make_server(spec, start=False) as srv:
            lg = GenerativeLoadGenerator(srv, seed=6, prompt_len=(1, 6),
                                         new_tokens=(2, 4),
                                         temperature=(0.5, 1.0))
            a = lg.request(3)
            lg.request(7)               # interleaved draw
            b = lg.request(3)           # same i -> same tuple regardless
            assert np.array_equal(a[0], b[0]) and a[1:] == b[1:]
            assert 0.5 <= a[3] <= 1.0
            assert isinstance(a[4], int)
            # default stays greedy: the pre-ISSUE-18 trace unchanged
            lg0 = GenerativeLoadGenerator(srv, seed=6, prompt_len=(1, 6),
                                          new_tokens=(2, 4))
            assert lg0.request(0)[3] == 0.0

    def test_closed_loop_sampled(self, spec):
        with make_server(spec, max_slots=2) as srv:
            lg = GenerativeLoadGenerator(srv, seed=5, prompt_len=(1, 6),
                                         new_tokens=(2, 4),
                                         temperature=0.8)
            res = lg.run_closed(n_requests=6, concurrency=2)
        assert res.n_ok == 6 and res.tokens_total > 0


# ----------------------------------------------------------------------
class TestObservability:
    def test_metrics_record_fold_and_prometheus(self, spec):
        from deeplearning4j_tpu.monitor.registry import MetricsRegistry
        with make_server(spec, max_slots=2) as srv:
            srv.generate(np.asarray([1, 2], np.int32), max_new_tokens=5)
            rec = srv.metrics.to_record()
        assert rec["type"] == "serving"
        g = rec["generative"]
        assert g["tokens_generated"] == 5 and g["prefills"] == 1
        assert 0 < g["slot_occupancy"] <= 1.0
        assert rec["latency_ms"]["ttft"]["count"] == 1
        assert rec["latency_ms"]["intertoken"]["count"] == 4
        reg = MetricsRegistry()
        reg.fold_serving(rec)
        text = reg.to_prometheus_text()
        for needle in ("dl4j_serving_tokens_generated_total",
                       "dl4j_serving_slot_occupancy_ratio",
                       "dl4j_serving_tokens_per_sec",
                       "dl4j_serving_latency_ms",
                       "dl4j_serving_sched_host_ms_sum_total",
                       "dl4j_serving_decode_launch_ms_sum_total",
                       "dl4j_serving_queue_wait_ms_sum_total",
                       "dl4j_serving_requests_admitted_total"):
            assert needle in text, needle
        c = rec["counters"]
        assert c["requests_admitted"] == 1
        assert c["queue_wait_ms_sum"] >= 0
        assert 0 < c["decode_launch_ms_sum"] \
            <= srv.metrics.exec_ms.total_ms
        assert c["sched_host_ms_sum"] > 0

    def test_report_renders_generative_panel(self, spec):
        from deeplearning4j_tpu.ui.report import render_report
        from deeplearning4j_tpu.ui.stats import StatsStorage
        storage = StatsStorage()
        with make_server(spec, max_slots=2,
                         stats_storage=storage) as srv:
            srv.generate(np.asarray([3], np.int32), max_new_tokens=4)
        html = render_report(storage)
        assert "generative:" in html
        assert "ttft" in html and "intertoken" in html
        assert "slot occupancy" in html

    def test_kv_slab_bytes_tracked(self, spec):
        from deeplearning4j_tpu.monitor import memstats
        with make_server(spec, max_slots=2) as srv:
            rep = srv.memory_report()
            assert rep["kv_slab_bytes"] == srv.kv_slab_bytes > 0
            assert rep["kv_bytes_per_slot"] * 2 == rep["kv_slab_bytes"]
            rec = memstats.memory_record()
            assert rec["tracked"].get("kv_slab", 0) >= srv.kv_slab_bytes
        # released on shutdown
        rec2 = memstats.memory_record()
        assert rec2["tracked"].get("kv_slab", 0) < rep["kv_slab_bytes"] \
            or rec2["tracked"].get("kv_slab", 0) == 0

    def test_warmup_captures_memory_plans(self, gpt_sd):
        from deeplearning4j_tpu.compilecache.aot import ph_shape_sig
        from deeplearning4j_tpu.monitor import memstats
        import jax
        import jax.numpy as jnp
        fresh_spec = gpt_generative_spec(gpt_sd, CFG)
        with make_server(fresh_spec, max_slots=3, warmup=True) as srv:
            S = 3
            sig = ph_shape_sig(
                {"tokens": jax.ShapeDtypeStruct((S,), jnp.int32),
                 "positions": jax.ShapeDtypeStruct((S,), jnp.int32),
                 "active": jax.ShapeDtypeStruct((S,), jnp.bool_)})
            plan = memstats.PLANS.get(sig)
            assert plan is not None
            assert srv.warmup_report["seconds"] > 0

    def test_decode_spans_emitted(self, spec):
        from deeplearning4j_tpu.monitor.trace import TRACER
        was = TRACER.enabled
        TRACER.enabled = True
        try:
            with make_server(spec, max_slots=2) as srv:
                TRACER.drain()      # discard history
                srv.generate(np.asarray([2, 4], np.int32),
                             max_new_tokens=4)
                time.sleep(0.02)
                names = {s.name for s in TRACER.drain()[0]}
        finally:
            TRACER.enabled = was
        assert {"serving.enqueue", "serving.step", "serving.admit",
                "serving.prefill", "serving.decode", "serving.launch",
                "serving.sync", "serving.emit", "serving.reply"} <= names

    def test_telemetry_endpoint_exports_generative_gauges(self, spec):
        from deeplearning4j_tpu.ui.stats import StatsStorage
        storage = StatsStorage()
        with make_server(spec, max_slots=2, stats_storage=storage,
                         telemetry_port=0) as srv:
            srv.generate(np.asarray([5], np.int32), max_new_tokens=4)
            url = srv.telemetry.url
            with urllib.request.urlopen(url + "/metrics", timeout=5) as r:
                text = r.read().decode()
            with urllib.request.urlopen(url + "/healthz", timeout=5) as r:
                assert r.status == 200
        assert "dl4j_serving_tokens_generated_total" in text
        assert "dl4j_serving_slot_occupancy_ratio" in text

    def test_two_seq_lens_both_stay_warm(self, gpt_sd):
        """Review regression: AOT entries are keyed per (spec, slab
        shape) — a second server over the same spec with a different
        max_seq_len must get its own warmed programs, not silently
        fall off the first server's onto lazy traffic compiles."""
        fresh_spec = gpt_generative_spec(gpt_sd, CFG)
        p = np.asarray([5, 6], np.int32)
        with GenerativeServer(fresh_spec, max_slots=4, max_seq_len=16,
                              warmup=True) as s1:
            s1.generate(p, max_new_tokens=4)
            assert s1.metrics.counters["compiles"] == 0
        with GenerativeServer(fresh_spec, max_slots=4, max_seq_len=32,
                              warmup=True) as s2:
            s2.generate(p, max_new_tokens=4)
            assert s2.metrics.counters["compiles"] == 0

    def test_tokens_timeout_typed_and_resumable(self, spec):
        """Review regression: a per-token wait timeout raises the
        builtin TimeoutError (not a leaked queue.Empty), and the
        stream resumes afterwards."""
        srv = make_server(spec, start=False)
        try:
            h = srv.submit(np.asarray([1], np.int32), max_new_tokens=3)
            it = h.tokens(timeout=0.05)
            with pytest.raises(TimeoutError, match="still in flight"):
                next(it)
            srv.start()
            h.result(timeout=60)
            assert list(h.tokens(timeout=5)) == h.result()
        finally:
            srv.shutdown()

    def test_shutdown_never_started_fails_queued_typed(self, spec):
        """Review regression: shutdown of a start=False server has no
        worker to drain — queued futures fail typed instead of
        hanging their clients forever."""
        srv = make_server(spec, start=False)
        h = srv.submit(np.asarray([1], np.int32), max_new_tokens=3)
        srv.shutdown(drain=True, timeout=5)
        with pytest.raises(ServerClosedError):
            h.result(timeout=5)
        with pytest.raises(ServerClosedError):
            list(h.tokens(timeout=5))

    def test_cancel_counted_consistently(self, spec):
        """Review regression: a cancel is requests_cancelled whether it
        was still queued or already occupying a slot — never silently
        unaccounted, never counted as served."""
        with make_server(spec, max_slots=1) as srv:
            # slot-occupying cancel
            h1 = srv.submit(np.asarray([1], np.int32), max_new_tokens=400,
                            on_token=lambda t: time.sleep(0.02))
            # queued cancel (slot busy behind h1)
            h2 = srv.submit(np.asarray([2], np.int32), max_new_tokens=4)
            time.sleep(0.05)
            h1.cancel()
            h2.cancel()
            h1.result(timeout=60)
            h2.result(timeout=60)
            deadline = time.monotonic() + 5
            while srv.metrics.counters["requests_cancelled"] < 2 and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
            c = srv.metrics.to_record()["counters"]
        assert c["requests_cancelled"] == 2
        assert c["requests_served"] + c["requests_cancelled"] \
            + c["requests_failed"] + c["requests_timed_out"] == 2

    def test_shutdown_drains_queued_generations(self, spec):
        srv = make_server(spec, max_slots=2, start=False)
        hs = [srv.submit(p, 4) for p in mixed_prompts(5, seed=9)]
        srv.start()
        srv.shutdown(drain=True, timeout=60)
        for h in hs:
            assert len(h.result(timeout=1)) == 4
