"""Paged KV serving (serving/paged/, ISSUE 16).

Pinned contracts:

- allocator discipline: refcounted blocks freed exactly once (a second
  release raises), pool exhaustion under admission pressure sheds TYPED
  (:class:`PoolExhaustedError` with ``retry_after_s``) instead of
  crashing a worker, and the full accounting invariant (free + held +
  evictable == capacity, refcounts == live-table occurrences) holds
  after every scheduler step under ``debug_leaks=True`` — through
  completion, shed, cancel AND crash-recovery requeue;
- block tables grow on demand at decode-step boundaries across every
  pow2 prefill bucket;
- prefix caching: chain-hashed full blocks are shared by refcount,
  survive interleaved admit/complete churn, skip their prefill (a hit
  dispatches the small SUFFIX bucket, not the full-prompt bucket), and
  never change greedy output;
- hot reload: ``update_model()`` flushes the prefix cache (its blocks
  hold K/V computed with the superseded weights) before any later
  lookup — a repeated prompt after a reload re-prefills from scratch
  and matches the NEW model's reference;
- permanent errors stay permanent: an invalid request raises
  ValueError even with the pool fully committed (validation precedes
  the block commitment), never a retryable PoolExhaustedError;
- greedy tokens are IDENTICAL to the dense server's reference
  (:func:`greedy_decode`) — paged vs dense is a memory-layout change,
  not a numerics change — including under tensor parallelism (tp=2 on
  the virtual 8-device CPU mesh).
"""
import time

import numpy as np
import pytest

from deeplearning4j_tpu.serving.generative import greedy_decode
from deeplearning4j_tpu.serving.paged import (NULL_BLOCK, BlockPool, KVTier,
                                              PagedGenerativeServer,
                                              PagedMetrics,
                                              PoolExhaustedError,
                                              blocks_for_tokens,
                                              prefix_block_hashes)
from deeplearning4j_tpu.serving.queue import (ServerOverloadedError,
                                              ServingTimeoutError)
from deeplearning4j_tpu.serving.resilience import ResilienceConfig
from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                        gpt_generative_spec,
                                        gpt_paged_spec)

CFG = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                intermediate_size=64, max_seq_len=32)
MSL = 32
BS = 8


@pytest.fixture(scope="module")
def gpt_sd():
    return build_gpt(CFG, batch=2, seq_len=8, seed=0)


@pytest.fixture(scope="module")
def spec(gpt_sd):
    # one spec for the whole module: the jitted programs are memoized
    # per (spec, geometry), so every server below shares one compile set
    return gpt_paged_spec(gpt_sd, CFG)


@pytest.fixture(scope="module")
def dense_spec(gpt_sd):
    return gpt_generative_spec(gpt_sd, CFG)


@pytest.fixture(scope="module")
def draft_spec():
    dcfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                     num_heads=2, intermediate_size=32, max_seq_len=32)
    return gpt_generative_spec(
        build_gpt(dcfg, batch=2, seq_len=8, seed=3), dcfg)


def make_server(spec, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", MSL)
    kw.setdefault("block_size", BS)
    kw.setdefault("warmup", False)
    kw.setdefault("debug_leaks", True)
    return PagedGenerativeServer(spec, **kw)


def ref_tokens(dense_spec, prompt, n):
    return greedy_decode(dense_spec, prompt, n, max_seq_len=MSL)


def mixed_prompts(n=6, seed=0, max_len=12):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size,
                         int(rng.integers(1, max_len + 1)))
            .astype(np.int32) for _ in range(n)]


def crash_decode_once(srv, after=2):
    """Make ``srv``'s decode dispatcher raise once, on its call number
    ``after + 1``. Returns the state: ``fired``, and ``leaves``, the
    pool's arrays as the dying worker held them."""
    real = srv._decode_disp
    state = {"calls": 0, "fired": False, "leaves": None}

    class CrashOnce:
        def __call__(self, *args):
            state["calls"] += 1
            if not state["fired"] and state["calls"] > after:
                state["fired"] = True
                state["leaves"] = srv._kc + srv._vc
                raise RuntimeError("chaos: decode worker dies")
            return real(*args)

    srv._decode_disp = CrashOnce()
    return state


def wait_uncommitted(srv, timeout=10.0):
    """Block-commitment release rides the request future's done
    callback, which CPython fires AFTER result() waiters wake — give
    the callbacks a moment before asserting on ``_committed``."""
    deadline = time.monotonic() + timeout
    while srv._committed and time.monotonic() < deadline:
        time.sleep(0.01)
    return srv._committed


# ----------------------------------------------------------------------
class TestBlockPool:
    def test_alloc_release_cycle(self):
        p = BlockPool(5, 4)
        assert p.capacity == 4 and p.free_count() == 4
        blocks = [p.alloc() for _ in range(4)]
        assert NULL_BLOCK not in blocks
        assert len(set(blocks)) == 4 and p.free_count() == 0
        with pytest.raises(PoolExhaustedError) as ei:
            p.alloc()
        assert ei.value.retry_after_s > 0
        assert isinstance(ei.value, ServerOverloadedError)
        for b in blocks:
            p.release(b)
        assert p.free_count() == 4
        p.check_invariant(tables=[])

    def test_double_free_raises(self):
        p = BlockPool(4, 2)
        b = p.alloc()
        p.release(b)
        with pytest.raises(RuntimeError, match="released twice"):
            p.release(b)

    def test_refcount_shared_block(self):
        p = BlockPool(4, 2)
        b = p.alloc()
        p.retain(b)
        p.release(b)
        assert p.held_count() == 1          # still held by one reader
        p.release(b)
        assert p.held_count() == 0 and p.free_count() == 3
        with pytest.raises(RuntimeError):
            p.retain(b)                      # retaining a free block

    def test_null_block_never_allocated(self):
        p = BlockPool(8, 2)
        got = {p.alloc() for _ in range(p.capacity)}
        assert NULL_BLOCK not in got
        with pytest.raises(ValueError):
            p.retain(NULL_BLOCK)

    def test_prefix_register_lookup_evict_lru(self):
        p = BlockPool(4, 2)                  # 3 usable blocks
        toks = np.arange(6, dtype=np.int32)
        hashes = prefix_block_hashes(toks, 2)
        assert len(hashes) == 3
        blocks = [p.alloc() for _ in range(3)]
        for h, b in zip(hashes, blocks):
            assert p.register(h, b)
        # a second registration of the same hash leaves the cache alone
        assert not p.register(hashes[0], blocks[1])
        for b in blocks:
            p.release(b)                     # refcount 0 -> evictable
        assert p.free_count() == 0 and p.usable_free_count() == 3
        hit = p.lookup(hashes)               # revives all three
        assert hit == blocks
        for b in hit:
            p.release(b)
        # pool pressure reclaims the LRU-released cached block first
        fresh = p.alloc()
        assert fresh == blocks[0] and p.evictions == 1
        # its hash is gone, and a chain lookup stops at the first miss
        assert p.lookup(hashes) == []
        p.release(fresh)
        p.check_invariant()

    def test_chain_hashes_depend_on_prefix(self):
        a = prefix_block_hashes(np.array([1, 2, 3, 4], np.int32), 2)
        b = prefix_block_hashes(np.array([9, 9, 3, 4], np.int32), 2)
        assert a[0] != b[0]
        assert a[1] != b[1]        # same block tokens, different prefix

    def test_partial_trailing_block_never_hashed(self):
        assert len(prefix_block_hashes(np.arange(7), 2)) == 3
        assert len(prefix_block_hashes(np.arange(1), 2)) == 0

    def test_flush_cache_drops_registrations_keeps_held(self):
        """The hot-reload flush: every registration drops (no future
        lookup reuses stale K/V), evictable blocks return to the free
        list, held shared blocks keep their refcounts for in-flight
        readers — and free straight to the free list on release."""
        p = BlockPool(6, 2)                  # 5 usable blocks
        h1, h2 = prefix_block_hashes(np.arange(4, dtype=np.int32), 2)
        held = p.alloc()
        p.register(h1, held)
        ev = p.alloc()
        p.register(h2, ev)
        p.release(ev)                        # refcount 0 -> evictable
        assert p.flush_cache() == 2
        assert p.cached_count() == 0
        assert p.lookup([h1, h2]) == []
        assert p.free_count() == 4           # the evictable one freed
        assert p.held_count() == 1           # in-flight reader intact
        p.check_invariant(tables=[[held]])
        p.release(held)                      # unregistered -> free, not
        assert p.free_count() == 5           # evictable
        p.check_invariant(tables=[])

    def test_reset_clears_everything(self):
        p = BlockPool(4, 2)
        b = p.alloc()
        p.register(prefix_block_hashes(np.arange(2), 2)[0], b)
        p.reset()
        assert p.free_count() == 3 and p.cached_count() == 0
        p.check_invariant(tables=[])

    def test_invariant_catches_seeded_leak(self):
        p = BlockPool(4, 2)
        b = p.alloc()
        with pytest.raises(AssertionError, match="diverge"):
            p.check_invariant(tables=[])     # held block in no table
        p.check_invariant(tables=[[b]])

    def test_blocks_for_tokens(self):
        assert blocks_for_tokens(1, 8) == 1
        assert blocks_for_tokens(8, 8) == 1
        assert blocks_for_tokens(9, 8) == 2


# ----------------------------------------------------------------------
class TestGreedyParity:
    @pytest.mark.slow
    def test_mixed_lengths_match_dense_reference(self, spec, dense_spec):
        # random mixed lengths + the degenerate/bucket-edge prompts the
        # tier-1 growth test drops for wall budget (1 token, exact
        # bucket edges 3 -> 4 and 16 -> 16)
        prompts = mixed_prompts(6) + [
            np.arange(L, dtype=np.int32) % CFG.vocab_size
            for L in (1, 3, 16)]
        with make_server(spec, num_blocks=64) as srv:
            handles = [srv.submit(p, max_new_tokens=8) for p in prompts]
            got = [h.result(timeout=60) for h in handles]
        assert got == [ref_tokens(dense_spec, p, 8) for p in prompts]

    def test_table_growth_across_buckets(self, spec, dense_spec):
        """Prompts landing in every pow2 prefill bucket, each decoding
        across at least one block boundary — growth at the step
        boundary keeps tokens identical to the dense reference.
        (The full per-bucket matrix incl. the degenerate 1-token and
        exact-bucket-edge prompts lives in the slow-tier mixed-lengths
        test; this keeps one spanning set inside the tier-1 budget.)"""
        lengths = [2, 5, 9, 17]                # buckets 2, 8, 16, 32
        prompts = [np.arange(L, dtype=np.int32) % CFG.vocab_size
                   for L in lengths]
        with make_server(spec, num_blocks=64) as srv:
            handles = [srv.submit(p, max_new_tokens=10) for p in prompts]
            got = [h.result(timeout=60) for h in handles]
        assert got == [ref_tokens(dense_spec, p, 10) for p in prompts]

    def test_pool_drains_clean_after_traffic(self, spec):
        srv = make_server(spec, num_blocks=64)
        hs = [srv.submit(p, max_new_tokens=6) for p in mixed_prompts(8)]
        for h in hs:
            h.result(timeout=60)
        srv.shutdown()
        st = srv.pool.stats()
        assert st["held"] == 0, st
        assert wait_uncommitted(srv) == 0
        srv.pool.check_invariant(tables=[])


# ----------------------------------------------------------------------
class TestPrefixCache:
    @pytest.mark.slow
    def test_repeat_prefix_hits_and_matches(self, spec, dense_spec):
        sys_prompt = (np.arange(17, dtype=np.int32) * 3) % CFG.vocab_size
        with make_server(spec) as srv:
            a = srv.submit(sys_prompt, max_new_tokens=6).result(timeout=60)
            b = srv.submit(sys_prompt, max_new_tokens=6).result(timeout=60)
        ref = ref_tokens(dense_spec, sys_prompt, 6)
        assert a == ref and b == ref
        rec = srv.metrics.to_record()["paged"]
        # 17 tokens = 2 full blocks of 8; the repeat reuses both (reuse
        # is capped at (L-1)//BS so >= 1 suffix token still prefills)
        assert rec["prefix_hit_rate"] > 0
        assert rec["prefix_blocks_hit"] == 2

    def test_hit_skips_prefill_to_suffix_bucket(self, spec):
        """A prefix hit dispatches the SUFFIX bucket (near-one-decode-
        step TTFT on repeats), not the full-prompt bucket — observable
        in the prefill shapes the server actually ran."""
        prompt = (np.arange(17, dtype=np.int32) * 5) % CFG.vocab_size
        with make_server(spec) as srv:
            srv.submit(prompt, max_new_tokens=2).result(timeout=60)
            before = set(srv._shapes_seen)
            srv.submit(prompt, max_new_tokens=2).result(timeout=60)
            new_shapes = srv._shapes_seen - before
        # 17 tokens cold runs bucket 32; the repeat reuses 2 blocks and
        # prefills only its 1-token suffix -> the ONLY new prefill
        # shape is bucket 1 ("hist" marks prefill signatures; shapes
        # are keyed (role, sig) since the speculative tier, because
        # draft and target share io signatures)
        new_buckets = {dict(sig)["tokens"][0]
                       for role, sig in new_shapes
                       if role == "target" and "hist" in dict(sig)}
        assert new_buckets == {1}

    @pytest.mark.slow
    def test_refcount_churn_interleaved_admit_complete(
            self, spec, dense_spec):
        """Many concurrent requests sharing one prefix, admitted and
        retired in interleaved waves through 3 slots: the shared
        blocks' refcounts drain to exactly zero, under the every-step
        invariant check."""
        shared = (np.arange(16, dtype=np.int32) * 7) % CFG.vocab_size
        rng = np.random.default_rng(3)
        prompts = [np.concatenate([shared,
                                   rng.integers(0, CFG.vocab_size,
                                                int(rng.integers(1, 6)))
                                   .astype(np.int32)])
                   for _ in range(10)]
        budgets = [int(rng.integers(1, 8)) for _ in prompts]
        with make_server(spec, max_slots=3, num_blocks=64) as srv:
            handles = [srv.submit(p, max_new_tokens=n)
                       for p, n in zip(prompts, budgets)]
            got = [h.result(timeout=120) for h in handles]
        assert got == [ref_tokens(dense_spec, p, n)
                       for p, n in zip(prompts, budgets)]
        st = srv.pool.stats()
        assert st["held"] == 0, st
        srv.pool.check_invariant(tables=[])

    def test_update_model_flushes_prefix_cache(self, spec, dense_spec,
                                               gpt_sd):
        """A hot reload must invalidate the prefix cache: the cached
        blocks' K/V were computed with the OLD weights, so a repeated
        prompt after update_model() re-prefills from scratch (zero
        hits) and its tokens match the NEW model's reference — no
        silent old/new mixing."""
        import jax.numpy as jnp
        prompt = (np.arange(17, dtype=np.int32) * 3) % CFG.vocab_size
        with make_server(spec) as srv:
            srv.submit(prompt, max_new_tokens=4).result(timeout=60)
            assert srv.pool.cached_count() > 0   # 2 full blocks cached
            old = gpt_sd._arrays["wte"]
            try:
                gpt_sd._arrays["wte"] = old + jnp.asarray(0.5)
                srv.update_model()
                after = srv.submit(prompt,
                                   max_new_tokens=4).result(timeout=60)
                want = ref_tokens(dense_spec, prompt, 4)
            finally:
                gpt_sd._arrays["wte"] = old
                srv.update_model()
        assert after == want        # the reference reads live params too
        rec = srv.metrics.to_record()["paged"]
        # the repeat ran AFTER the flush: nothing to hit
        assert rec["prefix_blocks_hit"] == 0
        assert srv.metrics.counters["prefix_cache_flushes"] >= 1
        srv.pool.check_invariant(tables=[])

    def test_disabled_cache_never_hits(self, spec):
        prompt = (np.arange(17, dtype=np.int32) * 3) % CFG.vocab_size
        with make_server(spec, prefix_cache=False) as srv:
            srv.submit(prompt, max_new_tokens=2).result(timeout=60)
            srv.submit(prompt, max_new_tokens=2).result(timeout=60)
        rec = srv.metrics.to_record()["paged"]
        assert rec["prefix_hit_rate"] == 0.0
        assert rec["cached_blocks"] == 0


# ----------------------------------------------------------------------
class TestPoolPressure:
    def test_exhaustion_sheds_typed_not_crash(self, spec):
        """A pool too small for the offered worst-case load sheds at
        SUBMIT with a retry_after_s hint — no worker crash — and the
        shed client's retry succeeds once completions release their
        commitment."""
        # capacity 8 blocks; each request commits ceil((12+8)/8) = 3
        # blocks worst-case -> two fit, the third sheds. start=False
        # keeps the accounting deterministic (nothing completes early)
        srv = make_server(spec, max_slots=4, num_blocks=9, start=False)
        try:
            p = np.arange(12, dtype=np.int32)
            h1 = srv.submit(p, max_new_tokens=8)
            h2 = srv.submit(p + 1, max_new_tokens=8)
            with pytest.raises(PoolExhaustedError) as ei:
                srv.submit(p + 2, max_new_tokens=8)
            assert ei.value.retry_after_s > 0
            srv.start()
            assert h1.result(timeout=60) and h2.result(timeout=60)
            # completions released their commitment: the retry now fits
            assert wait_uncommitted(srv) == 0
            h3 = srv.submit(p + 2, max_new_tokens=8)
            assert h3.result(timeout=60)
        finally:
            srv.shutdown()
        assert srv.metrics.counters["requests_shed"] >= 1
        assert wait_uncommitted(srv) == 0

    @pytest.mark.slow
    def test_shed_clients_retrying_all_complete(self, spec, dense_spec):
        """Admission-pressure end-to-end: 8 clients against a pool
        that holds ~3 requests' worst case, each retrying on typed
        shed with the server's own backoff hint — everything completes
        with reference tokens and the pool drains clean."""
        prompts = mixed_prompts(8, seed=5, max_len=8)
        with make_server(spec, max_slots=3, num_blocks=7) as srv:
            handles = []
            deadline = time.monotonic() + 120
            for p in prompts:
                while True:
                    assert time.monotonic() < deadline, "retry wedged"
                    try:
                        handles.append(srv.submit(p, max_new_tokens=4))
                        break
                    except PoolExhaustedError as e:
                        time.sleep(min(e.retry_after_s, 0.05))
            got = [h.result(timeout=120) for h in handles]
        assert got == [ref_tokens(dense_spec, p, 4) for p in prompts]
        assert srv.pool.stats()["held"] == 0
        assert wait_uncommitted(srv) == 0

    def test_failed_submit_rolls_back_commitment(self, spec):
        with make_server(spec, max_slots=4, num_blocks=9) as srv:
            with pytest.raises(ValueError):     # out-of-vocab prompt
                srv.submit(np.asarray([999]), max_new_tokens=4)
            assert srv._committed == 0

    def test_invalid_request_raises_valueerror_under_pressure(self, spec):
        """Permanent errors stay permanent under pool pressure: with
        the pool fully committed, an invalid request raises ValueError
        (validation runs BEFORE the block commitment) — not a
        retryable PoolExhaustedError telling the client to back off
        and resubmit something that can never run — and is not
        counted as shed."""
        srv = make_server(spec, max_slots=4, num_blocks=9, start=False)
        try:
            p = np.arange(12, dtype=np.int32)
            srv.submit(p, max_new_tokens=8)
            srv.submit(p + 1, max_new_tokens=8)     # 6 of 8 committed
            with pytest.raises(PoolExhaustedError):
                srv.submit(p + 2, max_new_tokens=8)  # valid -> typed shed
            shed = srv.metrics.counters["requests_shed"]
            with pytest.raises(ValueError):          # empty prompt
                srv.submit(np.asarray([], np.int32), 4)
            with pytest.raises(ValueError):          # out-of-vocab
                srv.submit(np.asarray([CFG.vocab_size], np.int32), 4)
            with pytest.raises(ValueError):          # zero budget
                srv.submit(np.asarray([1], np.int32), 0)
            with pytest.raises(ValueError):          # over-long prompt
                srv.submit(np.arange(MSL, dtype=np.int32) % CFG.vocab_size,
                           4)
            assert srv.metrics.counters["requests_shed"] == shed
            assert srv._committed == 6
        finally:
            srv.shutdown()


# ----------------------------------------------------------------------
class TestLifecycleRelease:
    def test_cancel_releases_blocks_once(self, spec):
        with make_server(spec) as srv:
            h = srv.submit(np.arange(9, dtype=np.int32),
                           max_new_tokens=30)
            next(iter(h.tokens(timeout=30)))      # it is in flight
            h.cancel()
            h.result(timeout=30)                  # partial token list
        assert srv.pool.stats()["held"] == 0
        assert wait_uncommitted(srv) == 0
        srv.pool.check_invariant(tables=[])

    def test_deadline_expiry_releases_blocks(self, spec):
        with make_server(spec) as srv:
            h = srv.submit(np.arange(6, dtype=np.int32),
                           max_new_tokens=25, timeout_ms=30.0)
            try:
                h.result(timeout=60)
            except Exception:
                pass     # timed out or not — either way nothing leaks
        assert srv.pool.stats()["held"] == 0
        assert wait_uncommitted(srv) == 0

    @pytest.mark.chaos
    def test_crash_requeue_releases_blocks_exactly_once(
            self, spec, dense_spec):
        """Kill the decode worker mid-generation: the pool hard-resets
        (every held block back exactly once, the prefix cache — which
        addresses now-garbage slab rows — dropped wholesale), the
        in-flight requests requeue at prefill exactly once, tokens
        still match the reference, and the accounting invariant holds
        on the respawned worker's every step."""
        prompts = mixed_prompts(4, seed=7)
        srv = make_server(spec, start=False,
                          resilience=ResilienceConfig(
                              worker_backoff_base_s=0.01,
                              worker_backoff_max_s=0.05))
        state = crash_decode_once(srv)
        try:
            srv.start()
            handles = [srv.submit(p, max_new_tokens=8) for p in prompts]
            got = [h.result(timeout=120) for h in handles]
        finally:
            srv.shutdown()
        assert state["fired"]
        assert got == [ref_tokens(dense_spec, p, 8) for p in prompts]
        assert srv.metrics.counters["worker_restarts"] >= 1
        assert srv.metrics.counters["requests_requeued"] >= 1
        assert srv.pool.stats()["held"] == 0, srv.pool.stats()
        assert wait_uncommitted(srv) == 0
        srv.pool.check_invariant(tables=[])


# ----------------------------------------------------------------------
class TestOneStepAhead:
    """ISSUE 33 over the block pool: the step launched ahead gets its
    blocks and its table width from positions alone, while the step
    before it is unread, under the every-step pool invariant
    (``debug_leaks``). Blocks of 4, so that lanes cross a block's edge
    every fourth step; the requests are queued before the server starts,
    so that the count of steps launched ahead follows from the budgets
    (tests/test_generative.py, the class of the same name)."""

    P = [np.asarray(p, np.int32) for p in
         ([3, 1, 4], [1, 5, 9, 2, 6, 5], [5, 3, 5, 8, 9, 7, 9])]

    @pytest.fixture(scope="class")
    def specs(self, gpt_sd, lively):
        # a model whose tokens follow positions and context (conftest):
        # the paged programs and the dense reference over one draw
        return (lively(gpt_paged_spec(gpt_sd, CFG)),
                lively(gpt_generative_spec(gpt_sd, CFG)))

    def served(self, spec, jobs, **kw):
        kw.setdefault("block_size", 4)
        kw.setdefault("max_slots", 2)
        srv = make_server(spec, start=False, **kw)
        try:
            hs = [srv.submit(p, max_new_tokens=n, **k) for p, n, k in jobs]
            srv.start()
            for h in hs:
                try:
                    h.result(timeout=120)
                except Exception:     # noqa: BLE001 — the test reads it
                    pass
            deadline = time.monotonic() + 10
            while (srv._n_active() or srv._ahead is not None) \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            wait_uncommitted(srv)
            c = dict(srv.metrics.counters)
            assert 0 <= c["decode_ahead_steps"] <= c["decode_steps"]
            assert srv._ahead is None and srv._unemitted is None
            assert srv.pool.held_count() == 0 and srv._committed == 0
            assert c["blocks_allocated"] == c["blocks_released"]
            srv.pool.check_invariant(tables=[])
            return hs, c, srv
        finally:
            srv.shutdown()

    def test_lanes_cross_block_edges_with_a_step_in_the_air(self, specs):
        spec, dense = specs
        budgets = (14, 17)
        hs, c, _ = self.served(
            spec, [(p, n, {}) for p, n in zip(self.P, budgets)])
        assert [h.result() for h in hs] == [
            ref_tokens(dense, p, n) for p, n in zip(self.P, budgets)]
        # both lanes from step 1; the first ends on its token 14
        assert (c["decode_ahead_steps"], c["decode_steps"]) == (12, 16)
        # 3 + 13 and 6 + 16 rows written: 4 and 6 blocks of 4
        assert c["blocks_allocated"] == 4 + 6

    @pytest.mark.parametrize("ending", ["eos", "cancel", "deadline",
                                        "raise"])
    def test_a_lane_that_ends_with_a_step_in_the_air_gives_its_blocks_back(
            self, specs, ending):
        spec, dense = specs
        full = [ref_tokens(dense, p, 14) for p in self.P[:2]]
        # lane 0 ends on its token j: by an EOS it has not seen before,
        # or by what its callback does at the third token
        j = next(k for k in range(3, 14)
                 if full[0][k] not in full[0][:k]) + 1 \
            if ending == "eos" else 3
        seen, box = [], {}

        class Boom(RuntimeError):
            pass

        def on_token(tok):
            seen.append(tok)
            if len(seen) == 3 and ending == "cancel":
                box["h"].cancel()
            elif len(seen) == 3 and ending == "deadline":
                time.sleep(1.1)
            elif len(seen) == 3 and ending == "raise":
                raise Boom("client callback fails")

        kw = {"on_token": on_token}
        if ending == "eos":
            kw["eos_id"] = full[0][j - 1]
        if ending == "deadline":
            kw["timeout_ms"] = 1000.0
        srv = make_server(spec, start=False, block_size=4, max_slots=2)
        try:
            box["h"] = h0 = srv.submit(self.P[0], max_new_tokens=14, **kw)
            h1 = srv.submit(self.P[1], max_new_tokens=14)
            srv.start()
            assert h1.result(timeout=120) == full[1]
            try:
                h0.result(timeout=120)
            except (ServingTimeoutError, Boom):
                assert ending in ("deadline", "raise")
            wait_uncommitted(srv)
            c = dict(srv.metrics.counters)
            assert srv.pool.held_count() == 0
            srv.pool.check_invariant(tables=[])
        finally:
            srv.shutdown()
        # the dropped token is never delivered, and the lane's books are
        # those of the tokens it was handed
        assert seen == h0.partial() == full[0][:j]
        assert c["tokens_generated"] == j + 14
        assert c["blocks_allocated"] == c["blocks_released"]
        ahead = {"eos": j - 1, "cancel": 2, "raise": 2, "deadline": 3}
        assert (c["decode_ahead_steps"], c["decode_steps"]) == \
            (ahead[ending], 13)

    def test_a_continuation_still_hits_over_the_generated_span(self, specs):
        """Lane 0 ends on an EOS with the next step in the air, which
        writes one row past the lane's books into its last block. The
        full blocks of ``prompt + generated`` are registered as ever, a
        request over that span finds them, and what they hold serves
        the reference's tokens."""
        spec, dense = specs
        full = ref_tokens(dense, self.P[0], 14)
        j = next(k for k in range(6, 14) if full[k] not in full[:k]) + 1
        srv = make_server(spec, start=False, block_size=4, max_slots=2)
        try:
            h0 = srv.submit(self.P[0], max_new_tokens=14,
                            eos_id=full[j - 1])
            h1 = srv.submit(self.P[1], max_new_tokens=16)
            srv.start()
            assert h0.result(timeout=120) == full[:j]
            assert h1.result(timeout=120) == ref_tokens(dense, self.P[1], 16)
            assert srv.metrics.counters["decode_ahead_steps"] >= j - 1
            hit0 = srv.metrics.counters["prefix_blocks_hit"]
            span = np.concatenate([self.P[0],
                                   np.asarray(full[:j], np.int32)])
            got = srv.submit(span, max_new_tokens=5).result(timeout=120)
            hits = srv.metrics.counters["prefix_blocks_hit"] - hit0
        finally:
            srv.shutdown()
        # every full block under the lane's books: rows [0, 3 + j - 1)
        assert hits == (len(span) - 1) // 4 >= 2
        assert got == ref_tokens(dense, span, 5)

    def test_a_free_slot_or_a_draft_holds_it_back(self, specs, draft_spec):
        spec, dense = specs
        jobs = [(p, 9, {}) for p in self.P[:2]]
        want = [ref_tokens(dense, p, 9) for p in self.P[:2]]
        hs, c, _ = self.served(spec, jobs, max_slots=3)
        assert [h.result() for h in hs] == want
        assert (c["decode_ahead_steps"], c["decode_steps"]) == (0, 8)
        hs, c, _ = self.served(spec, jobs, draft_spec=draft_spec,
                               speculate_k=3)
        assert [h.result() for h in hs] == want
        assert c["decode_ahead_steps"] == 0 and c["spec_rounds"] > 0

    def test_warmed_programs_take_the_tokens_on_the_device(self, specs):
        from deeplearning4j_tpu.compilecache import COMPILE_STATS
        spec, dense = specs
        srv = make_server(spec, start=False, block_size=4, max_slots=2,
                          warmup=True)
        try:
            mark = COMPILE_STATS.mark()
            hs = [srv.submit(p, max_new_tokens=12) for p in self.P[:2]]
            srv.start()
            got = [h.result(timeout=120) for h in hs]
            c = dict(srv.metrics.counters)
            assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
        finally:
            srv.shutdown()
        assert got == [ref_tokens(dense, p, 12) for p in self.P[:2]]
        assert c["compiles"] == 0 and c["decode_ahead_steps"] == 10

    def test_tp2_feeds_the_mesh_its_own_tokens(self, specs):
        """Over a mesh the step launched ahead takes the next tokens as
        the warmed program lays them out; the server reads off the
        compiled programs whether it may."""
        import jax
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        spec, dense = specs
        srv = make_server(spec, start=False, block_size=4, max_slots=2,
                          tp=2, warmup=True)
        try:
            assert srv._feed_on_device
            hs = [srv.submit(p, max_new_tokens=12) for p in self.P[:2]]
            srv.start()
            got = [h.result(timeout=120) for h in hs]
            c = dict(srv.metrics.counters)
        finally:
            srv.shutdown()
        assert got == [ref_tokens(dense, p, 12) for p in self.P[:2]]
        assert c["compiles"] == 0 and c["decode_ahead_steps"] == 10


class TestTensorParallel:
    @pytest.mark.slow
    def test_tp2_bit_identical_greedy(self, spec, dense_spec):
        """gpt served with tp=2 over the virtual CPU mesh produces the
        dense single-chip reference tokens, with sharded params + KV
        slabs and ZERO traffic compiles after the sharded AOT warmup."""
        import jax
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        prompts = mixed_prompts(5, seed=9)
        with make_server(spec, tp=2, num_blocks=64, warmup=True) as srv:
            assert srv._strategy is not None
            handles = [srv.submit(p, max_new_tokens=8) for p in prompts]
            got = [h.result(timeout=120) for h in handles]
            assert srv.metrics.counters["compiles"] == 0
        assert got == [ref_tokens(dense_spec, p, 8) for p in prompts]

    def test_tp_must_divide_heads(self, spec):
        with pytest.raises(ValueError, match="num_heads"):
            make_server(spec, tp=3)            # 2 heads % 3 != 0


# ----------------------------------------------------------------------
CFG6 = GPTConfig(vocab_size=64, hidden_size=32, num_layers=6, num_heads=2,
                 intermediate_size=64, max_seq_len=32)
POOL_BUCKETS = (8, 16, 32)
POOL_PROGRAMS = ("decode", "verify") + tuple(
    f"prefill{b}" for b in POOL_BUCKETS)


@pytest.fixture(scope="module")
def warmed_programs(draft_spec):
    """Optimised HLO of every program a 6-layer server warms, by K/V
    dtype: built once a dtype, through the server's own warmup."""
    sd6 = build_gpt(CFG6, batch=2, seq_len=8, seed=1)
    cache = {}

    def get(kv):
        if kv not in cache:
            spec6 = gpt_paged_spec(sd6, CFG6, quantize_kv=(kv == "int8"))
            srv = make_server(spec6, num_blocks=33, start=False,
                              buckets=list(POOL_BUCKETS), warmup=True,
                              draft_spec=draft_spec, speculate_k=4)
            try:
                leaf = srv._kc[0]
                assert str(leaf.dtype) == kv
                hlo = {"decode": next(iter(
                           srv._decode_disp.aot.values())).as_text(),
                       "verify": next(iter(
                           srv._verify_disp.aot.values())).as_text()}
                for sig, comp in srv._prefill_disp.aot.items():
                    hlo[f"prefill{dict(sig)['tokens'][0]}"] = comp.as_text()
                cache[kv] = (hlo, int(leaf.size), len(srv._kc))
            finally:
                srv.shutdown()
        return cache[kv]

    return get


class TestPoolLeaves:
    """ISSUE 27: K and V are one array a layer. No program holds a value
    of the pool's size, a dispatch donates every leaf, crash recovery
    rebuilds every leaf, and ``tp`` splits each leaf by heads."""

    @pytest.mark.parametrize("program", POOL_PROGRAMS)
    @pytest.mark.parametrize("kv", ["float32", "int8"])
    def test_no_instruction_as_large_as_two_layers(self, warmed_programs,
                                                   kv, program):
        import re
        hlo, leaf_elems, n_leaves = warmed_programs(kv)
        assert n_leaves == CFG6.num_layers
        assert set(hlo) == set(POOL_PROGRAMS)
        largest, where = 0, None
        for m in re.finditer(r"\b[a-z]+\d*\[([\d,]+)\]", hlo[program]):
            n = int(np.prod([int(d) for d in m.group(1).split(",")]))
            if n > largest:
                largest, where = n, m.group(0)
        # a leaf itself is there (parameter, scatter, result) ...
        assert largest >= leaf_elems, (largest, where)
        # ... and nothing, parameters included, holds two of them
        assert largest < 2 * leaf_elems, (
            f"{program}/{kv}: {where} has {largest} elements, two "
            f"layers of the pool have {2 * leaf_elems}")

    def test_dispatch_donates_every_leaf(self, spec):
        import jax
        import jax.numpy as jnp
        probe = jnp.zeros(8)
        jax.jit(lambda x: x + 1, donate_argnums=0)(probe)
        if not probe.is_deleted():
            pytest.skip("this backend does not donate")
        with make_server(spec) as srv:
            old = srv._kc + srv._vc
            assert len(old) == 2 * CFG.num_layers
            srv.submit(np.arange(5, dtype=np.int32),
                       max_new_tokens=3).result(timeout=60)
            assert all(leaf.is_deleted() for leaf in old)
            new = srv._kc + srv._vc
            assert len(srv._kc) == len(srv._vc) == CFG.num_layers
            assert not any(leaf.is_deleted() for leaf in new)
            assert {leaf.shape for leaf in new} == {
                (srv.pool.capacity + 1, BS, CFG.hidden_size)}

    @pytest.mark.chaos
    def test_reset_state_rebuilds_every_leaf(self, spec, dense_spec):
        """A worker crash mid-generation: ``_reset_state`` makes every
        leaf anew (none of the crashed worker's arrays is kept) and the
        requeued requests still produce the dense reference's tokens."""
        prompts = mixed_prompts(3, seed=11)
        srv = make_server(spec, start=False,
                          resilience=ResilienceConfig(
                              worker_backoff_base_s=0.01,
                              worker_backoff_max_s=0.05))
        state = crash_decode_once(srv)
        try:
            srv.start()
            handles = [srv.submit(p, max_new_tokens=8) for p in prompts]
            got = [h.result(timeout=120) for h in handles]
            after = srv._kc + srv._vc
        finally:
            srv.shutdown()
        assert state["fired"]
        assert srv.metrics.counters["worker_restarts"] >= 1
        assert len(after) == 2 * CFG.num_layers
        crashed = {id(leaf) for leaf in state["leaves"]}
        assert not crashed & {id(leaf) for leaf in after}
        assert not any(leaf.is_deleted() for leaf in after)
        assert got == [ref_tokens(dense_spec, p, 8) for p in prompts]

    def test_tp2_shards_every_leaf_by_heads(self, spec, dense_spec):
        import jax
        from jax.sharding import PartitionSpec

        from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        prompts = mixed_prompts(2, seed=13, max_len=6)
        H = CFG.hidden_size

        def held(srv):
            leaves = srv._kc + srv._vc
            assert len(leaves) == 2 * CFG.num_layers
            for leaf in leaves:
                assert leaf.sharding.spec == PartitionSpec(
                    None, None, MODEL_AXIS)
                shards = leaf.addressable_shards
                assert len({s.device for s in shards}) == 2
                # heads outermost in the last axis: half of it is one
                # of the two heads
                assert {s.data.shape for s in shards} == {
                    (leaf.shape[0], BS, H // 2)}
                assert sorted(s.index[2].start or 0 for s in shards) \
                    == [0, H // 2]

        with make_server(spec, tp=2, num_blocks=16) as srv:
            held(srv)
            got = [srv.submit(p, max_new_tokens=4).result(timeout=120)
                   for p in prompts]
            held(srv)           # a program's results stay split the same
            assert srv.memory_report()["kv_slab_shape"] == [
                CFG.num_layers, 16, BS, H]
        assert got == [ref_tokens(dense_spec, p, 4) for p in prompts]


# ----------------------------------------------------------------------
class TestMetricsAndReports:
    def test_paged_record_cold_start_no_nans(self):
        rec = PagedMetrics(4, 16, 8).to_record()
        p = rec["paged"]
        for k, v in p.items():
            assert v == v, f"NaN in cold paged record: {k}"
        assert p["pool_occupancy"] == 0.0
        assert p["prefix_hit_rate"] == 0.0
        assert p["blocks_per_request"] == 0.0

    def test_fold_serving_exports_paged_and_low_sample(self):
        from deeplearning4j_tpu.monitor.registry import MetricsRegistry
        m = PagedMetrics(4, 16, 8)
        m.observe_pool(4, stats={"cached": 1, "evictions": 0})
        m.observe_prefix(True, 2)
        m.observe_ttft(5.0)                  # 1 sample -> low_sample
        reg = MetricsRegistry()
        reg.fold_serving(m)
        text = reg.to_prometheus_text()
        for needle in ("dl4j_serving_pool_blocks",
                       "dl4j_serving_pool_occupancy_ratio",
                       "dl4j_serving_prefix_hit_rate",
                       "dl4j_serving_blocks_per_request",
                       "dl4j_serving_pool_cached_blocks",
                       "dl4j_serving_latency_count",
                       "dl4j_serving_latency_low_sample"):
            assert needle in text, needle
        assert "nan" not in text.lower()

    def test_report_renders_paged_panel(self, spec):
        from deeplearning4j_tpu.ui.report import render_report
        from deeplearning4j_tpu.ui.stats import StatsStorage
        storage = StatsStorage()
        with make_server(spec, stats_storage=storage) as srv:
            srv.generate(np.arange(9, dtype=np.int32), max_new_tokens=4)
        html = render_report(storage)
        assert "paged KV" in html
        assert "prefix hit" in html

    @pytest.mark.slow
    def test_memory_report_block_accounting(self, spec):
        with make_server(spec, num_blocks=32) as srv:
            srv.submit(np.arange(9, dtype=np.int32),
                       max_new_tokens=2).result(timeout=60)
            rep = srv.memory_report()
        assert rep["num_blocks"] == 31
        assert rep["block_size"] == BS
        assert rep["kv_bytes_per_block"] > 0
        assert rep["blocks_free"] + rep["blocks_held"] \
            + rep["blocks_evictable"] == 31


# ----------------------------------------------------------------------
class TestSpeculativeAndQuant:
    """ISSUE 18 on the paged tier: speculation never changes greedy
    tokens (rejected tails roll back KV write positions without
    touching committed blocks — ``debug_leaks=True`` audits the pool
    invariant after every scheduler step), and int8 KV multiplies the
    block pool's token capacity at equal slab bytes."""

    def test_paged_speculation_bit_identical(self, spec, dense_spec,
                                             draft_spec):
        prompts = mixed_prompts(6, seed=31)
        budgets = [4 + i % 5 for i in range(6)]
        with make_server(spec, draft_spec=draft_spec,
                         speculate_k=4) as srv:
            hs = [srv.submit(p, n) for p, n in zip(prompts, budgets)]
            got = [h.result(timeout=120) for h in hs]
            rec = srv.metrics.to_record()["generative"]
            assert not wait_uncommitted(srv)    # every block released
        for p, n, g in zip(prompts, budgets, got):
            assert g == ref_tokens(dense_spec, p, n)
        assert rec["spec_rounds"] >= 1          # speculation actually ran

    def test_int8_kv_multiplies_pool_capacity_equal_bytes(self, gpt_sd,
                                                          dense_spec):
        budget = 1 << 20
        f32 = make_server(gpt_paged_spec(gpt_sd, CFG),
                          kv_hbm_bytes=budget)
        q = make_server(gpt_paged_spec(gpt_sd, CFG,
                                       quantize_weights=True,
                                       quantize_kv=True),
                        kv_hbm_bytes=budget)
        try:
            nf = f32.metrics.to_record()["paged"]["num_blocks"]
            nq = q.metrics.to_record()["paged"]["num_blocks"]
            assert nq >= 1.9 * nf, (nq, nf)     # the acceptance bar
            # the quantized tier still serves a full generation
            p = np.asarray([5, 9, 2], np.int32)
            got = q.submit(p, max_new_tokens=6).result(timeout=120)
            assert len(got) == 6
        finally:
            f32.shutdown()
            q.shutdown()


class TestTierRows:
    """``KVTier`` by hand (ISSUE 35): a tier whose row stands for ``c``
    tokens counts rows; a window that tumbles is given back whole at its
    multiples; a sliding tier and a one-leaf pool read as before."""

    BS = 4

    def test_a_row_tier_counts_rows_not_tokens(self):
        t = KVTier("summary", (0, 1), row_tokens=4)
        assert [t.rows(n) for n in (0, 3, 4, 7, 8, 130)] \
            == [0, 0, 1, 1, 2, 32]
        assert list(t.rows(np.array([3, 4, 9]))) == [0, 1, 2]
        # 15 tokens are 3 rows, a block; 17 are 4 rows, still one; 20 are 5
        assert [t.blocks(n, self.BS) for n in (3, 4, 15, 17, 19, 20, 256)] \
            == [0, 1, 1, 1, 1, 2, 16]
        # the table of a request of 64 blocks of tokens: 256 / 4 rows
        assert t.table_blocks(self.BS, 64) == 16
        assert t.first_live_block(1000, self.BS) == 0
        assert t.peak_blocks(130, self.BS, 16) == 8
        assert t.peak_blocks(3, self.BS, 16) == 0

    @pytest.mark.parametrize("position, first", [
        (0, 0), (31, 0), (32, 8), (33, 8), (63, 8), (64, 16), (200, 48)])
    def test_a_tumbling_window_is_live_from_its_own_multiple(self, position,
                                                             first):
        t = KVTier("exact", (0,), 32, tumbles=True)
        assert t.first_live_block(position, self.BS) == first

    def test_a_tumbling_tier_holds_one_window_at_most(self):
        t = KVTier("exact", (0,), 32, tumbles=True)
        assert t.table_blocks(self.BS, 64) == 8        # a ring of a window
        assert t.table_blocks(self.BS, 5) == 5         # or the whole table
        # a run lies in one window (the server cuts a prompt's runs at
        # the multiples), so no run adds to the window's blocks
        assert [t.peak_blocks(n, self.BS, 16) for n in (1, 20, 32, 33, 500)] \
            == [1, 5, 8, 8, 8]
        assert t.blocks(33, self.BS) == 9

    def test_a_sliding_tier_reads_as_before(self):
        t = KVTier("window", (1, 2, 3), 8)
        assert (t.row_tokens, t.tumbles) == (1, False)
        assert t.table_blocks(self.BS, 16) == 3
        assert [t.first_live_block(p, self.BS) for p in (0, 7, 8, 11, 12)] \
            == [0, 0, 0, 1, 1]
        assert t.peak_blocks(60, self.BS, 8) == 5
        assert t.blocks(9, self.BS) == blocks_for_tokens(9, self.BS) == 3
        plain = KVTier("", (0, 1))
        assert plain.table_blocks(self.BS, 16) == 16
        assert plain.peak_blocks(60, self.BS, 8) == 15

    @pytest.mark.parametrize("kw", [
        dict(row_tokens=0), dict(tumbles=True),
        dict(window=8, tumbles=True, row_tokens=4),
        dict(window=8, row_tokens=4)])
    def test_a_tier_that_cannot_be_is_refused(self, kw):
        with pytest.raises(ValueError):
            KVTier("t", (0,), **kw)

    def test_advance_at_a_windows_end_gives_back_the_whole_window(self):
        from deeplearning4j_tpu.serving.paged.server import _TierState
        ts = _TierState(KVTier("exact", (0,), 32, tumbles=True), self.BS,
                        8, 17, 1)
        ts.grow(0, 8)                       # a run that ends on position 32
        assert ts.advance(0, 31) == 0       # position window - 1: all live
        assert len(ts.blocks(0)) == 8 and ts.pool.held_count() == 8
        assert ts.advance(0, 32) == 8       # position window: all given back
        assert ts.blocks(0) == [] and ts.pool.held_count() == 0
        assert int(ts.first[0]) == int(ts.stop[0]) == 8
        ts.grow(0, 9)                       # the first block of the next one
        assert ts.advance(0, 33) == 0 and ts.blocks(0) == [ts.tables[0, 0]]
        ts.clear(0)
        ts.pool.check_invariant(tables=[])

    def test_one_leaf_and_two_leaf_pools_are_handed_over_as_before(self):
        two = PagedGenerativeServer._two_sides
        assert two((("a",),)) == (("a",), ())
        assert two((("k",), ("v",))) == (("k",), ("v",))
        assert two((("k",), ("v",), ("ks",), ("vs",))) \
            == ((("k",), ("ks",)), (("v",), ("vs",)))
