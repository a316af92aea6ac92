"""EvaByte on the paged path at a tiny size (window 32, chunk 4, block 4,
2 layers, 64 wide, 4 heads, 8 prediction heads over 40 ids), seeded: the
plain ``forward`` and the server's own programs against the plain
reference's full forward IN LOGITS, through both stores, across window
turns, in chunks, with slots reused, with 8 lanes at different windows
in one step and with the decode loop ahead of its sync; the two tiers'
books; the counters of rows by kind. At a ring large enough to split
(window 96: 24 entries, read 16 or 24 wide; ``SPLIT``): each tier is
sent the rung of its own longest lane, the logits are the whole ring's
to the bit, and warm-up builds the product of the two tiers' widths.

THE TOLERANCE (``TOL`` of the reference logits' standard deviation): the
program rounds every product's operands to bfloat16 (2**-9 relative
each) and caches exact rows and summary rows in bfloat16; the reference
computes in float32 at the highest precision. Through 2 layers that reads
0.02-0.05 of the logits' spread (the readings are beside ``TOL``).
Float8 operands, the precision below the one the configuration states,
read some twenty times that; a program that lost its summaries or whose
window slid reads a hundred times that. So ``TOL`` has two times of room
over the program and lies five times under the nearest control."""
import time

import numpy as np
import pytest

from benchmark.adapters import evabyte as adapter
from benchmark.reference import evabyte as ref
from deeplearning4j_tpu.compilecache import COMPILE_STATS
from deeplearning4j_tpu.monitor.trace import TRACER
from deeplearning4j_tpu.serving.paged import (PagedGenerativeServer,
                                              PoolExhaustedError,
                                              PrefixCacheUnsupportedError)
from deeplearning4j_tpu.zoo.evabyte import (PROGRAM_COUNTERS, EvaByteConfig,
                                            evabyte_paged_spec,
                                            evabyte_param_names, forward)

CFG = {"family": "evabyte", "attention_bias": False,
       "attention_class": "eva", "chunk_size": 4, "hidden_act": "silu",
       "hidden_size": 64, "init_std": 0.125, "intermediate_size": 160,
       "max_position_embeddings": 256, "norm_add_unit_offset": True,
       "num_attention_heads": 4, "num_hidden_layers": 2,
       "num_key_value_heads": 4, "num_pred_heads": 8, "rms_norm_eps": 1e-5,
       "rope_scaling": None, "rope_theta": 100000,
       "tie_word_embeddings": False, "vocab_size": 40, "window_size": 32,
       "param_dtype": "bfloat16", "kv_dtype": "bfloat16"}
SEED = 2**31 + 5
WINDOW, CHUNK, BS, LAYERS, VOCAB = 32, 4, 4, 2, 40
#: of the reference logits' standard deviation (about 1 at this size).
#: Readings on the CPU: forward 0.053; served 0.027-0.045; float8
#: 0.96-1.3; summaries_off 4.7; window_slides 4.5
TOL = 0.1


@pytest.fixture(scope="module")
def spec():
    return evabyte_paged_spec(adapter.program_config(CFG),
                              adapter.program_params(CFG, SEED))


def server(spec, buckets=(8, 16), slots=3, **kw):
    return PagedGenerativeServer(spec, max_slots=slots, block_size=BS,
                                 max_seq_len=256, buckets=list(buckets),
                                 warmup=False, debug_leaks=True, **kw)


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def logits_served(srv, prompts, new_tokens):
    """Serve ``prompts`` together and keep the logits every token was
    chosen from, as the server's own programs returned them."""
    seen = getattr(srv, "_kept_logits", None)
    if seen is None:
        seen = srv._kept_logits = {}
        real = srv._resolve_token

        def keep(req, device_tok, logits_row):
            seen.setdefault(req.id, []).append(np.asarray(logits_row))
            return real(req, device_tok, None)

        srv._resolve_token = keep
        srv._sampled_active = lambda: True   # decode hands the logits over
    budgets = new_tokens if isinstance(new_tokens, (list, tuple)) \
        else [new_tokens] * len(prompts)
    hs = [srv.submit(p, max_new_tokens=m) for p, m in zip(prompts, budgets)]
    srv.start()                              # where the test held it back
    toks = [h.result(timeout=600) for h in hs]
    return toks, [np.stack(seen[h.id]) for h in hs]


def reference_logits(prompts, toks, **kw):
    seqs = [np.concatenate([p, t])[:-1] for p, t in zip(prompts, toks)]
    spans = [np.arange(len(p) - 1, len(p) + len(t) - 1)
             for p, t in zip(prompts, toks)]
    return [np.asarray(w) for w in
            ref.logits(CFG, SEED, seqs, spans, heads=1, **kw)]


def drained(srv):
    while srv._n_active() or srv._ahead is not None:
        time.sleep(0.005)


def assert_close(got, want):
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert np.abs(g - w).max() < TOL * w.std(), \
            (np.abs(g - w).max(), w.std())


def test_the_config_reads_the_published_keys_and_names_every_leaf():
    pc = EvaByteConfig.from_dict(CFG)
    assert (pc.num_layers, pc.num_heads, pc.head_dim) == (2, 4, 16)
    assert (pc.chunk, pc.window, pc.pred_heads, pc.unit_offset) \
        == (4, 32, 8, True)
    exact, summary = pc.kv_tiers()
    assert (exact.name, exact.layers, exact.window, exact.tumbles,
            exact.row_tokens) == ("exact", (0, 1), 32, True, 1)
    assert (summary.name, summary.layers, summary.window, summary.tumbles,
            summary.row_tokens) == ("summary", (0, 1), None, False, 4)
    assert [(lf.name, lf.width, lf.tier) for lf in pc.kv_leaves()] == [
        ("k", 64, "exact"), ("v", 64, "exact"),
        ("k_summary", 64, "summary"), ("v_summary", 64, "summary")]
    names = evabyte_param_names(pc)
    assert len(names) == 3 + 2 * 11 and len(set(names)) == len(names)
    assert set(names) == set(adapter.program_params(CFG, 1))
    for wrong in ({"tie_word_embeddings": True}, {"attention_class": "mha"},
                  {"rope_scaling": {"type": "linear"}},
                  {"num_key_value_heads": 2}, {"window_size": 30}):
        with pytest.raises(ValueError):
            EvaByteConfig.from_dict(dict(CFG, **wrong))


@pytest.mark.parametrize("n", [29, 150])
def test_the_plain_forward_gives_every_heads_logits_of_the_reference(n):
    """All 8 x 40 logits at every position of a sequence inside the first
    window and of one over four windows with a chunk left half full."""
    import jax
    pc = adapter.program_config(CFG)
    params = adapter.program_params(CFG, SEED)
    toks = prompt(n, 11)
    got = np.asarray(jax.jit(lambda p, t: forward(pc, p, t))(params, toks))
    want = np.asarray(ref.logits(CFG, SEED, [toks])[0])
    assert got.shape == want.shape == (n, 8 * VOCAB)
    assert np.abs(got - want).max() < TOL * want.std()
    # every head is read, not the first alone
    assert np.abs(got[:, VOCAB:] - want[:, VOCAB:]).max() > 0


def test_prefill_then_decode_agrees_in_logits_with_the_reference(spec):
    """Three requests side by side, decoded across three window turns
    each: one inside its first window at the start, one whose prompt
    (45) goes through in chunks cut at the window's end and leaves a
    chunk one token full for decode to finish, one whose prompt (70) has
    turned two windows before its first decode step."""
    prompts = [prompt(5, 1), prompt(45, 2), prompt(70, 3)]
    with server(spec) as srv:
        toks, got = logits_served(srv, prompts, 3 * WINDOW + 7)
        drained(srv)
        c = dict(srv.metrics.counters)
    want = reference_logits(prompts, toks)
    assert_close(got, want)
    assert all(w.shape[0] > 3 * WINDOW for w in want)
    # 45 is runs of 16, 16 (to the window's end) and 13; 70 is 16, 16,
    # 16, 16, 6: a run never straddles a window
    assert c["prefill_runs"] == 1 + 3 + 5
    # windows given back whole: 3 by the first lane, 1 + 3 and 2 + 3
    assert c["window_turns"] == 3 + 4 + 5
    assert c["window_blocks_released"] == c["window_turns"] * WINDOW // BS
    # one summary row for every chunk a lane completed
    assert c["summary_rows_written"] == sum(
        (len(p) + len(t) - 1) // CHUNK for p, t in zip(prompts, toks))


@pytest.mark.parametrize("control", ["float8", "summaries_off",
                                     "window_slides"])
def test_a_control_is_over_the_tolerance_the_program_is_under(spec, control):
    """At the positions of a served request five windows long: float8
    operands (the file states bfloat16), no summaries (a program that
    lost the far context), a window that slides (a program that did not
    tumble)."""
    p = [prompt(70, 5)]
    with server(spec) as srv:
        toks, got = logits_served(srv, p, 3 * WINDOW)
    want = reference_logits(p, toks)[0]
    mode, variant = ref.control_of(control)
    low = reference_logits(p, toks, mode=mode, variant=variant)[0]
    assert np.abs(got[0] - want).max() < TOL * want.std()
    assert np.abs(low - want).max() > 5 * TOL * want.std()


def test_a_prompt_in_chunks_gives_the_logits_of_one_run(spec):
    """30 tokens through buckets of 8 (four runs, ``hist`` advancing, the
    last leaving a chunk half full) and through one bucket of 32: the
    same rows reach the same queries, in another order of summation."""
    p = prompt(30, 7)
    with server(spec, buckets=(8,)) as srv:
        t1, l1 = logits_served(srv, [p], 8)
        assert srv.metrics.counters["prefill_runs"] == 4
        assert srv.metrics.counters["prefills"] == 1
    with server(spec, buckets=(32,)) as srv:
        t2, l2 = logits_served(srv, [p], 8)
        assert srv.metrics.counters["prefill_runs"] == 1
    assert t1 == t2
    np.testing.assert_allclose(l1[0], l2[0], rtol=0, atol=2e-2)
    assert_close(l1, reference_logits([p], t1))


def test_a_slot_taken_again_reads_no_stale_summary_row(spec):
    """Two slots, six requests one behind the other, each past a window:
    a slot's second and third tenants read blocks of both tiers that its
    earlier tenants wrote and gave back."""
    prompts = [prompt(n, n) for n in (40, 9, 37, 66, 12, 35)]
    toks, got = [], []
    with server(spec, slots=2) as srv:
        for k in (0, 2, 4):              # the pool is two slots' worst case
            t, g = logits_served(srv, prompts[k:k + 2], WINDOW + 9)
            toks, got = toks + t, got + g
        drained(srv)
        assert srv.metrics.counters["requests_retired"] == 6
        for ts in srv._tiers:
            ts.pool.check_invariant(tables=[])
            assert ts.pool.held_count() == 0
    assert_close(got, reference_logits(prompts, toks))


def test_eight_lanes_at_different_windows_in_one_step(spec):
    """Eight lanes whose first decode steps lie in windows 0 to 3, with
    budgets that end at different steps, so that every decode step holds
    lanes in several windows and the summary tier's table is cut to the
    longest lane's blocks."""
    lens = (3, 20, 33, 50, 64, 75, 97, 120)
    prompts = [prompt(n, 100 + n) for n in lens]
    budgets = [40, 44, 36, 48, 40, 52, 36, 44]
    windows = []
    with server(spec, slots=8) as srv:
        real = srv._decode_io

        def spy(*lead):
            io = real(*lead)
            if io is not None:
                windows.append(len(set(
                    (io["positions"][io["active"]] // WINDOW).tolist())))
            return io

        srv._decode_io = spy
        toks, got = logits_served(srv, prompts, budgets)
        drained(srv)
        assert {ts.widths for ts in srv._tiers} == {(8, 8), (8, 16, 16)}
    assert max(windows) >= 4
    assert_close(got, reference_logits(prompts, toks))


def test_one_step_ahead_across_a_turn_serves_the_synchronous_loops_logits(
        spec):
    """ISSUE 35, item 4: three lanes on three slots, decoded across three
    window turns each, with the decode loop one step ahead and with every
    boundary synchronous. The step launched ahead is HANDED THE TURNED
    RING: its books give the whole window back and take the fresh block
    from positions alone while the step before it, which still reads the
    window (and writes the summary row the next step reads), is unread;
    the device runs them in the order launched. Tokens, logits, the rows
    the program counted and the blocks given back are the same to the
    bit; the pool invariant is checked at every step."""
    prompts = [prompt(5, 1), prompt(45, 2), prompt(30, 3)]
    n = 3 * WINDOW + 6
    runs = []
    for ahead in (True, False):
        with server(spec, start=False) as srv:
            if not ahead:
                srv._may_run_ahead = lambda: False
            toks, lg = logits_served(srv, prompts, n)
            drained(srv)
            c = dict(srv.metrics.counters)
            for ts in srv._tiers:
                assert ts.pool.held_count() == 0 and not ts.stop.any()
        assert c["decode_steps"] == n - 1
        # every lane has the same budget: steps 2 to n - 1 run ahead
        assert c["decode_ahead_steps"] == (n - 2 if ahead else 0)
        assert c["window_turns"] == 3 + 4 + 4
        runs.append((toks, lg, {k: v for k, v in c.items()
                                if k.startswith(("kv_", "window_", "blocks_",
                                                 "summary_",
                                                 "decode_table"))}))
    (t1, l1, c1), (t2, l2, c2) = runs
    assert t1 == t2 and c1 == c2
    for a, b in zip(l1, l2):
        assert np.array_equal(a, b)


def test_both_tiers_stay_in_their_bounds_and_end_empty(spec):
    with server(spec) as srv:
        exact, summary = srv._tiers
        assert (exact.entries, summary.entries) == (WINDOW // BS, 16)
        assert exact.widths == (8, 8) and summary.widths == (8, 16, 16)
        held = []
        sample = srv._sample_pool

        def watch():
            sample()
            held.append((max(len(exact.blocks(s)) for s in range(3)),
                         max(len(summary.blocks(s)) for s in range(3))))

        srv._sample_pool = watch
        hs = [srv.submit(prompt(n, n), max_new_tokens=60)
              for n in (70, 3, 25)]
        hs[1].result(timeout=600)
        hs.append(srv.submit(prompt(9, 9), max_new_tokens=40))
        for h in hs:
            h.result(timeout=600)
        drained(srv)
        # a request holds at most one window of exact rows and a row a
        # chunk of summaries: 130 tokens are 32 rows, 8 blocks
        assert max(h[0] for h in held) == WINDOW // BS
        assert max(h[1] for h in held) == (70 + 60) // CHUNK // BS
        c = srv.metrics.counters
        assert c["window_blocks_held_sum"] <= c["window_blocks_capacity_sum"]
        assert c["blocks_allocated"] == c["blocks_released"]
        for ts in srv._tiers:
            ts.pool.check_invariant(tables=[])
            assert ts.pool.held_count() == 0
            assert not ts.tables.any() and not ts.stop.any()
        assert srv._committed == 0
        # 130 tokens: a window of exact rows, 32 summary rows
        assert srv._worst_case_blocks(70, 60) == [8, 8]
        assert srv._worst_case_blocks(10, 10) == [5, 2]
        assert spec.program_counters == PROGRAM_COUNTERS


def test_the_counters_of_rows_by_kind_follow_the_positions(spec):
    """One lane, one request: at the decode step of position ``p`` the
    lane holds ``p + 1 - 32 (p // 32)`` exact rows and ``(p + 1) // 4``
    summary rows a layer, its query attends to the exact rows and to the
    ``8 (p // 32)`` summaries of earlier windows, and the program is
    handed the ring and a rung of the summary table for the lane."""
    n, m = 45, 60
    TRACER.reset().enable()
    try:
        with server(spec, slots=1) as srv:
            logits_served(srv, [prompt(n, 4)], m)
            drained(srv)
            c = dict(srv.metrics.counters)
            widths = srv._tiers[1].widths
        spans = [s for s in TRACER.spans() if s.name == "serving.decode"]
    finally:
        TRACER.disable().reset()
    ps = np.arange(n, n + m - 1)                 # the decode steps' queries
    exact = ps + 1 - WINDOW * (ps // WINDOW)
    assert c["kv_positions_sum"] == LAYERS * int((ps + 1).sum())
    assert c["kv_rows_held_sum"] == LAYERS * int(
        (exact + (ps + 1) // CHUNK).sum())
    assert c["kv_rows_attended_sum"] == LAYERS * int(
        (exact + (WINDOW // CHUNK) * (ps // WINDOW)).sum())
    rung = np.searchsorted(widths, -(-((ps + 1) // CHUNK) // BS))
    assert c["kv_rows_gathered_sum"] == LAYERS * BS * int(
        (WINDOW // BS + np.asarray(widths)[rung]).sum())
    assert 0 < c["kv_rows_attended_sum"] < c["kv_rows_gathered_sum"]
    # the span of a step at whose boundary the window turned says so
    turned = [s for s in spans if s.args.get("turns")]
    assert len(turned) == sum(1 for p in ps if p % WINDOW == 0) == 2
    assert all(s.args["turns"] == 1 for s in turned)
    assert len(spans) == m - 1


def test_the_report_gives_four_leaves_on_two_tiers(spec):
    with server(spec, start=False) as srv:
        rep = srv.memory_report()
    assert rep["kv_leaves"] == {"k": 64, "v": 64, "k_summary": 64,
                                "v_summary": 64}
    # two layers of K and V rows a token, and a sixteenth... a quarter of
    # that at a summary row every 4 tokens, in bfloat16
    assert rep["kv_bytes_per_token"] == 2 * 128 * 2 + 2 * 128 * 2 // 4
    assert rep["kv_bytes_per_token_filled"] == rep["kv_bytes_per_token"]
    tiers = rep["kv_tiers"]
    assert tiers["exact"]["leaves"] == ["k", "v"] \
        and tiers["summary"]["leaves"] == ["k_summary", "v_summary"]
    assert (tiers["exact"]["tumbles"], tiers["exact"]["window"],
            tiers["exact"]["table_entries"]) == (True, 32, 8)
    assert (tiers["summary"]["row_tokens"], tiers["summary"]["window"],
            tiers["summary"]["table_entries"]) == (4, None, 16)
    # every slot at its worst: a window of exact rows, 64 summary rows
    assert tiers["exact"]["num_blocks"] == 3 * 8
    assert tiers["summary"]["num_blocks"] == 3 * 16
    assert rep["kv_bytes_per_block"] == 2 * (2 * BS * 128 * 2)


def test_the_spec_refuses_the_prefix_cache_and_sheds_typed(spec):
    with pytest.raises(PrefixCacheUnsupportedError):
        server(spec, prefix_cache=True)
    srv = server(spec, start=False)
    try:
        assert srv.prefix_cache_enabled is False
        # 200 tokens hold 8 exact and 13 summary blocks at most
        for _ in range(3):
            srv.submit(prompt(100), max_new_tokens=100)
        assert srv._reserved == [24, 39]
        with pytest.raises(PoolExhaustedError, match="24 of 24"):
            srv.submit(prompt(8), max_new_tokens=8)
    finally:
        srv.shutdown(drain=False)


@pytest.mark.parametrize("bad", [dict(block_size=5), dict(buckets=[6]),
                                 dict(buckets=[8, 30])])
def test_a_geometry_the_two_stores_cannot_hold_is_refused(spec, bad):
    """A window is whole blocks, and a run starts on a chunk."""
    kw = dict(max_slots=2, block_size=BS, max_seq_len=256, buckets=[8, 16],
              warmup=False, start=False)
    kw.update(bad)
    with pytest.raises(ValueError):
        PagedGenerativeServer(spec, **kw)


# -- a ring large enough to split: each tier on a rung of its own ---------

#: window 96 in blocks of 4 is a ring of 24 entries, half and whole (16,
#: 24); 512 positions are 128 summary rows, 32 entries (16, 24, 32)
SPLIT = dict(CFG, window_size=96, max_position_embeddings=512)
RING, EXACT_W, SUMMARY_W = 96, (16, 24), (16, 24, 32)


@pytest.fixture(scope="module")
def split_spec():
    return evabyte_paged_spec(adapter.program_config(SPLIT),
                              adapter.program_params(SPLIT, SEED))


def split_server(spec, **kw):
    kw.setdefault("warmup", False)
    kw.setdefault("max_slots", 3)
    return PagedGenerativeServer(spec, block_size=BS, max_seq_len=512,
                                 buckets=[16, 32], debug_leaks=True, **kw)


def narrowest(widths, need):
    return min(w for w in widths if w >= need)


def spy_widths(srv, steps):
    """Every decode step's ``(exact width, summary width, positions of
    the active lanes, lead, lanes that turned)`` into ``steps``."""
    real = srv._decode_io

    def spy(*lead):
        io = real(*lead)
        if io is not None:
            steps.append((io["tables.exact"].shape[1],
                          io["tables.summary"].shape[1],
                          io["positions"][io["active"]].tolist(),
                          sum(lead), srv._turns))
        return io

    srv._decode_io = spy


def test_each_tier_is_sent_the_rung_of_its_own_longest_lane(split_spec):
    """By hand: a lane whose query stands at ``p`` holds ``(p % 96) // 4
    + 1`` blocks of its window (the first entries of the ring, which
    refills from entry 0 at every turn) and ``ceil(((p + 1) // 4) / 4)``
    summary blocks. Three lanes in different windows, one of them 300
    bytes in so that the summary tier stands on its upper rungs while the
    ring is on its lowest; two more admitted once the first retire."""
    jobs = [(5, 120), (130, 60), (300, 110), (7, 30), (40, 50)]
    steps = []
    with split_server(split_spec) as srv:
        exact, summary = srv._tiers
        assert exact.on_ladder and summary.on_ladder
        assert (exact.entries, exact.widths) == (24, EXACT_W)
        assert (summary.entries, summary.widths) == (32, SUMMARY_W)
        spy_widths(srv, steps)
        # the pool is three slots' worst case: the late two are offered
        # once an early one has given its reservation back
        hs = [srv.submit(prompt(n, n), max_new_tokens=m)
              for n, m in jobs[:3]]
        for (n, m), done in zip(jobs[3:], (hs[1], hs[2])):
            done.result(timeout=600)
            hs.append(srv.submit(prompt(n, n), max_new_tokens=m))
        for h in hs:
            h.result(timeout=600)
        drained(srv)
        c = dict(srv.metrics.counters)
    assert len(steps) == c["decode_steps"] > 150
    for we, ws, ps, _, _ in steps:
        assert we == narrowest(EXACT_W, max((p % RING) // BS + 1
                                            for p in ps))
        assert ws == narrowest(SUMMARY_W, max(
            -(-((p + 1) // CHUNK) // BS) for p in ps))
    # both tiers stood on every rung, and not on the same one: the ring
    # fell back to its half at each turn while the summaries stayed wide
    assert {we for we, *_ in steps} == set(EXACT_W)
    assert {ws for _, ws, *_ in steps} == set(SUMMARY_W)
    assert (16, 32) in {(we, ws) for we, ws, *_ in steps}
    sent = [we for we, *_ in steps]
    assert any(b < a for a, b in zip(sent, sent[1:]))
    # the counters sum over BOTH tiers
    assert c["decode_table_entries_sum"] == sum(we + ws
                                                for we, ws, *_ in steps)
    assert c["decode_table_capacity_sum"] == (24 + 32) * len(steps)
    assert c["kv_rows_gathered_sum"] == LAYERS * BS * sum(
        (we + ws) * len(ps) for we, ws, ps, _, _ in steps)
    assert 0 < c["kv_rows_attended_sum"] < c["kv_rows_gathered_sum"]


def test_the_ladders_logits_are_the_whole_rings_to_the_bit(split_spec):
    """Three lanes in different windows across three turns each, the
    loop one step ahead from the second step on, so that every rung
    crossing and every turn is built with the step before in the air:
    the server that sends each tier its own rung against the same server
    with the ring sent whole at every step. A narrower table leaves out
    entries whose rows no query sees (their weights are exact zeros), so
    tokens, logits and the rows the program counted are equal to the
    bit; against the reference they lie under the tolerance."""
    # windows 0, 1 and 2, within 9 positions of one another in them, so
    # that the ring's lower rung is reached after every round of turns
    prompts = [prompt(5, 1), prompt(110, 2), prompt(205, 3)]
    n = 3 * RING + 6
    runs = []
    for ladder in (True, False):
        steps = []
        with split_server(split_spec, start=False) as srv:
            if not ladder:
                srv._tiers[0].widths = (24,) * 3
            spy_widths(srv, steps)
            toks, lg = logits_served(srv, prompts, n)
            drained(srv)
            c = dict(srv.metrics.counters)
        assert c["decode_steps"] == n - 1
        assert c["decode_ahead_steps"] == n - 2
        # three turns a lane at decode; one and two in the prefills
        assert c["window_turns"] == 3 + 4 + 5
        runs.append((toks, lg, c, steps))
    (t1, l1, c1, s1), (t2, l2, c2, s2) = runs
    assert {we for we, *_ in s2} == {24}
    assert {we for we, *_ in s1} == set(EXACT_W)
    # with the step before in the air: a lane crossed to a wider rung,
    # and a lane's turn let the ring fall to a narrower one
    ahead = [(a[0], b[0], b[4]) for a, b in zip(s1, s1[1:]) if b[3] == 1]
    assert any(wide > narrow for narrow, wide, _ in ahead)
    assert any(after < before and turned for before, after, turned in ahead)
    assert t1 == t2
    for a, b in zip(l1, l2):
        assert np.array_equal(a, b)
    assert c1["kv_rows_attended_sum"] == c2["kv_rows_attended_sum"]
    assert c1["kv_rows_held_sum"] == c2["kv_rows_held_sum"]
    assert c1["kv_rows_gathered_sum"] < c2["kv_rows_gathered_sum"]
    seqs = [np.concatenate([p, t])[:-1] for p, t in zip(prompts, t1)]
    spans = [np.arange(len(p) - 1, len(p) + len(t) - 1)
             for p, t in zip(prompts, t1)]
    want = [np.asarray(w) for w in ref.logits(SPLIT, SEED, seqs, spans,
                                              heads=1)]
    assert_close(l1, want)


def test_warmup_builds_the_product_of_the_tiers_widths_and_no_step_compiles(
        split_spec):
    with split_server(split_spec, warmup=True) as srv:
        rep = srv.warmup_report
        assert rep["decode_table_widths"] == {"exact": list(EXACT_W),
                                              "summary": list(SUMMARY_W)}
        # two by three decode programs and one prefill program a bucket
        assert len(srv._decode_disp.aot) == 6
        assert srv.metrics.counters["warmup_compiles"] == \
            6 + len(rep["prefill_buckets"])
        steps = []
        spy_widths(srv, steps)
        mark = COMPILE_STATS.mark()
        hs = [srv.submit(prompt(n, n), max_new_tokens=m)
              for n, m in [(5, 100), (300, 110), (60, 30)]]
        for h in hs:
            h.result(timeout=600)
        drained(srv)
        assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
        assert srv.metrics.counters["compiles"] == 0
    assert len({(we, ws) for we, ws, *_ in steps}) >= 4


def test_the_span_names_each_tiers_width_beside_the_sum(split_spec):
    TRACER.reset().enable()
    try:
        with split_server(split_spec, max_slots=1) as srv:
            srv.submit(prompt(60, 6), max_new_tokens=50).result(timeout=600)
            drained(srv)
        spans = [s for s in TRACER.spans() if s.name == "serving.decode"]
    finally:
        TRACER.disable().reset()
    assert len(spans) == 49
    for sp, p in zip(spans, range(60, 109)):
        assert sp.args["table_entries.exact"] == narrowest(
            EXACT_W, (p % RING) // BS + 1)
        assert sp.args["table_entries.summary"] == 16
        assert sp.args["table_entries"] == \
            sp.args["table_entries.exact"] + 16
    # 16 wide to position 63, 24 wide to the turn at 96, then 16 again
    assert [sp.args["table_entries.exact"] for sp in spans] == \
        [16] * 4 + [24] * 32 + [16] * 13


def split_program_args(spec, program, exact, summary):
    """What a program of the split geometry is traced on, as shapes:
    three lanes or one run of 16, the tables ``exact`` and ``summary``
    entries wide."""
    import jax
    import jax.numpy as jnp
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if program == "decode":
        io = {"tokens": i32(3), "positions": i32(3), "write_off": i32(3),
              "active": jax.ShapeDtypeStruct((3,), jnp.bool_),
              "tables.exact": i32(3, exact), "write_block.exact": i32(3),
              "tables.summary": i32(3, summary),
              "write_block.summary": i32(3)}
    else:
        io = {"tokens": i32(16), "length": i32(), "hist": i32(),
              "table.exact": i32(exact), "write_block.exact": i32(16),
              "table.summary": i32(summary), "write_block.summary": i32(4)}
    params = {n: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
              for n, a in spec.params().items()}
    leaf = jax.ShapeDtypeStruct((9, BS, 64), jnp.bfloat16)
    side = ((leaf,) * LAYERS,) * 2
    return params, side, side, io


@pytest.mark.parametrize("program, exact, summary, ok", [
    ("decode", 24, 32, True), ("decode", 16, 16, True),
    ("decode", 16, 32, True), ("decode", 25, 32, False),
    ("decode", 24, 33, False), ("prefill", 24, 32, True),
    ("prefill", 16, 32, False), ("prefill", 24, 24, False),
    ("prefill", 25, 32, False)])
def test_the_program_takes_the_first_entries_of_a_ring_at_decode_alone(
        split_spec, program, exact, summary, ok):
    """A decode table of either tier may be narrower than the tier's and
    never wider; a prefill run's tables come whole."""
    import jax
    fn = dict(zip(("prefill", "decode"), split_spec.make_fns(BS, 128)))[
        program]
    args = split_program_args(split_spec, program, exact, summary)
    if ok:
        out = jax.eval_shape(fn, *args)
        assert out[3].shape[-1] == VOCAB
    else:
        with pytest.raises(ValueError, match="entries, the tier's table"):
            jax.eval_shape(fn, *args)
