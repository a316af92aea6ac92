"""SmallThinker on the paged path at a tiny size (window 8, block 4, 4
layers, 8 experts top-2, a 61-row vocabulary), seeded: the server's own
programs against the plain reference's full forward, a prompt in chunks
against the same prompt in one run, the two KV tiers' books, and that a
spec with one tier is served as it always was."""
import time

import numpy as np
import pytest

from benchmark.adapters import smallthinker as adapter
from benchmark.reference import smallthinker as ref
from deeplearning4j_tpu.serving.paged import (KVTier, PagedGenerativeServer,
                                              PoolExhaustedError,
                                              PrefixCacheUnsupportedError)
from deeplearning4j_tpu.zoo.smallthinker import (PROGRAM_COUNTERS,
                                                 SmallThinkerConfig,
                                                 smallthinker_paged_spec,
                                                 smallthinker_param_names)

CFG = {"head_dim": 8, "hidden_size": 32, "max_position_embeddings": 128,
       "moe_ffn_hidden_size": 16, "moe_num_active_primary_experts": 2,
       "moe_num_primary_experts": 8,
       "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
       "num_attention_heads": 4, "num_hidden_layers": 4,
       "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
       "rope_layout": [0, 1, 1, 1] * 2, "rope_scaling": None,
       "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 2,
       "sliding_window_size": 8, "tie_word_embeddings": False,
       "vocab_size": 61, "param_dtype": "bfloat16", "kv_dtype": "bfloat16"}
SEED = 2**31 + 5
WINDOW, BS = 8, 4


@pytest.fixture(scope="module")
def spec():
    return smallthinker_paged_spec(adapter.program_config(CFG),
                                   adapter.program_params(CFG, SEED))


def server(spec, buckets=(4, 8), **kw):
    return PagedGenerativeServer(spec, max_slots=3, block_size=BS,
                                 max_seq_len=64, buckets=list(buckets),
                                 warmup=False, debug_leaks=True, **kw)


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], n).astype(np.int32)


def logits_served(srv, prompts, new_tokens):
    """Serve ``prompts`` together and keep the logits every token was
    chosen from, as the server's own programs returned them."""
    seen = {}
    real = srv._resolve_token

    def keep(req, device_tok, logits_row):
        seen.setdefault(req.id, []).append(np.asarray(logits_row))
        return real(req, device_tok, None)

    srv._resolve_token = keep
    srv._sampled_active = lambda: True       # decode hands the logits over
    hs = [srv.submit(p, max_new_tokens=new_tokens) for p in prompts]
    srv.start()                              # where the test held it back
    toks = [h.result(timeout=300) for h in hs]
    return toks, [np.stack(seen[h.id]) for h in hs]


def test_the_config_reads_the_published_keys_and_names_every_leaf():
    pc = SmallThinkerConfig.from_dict(CFG)
    assert (pc.num_layers, pc.num_heads, pc.num_kv_heads) == (4, 4, 2)
    assert pc.window_layout == (0, 1, 1, 1) and pc.rope_layout == (0, 1, 1, 1)
    glob, win = pc.kv_tiers()
    assert (glob.name, glob.layers, glob.window) == ("global", (0,), None)
    assert (win.name, win.layers, win.window) == ("window", (1, 2, 3), 8)
    names = smallthinker_param_names(pc)
    assert len(names) == 3 + 4 * 10 and len(set(names)) == len(names)
    assert set(names) == set(adapter.program_params(CFG, 1))
    with pytest.raises(ValueError):
        SmallThinkerConfig.from_dict(dict(CFG, tie_word_embeddings=True))


def test_prefill_then_decode_agrees_in_logits_with_the_reference(spec):
    """Three requests side by side, two of them prompts in chunks, each
    decoded to more than three windows: the logits every served token was
    chosen from against the reference's full forward over the same
    tokens.

    The tolerance: the program rounds every product's operands to
    bfloat16 (2**-9 relative each) and caches K and V in bfloat16, the
    reference computes in float32. Through 4 layers that is some
    hundredths of the logits' spread, so 0.05 of their standard
    deviation; float8 operands read some ten times that and fail it."""
    prompts = [prompt(5, 1), prompt(21, 2), prompt(30, 3)]
    with server(spec) as srv:
        toks, got = logits_served(srv, prompts, 3 * WINDOW + 4)
    seqs = [np.concatenate([p, t])[:-1] for p, t in zip(prompts, toks)]
    spans = [np.arange(len(p) - 1, len(p) + len(t) - 1)
             for p, t in zip(prompts, toks)]
    want = ref.logits(CFG, SEED, seqs, spans)
    low = ref.logits(CFG, SEED, seqs, spans, mode="float8")
    for g, w, lo in zip(got, want, low):
        w = np.asarray(w)
        tol = 0.05 * w.std()
        assert g.shape == w.shape and w.shape[0] > 3 * WINDOW
        assert np.abs(g - w).max() < tol
        assert np.abs(np.asarray(lo) - w).max() > tol


def test_a_prompt_in_chunks_gives_the_logits_of_one_run(spec):
    """30 tokens through buckets of 4 and 8 (four runs, ``hist``
    advancing) and through one bucket of 32: the same K and V reach the
    same queries, in another order of summation only."""
    p = prompt(30, 7)
    with server(spec, buckets=(4, 8)) as srv:
        t1, l1 = logits_served(srv, [p], 6)
        assert srv.metrics.counters["prefill_runs"] == 4
        assert srv.metrics.counters["prefills"] == 1
    with server(spec, buckets=(32,)) as srv:
        t2, l2 = logits_served(srv, [p], 6)
        assert srv.metrics.counters["prefill_runs"] == 1
    assert t1 == t2
    np.testing.assert_allclose(l1[0], l2[0], rtol=0, atol=1e-5)


def test_the_window_tier_stays_in_its_bound_and_both_tiers_end_empty(spec):
    with server(spec) as srv:
        glob, win = srv._tiers
        assert (glob.entries, win.entries) == (16, WINDOW // BS + 1)
        held = []
        sample = srv._sample_pool

        def watch():
            sample()
            held.append(max(len(win.blocks(s)) for s in range(3)))

        srv._sample_pool = watch
        # the pool is sized for three requests at their worst: a fourth
        # goes in once one of them is done
        hs = [srv.submit(prompt(n, n), max_new_tokens=30)
              for n in (30, 3, 25)]
        hs[1].result(timeout=300)
        hs.append(srv.submit(prompt(9, 9), max_new_tokens=30))
        for h in hs:
            h.result(timeout=300)
        while srv._n_active():
            time.sleep(0.005)
        # at a decode step a request holds what one window can touch
        assert max(held) == win.entries
        c = srv.metrics.counters
        assert c["window_blocks_released"] > 0
        assert c["window_blocks_held_sum"] <= c["window_blocks_capacity_sum"]
        assert c["blocks_allocated"] == c["blocks_released"]
        for ts in srv._tiers:
            assert ts.pool.held_count() == 0
            assert not ts.tables.any() and not ts.stop.any()
        assert srv._committed == 0
        # 60 tokens hold 15 global blocks and, with a run of 8, at most
        # (8 + 8) / 4 + 1 window blocks
        assert srv._worst_case_blocks(30, 30) == [15, 5]
        assert spec.program_counters == PROGRAM_COUNTERS
        assert c["moe_layer_steps"] == 4 * c["decode_steps"]
        assert 0 < c["moe_experts_touched_sum"] <= c["moe_tokens_routed_sum"]
        assert c["moe_tokens_routed_sum"] == 2 * 4 * c["slots_active_sum"]
        # the fullest expert of a layer holds a token, at most a lane's
        assert c["moe_layer_steps"] <= c["moe_peak_expert_tokens_sum"] \
            <= 4 * c["slots_active_sum"]


def test_one_step_ahead_serves_what_the_synchronous_loop_serves(spec):
    """ISSUE 33: three lanes on three slots from the first step, decoded
    past three windows, with the decode loop one step ahead and with
    every boundary held synchronous (the loop of before, over the same
    programs and lanes). The step launched ahead advances the window
    tier's ring, gives back the block behind the window and takes its
    fresh block from positions alone, while the step before it, which
    still reads that block, is unread; its tokens are the packed
    array's first entries, cut on the device. Tokens, logits, the
    program's own counts and the blocks given back are the same, to the
    bit; the pool invariant is checked at every step (``debug_leaks``)."""
    prompts = [prompt(5, 1), prompt(21, 2), prompt(9, 3)]
    n = 3 * WINDOW + 4
    runs = []
    for ahead in (True, False):
        with server(spec, start=False) as srv:
            if not ahead:
                srv._may_run_ahead = lambda: False
            toks, lg = logits_served(srv, prompts, n)
            while srv._n_active() or srv._ahead is not None:
                time.sleep(0.005)
            c = dict(srv.metrics.counters)
            for ts in srv._tiers:
                assert ts.pool.held_count() == 0 and not ts.stop.any()
        assert c["decode_steps"] == n - 1
        # every lane has the same budget: steps 2 to n - 1 run ahead
        assert c["decode_ahead_steps"] == (n - 2 if ahead else 0)
        assert c["window_blocks_released"] > 0
        assert c["blocks_allocated"] == c["blocks_released"]
        runs.append((toks, lg, {k: v for k, v in c.items()
                                if k.startswith(("moe_", "window_",
                                                 "blocks_", "decode_table"))}))
    (t1, l1, c1), (t2, l2, c2) = runs
    assert t1 == t2 and c1 == c2
    for a, b in zip(l1, l2):
        assert np.array_equal(a, b)


def test_a_window_spec_refuses_the_prefix_cache_typed(spec):
    with pytest.raises(PrefixCacheUnsupportedError):
        server(spec, prefix_cache=True)
    with server(spec) as srv:
        assert srv.prefix_cache_enabled is False


def test_a_request_over_the_window_tiers_pool_is_shed_typed(spec):
    """16 tokens hold 4 blocks of either tier. Three slots' worst case
    is 48 global blocks and 15 window blocks, so the fourth request to
    wait (nothing runs: reservations stand) fits the global tier and is
    shed by the window tier."""
    srv = server(spec, start=False)
    try:
        for _ in range(3):
            srv.submit(prompt(8), max_new_tokens=8)
        assert srv._reserved == [12, 12]
        with pytest.raises(PoolExhaustedError, match="12 of 15"):
            srv.submit(prompt(8), max_new_tokens=8)
        with pytest.raises(ValueError, match="one tier"):
            server(spec, num_blocks=64, start=False)
    finally:
        srv.shutdown(drain=False)


def test_the_global_tiers_table_is_cut_to_the_lanes_and_the_ring_is_not(
        spec, monkeypatch):
    """The global tier's 16 entries come 8 or 16 wide (``table_widths``),
    the window tier's ring of 3 whole at every step; the logits are
    those of a server that reads every table whole, in another order of
    summation only."""
    from deeplearning4j_tpu.serving.paged import server as paged_server
    from deeplearning4j_tpu.serving.paged.pool import TABLE_RUNGS
    prompts, late = [prompt(5, 1), prompt(21, 2), prompt(9, 3)], [prompt(6, 4)]
    shapes = []
    with server(spec) as srv:
        glob, win = srv._tiers
        assert glob.widths == (8, 16, 16) and win.widths == (3, 3, 3)
        real = srv._decode_io

        def spy(*lead):
            io = real(*lead)
            if io is not None:
                shapes.append((io["tables.global"].shape[1],
                               io["tables.window"].shape[1],
                               int(glob.stop[io["active"]].max())))
            return io

        srv._decode_io = spy
        t1, l1 = logits_served(srv, prompts, 3 * WINDOW + 4)
        t1 += logits_served(srv, late, 4)[0]
        c = dict(srv.metrics.counters)
    assert {w for _, w, _ in shapes} == {3}
    assert {g for g, _, _ in shapes} == {8, 16}
    assert all(g == (8 if held <= 8 else 16) for g, _, held in shapes)
    # the width falls back once the long lanes have retired
    sent = [g for g, _, _ in shapes]
    assert any(b < a for a, b in zip(sent, sent[1:]))
    assert c["decode_table_entries_sum"] == sum(sent)
    assert c["decode_table_capacity_sum"] == 16 * len(sent)
    monkeypatch.setattr(paged_server, "table_widths",
                        lambda entries: (int(entries),) * TABLE_RUNGS)
    with server(spec) as srv:
        assert srv._tiers[0].widths == (16, 16, 16)
        t2, l2 = logits_served(srv, prompts, 3 * WINDOW + 4)
        t2 += logits_served(srv, late, 4)[0]
        c = srv.metrics.counters
        assert c["decode_table_entries_sum"] == \
            c["decode_table_capacity_sum"] == 16 * c["decode_steps"]
    assert t1 == t2
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)


@pytest.mark.parametrize("glob, win, ok", [
    (16, 3, True), (8, 3, True), (17, 3, False), (16, 2, False),
    (8, 4, False)])
def test_the_program_takes_a_cut_global_table_and_only_a_whole_ring(
        spec, glob, win, ok):
    import jax
    import jax.numpy as jnp
    _, decode_fn = spec.make_fns(BS, 16)
    S = 3
    lane = jax.ShapeDtypeStruct((S,), jnp.int32)
    io = {"tokens": lane, "positions": lane, "write_off": lane,
          "active": jax.ShapeDtypeStruct((S,), jnp.bool_),
          "tables.global": jax.ShapeDtypeStruct((S, glob), jnp.int32),
          "tables.window": jax.ShapeDtypeStruct((S, win), jnp.int32),
          "write_block.global": lane, "write_block.window": lane}
    params = {n: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
              for n, a in spec.params().items()}
    side = tuple(jax.ShapeDtypeStruct((9, BS, 2 * 8), jnp.bfloat16)
                 for _ in range(4))
    if ok:
        out = jax.eval_shape(decode_fn, params, side, side, io)
        assert out[3].shape == (S, CFG["vocab_size"])
    else:
        with pytest.raises(ValueError, match="entries"):
            jax.eval_shape(decode_fn, params, side, side, io)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_both_programs_call_the_one_block_a_kind_of_layer(spec, program):
    """One layer function for both programs, jitted on its own: a
    program's trace holds it once a kind of layer (global, window) and
    calls it ``num_layers`` times."""
    import jax
    import jax.numpy as jnp
    prefill_fn, decode_fn = spec.make_fns(BS, 16)
    S = 3
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if program == "decode":
        fn, io = decode_fn, {
            "tokens": i32(S), "positions": i32(S), "write_off": i32(S),
            "active": jax.ShapeDtypeStruct((S,), jnp.bool_),
            "tables.global": i32(S, 8), "tables.window": i32(S, 3),
            "write_block.global": i32(S), "write_block.window": i32(S)}
    else:
        fn, io = prefill_fn, {
            "tokens": i32(8), "length": i32(), "hist": i32(),
            "table.global": i32(16), "table.window": i32(3),
            "write_block.global": i32(8), "write_block.window": i32(8)}
    params = {n: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
              for n, a in spec.params().items()}
    side = tuple(jax.ShapeDtypeStruct((9, BS, 2 * 8), jnp.bfloat16)
                 for _ in range(4))
    jaxpr = jax.make_jaxpr(fn)(params, side, side, io)
    blocks = [e for e in jaxpr.eqns if e.params.get("name") == "_block"]
    assert len(blocks) == 4
    pc = SmallThinkerConfig.from_dict(CFG)
    kinds = {(bool(r), bool(w))
             for r, w in zip(pc.rope_layout, pc.window_layout)}
    assert len({id(e.params["jaxpr"]) for e in blocks}) == len(kinds)


def test_tier_arithmetic():
    t = KVTier("window", (1,), 4096)
    assert t.table_blocks(16, 512) == 257 and t.key("tables") == \
        "tables.window"
    assert t.first_live_block(4095, 16) == 0
    assert t.first_live_block(4096 + 15, 16) == 1
    assert t.peak_blocks(8192, 16, 512) == (4096 + 512) // 16 + 1
    assert t.peak_blocks(100, 16, 512) == 7
    g = KVTier("", (0,))
    assert g.table_blocks(16, 512) == 512 and g.key("tables") == "tables"
    assert g.first_live_block(10**6, 16) == 0


# ----------------------------------------------------------------------
# one tier: GPT-2's path is what it was
def test_a_one_tier_specs_tables_and_programs_are_what_they_were():
    from deeplearning4j_tpu.serving.generative import greedy_decode
    from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                            gpt_generative_spec,
                                            gpt_paged_spec)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, intermediate_size=64, max_seq_len=32)
    sd = build_gpt(cfg, batch=2, seq_len=8, seed=0)
    spec = gpt_paged_spec(sd, cfg)
    assert spec.kv_tiers is None
    ios = {"serving.prefill": [], "serving.decode": []}
    with PagedGenerativeServer(spec, max_slots=2, max_seq_len=32,
                               block_size=8, warmup=True,
                               debug_leaks=True) as srv:
        (tier,) = srv._tiers
        assert tier.tier.name == "" and tier.tier.window is None
        assert srv._tables is tier.tables and srv._nblocks is tier.stop
        assert srv.pool is tier.pool and srv.prefix_cache_enabled
        # only a spec that names program counters has them registered
        assert spec.program_counters == () and not any(
            c.startswith("moe_") for c in srv.metrics.counters)
        real = srv._launch

        def spy(disp, io, span, *draft):
            ios[span].append({k: np.shape(v) for k, v in io.items()})
            return real(disp, io, span, *draft)

        srv._launch = spy
        p = prompt(11, 4) % 64
        got = srv.submit(p, max_new_tokens=5).result(timeout=120)
        # nothing compiled under traffic: the warmed programs are the
        # ones the io asks for
        assert srv.metrics.counters["compiles"] == 0
    assert ios["serving.prefill"] == [
        {"tokens": (16,), "length": (), "hist": (), "table": (4,)}]
    assert ios["serving.decode"][0] == {
        "tokens": (2,), "positions": (2,), "active": (2,),
        "tables": (2, 4), "write_block": (2,), "write_off": (2,)}
    assert got == list(greedy_decode(gpt_generative_spec(sd, cfg), p, 5))
