"""The decode program's table comes in a ladder of widths
(``serving.paged.table_widths``), chosen at every step boundary from
what the active lanes hold.

On the CPU at a tiny size (GPT, 2 layers, blocks of 4, 96 positions: a
table of 24 entries, read 8, 16 or 24 wide): the tokens of a server with
the ladder are those of the same server held to the whole table and of
the dense reference, on a mix whose lanes cross a width's edge while
they run and whose longest lane retires so that the width falls again;
every step is sent the narrowest width that covers its longest active
lane, and the counters say so; no step compiles after warm-up; a table
too short to split has one width; ``tp = 2`` serves the same tokens.

Which tiers are on the ladder is read off ``KVTier`` alone: one that
keeps every block and one whose window tumbles are, each on a rung of
its own; a ring that slides is sent whole (SmallThinker's, at a ring
long enough to split); warm-up builds one decode program a combination
of the tiers' widths, which for every family with one ladder tier is
the programs it always built.
"""
import numpy as np
import pytest

from deeplearning4j_tpu.compilecache import COMPILE_STATS
from deeplearning4j_tpu.serving.generative import greedy_decode
from deeplearning4j_tpu.serving.paged import (KVTier, PagedGenerativeServer,
                                              table_widths)
from deeplearning4j_tpu.serving.paged import server as paged_server
from deeplearning4j_tpu.serving.paged.pool import TABLE_RUNGS
from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                        gpt_generative_spec,
                                        gpt_paged_decode_fns,
                                        gpt_paged_spec)

CFG = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                intermediate_size=64, max_seq_len=96)
MSL, BS, SLOTS = 96, 4, 3
LADDER = (8, 16, 24)
#: (prompt tokens, new tokens), in the order submitted to three slots:
#: the first grows through all three widths and retires while the last
#: two, admitted later, are still short
MIX = ((10, 75), (5, 8), (28, 12), (6, 70), (7, 30), (4, 60), (9, 20))


@pytest.fixture(scope="module")
def gpt_sd():
    return build_gpt(CFG, batch=2, seq_len=8, seed=0)


@pytest.fixture(scope="module")
def spec(gpt_sd):
    return gpt_paged_spec(gpt_sd, CFG)


def prompts():
    rng = np.random.default_rng(11)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n, _ in MIX]


def make_server(spec, **kw):
    kw.setdefault("max_slots", SLOTS)
    kw.setdefault("max_seq_len", MSL)
    kw.setdefault("block_size", BS)
    # room for the whole mix's reservations at once: it is submitted in
    # one go and admitted as slots come free
    kw.setdefault("num_blocks", 128)
    kw.setdefault("warmup", False)
    kw.setdefault("debug_leaks", True)
    return PagedGenerativeServer(spec, **kw)


def whole_tables(monkeypatch):
    """Servers built from here on read every table whole."""
    monkeypatch.setattr(paged_server, "table_widths",
                        lambda entries: (int(entries),) * TABLE_RUNGS)


def serve(srv, steps=None):
    """The mix through ``srv``; ``steps`` collects ``(entries sent,
    blocks the longest active lane holds)`` of every decode step."""
    if steps is not None:
        real = srv._decode_io

        def spy(*lead):
            io = real(*lead)
            if io is not None:
                (tier,) = srv._tiers
                steps.append((io["tables"].shape[1],
                              int(tier.stop[io["active"]].max())))
            return io

        srv._decode_io = spy
    hs = [srv.submit(p, max_new_tokens=n)
          for p, (_, n) in zip(prompts(), MIX)]
    return [h.result(timeout=300) for h in hs]


@pytest.mark.parametrize("entries, widths", [
    (24, (8, 16, 24)), (512, (176, 344, 512)), (64, (24, 48, 64)),
    (16, (8, 16, 16)), (4, (4, 4, 4)), (8, (8, 8, 8)), (1, (1, 1, 1))])
def test_the_ladder_is_thirds_rounded_up_to_eight_entries(entries, widths):
    assert table_widths(entries) == widths
    assert widths[-1] == entries and list(widths) == sorted(widths)


@pytest.mark.parametrize("entries, widths", [
    (128, (64, 128)), (24, (16, 24)), (20, (16, 20)), (8, (8, 8))])
def test_on_two_rungs_the_ladder_is_half_and_whole(entries, widths):
    assert table_widths(entries, 2) == widths


def test_tokens_are_those_of_the_whole_table_and_of_the_dense_path(
        spec, gpt_sd, monkeypatch):
    steps = []
    with make_server(spec) as srv:
        assert srv._tiers[0].widths == LADDER
        got = serve(srv, steps)
    sent = [w for w, _ in steps]
    # lanes crossed both edges while they ran, and the width fell again
    # when the longest lane retired
    assert set(sent) == set(LADDER)
    assert any(b > a for a, b in zip(sent, sent[1:]))
    assert any(b < a for a, b in zip(sent, sent[1:]))
    whole_tables(monkeypatch)
    whole = []
    with make_server(spec) as srv:
        assert srv._tiers[0].widths == (24, 24, 24)
        assert got == serve(srv, whole)
    assert {w for w, _ in whole} == {24}
    dense = gpt_generative_spec(gpt_sd, CFG)
    assert got == [greedy_decode(dense, p, n, max_seq_len=MSL)
                   for p, (_, n) in zip(prompts(), MIX)]


def test_every_step_is_sent_the_narrowest_width_that_covers_it(spec):
    steps = []
    with make_server(spec) as srv:
        serve(srv, steps)
        c = dict(srv.metrics.counters)
    assert len(steps) == c["decode_steps"] > 90
    for sent, held in steps:
        assert sent == min(w for w in LADDER if w >= held)
    assert c["decode_table_entries_sum"] == sum(w for w, _ in steps)
    assert c["decode_table_capacity_sum"] == 24 * len(steps)
    share = c["decode_table_entries_sum"] / c["decode_table_capacity_sum"]
    assert share == pytest.approx(np.mean([w for w, _ in steps]) / 24)
    assert 0.4 < share < 0.9


def test_no_step_compiles_after_warmup_and_the_report_names_the_widths(
        spec):
    with make_server(spec, warmup=True) as srv:
        rep = srv.warmup_report
        assert rep["decode_table_widths"] == {"all": list(LADDER)}
        # three decode programs and one prefill program a bucket
        assert srv.metrics.counters["warmup_compiles"] == \
            len(LADDER) + len(rep["prefill_buckets"])
        assert len(srv._decode_disp.aot) == len(LADDER)
        steps = []
        mark = COMPILE_STATS.mark()
        serve(srv, steps)
        assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
        assert srv.metrics.counters["compiles"] == 0
    assert {w for w, _ in steps} == set(LADDER)


def test_a_table_too_short_to_split_has_one_width_and_one_program(
        spec, gpt_sd):
    with make_server(spec, max_seq_len=32, warmup=True) as srv:
        (tier,) = srv._tiers
        assert tier.entries == 8 and set(tier.widths) == {8}
        assert srv.warmup_report["decode_table_widths"] == {"all": [8]}
        assert len(srv._decode_disp.aot) == 1
        assert srv.metrics.counters["warmup_compiles"] == \
            1 + len(srv.warmup_report["prefill_buckets"])
        p = prompts()[0]
        assert srv.submit(p, max_new_tokens=12).result(timeout=120) == \
            greedy_decode(gpt_generative_spec(gpt_sd, CFG), p, 12,
                          max_seq_len=32)
        c = srv.metrics.counters
        assert c["decode_table_entries_sum"] == \
            c["decode_table_capacity_sum"] == 8 * c["decode_steps"]


def test_the_span_carries_the_width(spec):
    from deeplearning4j_tpu.monitor.trace import TRACER
    with make_server(spec) as srv:
        was, mark = TRACER.enabled, TRACER.mark()
        TRACER.enabled = True
        try:
            srv.submit(prompts()[2], max_new_tokens=8).result(timeout=120)
            spans = [sp for sp in TRACER.drain(mark)[0]
                     if sp.name == "serving.decode"]
        finally:
            TRACER.enabled = was
    # 28 tokens and seven more: 8 blocks, then a ninth
    assert len(spans) == 7
    assert {sp.args["table_entries"] for sp in spans} == {8, 16}


def test_a_lane_crosses_a_rung_with_the_step_before_it_in_the_air(
        gpt_sd, lively, monkeypatch):
    """ISSUE 33: three lanes on three slots from the first step, each
    long enough to pass 8 and 16 blocks. The step launched ahead of its
    predecessor's tokens is given its width from positions alone, the
    boundary's growth included, so a lane's first step on a wider table
    is built while the last step on the narrower one is unread; the
    tokens are those of the whole table and of the dense path, over a
    model whose tokens follow positions (conftest's ``lively``)."""
    spec = lively(gpt_paged_spec(gpt_sd, CFG))
    jobs = list(zip(prompts()[:3], (70, 74, 66)))
    steps = []

    def served(srv):
        real = srv._decode_io

        def spy(*lead):
            io = real(*lead)
            (tier,) = srv._tiers
            steps.append((io["tables"].shape[1],
                          int(tier.stop[io["active"]].max()), sum(lead)))
            return io

        srv._decode_io = spy
        hs = [srv.submit(p, max_new_tokens=n) for p, n in jobs]
        srv.start()
        return [h.result(timeout=300) for h in hs]

    with make_server(spec, start=False) as srv:
        got = served(srv)
        c = dict(srv.metrics.counters)
    assert len(steps) == c["decode_steps"] == 73
    # all three lanes until the shortest ends on its token 66
    assert c["decode_ahead_steps"] == 64 == sum(a for _, _, a in steps)
    for sent, held, _ in steps:
        assert sent == min(w for w in LADDER if w >= held)
    wider = [(a, b) for a, b in zip(steps, steps[1:]) if b[0] > a[0]]
    assert [(a[0], b[0]) for a, b in wider] == [(8, 16), (16, 24)]
    assert all(b[2] == 1 for _, b in wider)
    ahead = list(steps)
    whole_tables(monkeypatch)
    with make_server(spec, start=False) as srv:
        assert got == served(srv)
    assert {w for w, _, _ in steps[len(ahead):]} == {24}
    dense = lively(gpt_generative_spec(gpt_sd, CFG))
    assert got == [greedy_decode(dense, p, n, max_seq_len=MSL)
                   for p, n in jobs]


def abstract_decode_args(spec, width):
    """What ``decode_fn`` is traced on, as shapes: parameters, one side
    of a five-block pool, and the io of three lanes at ``width``."""
    import jax
    import jax.numpy as jnp
    lane = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    io = {"tokens": lane, "positions": lane, "write_block": lane,
          "write_off": lane,
          "active": jax.ShapeDtypeStruct((SLOTS,), jnp.bool_),
          "tables": jax.ShapeDtypeStruct((SLOTS, width), jnp.int32)}
    params = {n: jax.ShapeDtypeStruct(np.shape(a), jnp.float32)
              for n, a in spec.params().items()}
    leaf = jax.ShapeDtypeStruct((5, BS, CFG.hidden_size), jnp.float32)
    side = (leaf,) * CFG.num_layers
    return params, side, side, io


def test_the_program_refuses_a_table_wider_than_a_request(spec):
    import jax
    _, decode_fn, _ = gpt_paged_decode_fns(CFG, BS, 24)
    for width in (8, 24):
        out = jax.eval_shape(decode_fn, *abstract_decode_args(spec, width))
        assert out[3].shape == (SLOTS, CFG.vocab_size)
    with pytest.raises(ValueError, match="25 entries"):
        jax.eval_shape(decode_fn, *abstract_decode_args(spec, 25))


@pytest.mark.parametrize("program", ["prefill", "decode", "verify"])
def test_a_program_holds_its_layer_once(spec, program):
    """Set-up builds the decode program once a width, so a paged
    program's trace and lowering hold one layer and ``num_layers`` calls
    of it: each of the three the same way."""
    import jax
    import jax.numpy as jnp
    params, side, _, io = abstract_decode_args(spec, 16)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if program == "prefill":
        io = {"tokens": i32(8), "length": i32(), "hist": i32(),
              "table": i32(24)}
    elif program == "verify":
        io = dict(io, tokens=i32(SLOTS, 4), write_block=i32(SLOTS, 4),
                  write_off=i32(SLOTS, 4), tables=i32(SLOTS, 24))
    fn = dict(zip(("prefill", "decode", "verify"),
                  gpt_paged_decode_fns(CFG, BS, 24)))[program]
    jaxpr = jax.make_jaxpr(fn)(params, side, side, io)
    layers = [e for e in jaxpr.eqns
              if e.params.get("name") == f"_{program}_layer"]
    assert len(layers) == CFG.num_layers
    assert len({id(e.params["jaxpr"]) for e in layers}) == 1


def test_tp2_serves_the_same_tokens_with_the_ladder_as_without(
        spec, monkeypatch):
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    steps = []
    with make_server(spec, tp=2, warmup=True) as srv:
        assert srv._strategy is not None
        assert srv.warmup_report["decode_table_widths"] == {
            "all": list(LADDER)}
        got = serve(srv, steps)
        assert srv.metrics.counters["compiles"] == 0
    assert {w for w, _ in steps} == set(LADDER)
    whole_tables(monkeypatch)
    with make_server(spec, tp=2) as srv:
        assert got == serve(srv)
    with make_server(spec) as srv:
        assert got == serve(srv)


# -- which tiers are on the ladder, each on a rung of its own -------------

@pytest.mark.parametrize("tier, entries, widths", [
    (KVTier("", (0, 1)), 24, LADDER),
    (KVTier("summary", (0, 1), row_tokens=4), 24, LADDER),
    (KVTier("exact", (0, 1), 96, tumbles=True), 24, (16, 24)),
    (KVTier("window", (0, 1), 92), 24, (24, 24, 24)),
    (KVTier("exact", (0, 1), 32, tumbles=True), 8, (8, 8))])
def test_a_tier_is_on_the_ladder_unless_its_window_slides(tier, entries,
                                                          widths):
    """The rule reads the tier alone. On the ladder a lane needs the
    entries its live blocks take, ``stop - first``: ``first`` stays 0
    where every block is kept and is a multiple of the ring where the
    window tumbles, so a lane three turns on needs what it holds of its
    own window. A ring that tumbles takes two rungs, half and whole: it
    is a second tier on the ladder, and the tiers' widths multiply the
    programs to warm."""
    ts = paged_server._TierState(tier, BS, entries, 64, max_slots=3)
    assert ts.widths == widths
    assert ts.on_ladder == (tier.window is None or tier.tumbles)
    act = np.array([True, True, False])
    turned = entries * 3 if tier.tumbles else 0
    for held in (1, 8, 9, 16, 17, 24):
        if held > entries:
            continue
        ts.first[:] = [turned, 0, 0]
        # the idle lane's books are no part of the need
        ts.stop[:] = [turned + held, min(held, 3), entries]
        assert ts.decode_width(act) == min(w for w in widths if w >= held)


def family_spec(family):
    """The tiny spec of a family's own tests, with its geometry there."""
    if family == "gpt":
        return (gpt_paged_spec(build_gpt(CFG, batch=2, seq_len=8, seed=0),
                               CFG),
                dict(max_seq_len=MSL, num_blocks=128))
    if family == "smallthinker":
        import test_smallthinker as t
        from deeplearning4j_tpu.zoo.smallthinker import \
            smallthinker_paged_spec as make
        kw = dict(max_seq_len=64, buckets=[4, 8], num_blocks=None)
    elif family == "glm":
        import test_glm_moe_lite as t
        from deeplearning4j_tpu.zoo.glm_moe_lite import \
            glm_moe_lite_paged_spec as make
        kw = dict(max_seq_len=64, buckets=[4, 8])
    else:
        import test_evabyte as t
        from deeplearning4j_tpu.zoo.evabyte import evabyte_paged_spec as make
        kw = dict(max_seq_len=256, buckets=[8, 16], num_blocks=None)
    return make(t.adapter.program_config(t.CFG),
                t.adapter.program_params(t.CFG, t.SEED)), kw


@pytest.mark.parametrize("family, widths", [
    ("gpt", {"all": [8, 16, 24]}),
    ("smallthinker", {"global": [8, 16], "window": [3]}),
    ("glm", {"all": [8, 16]}),
    ("evabyte", {"exact": [8], "summary": [8, 16]})])
def test_a_spec_with_one_tier_to_cut_warms_the_programs_it_always_did(
        family, widths):
    """One decode program a combination of the tiers' distinct widths:
    with one tier that has more than one width, one a width, as before
    each tier took a rung of its own."""
    spec, kw = family_spec(family)
    with make_server(spec, warmup=True, **kw) as srv:
        rep = srv.warmup_report
        assert rep["decode_table_widths"] == widths
        programs = int(np.prod([len(w) for w in widths.values()]))
        assert programs == max(len(w) for w in widths.values())
        assert len(srv._decode_disp.aot) == programs
        assert srv.metrics.counters["warmup_compiles"] == \
            programs + len(rep["prefill_buckets"])


def test_a_sliding_ring_long_enough_to_split_is_still_sent_whole():
    """SmallThinker with a window of 60 in blocks of 4: a ring of 16
    entries, which ``table_widths`` would cut to 8 and 16. Block ``u``
    sits in entry ``u % 16`` and every entry is live once a lane has
    wrapped, so the ring comes whole at every step while the global
    tier beside it is cut to its lanes."""
    import test_smallthinker as t
    from deeplearning4j_tpu.zoo.smallthinker import smallthinker_paged_spec
    cfg = dict(t.CFG, sliding_window_size=60)
    spec = smallthinker_paged_spec(t.adapter.program_config(cfg),
                                   t.adapter.program_params(cfg, t.SEED))
    shapes = []
    with make_server(spec, max_seq_len=128, buckets=[4, 8],
                     num_blocks=None, warmup=True) as srv:
        glob, win = srv._tiers
        assert glob.on_ladder and not win.on_ladder
        assert table_widths(win.entries) == (8, 16, 16)
        assert win.widths == (16, 16, 16) and glob.widths == (16, 24, 32)
        assert srv.warmup_report["decode_table_widths"] == {
            "global": [16, 24, 32], "window": [16]}
        assert len(srv._decode_disp.aot) == 3
        real = srv._decode_io

        def spy(*lead):
            io = real(*lead)
            if io is not None:
                shapes.append((io["tables.global"].shape[1],
                               io["tables.window"].shape[1]))
            return io

        srv._decode_io = spy
        rng = np.random.default_rng(3)
        hs = [srv.submit(rng.integers(0, 61, n).astype(np.int32),
                         max_new_tokens=m)
              for n, m in ((5, 20), (21, 60), (9, 8))]
        for h in hs:
            h.result(timeout=300)
        c = srv.metrics.counters
        assert c["compiles"] == 0
        # the ring is no part of the ladder's sums
        assert c["decode_table_entries_sum"] == sum(g for g, _ in shapes)
        assert c["decode_table_capacity_sum"] == 32 * len(shapes)
    assert {w for _, w in shapes} == {16}
    assert {g for g, _ in shapes} == {16, 24}
