"""Command A+ on the paged path at a tiny size (window 8, block 4, one
period of 4 layers, 4 of 8 experts held from expert 2, top-2, 4 shared
experts, a 61-row vocabulary), seeded: the server's own programs and the
whole-sequence forward against the plain reference, the controls that
must fail the same tolerance, the shares of a layer that add up to the
uncut layer, the program's counters against a count by hand, and the
vocabulary slice; the decode program's read of each lane's own pages
through the ragged kernel (the shipped reference in the kernel's place)
against the plain path, and its counter."""
import numpy as np
import pytest

from benchmark.adapters import cohere2_moe as adapter
from benchmark.counts import cohere2_moe as counts
from benchmark.generators import closed_mix
from benchmark.reference import cohere2_moe as ref
from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
from deeplearning4j_tpu.monitor import attention
from deeplearning4j_tpu.zoo import cohere2_moe as zoo
from deeplearning4j_tpu.zoo import paged_attend
from deeplearning4j_tpu.zoo.cohere2_moe import (PROGRAM_COUNTERS,
                                                Cohere2MoeConfig,
                                                Cohere2MoeUnsupportedError,
                                                cohere2_moe_forward,
                                                cohere2_moe_paged_spec,
                                                cohere2_moe_param_names,
                                                cohere2_moe_param_shapes)

CFG = {"family": "cohere2_moe", "hidden_size": 32, "head_dim": 8,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "intermediate_size": 16, "num_experts": 4, "router_experts": 8,
       "first_expert": 2, "num_experts_per_tok": 2,
       "num_shared_experts": 4, "sliding_window": 8,
       "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
       "num_hidden_layers": 4, "layer_norm_eps": 1e-5,
       "rope_theta": 50000, "logit_scale": 1, "vocab_size": 61,
       "max_position_embeddings": 128, "use_parallel_block": True,
       "position_embedding_type": "rope_gptj", "rotary_pct": 1,
       "use_qk_norm": False, "first_k_dense_replace": 0,
       "shared_expert_combination_strategy": "average",
       "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
       "tie_word_embeddings": True, "param_dtype": "bfloat16",
       "kv_dtype": "bfloat16"}
SEED = 2**31 + 5
WINDOW, BS = 8, 4
#: the tolerance in logits, in standard deviations of the reference's
#: logits. The program rounds every product's operands to bfloat16 (2**-9
#: relative each) and caches K and V in bfloat16, the reference computes
#: in float32: through one period of 4 layers that reads 0.006 to 0.009
#: of a deviation on these seeds (the bfloat16 control as much). Every
#: control reads at least 0.020 (rotate-half where GPT-J's pairs are
#: meant, the nearest; float8, the sequential block and the summed shared
#: experts 0.08 to 0.3; the window off 1.0 and more): a tolerance of
#: 0.013 leaves half again of room on either side
TOL = 0.013


@pytest.fixture(scope="module")
def spec():
    return cohere2_moe_paged_spec(adapter.program_config(CFG),
                                  adapter.program_params(CFG, SEED))


def server(spec, buckets=(4, 8), **kw):
    return PagedGenerativeServer(spec, max_slots=3, block_size=BS,
                                 max_seq_len=64, buckets=list(buckets),
                                 warmup=False, debug_leaks=True, **kw)


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], n).astype(np.int32)


def logits_served(srv, prompts, new_tokens):
    """Serve ``prompts`` together (``new_tokens`` each, or one number
    for all) and keep the logits every token was chosen from, as the
    server's own programs returned them."""
    seen = {}
    real = srv._resolve_token

    def keep(req, device_tok, logits_row):
        seen.setdefault(req.id, []).append(np.asarray(logits_row))
        return real(req, device_tok, None)

    srv._resolve_token = keep
    srv._sampled_active = lambda: True       # decode hands the logits over
    if np.ndim(new_tokens) == 0:
        new_tokens = [new_tokens] * len(prompts)
    hs = [srv.submit(p, max_new_tokens=int(n))
          for p, n in zip(prompts, new_tokens)]
    srv.start()                              # where the test held it back
    toks = [h.result(timeout=300) for h in hs]
    return toks, [np.stack(seen[h.id]) for h in hs]


def off_by(got, want):
    """The largest gap of ``got`` from ``want``, in deviations of
    ``want``."""
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / want.std())


def test_the_config_reads_the_published_keys_and_names_every_leaf():
    pc = Cohere2MoeConfig.from_dict(CFG)
    assert (pc.num_layers, pc.num_heads, pc.num_kv_heads) == (4, 4, 2)
    assert (pc.num_experts, pc.router_experts, pc.first_expert) == (4, 8, 2)
    assert pc.window_layout == (1, 1, 1, 0)
    glob, win = pc.kv_tiers()
    assert (glob.name, glob.layers, glob.window) == ("global", (3,), None)
    assert (win.name, win.layers, win.window) == ("window", (0, 1, 2), 8)
    names = cohere2_moe_param_names(pc)
    # the embedding and the final gain; 12 leaves a layer; no head
    assert len(names) == 2 + 4 * 12 and "lm_head" not in names
    assert set(names) == set(adapter.program_params(CFG, 1))
    shapes = cohere2_moe_param_shapes(pc)
    assert shapes["h0/router"] == (32, 8)              # all the experts
    assert shapes["h0/experts/gate"] == (4, 32, 16)    # the held ones
    assert shapes["h0/shared/down"] == (4 * 16, 32)    # side by side
    # without the deployment's keys every expert is held
    whole = dict(CFG, num_experts=8)
    del whole["router_experts"], whole["first_expert"]
    pw = Cohere2MoeConfig.from_dict(whole)
    assert (pw.num_experts, pw.router_experts, pw.first_expert) == (8, 8, 0)
    with pytest.raises(ValueError, match="held"):
        Cohere2MoeConfig.from_dict(dict(CFG, first_expert=5))


@pytest.mark.parametrize("key,value", [
    ("use_parallel_block", False), ("position_embedding_type", "rope"),
    ("use_qk_norm", True), ("first_k_dense_replace", 1),
    ("shared_expert_combination_strategy", "sum"),
    ("expert_selection_fn", "softmax"), ("rotary_pct", 0.5),
    ("tie_word_embeddings", False), ("norm_topk_prob", False),
    ("layer_types", ["chunked_attention"] * 4)])
def test_what_the_block_does_not_compute_is_refused_by_its_key(key, value):
    with pytest.raises(Cohere2MoeUnsupportedError) as e:
        Cohere2MoeConfig.from_dict(dict(CFG, **{key: value}))
    assert e.value.key == key


def test_the_forward_agrees_in_logits_with_the_reference():
    """The block over 64 tokens with nothing cached (the ring of a window
    layer is not read: every row is fresh) against the reference's full
    forward; the routers' own counts come back beside the logits."""
    toks = prompt(64, 11)
    got, _, did = cohere2_moe_forward(adapter.program_config(CFG),
                                      adapter.program_params(CFG, SEED),
                                      toks)
    want = ref.logits(CFG, SEED, [toks])[0]
    assert got.shape == (64, CFG["vocab_size"])
    assert off_by(got, want) < TOL
    did = np.asarray(did)
    # [layers, held + 1]: each layer routed 2 of 8 for each of 64 tokens
    assert did.shape == (4, 5) and (did[:, -1] == 2 * 64).all()
    assert (did[:, :-1].sum(axis=1) <= 2 * 64).all()


def test_prefill_then_decode_agrees_in_logits_with_the_reference(spec):
    """Three requests side by side, two of them prompts in chunks (a run
    is at most 8 tokens), each decoded past three windows, so that the
    window layers' ring turns and the global layer holds every position:
    the logits every served token was chosen from against the reference's
    full forward over the same tokens (:data:`TOL` says why that
    tolerance)."""
    prompts = [prompt(5, 1), prompt(21, 2), prompt(30, 3)]
    with server(spec) as srv:
        toks, got = logits_served(srv, prompts, 3 * WINDOW + 4)
    seqs = [np.concatenate([p, t])[:-1] for p, t in zip(prompts, toks)]
    spans = [np.arange(len(p) - 1, len(p) + len(t) - 1)
             for p, t in zip(prompts, toks)]
    want = ref.logits(CFG, SEED, seqs, spans)
    for g, w in zip(got, want):
        assert g.shape == w.shape and w.shape[0] > 3 * WINDOW
        assert off_by(g, w) < TOL


@pytest.fixture(scope="module")
def long_rows():
    toks = [prompt(64, 11), prompt(48, 12)]
    return toks, ref.logits(CFG, SEED, toks)


#: why each control must fail: it is the reference put in the program's
#: place with one thing wrong that a program could get wrong
CONTROL_WHY = {
    "float8": "one precision below the bfloat16 the configuration states",
    "sequential_block": "attention first, the experts on a second norm "
                        "of the updated stream: the block made sequential",
    "shared_summed": "the four shared experts summed, not averaged",
    "rope_half": "rotate-half where the file says GPT-J's interleaved "
                 "pairs",
    "window_off": "window layers read every earlier position",
}


@pytest.mark.parametrize("control", sorted(CONTROL_WHY))
def test_a_control_fails_the_tolerance(long_rows, control):
    """The program passes :data:`TOL` on these rows (the forward test);
    a control does not (:data:`CONTROL_WHY`)."""
    toks, want = long_rows
    mode, variant = ref.control_of(control)
    low = ref.logits(CFG, SEED, toks, mode=mode, variant=variant)
    assert max(off_by(lo, w) for lo, w in zip(low, want)) > TOL, \
        CONTROL_WHY[control]


def test_a_prompt_in_chunks_and_in_spans_gives_the_logits_of_one_run(
        spec, monkeypatch):
    """30 tokens through runs of 4 and 8 reading the cached rows two
    table entries at a time (six spans over the global table of 16, the
    last one ending at the table's end; two over the ring of 3, the
    second counting only its last entry), and through one run of 32: the
    same K and V reach the same queries. A run weighs its cached rows
    under each span's own largest score and its fresh rows under the
    joint one, and rounds the weights to bfloat16 for the product, so the
    ways differ by that rounding (2**-9 of a weight; 0.006 of the logits'
    spread read), as GLM-4.7-Flash's runs do: 0.02 is allowed, and a row
    lost or read twice moves them by tenths."""
    p = prompt(30, 7)
    monkeypatch.setattr(zoo, "PREFILL_SPAN", 2)
    with server(spec, buckets=(4, 8)) as srv:
        t1, l1 = logits_served(srv, [p], 6)
        assert srv.metrics.counters["prefill_runs"] == 4
    monkeypatch.setattr(zoo, "PREFILL_SPAN", 128)
    with server(spec, buckets=(32,)) as srv:
        t2, l2 = logits_served(srv, [p], 6)
        assert srv.metrics.counters["prefill_runs"] == 1
    assert t1 == t2
    np.testing.assert_allclose(l1[0], l2[0], rtol=0,
                               atol=0.02 * l2[0].std())


def test_a_chunk_of_512_tokens_keeps_ragged_dot_and_the_reference():
    """A prefill chunk of 512 tokens at hidden and expert width 512 (4 of
    8 experts held, top-2: 1,024 pairs, about half of them held here)
    multiplies its held pairs through ``ragged_dot``, as every other run
    does: the tiled kernel, which would take every row, is named by no
    run. A prompt of 600 tokens through a chunk and a tail, then decoded,
    agrees in logits with the reference as the tiny block does."""
    from deeplearning4j_tpu.parallel import moe
    wide = dict(CFG, hidden_size=512, intermediate_size=512,
                num_hidden_layers=1, layer_types=["full_attention"],
                max_position_embeddings=1024)
    sp = cohere2_moe_paged_spec(adapter.program_config(wide),
                                adapter.program_params(wide, SEED))
    named = []
    real = moe.tiled_grouped_dot
    moe.tiled_grouped_dot = \
        lambda lhs, *a: named.append(lhs.shape[0]) or real(lhs, *a)
    p = prompt(600, 5)
    try:
        with PagedGenerativeServer(
                sp, max_slots=2, block_size=BS, max_seq_len=640,
                buckets=[8, 128, 512], warmup=False) as srv:
            toks, got = logits_served(srv, [p], 4)
            assert srv.metrics.counters["prefill_runs"] == 2
    finally:
        moe.tiled_grouped_dot = real
    assert named == []
    seq = np.concatenate([p, toks[0]])[:-1]
    span = np.arange(len(p) - 1, len(seq))
    # padded behind to whole blocks of the reference's queries
    seq = np.concatenate([seq, np.zeros(640 - len(seq), np.int32)])
    want = ref.logits(wide, SEED, [seq], [span])[0]
    assert off_by(got[0], want) < TOL


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """One layer whose 8 experts are divided over four chips, 2 each
    (``first_expert`` 0, 2, 4, 6), each chip routing over all 8: the
    streams the four shares' programs give, with what every chip
    computes alike (the stream, attention, the shared experts, the norm)
    counted ONCE, add up to the uncut reference's layer over all 8
    experts. What every chip computes alike is a share whose routed
    experts give nothing (their down-projections zero)."""
    import jax.numpy as jnp
    one = dict(CFG, num_hidden_layers=1, layer_types=["sliding_attention"],
               num_experts=2, router_experts=8)
    toks = prompt(40, 5)

    def stream(cfg, zero_experts=False):
        params = adapter.program_params(cfg, SEED)
        if zero_experts:
            params["h0/experts/down"] = jnp.zeros_like(
                params["h0/experts/down"])
        _, x, did = cohere2_moe_forward(adapter.program_config(cfg),
                                        params, toks)
        return np.asarray(x), np.asarray(did)

    shares = [stream(dict(one, first_expert=f)) for f in (0, 2, 4, 6)]
    common, _ = stream(dict(one, first_expert=0), zero_experts=True)
    got = sum(x for x, _ in shares) - 3 * common
    whole = dict(one, num_experts=8, first_expert=0)
    want = np.asarray(ref.hidden(whole, SEED, [toks])[0][0])
    assert np.abs(got - want).max() < 0.02 * want.std()
    # every pair the routers chose is held by exactly one share
    held = sum(d[0, :-1].sum() for _, d in shares)
    assert held == 2 * 40 == shares[0][1][0, -1]


def test_the_program_counts_what_a_count_by_hand_gives(spec):
    """One decode step of three lanes, two of them active at position 0
    (nothing cached): the counters behind the tokens against a count by
    hand from each lane's own forward (a token at position 0 attends to
    itself alone, as it does at decode)."""
    import jax
    import jax.numpy as jnp
    pc = adapter.program_config(CFG)
    params = spec.params()
    _, decode_fn = spec.make_fns(BS, 16)
    tiers = pc.kv_tiers()
    S, held = 3, pc.num_experts
    tokens = np.array([5, 17, 40], np.int32)
    active = np.array([True, True, False])
    io = {"tokens": tokens, "positions": np.zeros(S, np.int32),
          "active": active, "write_off": np.zeros(S, np.int32)}
    for t in tiers:
        io[t.key("tables")] = np.zeros((S, t.table_blocks(BS, 16)),
                                       np.int32)
        io[t.key("write_block")] = np.array([1, 2, 3], np.int32)
    leaf = jnp.zeros((8, BS, 2 * pc.num_kv_heads * pc.head_dim),
                     jnp.bfloat16)
    _, _, nxt, logits = jax.jit(decode_fn)(params, (leaf,) * 4, (), io)
    got = dict(zip(PROGRAM_COUNTERS, np.asarray(nxt)[S:].tolist()))
    served = np.zeros((4, held), np.int64)
    for tok, on in zip(tokens, active):
        if on:
            lg, _, did = cohere2_moe_forward(pc, params,
                                             jnp.asarray([tok]))
            served += np.asarray(did)[:, :held]
            np.testing.assert_allclose(
                np.asarray(logits)[list(tokens).index(tok)],
                np.asarray(lg)[0], rtol=0, atol=1e-5)
    assert got == {
        "moe_layer_steps": 4,
        "moe_experts_touched_sum": int((served > 0).sum()),
        "moe_tokens_routed_sum": 2 * 2 * 4,          # k x lanes x layers
        "moe_peak_expert_tokens_sum": int(served.max(axis=1).sum()),
        "moe_held_pairs_sum": int(served.sum()),
        # the plain path (the CPU) reads every entry it gathered: the
        # global table's 16 and the ring's 3 on three layers, a lane
        "kv_pages_read_sum": 2 * (16 + 3 * 3)}
    # layer 0 by hand in float64: the router reads the norm of the
    # embedding row, which program and reference compute alike
    emb = np.asarray(params["embed"], np.float64)
    g = np.asarray(params["h0/norm"], np.float64)
    wr = np.asarray(params["h0/router"], np.float64)
    mine = 0
    for tok in tokens[active]:
        x = emb[tok] - emb[tok].mean()
        n = x / np.sqrt((x * x).mean() + 1e-5) * g
        top = np.argsort(-(n @ wr))[:2]
        mine += int(((top >= 2) & (top < 6)).sum())
    assert served[0].sum() == mine


def test_the_window_tier_stays_in_its_bound_and_the_counters_add_up(spec):
    with server(spec) as srv:
        glob, win = srv._tiers
        assert (glob.entries, win.entries) == (16, WINDOW // BS + 1)
        hs = [srv.submit(prompt(n, n), max_new_tokens=30)
              for n in (30, 3, 25)]
        for h in hs:
            h.result(timeout=300)
        c = dict(srv.metrics.counters)
        for ts in srv._tiers:
            assert ts.pool.held_count() == 0
    assert c["window_blocks_released"] > 0
    assert c["moe_layer_steps"] == 4 * c["decode_steps"]
    assert c["moe_tokens_routed_sum"] == 2 * 4 * c["slots_active_sum"]
    # the held experts take about half of the pairs (4 of 8), never more
    # than all of them; a held expert with a token took at least one
    assert 0 < c["moe_held_pairs_sum"] < c["moe_tokens_routed_sum"]
    assert 0 < c["moe_experts_touched_sum"] <= c["moe_held_pairs_sum"]
    assert c["moe_experts_touched_sum"] <= c["moe_peak_expert_tokens_sum"] \
        * 4 <= 4 * c["moe_held_pairs_sum"]


def test_ids_come_from_the_slice_and_the_head_is_over_it(spec):
    """The cell's traffic draws its ids from the 32,768 the configuration
    holds, and over the whole of them; the program's logits are as wide
    as the slice, are the embedding's rows against the last stream, and
    an id outside the slice is refused."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "command-a-plus-05-2026.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "mixed_closed_sharegpt.json")) as f:
        traffic = json.load(f)
    assert cfg["vocab_size"] == 32768 == cfg["published"]["vocab_size"] // 8
    ids = np.concatenate([r["prompt"] for r in
                          closed_mix.generate(traffic, cfg, 2**33 + 9)[:64]])
    assert ids.min() >= 0 and ids.max() < 32768 and ids.max() > 32000
    pc = adapter.program_config(CFG)
    params = spec.params()
    lg, x, _ = cohere2_moe_forward(pc, params, prompt(9, 2))
    assert lg.shape == (9, CFG["vocab_size"])
    # the head is the embedding: LN(x) @ E^T
    x = np.asarray(x, np.float64)
    c = x - x.mean(1, keepdims=True)
    n = c / np.sqrt((c * c).mean(1, keepdims=True) + 1e-5) \
        * np.asarray(params["norm_f"], np.float64)
    np.testing.assert_allclose(
        np.asarray(lg), n @ np.asarray(params["embed"], np.float64).T,
        rtol=0, atol=0.02 * float(np.asarray(lg).std()))
    with server(spec) as srv:
        with pytest.raises(ValueError):
            srv.submit(np.array([3, CFG["vocab_size"]], np.int32),
                       max_new_tokens=2)


def test_the_cells_pool_fits_at_its_worst_and_the_count_matches_the_leaves():
    """At 32 slots and 10,752 positions the server sizes each tier for
    every slot at its worst (``PagedGenerativeServer``'s own rule): 672
    blocks of 16 a slot in the global tier, 289 in the window tier. The
    parameters the counts give are those of the program's leaves."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "command-a-plus-05-2026.json")) as f:
        cfg = json.load(f)
    pc = Cohere2MoeConfig.from_dict(cfg)
    glob, win = pc.kv_tiers()
    assert glob.layers == (3,) and win.layers == (0, 1, 2)
    assert glob.peak_blocks(10752, 16, 512) == 672
    assert win.peak_blocks(10752, 16, 512) == 289
    row = 16 * 2 * pc.num_kv_heads * pc.head_dim * 2      # K and V, bf16
    pool = (32 * 672 + 1) * row + (32 * 289 + 1) * 3 * row
    assert 3.1e9 < pool < 3.3e9
    n = sum(int(np.prod(s)) for s in cohere2_moe_param_shapes(pc).values())
    assert n == counts.param_count(cfg)
    assert 4.73e9 < n < 4.74e9
    assert counts.held_per_token(cfg) == 1.0


def test_the_counts_expect_the_held_experts_the_router_touches():
    """``counts.experts_touched`` assumes the seeded sigmoid router
    chooses evenly over all its experts: the reference's own router over
    the seeded weights, at a width where that can be told (64 experts, 8
    held, top-8), touches as many held experts as it expects."""
    cfg = dict(CFG, hidden_size=256, num_experts=8, router_experts=64,
               first_expert=0, num_experts_per_tok=8)
    wr = np.asarray(ref.draw(cfg, SEED, "router", 1), np.float64)
    x = np.asarray(ref.draw(cfg, SEED, "embed"), np.float64)[:48]
    x = (x - x.mean(1, keepdims=True)) / x.std(1, keepdims=True)
    chosen = np.argsort(-(x @ wr), axis=1)[:, :8]
    for n in (1, 4, 16):
        runs = chosen.reshape(-1, n, 8)
        got = np.mean([len({e for e in r.ravel() if e < 8}) for r in runs])
        want = counts.experts_touched(cfg, n)
        assert abs(got - want) < 0.35 * want + 0.3, (n, got, want)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_both_programs_call_the_one_block_a_kind_of_layer(spec, program):
    """One layer function for both programs, jitted on its own: a
    program's trace holds it once a kind of layer (window, global) and
    calls it ``num_layers`` times."""
    import jax
    import jax.numpy as jnp
    prefill_fn, decode_fn = spec.make_fns(BS, 16)
    S = 3
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa
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
    side = tuple(jax.ShapeDtypeStruct((9, BS, 2 * 2 * 8), jnp.bfloat16)
                 for _ in range(4))
    jaxpr = jax.make_jaxpr(fn)(params, side, (), io)
    blocks = [e for e in jaxpr.eqns if e.params.get("name") == "_block"]
    assert len(blocks) == 4
    assert len({id(e.params["jaxpr"]) for e in blocks}) == 2


# -- the decode program's read through the ragged kernel -----------------
#: window 64 over blocks of 16: a ring of 5 entries
CFG_KERNEL = dict(CFG, sliding_window=64, max_position_embeddings=256)
BS_KERNEL = 16


def _reference_kernel(q, leaf, pages, rows, reach, *, scale, per_block):
    """JAX's shipped ragged paged-attention kernel's reference in the
    decode kernel's place, fed the program's exact inputs (the leaf as
    its ``kv_pages``; the decode kernel is a TPU program, the reference
    runs anywhere, eagerly, so the program calls it back)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import \
        ref_ragged_paged_attention
    R, _, D = q.shape
    kv_pages = leaf.reshape(leaf.shape[0], leaf.shape[1], -1, D)

    def run(q, kv_pages, rows, pages, reach):
        window = int(reach[0])
        return np.asarray(ref_ragged_paged_attention(
            q, kv_pages, rows, pages, np.arange(R + 1, dtype=np.int32),
            np.asarray([R], np.int32), sm_scale=scale,
            sliding_window=None if window == paged_attend._EVERY_ROW
            else window))

    return jax.pure_callback(run, jax.ShapeDtypeStruct(q.shape, q.dtype),
                             q, kv_pages, rows, pages, reach)


def _through_kernel(monkeypatch, window_start=None):
    """The decode program takes the kernel path on the CPU, with the
    kernel's reference in the kernel's place."""
    monkeypatch.setattr(paged_attend, "kernel_refusal", lambda *a: None)
    monkeypatch.setattr(paged_attend, "paged_kernel",
                        lambda: _reference_kernel)
    if window_start is not None:
        monkeypatch.setattr(paged_attend, "window_start", window_start)


#: lanes that cover the ring's cases at window 64, block 16: a lane in
#: its first window (positions 20-39), a lane whose ring has turned
#: (positions 90-109: entry 0 holds block 5 from the first step, at 90,
#: which is no block boundary; it crosses the boundary at 96), a lane
#: that retires after 6 tokens and idles, and a slot never used
KERNEL_PROMPTS = (20, 90, 70)
KERNEL_NEW = (20, 20, 6)


def _served_wide(prompts, new):
    spec = cohere2_moe_paged_spec(
        adapter.program_config(CFG_KERNEL),
        adapter.program_params(CFG_KERNEL, SEED))
    with PagedGenerativeServer(spec, max_slots=4, block_size=BS_KERNEL,
                               max_seq_len=128, buckets=[16, 64],
                               warmup=False, debug_leaks=True) as srv:
        assert srv._tiers[1].entries == 5
        toks, got = logits_served(srv, prompts, new)
    return toks, got, attention.last_decode_program().counts()


@pytest.fixture(scope="module")
def plain_served():
    prompts = [prompt(n, 40 + n) for n in KERNEL_PROMPTS]
    return prompts, _served_wide(prompts, KERNEL_NEW)


@pytest.mark.parametrize("start", ["window_start", "a_block_early"])
def test_the_kernel_path_gives_the_plain_paths_decode_logits(
        plain_served, monkeypatch, start):
    """The decode program through the kernel's inputs (the interleaved
    leaf, the global table as handed, the ring laid out from the oldest
    block the window sees with its rows shifted by as many blocks, idle
    lanes on one row of the null block), with the shipped reference in
    the kernel's place, gives the plain path's logits at every served
    token, within :data:`TOL` of their spread; the plain path is the
    CPU's own. Read from one block earlier (``last - 5`` where the ring
    has 5 entries: its newest block laid out first, every lane's rows
    shifted one block too far), the same comparison fails: the test sees
    a wrong ring order."""
    prompts, (want_toks, want, sites) = plain_served
    assert sites == (0, 4, "backend cpu")
    early = (lambda last, entries: last - entries) \
        if start == "a_block_early" else None
    _through_kernel(monkeypatch, early)
    toks, got, sites = _served_wide(prompts, KERNEL_NEW)
    assert sites == (4, 0, None)
    gaps = [off_by(g, w) for g, w in zip(got, want)]
    if start == "window_start":
        assert toks == want_toks
        assert max(gaps) < TOL, gaps
    else:
        # the lane in its first window reads its rows shifted past the
        # table's end, the turned lane its newest block as its oldest
        assert gaps[0] > TOL and gaps[1] > TOL, gaps


def test_the_cells_decode_shapes_pass_the_kernels_validation(monkeypatch):
    """The cell's decode program, traced at its own widths (32 lanes,
    128 query heads over 8 K/V heads of 128, blocks of 16 in bf16, the
    global table at its top rung of 672 entries and the ring of 257),
    hands the decode kernel inputs that the shipped ragged kernel's
    ``static_validate_inputs`` accepts, the leaf as its ``kv_pages``: the
    block is traced once a kind of layer, the window layers' with the
    window of 4,096."""
    import jax
    import jax.numpy as jnp
    import json
    import os
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import \
        kernel as rpa
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "command-a-plus-05-2026.json")) as f:
        pc = Cohere2MoeConfig.from_dict(json.load(f))
    calls = []

    def validate(q, leaf, pages, rows, reach, *, scale, per_block):
        kv_pages = leaf.reshape(leaf.shape[0], leaf.shape[1], -1,
                                q.shape[2])
        rpa.static_validate_inputs(
            q, kv_pages, rows, pages,
            jnp.arange(q.shape[0] + 1, dtype=jnp.int32),
            jnp.full((1,), q.shape[0], jnp.int32), sm_scale=scale,
            sliding_window=4096, num_kv_pages_per_block=per_block)
        calls.append((q.shape, q.dtype, kv_pages.shape, pages.shape,
                      reach.shape, per_block))
        return jnp.zeros(q.shape, q.dtype)

    monkeypatch.setattr(paged_attend, "kernel_refusal", lambda *a: None)
    monkeypatch.setattr(paged_attend, "paged_kernel", lambda: validate)
    S, width = 32, 2 * pc.num_kv_heads * pc.head_dim
    _, decode_fn = zoo.cohere2_moe_paged_decode_fns(pc, 16, 672)
    params = {n: jax.ShapeDtypeStruct(s, jnp.bfloat16)
              for n, s in cohere2_moe_param_shapes(pc).items()}
    kc = tuple(jax.ShapeDtypeStruct(((32 * 289 if w else 32 * 672) + 1,
                                     16, width), jnp.bfloat16)
               for w in pc.window_layout)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa
    io = {"tokens": i32(S), "positions": i32(S), "write_off": i32(S),
          "active": jax.ShapeDtypeStruct((S,), jnp.bool_),
          "tables.global": i32(S, 672), "tables.window": i32(S, 257),
          "write_block.global": i32(S), "write_block.window": i32(S)}
    out = jax.eval_shape(decode_fn, params, kc, (), io)
    assert out[2].shape == (S + len(PROGRAM_COUNTERS),)
    # the ring's 257 pages padded to the global table's 672, the window
    # a number: one kernel for both kinds of layer
    win = (S, 128, 128), jnp.bfloat16, (9249, 16, 16, 128), (S, 672), \
        (1,), paged_attend.KV_PAGES_PER_BLOCK
    glob = (S, 128, 128), jnp.bfloat16, (21505, 16, 16, 128), (S, 672), \
        (1,), paged_attend.KV_PAGES_PER_BLOCK
    assert calls == [win, glob]


def test_kv_pages_read_sum_is_a_count_by_hand(spec, monkeypatch):
    """One decode step of four lanes (window 8, blocks of 4: a ring of 3
    entries, a global table of 16), three active at positions 0, 37 and
    22 and one idle: the kernel path counts each active lane's own pages
    a layer, the global layer's up to its position and the ring's from
    the oldest block its window sees; the plain path counts every entry
    it gathered."""
    import jax
    import jax.numpy as jnp
    pc = adapter.program_config(CFG)
    tiers = pc.kv_tiers()
    S = 4
    io = {"tokens": np.array([5, 17, 40, 3], np.int32),
          "positions": np.array([0, 37, 22, 9], np.int32),
          "active": np.array([True, True, True, False]),
          "write_off": np.array([0, 1, 2, 0], np.int32)}
    for t in tiers:
        io[t.key("tables")] = np.zeros((S, t.table_blocks(BS, 16)),
                                       np.int32)
        io[t.key("write_block")] = np.array([1, 2, 3, 0], np.int32)
    leaf = jnp.zeros((8, BS, 2 * pc.num_kv_heads * pc.head_dim),
                     jnp.bfloat16)

    def read(kernel):
        if kernel:
            _through_kernel(monkeypatch)
        _, decode_fn = cohere2_moe_paged_spec(
            pc, spec.params()).make_fns(BS, 16)
        _, _, nxt, _ = jax.jit(decode_fn)(spec.params(), (leaf,) * 4, (),
                                          io)
        return dict(zip(PROGRAM_COUNTERS, np.asarray(nxt)[S:].tolist()))

    plain = read(False)["kv_pages_read_sum"]
    kernel = read(True)["kv_pages_read_sum"]
    # by hand: a lane at position p holds blocks 0 to p // 4 of the
    # global table; the ring of 3 is read from block max(0, p // 4 - 2)
    hand_global = sum(p // 4 + 1 for p in (0, 37, 22))       # 1 + 10 + 6
    hand_ring = sum(min(p // 4, 2) + 1 for p in (0, 37, 22))  # 1 + 3 + 3
    assert kernel == hand_global + 3 * hand_ring == 38
    assert plain == 3 * (16 + 3 * 3)


def test_the_one_leaf_keeps_the_cells_bytes_and_memory_report():
    """Command A+'s one interleaved leaf a layer against the K-and-V pair
    of before, at the cell's KV geometry (4 layers, one global and three
    window, 8 K/V heads of 128, window 4,096, blocks of 16, 10,752
    positions, bf16; the widths no leaf depends on cut, and one slot):
    the same bytes a token, a block and a pool, the same tiers, and the
    same ``memory_report`` but for the leaves' names and widths. A leaf
    without heads refuses ``tp`` typed."""
    import dataclasses
    import json
    import os
    from deeplearning4j_tpu.serving.paged import KVLeafUnsupportedError
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "command-a-plus-05-2026.json")) as f:
        cell = json.load(f)
    cut = dict(cell, hidden_size=64, intermediate_size=8, num_experts=2,
               vocab_size=64)
    one = cohere2_moe_paged_spec(adapter.program_config(cut),
                                 adapter.program_params(cut, SEED))
    pair = dataclasses.replace(one, kv_leaves=None)
    reports = []
    for sp in (one, pair):
        with PagedGenerativeServer(sp, max_slots=1, block_size=16,
                                   max_seq_len=10752, buckets=[16],
                                   warmup=False) as srv:
            reports.append(srv.memory_report())
    got, want = reports
    assert got["kv_leaves"] == {"kv": 2048}
    assert want["kv_leaves"] == {"k": 1024, "v": 1024}
    # 4 layers x 2,048 numbers x 2 bytes: the cell's 16 KB a token
    assert got["kv_bytes_per_token"] == want["kv_bytes_per_token"] \
        == 4 * 2048 * 2
    # the first leaf's arrays: one row of 2,048 numbers where K's was
    # 1,024 wide beside V's
    assert got["kv_slab_shape"][:3] == want["kv_slab_shape"][:3]
    assert (got["kv_slab_shape"][3], want["kv_slab_shape"][3]) \
        == (2048, 1024)
    names = ("kv_leaves", "kv_leaves_filled", "kv_slab_shape")
    for r in reports:
        for t in r["kv_tiers"].values():
            del t["leaves"]
    assert {k: v for k, v in got.items() if k not in names} \
        == {k: v for k, v in want.items() if k not in names}
    with pytest.raises(KVLeafUnsupportedError):
        PagedGenerativeServer(one, max_slots=1, block_size=16,
                              max_seq_len=64, buckets=[16], warmup=False,
                              tp=2)


@pytest.mark.parametrize("window,per_block", [(64, 1), (64, 2), (None, 2),
                                              (None, 5)])
def test_the_decode_kernel_gives_the_shipped_kernels_reference(window,
                                                               per_block):
    """The repo's decode kernel, run by the TPU interpreter on the CPU,
    against the shipped ragged kernel's reference over the same inputs
    (the leaf as its ``kv_pages``): 4 lanes of 32 query heads over 2 K/V
    heads of 128, blocks of 16, a ring of 5 (window 64) or a table of 5;
    a lane in its first window, one whose ring has turned, one at the
    end of a block, an idle one; a page or two a block, or the whole
    table in one. Within the rounding of the bf16 result."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import \
        ref_ragged_paged_attention
    R, KV, G, D, B, E, nb = 4, 2, 16, 128, 16, 5, 40
    rng = np.random.default_rng(7)
    leaf = jnp.asarray(rng.standard_normal((nb, B, 2 * KV * D)),
                       jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((R, KV * G, D)), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(np.arange(1, nb))[:R * E]
                        .reshape(R, E), jnp.int32)
    pos = jnp.asarray([20, 90, 79, 9] if window else [20, 70, 79, 0],
                      jnp.int32)
    active = jnp.asarray([True, True, True, False])
    wb = table[jnp.arange(R), (pos // B) % E]
    pages, rows = paged_attend.decode_pages(table, pos, active, wb, B,
                                            window is not None)
    want = np.asarray(ref_ragged_paged_attention(
        q, leaf.reshape(nb, B, 2 * KV, D), rows, pages,
        jnp.arange(R + 1, dtype=jnp.int32), jnp.asarray([R], jnp.int32),
        sm_scale=D ** -0.5, sliding_window=window), np.float32)
    reach = jnp.asarray([window or paged_attend._EVERY_ROW], jnp.int32)
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(paged_attend.paged_kernel()(
            q, leaf, pages, rows, reach, scale=D ** -0.5,
            per_block=per_block), np.float32)
    # bf16 output: 2**-9 of a value, a few deviations out
    assert np.abs(got - want).max() < 0.02 * want.std()
