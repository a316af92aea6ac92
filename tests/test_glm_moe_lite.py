"""GLM-4.7-Flash's block on the paged path at a tiny size (3 layers of
which the first dense, hidden 64, 4 heads with ranks 24/16 and head
widths 12 + 4 / 16, 8 experts top-2 with a shared one, a 61-row
vocabulary, block 4), seeded: the server's own programs against the plain
reference's full forward (the PUBLISHED form of latent attention), a
prompt in chunks against the same prompt in one run, the absorbed form
against the published one, the books of a pool that holds ONE row a
token, and that a spec of K and V is served as it always was."""
import dataclasses
import re
import time

import numpy as np
import pytest

from benchmark.adapters import glm4_moe_lite as adapter
from benchmark.reference import glm4_moe_lite as ref
from deeplearning4j_tpu.serving.paged import (KVLeaf, KVLeafUnsupportedError,
                                              PagedGenerativeServer)
from deeplearning4j_tpu.zoo.glm_moe_lite import (
    PROGRAM_COUNTERS, GlmMoeLiteConfig, glm_moe_lite_paged_spec,
    glm_moe_lite_param_names)

CFG = {"family": "glm4_moe_lite", "attention_bias": False,
       "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
       "max_position_embeddings": 128, "moe_intermediate_size": 48,
       "topk_method": "noaux_tc", "norm_topk_prob": True,
       "num_attention_heads": 4, "n_group": 1, "topk_group": 1,
       "n_routed_experts": 8, "n_shared_experts": 1,
       "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
       "first_k_dense_replace": 1, "num_hidden_layers": 3,
       "num_key_value_heads": 4, "num_nextn_predict_layers": 0,
       "partial_rotary_factor": 1, "rms_norm_eps": 1e-5,
       "rope_scaling": None, "rope_theta": 1000000,
       "tie_word_embeddings": False, "q_lora_rank": 24, "kv_lora_rank": 16,
       "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 16,
       "vocab_size": 61, "param_dtype": "bfloat16", "kv_dtype": "bfloat16"}
SEED = 2**31 + 5
BS, ROW = 4, 16 + 4


@pytest.fixture(scope="module")
def spec():
    return glm_moe_lite_paged_spec(adapter.program_config(CFG),
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
    pc = GlmMoeLiteConfig.from_dict(CFG)
    assert (pc.num_layers, pc.num_heads, pc.first_dense_layers) == (3, 4, 1)
    assert (pc.q_lora_rank, pc.kv_lora_rank, pc.row_width) == (24, 16, ROW)
    assert (pc.num_experts, pc.experts_per_token, pc.routed_scale) == \
        (8, 2, 1.8)
    assert [pc.is_dense(i) for i in range(3)] == [True, False, False]
    names = glm_moe_lite_param_names(pc)
    # 9 of attention and the norms a layer; 3 of a dense layer; a router,
    # its bias, three of the experts and three of the shared one
    assert len(names) == 3 + 3 * 9 + 3 + 2 * 8 and len(set(names)) == \
        len(names)
    assert set(names) == set(adapter.program_params(CFG, 1))
    for key, value in (("num_nextn_predict_layers", 1),
                       ("tie_word_embeddings", True), ("n_group", 2),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key.split("_")[0]):
            GlmMoeLiteConfig.from_dict(dict(CFG, **{key: value}))


def test_prefill_then_decode_agrees_in_logits_with_the_reference(spec):
    """Three requests side by side, two of them prompts in chunks, each
    decoded past the first rung of the table's widths (8 entries of 4:
    32 positions): the logits every served token was chosen from against
    the reference's full forward, in the PUBLISHED form, over the same
    tokens.

    The tolerance: the program rounds every product's operands to
    bfloat16 (2**-9 relative each), carries its queries into the latent
    space in bfloat16 and caches the latent rows in bfloat16; the
    reference computes in float32. Through 3 layers that is under a
    hundredth of the logits' spread (0.007-0.009 read), so 0.03 of their
    standard deviation; float8 operands read 0.16-0.33 and fail it, and
    so do the router's rules got wrong (no bias in the choice 0.6-0.9, a
    scale of 1 for 1.8 0.2-0.3)."""
    prompts = [prompt(7, 1), prompt(21, 2), prompt(30, 3)]
    with server(spec) as srv:
        assert srv._tiers[0].widths == (8, 16, 16)
        toks, got = logits_served(srv, prompts, 30)
        c = dict(srv.metrics.counters)
    seqs = [np.concatenate([p, t])[:-1] for p, t in zip(prompts, toks)]
    spans = [np.arange(len(p) - 1, len(p) + len(t) - 1)
             for p, t in zip(prompts, toks)]
    want = ref.logits(CFG, SEED, seqs, spans)
    wrong = {}
    for name in ("float8", "bias_off", "scale_off"):
        bad, mode = ref.control_of(CFG, name)
        wrong[name] = ref.logits(bad, SEED, seqs, spans, mode)
    for r, (g, w) in enumerate(zip(got, want)):
        w = np.asarray(w)
        tol = 0.03 * w.std()
        assert g.shape == w.shape and len(seqs[r]) > 32
        assert np.abs(g - w).max() < tol
        for name, lo in wrong.items():
            assert np.abs(np.asarray(lo[r]) - w).max() > tol, name
    # what the decode program counted: two expert layers a step, two
    # experts a token and layer, and a bias that steers
    assert spec.program_counters == PROGRAM_COUNTERS
    assert c["moe_layer_steps"] == 2 * c["decode_steps"]
    assert c["moe_tokens_routed_sum"] == 2 * 2 * c["slots_active_sum"]
    assert 0 < c["moe_experts_touched_sum"] <= c["moe_tokens_routed_sum"]
    assert c["moe_layer_steps"] <= c["moe_peak_expert_tokens_sum"] \
        <= 2 * c["slots_active_sum"]
    assert 0 < c["moe_bias_moved_sum"] <= c["moe_tokens_routed_sum"]


def test_a_prompt_in_chunks_gives_the_logits_of_one_run(spec):
    """30 tokens through buckets of 4 and 8 (four runs, ``hist``
    advancing) and through one bucket of 32: the same latent rows reach
    the same queries. A run weighs its cached rows under their own
    largest score and its fresh rows under the joint one, and rounds the
    weights to bfloat16 for the product, so the two ways differ by that
    rounding (2**-9 of a weight): 0.005 of the logits' spread was read,
    0.02 is allowed; a row lost or read twice moves them by tenths."""
    p = prompt(30, 7)
    with server(spec, buckets=(4, 8)) as srv:
        t1, l1 = logits_served(srv, [p], 6)
        assert srv.metrics.counters["prefill_runs"] == 4
        assert srv.metrics.counters["prefills"] == 1
    with server(spec, buckets=(32,)) as srv:
        t2, l2 = logits_served(srv, [p], 6)
        assert srv.metrics.counters["prefill_runs"] == 1
    assert t1 == t2
    np.testing.assert_allclose(l1[0], l2[0], rtol=0,
                               atol=0.02 * l2[0].std())


def test_a_chunk_of_512_tokens_takes_the_tiled_grouped_product():
    """The one long run of the paged path, a prefill chunk of
    ``TILED_RUN_TOKENS``, names ``parallel.moe.tiled_grouped_dot`` where
    the widths fill its tiles (hidden and expert width 512 here, 2 layers,
    4 experts top-2: 1,024 sorted rows); shorter runs and the decode step
    keep ``ragged_dot``. The kernel is interpreted on the CPU: a prompt of
    600 tokens through a chunk and a tail, then decoded, agrees in logits
    with the reference as the tiny block does."""
    import jax

    from deeplearning4j_tpu.zoo import glm_moe_lite
    wide = dict(CFG, hidden_size=512, moe_intermediate_size=512,
                n_routed_experts=4, num_hidden_layers=2,
                max_position_embeddings=1024)
    sp = glm_moe_lite_paged_spec(adapter.program_config(wide),
                                 adapter.program_params(wide, SEED))
    named = []
    real = glm_moe_lite.tiled_grouped_dot
    glm_moe_lite.tiled_grouped_dot = \
        lambda lhs, *a: named.append(lhs.shape[0]) or real(lhs, *a)
    p = prompt(600, 5)
    try:
        with PagedGenerativeServer(
                sp, max_slots=2, block_size=BS, max_seq_len=640,
                buckets=[8, 128, 512], warmup=False) as srv:
            toks, got = logits_served(srv, [p], 4)
            assert srv.metrics.counters["prefill_runs"] == 2
    finally:
        glm_moe_lite.tiled_grouped_dot = real
    # the chunk's three products and nobody else's (the tail is a run of
    # 128 tokens, a decode step one of 2)
    assert named == [1024] * 3
    seq = np.concatenate([p, toks[0]])[:-1]
    span = np.arange(len(p) - 1, len(seq))
    want = np.asarray(ref.logits(wide, SEED, [seq], [span])[0])
    assert np.abs(got[0] - want).max() < 0.03 * want.std()


def test_a_run_reads_its_cached_rows_a_span_at_a_time(spec, monkeypatch):
    """At the cell's size a prefill run reads its cached rows 128 table
    entries at a time, as many spans as ``hist`` needs, in a loop whose
    length the device reads off ``hist``. Here a span is 2 entries (8
    positions) of the 16: a prompt of 30 through buckets of 4 and 8 runs
    the loop 0, 1, 2 and 3 times, and gives the logits of the one read
    of the whole table and the reference's."""
    import jax

    from deeplearning4j_tpu.zoo import glm_moe_lite
    p = prompt(30, 7)
    with server(spec) as srv:
        t1, l1 = logits_served(srv, [p], 6)
    monkeypatch.setattr(glm_moe_lite, "PREFILL_SPAN", 2)
    spanned = glm_moe_lite_paged_spec(adapter.program_config(CFG),
                                      spec.params())
    prefill_fn, _ = spanned.make_fns(BS, 16)
    pre_io, _ = _programs_io()
    params = {n: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
              for n, a in spec.params().items()}
    side = tuple(jax.ShapeDtypeStruct((9, BS, ROW),
                                      spec.params()["embed"].dtype)
                 for _ in range(3))
    assert "while" in str(jax.make_jaxpr(prefill_fn)(params, side, (),
                                                     pre_io))
    with server(spanned) as srv:
        t2, l2 = logits_served(srv, [p], 6)
        assert srv.metrics.counters["prefill_runs"] == 4
    assert t1 == t2
    np.testing.assert_allclose(l1[0], l2[0], rtol=0,
                               atol=0.02 * l1[0].std())
    seq = np.concatenate([p, t2[0]])[:-1]
    (want,) = ref.logits(CFG, SEED, [seq], [np.arange(29, 35)])
    assert np.abs(l2[0] - np.asarray(want)).max() < \
        0.03 * np.asarray(want).std()


def _programs_io(S=3, entries=8, bucket=8):
    import jax
    import jax.numpy as jnp
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa
    return ({"tokens": i32(bucket), "length": i32(), "hist": i32(),
             "table": i32(16)},
            {"tokens": i32(S), "positions": i32(S), "write_off": i32(S),
             "write_block": i32(S), "tables": i32(S, entries),
             "active": jax.ShapeDtypeStruct((S,), jnp.bool_)})


def test_the_absorbed_and_the_published_form_give_the_same_numbers(spec):
    """The program attends absorbed, the reference in the published form
    (the context expanded to per-head K and V through ``Wkvb``). With the
    parameters raised to float32 (the same values: the reference raises
    them too) and the rows cached in float32, only the order of the
    products differs: a prompt of 21 rows, the last 8 as a second run
    behind 13 cached ones through a shuffled table, gives the reference's
    logits to rounding (2e-5 of their spread; in bfloat16 the forms round
    at different places and the comparison above holds them to 0.03)."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.permutation(np.arange(1, 17)), jnp.int32)
    seq = prompt(21, 5)
    params = {n: a.astype(jnp.float32) for n, a in spec.params().items()}
    kc = tuple(jnp.asarray(rng.normal(size=(17, BS, ROW)), jnp.float32)
               for _ in range(3))
    prefill_fn, _ = spec.make_fns(BS, 16)
    run = jax.jit(prefill_fn)

    def io(rows, hist, bucket):
        toks = np.zeros(bucket, np.int32)
        toks[:len(rows)] = rows
        return {"tokens": jnp.asarray(toks), "length": jnp.int32(len(rows)),
                "hist": jnp.int32(hist), "table": table}

    mid, vc, _, _ = run(params, kc, (), io(seq[:13], 0, 16))
    out, vc, tok, got = run(params, mid, vc, io(seq[13:], 13, 8))
    assert vc == ()
    (want,) = ref.logits(CFG, SEED, [seq], [np.asarray([20])])
    want = np.asarray(want)[0]
    assert np.abs(np.asarray(got) - want).max() < 2e-5 * want.std()
    assert int(tok) == int(want.argmax())
    # the second run's 8 rows went where the table says (position 13 is
    # row 1 of the table's entry 3), nothing went into the null block
    # (no padding in a full bucket), and nothing else was written
    wrote = np.asarray(out[0] != mid[0]).any(axis=-1)
    assert wrote.sum() == 8 and not wrote[0].any()
    assert wrote[np.asarray(table)[3], 1] and wrote[np.asarray(table)[5], 0]


_SHAPE = re.compile(r"(?:bf16|f32|f16)\[([0-9,]+)\]")


def _per_head_context_arrays(text, T, heads, widths):
    """Arrays of the optimised program that run over ``T`` context rows
    AND hold a per-head K or V: ``[.., T, .., heads, .., width]`` or the
    heads and the width as one axis."""
    found = set()
    for dims in _SHAPE.findall(text):
        d = [int(x) for x in dims.split(",")]
        if T in d and ((heads in d and any(w in d for w in widths))
                       or any(heads * w in d for w in widths)):
            found.add(tuple(d))
    return found


def test_the_programs_attend_absorbed_and_expand_no_context(spec):
    """In the optimised decode program, and in the prefill program, no
    array of the context's length (10 entries of 4: 40 rows a lane, a
    number no width of the model equals) has a per-head K or V width (4
    heads of 12, 16 or 12 + 16): every head reads the same 20-wide rows.
    The same search FINDS such arrays in the reference's layer, which
    takes the published form, so it can see them."""
    import jax
    import jax.numpy as jnp
    params = {n: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
              for n, a in spec.params().items()}
    side = tuple(jax.ShapeDtypeStruct((9, BS, ROW),
                                      spec.params()["embed"].dtype)
                 for _ in range(3))
    pre_io, dec_io = _programs_io(entries=10)
    pre_io["table"] = jax.ShapeDtypeStruct((10,), jnp.int32)
    T, heads, widths = 40, 4, (12, 16, 12 + 16)

    def optimised(fn, io):
        return jax.jit(fn).lower(params, side, (), io).compile().as_text()

    prefill_fn, decode_fn = spec.make_fns(BS, 16)
    text = optimised(decode_fn, dec_io)
    assert f"[3,{T},{ROW}]" in text                  # the gathered rows
    assert not _per_head_context_arrays(text, T, heads, widths)
    assert not _per_head_context_arrays(optimised(prefill_fn, pre_io), T,
                                        heads, widths)
    layer = {k: ref.draw(CFG, SEED, k, 2) for k in ref.layer_kinds(CFG, 1)}
    published = ref._layer.lower(
        jnp.zeros((T, CFG["hidden_size"]), jnp.float32), layer, heads, 12,
        4, 2, 1.8, 1e6, 1e-5, "float32").compile().as_text()
    assert _per_head_context_arrays(published, T, heads, widths)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_both_programs_call_the_one_block_a_kind_of_layer(spec, program):
    """One layer function for both programs, jitted on its own: a
    program's trace holds it once a kind of layer (dense, experts) and
    calls it ``num_layers`` times."""
    import jax
    pre_io, dec_io = _programs_io()
    prefill_fn, decode_fn = spec.make_fns(BS, 16)
    fn, io = (decode_fn, dec_io) if program == "decode" \
        else (prefill_fn, pre_io)
    params = {n: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
              for n, a in spec.params().items()}
    side = tuple(jax.ShapeDtypeStruct((9, BS, ROW),
                                      spec.params()["embed"].dtype)
                 for _ in range(3))
    jaxpr = jax.make_jaxpr(fn)(params, side, (), io)
    blocks = [e for e in jaxpr.eqns if e.params.get("name") == "_block"]
    assert len(blocks) == 3
    assert len({id(e.params["jaxpr"]) for e in blocks}) == 2
    with pytest.raises(ValueError, match="entries"):
        jax.eval_shape(decode_fn, params, side, (),
                       _programs_io(entries=17)[1])


def test_the_pool_holds_one_row_a_token_and_every_block_comes_back(spec):
    """3 layers x 4 tokens x 20 numbers x 2 bytes a block, one leaf a
    layer and nothing on the second side; every block is free again
    after the last request."""
    assert spec.kv_leaves == (KVLeaf("latent", ROW, filled=ROW),)
    with server(spec) as srv:
        assert srv.bytes_per_block == 3 * BS * ROW * 2 == 480
        assert srv.kv_bytes_per_token == 3 * ROW * 2
        blocks = 1 + 3 * 16
        assert srv.kv_slab_bytes == blocks * 480
        assert srv._vc == () and len(srv._kc) == 3
        assert {tuple(leaf.shape) for leaf in srv._kc} == \
            {(blocks, BS, ROW)}
        rep = srv.memory_report()
        assert rep["kv_leaves"] == rep["kv_leaves_filled"] == {"latent": ROW}
        assert rep["kv_bytes_per_token"] == 120 \
            == rep["kv_bytes_per_token_filled"]
        assert rep["kv_bytes_per_block"] == 480
        assert rep["kv_slab_shape"] == [3, blocks, BS, ROW]
        assert srv.metrics.to_record()["paged"]["kv_bytes_per_token"] == 120
        # a pair of heads' K and V at these widths would cost 4 x (16 +
        # 16) x 2 numbers a token and layer: 12.8 times the latent row
        old = srv._kc
        hs = [srv.submit(prompt(n, n), max_new_tokens=20)
              for n in (30, 3, 25, 9)]
        for h in hs:
            h.result(timeout=300)
        while srv._n_active():
            time.sleep(0.005)
        # every dispatch donated the leaves and took new ones back
        assert all(leaf.is_deleted() for leaf in old)
        assert srv._vc == () and len(srv._kc) == 3
        c = srv.metrics.counters
        assert c["blocks_allocated"] == c["blocks_released"] > 0
        (tier,) = srv._tiers
        assert tier.pool.held_count() == 0 and not tier.stop.any()
        assert tier.pool.free_count() + len(tier.pool._evictable) == \
            tier.pool.capacity
        assert srv._committed == 0
        assert srv._worst_case_blocks(30, 20) == [13]


def test_one_step_ahead_serves_what_the_synchronous_loop_serves(spec):
    """ISSUE 33 over the latent pool: three lanes on three slots from
    the first step, with the decode loop one step ahead and with every
    boundary held synchronous (the loop of before, over the same
    programs and lanes). The step launched ahead takes the packed
    array's first entries as its tokens, cut on the device, and its
    block from positions alone. Tokens, logits and the router's own
    counts are the same, to the bit; the pool invariant is checked at
    every step (``debug_leaks``)."""
    prompts = [prompt(5, 1), prompt(21, 2), prompt(9, 3)]
    n = 22
    runs = []
    for ahead in (True, False):
        with server(spec, start=False) as srv:
            if not ahead:
                srv._may_run_ahead = lambda: False
            toks, lg = logits_served(srv, prompts, n)
            while srv._n_active() or srv._ahead is not None:
                time.sleep(0.005)
            c = dict(srv.metrics.counters)
            (tier,) = srv._tiers
            assert tier.pool.held_count() == 0 and not tier.stop.any()
        assert c["decode_steps"] == n - 1
        # every lane has the same budget: steps 2 to n - 1 run ahead
        assert c["decode_ahead_steps"] == (n - 2 if ahead else 0)
        assert c["blocks_allocated"] == c["blocks_released"]
        runs.append((toks, lg, {k: v for k, v in c.items()
                                if k.startswith(("moe_", "blocks_",
                                                 "decode_table"))}))
    (t1, l1, c1), (t2, l2, c2) = runs
    assert t1 == t2 and c1 == c2 and c1["moe_layer_steps"] > 0
    for a, b in zip(l1, l2):
        assert np.array_equal(a, b)


def test_a_row_wider_than_a_lane_tile_is_laid_out_in_whole_tiles():
    """A row under the TPU's 128 lanes is cached as wide as it is (the
    tiny block's 20); from there on in whole tiles, the rest zeros (the
    published 576 -> 640; 144 -> 256 here). The pool is sized by the
    laid-out row, the report says what the model fills of it, and the
    padding reaches no logit."""
    pc = adapter.program_config
    assert pc(CFG).leaf_width == pc(CFG).row_width == ROW
    assert pc(dict(CFG, kv_lora_rank=124)).leaf_width == 128
    assert pc(dict(CFG, kv_lora_rank=512, qk_rope_head_dim=64,
                   qk_nope_head_dim=12)).leaf_width == 640
    wide = dict(CFG, kv_lora_rank=140)
    sp = glm_moe_lite_paged_spec(pc(wide), adapter.program_params(wide, SEED))
    assert sp.kv_leaves == (KVLeaf("latent", 256, filled=144),)
    p = prompt(21, 4)
    with server(sp) as srv:
        rep = srv.memory_report()
        assert rep["kv_leaves"] == {"latent": 256}
        assert rep["kv_leaves_filled"] == {"latent": 144}
        assert rep["kv_bytes_per_token"] == 3 * 256 * 2 \
            == srv.bytes_per_block // BS
        assert rep["kv_bytes_per_token_filled"] == 3 * 144 * 2
        toks, got = logits_served(srv, [p], 12)
    seq = np.concatenate([p, toks[0]])[:-1]
    want = np.asarray(ref.logits(
        wide, SEED, [seq], [np.arange(len(p) - 1, len(seq))])[0])
    assert np.abs(got[0] - want).max() < 0.03 * want.std()


def test_a_prefix_the_cache_holds_is_not_prefilled_again(spec):
    """One tier that keeps every block: the prefix cache serves the
    latent pool as it is (``hist`` rows are read from the cached blocks,
    the suffix runs)."""
    p = prompt(26, 11)
    with server(spec) as srv:
        assert srv.prefix_cache_enabled
        first = srv.submit(p, max_new_tokens=6).result(timeout=300)
        again = srv.submit(p, max_new_tokens=6).result(timeout=300)
        assert first == again
        assert srv.metrics.counters["prefix_blocks_hit"] == 6


def test_a_leaf_without_heads_refuses_tp_int8_and_a_draft_typed(spec):
    from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                            gpt_generative_spec)
    with pytest.raises(KVLeafUnsupportedError, match="'latent'.*tp=2"):
        server(spec, tp=2, start=False)
    with pytest.raises(KVLeafUnsupportedError, match="'latent'.*int8"):
        server(dataclasses.replace(spec, kv_dtype="int8"), start=False)
    cfg = GPTConfig(vocab_size=CFG["vocab_size"], hidden_size=32,
                    num_layers=1, num_heads=2, intermediate_size=64,
                    max_seq_len=128)
    draft = gpt_generative_spec(build_gpt(cfg, batch=1, seq_len=8, seed=0),
                                cfg)
    with pytest.raises(KVLeafUnsupportedError, match="'latent'.*draft"):
        server(spec, draft_spec=draft, start=False)
    with pytest.raises(ValueError, match="one or two leaves"):
        server(dataclasses.replace(spec, kv_leaves=(
            KVLeaf("a", 4), KVLeaf("b", 4), KVLeaf("c", 4))), start=False)


# ----------------------------------------------------------------------
# K and V: GPT-2's pool is what it was
def test_a_spec_of_k_and_v_gets_the_pair_it_always_got():
    from deeplearning4j_tpu.serving.generative import greedy_decode
    from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                            gpt_generative_spec,
                                            gpt_paged_spec)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, intermediate_size=64, max_seq_len=32)
    sd = build_gpt(cfg, batch=2, seq_len=8, seed=0)
    spec = gpt_paged_spec(sd, cfg)
    assert spec.kv_leaves is None
    ios = {"serving.prefill": [], "serving.decode": []}
    with PagedGenerativeServer(spec, max_slots=2, max_seq_len=32,
                               block_size=8, warmup=True,
                               debug_leaks=True) as srv:
        assert srv._kv_leaves == (KVLeaf("k", 32, 2), KVLeaf("v", 32, 2))
        # 2 x layers x heads x block x head size x 4 bytes, as ever
        assert srv.bytes_per_block == 2 * 2 * 2 * 8 * 16 * 4
        assert srv.kv_bytes_per_token == srv.bytes_per_block // 8
        blocks = srv.pool.num_blocks
        assert srv.kv_slab_bytes == blocks * srv.bytes_per_block
        assert len(srv._kc) == len(srv._vc) == 2
        assert {tuple(a.shape) for a in srv._kc + srv._vc} == \
            {(blocks, 8, 32)}
        rep = srv.memory_report()
        assert rep["kv_slab_shape"] == [2, blocks, 8, 32]
        assert rep["kv_leaves"] == {"k": 32, "v": 32}
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
