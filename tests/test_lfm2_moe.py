"""LFM2-8B-A1B on the paged path at a tiny size (8 layers: conv, conv,
attention, conv, conv, conv, attention, conv; two dense, six with 8
experts, top-2; hidden 64, 4 heads over 2 of 16; a vocabulary of 256),
seeded: the server's own programs and the whole-sequence forward against
the plain reference in LOGITS (prompts in chunks, prompts of one and two
tokens and of no bucket's length, a slot reused behind a step launched
ahead), the controls that must fail the same tolerance, the per-lane
state store's rules held at the programs themselves, the refusals, the
program's and the server's counters against counts by hand, the decode
read through the kernel's inputs, and the cell's parameters and memory
with nothing allocated."""
import json
import os

import numpy as np
import pytest

from benchmark.adapters import lfm2_moe as adapter
from benchmark.counts import lfm2_moe as counts
from benchmark.reference import lfm2_moe as ref
from deeplearning4j_tpu.monitor import attention
from deeplearning4j_tpu.serving.paged import (PagedGenerativeServer,
                                              PrefixCacheUnsupportedError,
                                              StateUnsupportedError)
from deeplearning4j_tpu.zoo import paged_attend
from deeplearning4j_tpu.zoo.lfm2_moe import (PROGRAM_COUNTERS,
                                             Lfm2MoeConfig,
                                             Lfm2MoeUnsupportedError,
                                             lfm2_moe_forward,
                                             lfm2_moe_paged_spec,
                                             lfm2_moe_param_shapes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the convolution's leaves are drawn with std 0.08 (the reference's
#: ``conv_std``): at hidden 64 that gives its output the size that 0.02
#: gives it at the published 2,048, where 0.02 here would leave it a
#: thousandth of the stream and no fault of the convolution would show
CFG = {"family": "lfm2_moe", "hidden_size": 64, "num_attention_heads": 4,
       "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "num_experts": 8, "num_experts_per_tok": 2, "num_dense_layers": 2,
       "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                       "conv", "full_attention", "conv"],
       "num_hidden_layers": 8, "norm_eps": 1e-5, "rope_theta": 1e6,
       "routed_scaling_factor": 1, "norm_topk_prob": True,
       "use_expert_bias": True, "conv_std": 0.08, "vocab_size": 256,
       "max_position_embeddings": 128, "param_dtype": "bfloat16",
       "kv_dtype": "bfloat16"}
SEED = 2**31 + 5
BS = 4
#: the tolerance in logits, in standard deviations of the reference's
#: logits. The program rounds every product's operands to bfloat16 (2**-9
#: relative each), caches K, V and the convolution's inputs in bfloat16,
#: the reference computes in float32: through 8 layers that reads 0.05 to
#: 0.09 of a deviation on these seeds (the bfloat16 control as much; the
#: convolution multiplies three rounded factors). Every control reads at
#: least 0.45 (the bias off, the nearest; float8 1.1 to 1.6, the q/k norms
#: off 1.7 and more, the taps reversed or the state dropped 3 and more): a
#: tolerance of 0.2 leaves twice the program's reading on either side
TOL = 0.2


@pytest.fixture(scope="module")
def pc():
    return adapter.program_config(CFG)


@pytest.fixture(scope="module")
def spec(pc):
    return lfm2_moe_paged_spec(pc, adapter.program_params(CFG, SEED))


def server(spec, buckets=(4, 8), max_slots=3, **kw):
    return PagedGenerativeServer(spec, max_slots=max_slots, block_size=BS,
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


def reference_of(prompts, toks):
    """The reference's logits at the positions that chose each served
    token: ``prompt + served`` without its last token, at every position
    from the prompt's last on."""
    seqs = [np.concatenate([p, t])[:-1] for p, t in zip(prompts, toks)]
    spans = [np.arange(len(p) - 1, len(p) + len(t) - 1)
             for p, t in zip(prompts, toks)]
    return ref.logits(CFG, SEED, seqs, spans)


# -- the configuration ----------------------------------------------------
def test_the_config_reads_the_published_keys_and_names_every_leaf(pc):
    assert (pc.num_layers, pc.num_heads, pc.num_kv_heads, pc.head_dim) == \
        (8, 4, 2, 16)
    assert pc.attention_layers == (2, 6)
    assert pc.conv_layers == (0, 1, 3, 4, 5, 7)
    assert [pc.is_dense(i) for i in range(8)] == [True] * 2 + [False] * 6
    shapes = lfm2_moe_param_shapes(pc)
    assert set(shapes) == set(adapter.program_params(CFG, 1))
    assert "lm_head" not in shapes                     # tied
    assert shapes["h0/conv/in"] == (64, 3 * 64)
    assert shapes["h0/conv/w"] == (64, 3)
    assert shapes["h2/attn/q_norm"] == (16,) == shapes["h2/attn/k_norm"]
    assert shapes["h0/mlp/gate"] == (64, 96)
    assert shapes["h3/experts/down"] == (8, 32, 64)
    assert shapes["h3/router_bias"] == (8,)


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("use_expert_bias", False),
    ("norm_topk_prob", False), ("tie_word_embeddings", False),
    ("layer_types", ["conv", "sliding_attention"] * 4),
    ("num_experts_per_tok", 9)])
def test_what_the_block_does_not_compute_is_refused_by_its_key(key, value):
    with pytest.raises(Lfm2MoeUnsupportedError) as e:
        Lfm2MoeConfig.from_dict(dict(CFG, **{key: value}))
    assert e.value.key == key


# -- the block against the reference, in logits --------------------------
def test_the_forward_agrees_in_logits_with_the_reference(pc):
    """The block over 40 tokens with nothing cached and a state of zeros
    against the reference's full forward; the routers' own counts come
    back beside the logits."""
    toks = prompt(40, 11)
    got, _, did = lfm2_moe_forward(pc, adapter.program_params(CFG, SEED),
                                   toks)
    want = ref.logits(CFG, SEED, [toks])[0]
    assert got.shape == (40, CFG["vocab_size"])
    assert off_by(got, want) < TOL
    did = np.asarray(did)
    # [expert layers, E + 1]: each routed 2 of 8 for each of 40 tokens
    assert did.shape == (6, 9) and (did[:, :-1].sum(axis=1) == 80).all()


def test_prefill_in_chunks_then_decode_agrees_with_the_reference(spec):
    """Three requests side by side, the prompts of 1, 2 and 21 tokens (no
    bucket's length, the last through runs of at most 8: 8 + 8 + 5, each
    conv layer starting the next run from the state the run before
    left), each decoded 20 tokens through the state store and the pool:
    the logits every served token was chosen from against the reference's
    full forward over the same tokens (:data:`TOL` says why that
    tolerance)."""
    prompts = [prompt(1, 1), prompt(2, 2), prompt(21, 3)]
    with server(spec) as srv:
        toks, got = logits_served(srv, prompts, 20)
        c = dict(srv.metrics.counters)
    assert c["state_lanes_started"] == 3 and c["state_runs_carried"] == 2
    for g, w in zip(got, reference_of(prompts, toks)):
        assert g.shape == w.shape == (20, CFG["vocab_size"])
        assert off_by(g, w) < TOL


def test_a_prompt_in_chunks_of_4_gives_the_logits_of_one_run(spec):
    """30 tokens through runs of 4 (8 runs, 7 of them reading their first
    taps from the store and their cached rows two table entries at a
    time) and through one run of 32: the same K, V and convolution inputs
    reach the same queries. The ways differ by how the running softmax
    rounds its weights (2**-9 of a weight), as the other families' runs
    do: 0.05 of the logits' spread is allowed, and a state lost at a
    chunk's edge moves them by whole deviations (the ``state_dropped``
    control)."""
    p = prompt(30, 7)
    with server(spec, buckets=(4,)) as srv:
        t1, l1 = logits_served(srv, [p], 6)
        assert srv.metrics.counters["prefill_runs"] == 8
        assert srv.metrics.counters["state_runs_carried"] == 7
    with server(spec, buckets=(32,)) as srv:
        t2, l2 = logits_served(srv, [p], 6)
        assert srv.metrics.counters["prefill_runs"] == 1
    assert t1 == t2
    np.testing.assert_allclose(l1[0], l2[0], rtol=0,
                               atol=0.05 * l2[0].std())


def _first_new(stream, avoid):
    """The index of the first token of ``stream`` from the third on that
    appears nowhere before it nor in any of ``avoid``."""
    for j in range(2, len(stream)):
        if stream[j] not in stream[:j] and all(stream[j] not in a
                                               for a in avoid):
            return j
    return None


def test_a_slot_reused_behind_a_step_launched_ahead_starts_from_zeros(
        spec, pc):
    """Two slots, both full, so the decode loop runs one step ahead. One
    request ends at an end-of-sequence token the server learns of only
    when that step's tokens are read, with the step after it already in
    the air: that step still writes the ended lane's state row. A third
    request then takes the slot; its first run starts at position 0 and
    reads zeros, not the row, with no program to reset it: its logits
    agree with the reference's."""
    import dataclasses
    a, c, b = prompt(5, 21), prompt(9, 22), prompt(6, 23)
    with server(spec, max_slots=2) as srv:
        plain, _ = logits_served(srv, [a, c], 16)
    j = _first_new(plain[0], [plain[1]])
    assert j is not None, plain
    eos = int(plain[0][j])
    ended = dataclasses.replace(spec, eos_id=eos)
    in_air = []
    with server(ended, max_slots=2) as srv:
        retire = srv._retire

        def noting(s, **kw):
            if srv._slot_reqs[s] is not None:
                in_air.append(srv._unemitted is not None)
            return retire(s, **kw)

        srv._retire = noting
        toks, got = logits_served(srv, [a, c, b], 16)
        ahead = srv.metrics.counters["decode_ahead_steps"]
    assert toks[0] == plain[0][:j + 1]
    # the first to end did so with the step after it in the air
    assert in_air[0] and ahead > 0
    want = reference_of([b], [toks[2]])[0]
    assert off_by(got[2], want) < TOL


@pytest.fixture(scope="module")
def long_rows():
    toks = [prompt(40, 11), prompt(33, 12)]
    return toks, ref.logits(CFG, SEED, toks)


#: why each control must fail: it is the reference put in the program's
#: place with one thing wrong that a program could get wrong
CONTROL_WHY = {
    "float8": "one precision below the bfloat16 the configuration states",
    "conv_taps_reversed": "tap k applied to v_{t-k}: the kernel laid out "
                          "the wrong way round",
    "bias_off": "the choice by the bare sigmoids, the expert bias left out",
    "state_dropped_8": "every run of a prompt after the first (chunks of "
                       "8) starting its convolutions from zeros",
    "qk_norm_off": "q and k rotated without their per-head norms",
}


@pytest.mark.parametrize("control", sorted(CONTROL_WHY))
def test_a_control_fails_the_tolerance(long_rows, control):
    """The program passes :data:`TOL` on these rows (the forward test);
    a control does not (:data:`CONTROL_WHY`)."""
    toks, want = long_rows
    mode, variant, chunk = ref.control_of(control)
    segs = None if chunk is None else [
        ref._segments(len(t), len(t), chunk) for t in toks]
    low = ref.logits(CFG, SEED, toks, mode=mode, variant=variant, segs=segs)
    assert max(off_by(lo, w) for lo, w in zip(low, want)) > TOL, \
        CONTROL_WHY[control]


# -- the state store's rules, at the programs ------------------------------
def _programs(spec, pc, slots=3, entries=16, blocks=20):
    import jax
    import jax.numpy as jnp
    prefill_fn, decode_fn = spec.make_fns(BS, entries)
    leaf = tuple(jnp.zeros((blocks, BS, 2 * 2 * 16), jnp.bfloat16)
                 for _ in pc.attention_layers)
    rng = np.random.default_rng(5)
    state = (tuple(jnp.asarray(rng.normal(size=(slots + 1, 2, 64)),
                               jnp.bfloat16) for _ in pc.conv_layers),)
    return jax.jit(prefill_fn), jax.jit(decode_fn), leaf, state


def _prefill_io(tokens, bucket, hist, slot, table):
    toks = np.zeros(bucket, np.int32)
    toks[:len(tokens)] = tokens
    return {"tokens": toks, "length": np.int32(len(tokens)),
            "hist": np.int32(hist), "table": np.asarray(table, np.int32),
            "slot": np.int32(slot)}


def test_a_run_at_position_0_reads_zeros_and_keeps_its_last_real_inputs(
        spec, pc):
    """A prefill at ``hist`` 0 reads zeros whatever the slot's row holds
    (a store of noise and a store of zeros give the same logits), writes
    the slot's row alone, and leaves in it the ``v`` of its last two REAL
    positions: the same row whatever the bucket's padding holds."""
    import jax.numpy as jnp
    prefill, _, leaf, noisy = _programs(spec, pc)
    params = spec.params()
    table = np.arange(1, 17)
    zeros = (tuple(jnp.zeros_like(a) for a in noisy[0]),)
    io = _prefill_io(prompt(5, 3), 8, 0, 1, table)
    _, _, tok_n, lg_n, st_n = prefill(params, leaf, (), io, noisy)
    _, _, tok_z, lg_z, st_z = prefill(params, leaf, (), io, zeros)
    assert int(tok_n) == int(tok_z)
    np.testing.assert_array_equal(np.asarray(lg_n), np.asarray(lg_z))
    for before, after, clean in zip(noisy[0], st_n[0], st_z[0]):
        before, after = np.asarray(before), np.asarray(after)
        np.testing.assert_array_equal(np.delete(after, 1, 0),
                                      np.delete(before, 1, 0))
        np.testing.assert_array_equal(after[1], np.asarray(clean)[1])
        assert np.abs(after[1]).max() > 0
    # the padding behind the 5 real tokens does not reach the state
    io_pad = dict(io, tokens=np.where(np.arange(8) < 5, io["tokens"], 77))
    _, _, _, lg_p, st_p = prefill(params, leaf, (), io_pad, zeros)
    np.testing.assert_array_equal(np.asarray(lg_p), np.asarray(lg_z))
    for a, b in zip(st_p[0], st_z[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_run_of_one_token_keeps_one_old_column(spec, pc):
    """A run of 1 token at ``hist`` 5 reads the slot's row and leaves its
    newer column first and its own ``v`` after it: what the forward's
    convolution reads at the next position."""
    prefill, _, leaf, _ = _programs(spec, pc)
    params = spec.params()
    table = np.arange(1, 17)
    import jax.numpy as jnp
    zeros = (tuple(jnp.zeros((4, 2, 64), jnp.bfloat16)
                   for _ in pc.conv_layers),)
    p = prompt(6, 4)
    kc, _, _, _, st = prefill(params, leaf, (), _prefill_io(p[:5], 8, 0, 2,
                                                            table), zeros)
    _, _, _, lg1, st1 = prefill(params, kc, (), _prefill_io(p[5:], 8, 5, 2,
                                                            table), st)
    # the same 6 tokens in one run
    _, _, _, lg6, st6 = prefill(params, leaf, (), _prefill_io(p, 8, 0, 2,
                                                              table), zeros)
    for a, b, c in zip(st[0], st1[0], st6[0]):
        a, b = np.asarray(a)[2], np.asarray(b)[2]
        np.testing.assert_array_equal(b[0], a[1])
        # the two ways round upstream of v apart by a few bfloat16 steps
        c = np.asarray(c, np.float32)[2]
        np.testing.assert_allclose(np.asarray(b, np.float32), c, rtol=0,
                                   atol=0.05 * c.std())
    assert off_by(lg1, lg6) < 0.02


def test_a_decode_step_leaves_an_idle_lanes_row_as_it_was(spec, pc):
    """Three lanes, the middle one idle: its row of every conv layer's
    store comes back bit for bit; the active lanes' rows take their new
    column; the spare row behind the slots takes what the idle lane
    computed, which nothing reads."""
    _, decode, leaf, state = _programs(spec, pc)
    io = {"tokens": np.array([5, 17, 40], np.int32),
          "positions": np.array([3, 9, 6], np.int32),
          "active": np.array([True, False, True]),
          "write_off": np.array([3, 0, 2], np.int32),
          "tables": np.tile(np.arange(1, 17, dtype=np.int32), (3, 1)),
          "write_block": np.array([1, 0, 2], np.int32)}
    *_, after = decode(spec.params(), leaf, (), io, state)
    for before, new in zip(state[0], after[0]):
        before, new = np.asarray(before), np.asarray(new)
        np.testing.assert_array_equal(new[1], before[1])
        for lane in (0, 2):
            np.testing.assert_array_equal(new[lane, 0], before[lane, 1])
            assert not np.array_equal(new[lane, 1], before[lane, 1])


# -- refusals ---------------------------------------------------------------
@pytest.mark.parametrize("asked,error", [
    ({"prefix_cache": True}, PrefixCacheUnsupportedError),
    ({"tp": 2}, StateUnsupportedError)])
def test_what_cannot_bring_a_state_back_is_refused(spec, asked, error):
    with pytest.raises(error, match="state"):
        server(spec, **asked)


def test_a_draft_is_refused(spec):
    from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                            gpt_generative_spec)
    cfg = GPTConfig(vocab_size=CFG["vocab_size"], hidden_size=16,
                    num_layers=1, num_heads=2, intermediate_size=32,
                    max_seq_len=64)
    draft = gpt_generative_spec(build_gpt(cfg, batch=2, seq_len=8, seed=4),
                                cfg)
    with pytest.raises(StateUnsupportedError, match="draft"):
        server(spec, draft_spec=draft, speculate_k=3)


def test_the_prefix_cache_is_off_where_it_is_not_asked_for(spec):
    with server(spec) as srv:
        assert not srv.prefix_cache_enabled
        assert srv.memory_report()["state_bytes_per_lane"] == 6 * 2 * 64 * 2


# -- counters ---------------------------------------------------------------
def test_the_program_counts_what_a_count_by_hand_gives(spec, pc):
    """One decode step of three lanes, two of them active at position 0
    (nothing cached, a state of zeros): the counters behind the tokens
    against a count by hand from each lane's own forward, and its logits
    the forward's; the plain read gathers the whole table of 16 entries,
    one span, for each active lane on each attention layer."""
    import jax.numpy as jnp
    _, decode, leaf, state = _programs(spec, pc)
    state = (tuple(jnp.zeros_like(a) for a in state[0]),)
    params = spec.params()
    S, E = 3, pc.num_experts
    tokens = np.array([5, 17, 40], np.int32)
    active = np.array([True, True, False])
    io = {"tokens": tokens, "positions": np.zeros(S, np.int32),
          "active": active, "write_off": np.zeros(S, np.int32),
          "tables": np.zeros((S, 16), np.int32),
          "write_block": np.array([1, 2, 3], np.int32)}
    _, _, nxt, logits, _ = decode(params, leaf, (), io, state)
    got = dict(zip(PROGRAM_COUNTERS, np.asarray(nxt)[S:].tolist()))
    served = np.zeros((6, E), np.int64)
    moved = 0
    for i, (tok, on) in enumerate(zip(tokens, active)):
        if on:
            lg, _, did = lfm2_moe_forward(pc, params, jnp.asarray([tok]))
            served += np.asarray(did)[:, :E]
            moved += int(np.asarray(did)[:, E].sum())
            np.testing.assert_allclose(np.asarray(logits)[i],
                                       np.asarray(lg)[0], rtol=0,
                                       atol=1e-5)
    assert got == {
        "moe_layer_steps": 6,
        "moe_experts_touched_sum": int((served > 0).sum()),
        "moe_tokens_routed_sum": 2 * 2 * 6,          # k x lanes x layers
        "moe_peak_expert_tokens_sum": int(served.max(axis=1).sum()),
        "moe_bias_moved_sum": moved,
        "kv_pages_read_sum": 2 * 16 * 2,      # lanes x entries x layers
        # three lanes stream the experts of every expert layer
        "moe_stream_layer_steps": 6}
    assert served.sum() == 2 * 2 * 6


def test_the_servers_counters_are_a_count_by_hand(spec):
    """One request alone, a prompt of 7 and 9 tokens: 8 decode steps at
    positions 7 to 14, each a row a position on the 2 attention layers
    (the pool) and the 6 conv layers' state at 2 rows of K and V a lane
    (2 x 64 numbers against 2 x 2 x 16 a row), against a row a position
    on all 8 layers; one lane started, no run carried."""
    with server(spec, max_slots=1) as srv:
        h = srv.submit(prompt(7, 9), max_new_tokens=9)
        srv.start()
        h.result(timeout=300)
        c = dict(srv.metrics.counters)
        report = srv.memory_report()
    written = np.arange(7, 15) + 1
    assert c["decode_steps"] == 8
    assert c["kv_rows_held_sum"] == int((2 * written + 6 * 2).sum())
    assert c["kv_positions_sum"] == int((8 * written).sum())
    assert (c["state_lanes_started"], c["state_runs_carried"]) == (1, 0)
    assert report["state_leaves"] == {"conv": {"layers": 6,
                                               "shape": [2, 64]}}
    assert report["state_bytes"] == 2 * report["state_bytes_per_lane"]
    assert report["kv_slab_shape"][0] == 2             # attention layers


# -- the decode read through the kernel's inputs ----------------------------
def _reference_kernel(q, leaf, pages, rows, reach, *, scale, per_block):
    """JAX's shipped ragged paged-attention kernel's reference in the
    decode kernel's place, fed the program's exact inputs (the leaf as
    its ``kv_pages``)."""
    import jax
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import \
        ref_ragged_paged_attention
    R, _, D = q.shape
    kv_pages = leaf.reshape(leaf.shape[0], leaf.shape[1], -1, D)

    def run(q, kv_pages, rows, pages):
        return np.asarray(ref_ragged_paged_attention(
            q, kv_pages, rows, pages, np.arange(R + 1, dtype=np.int32),
            np.asarray([R], np.int32), sm_scale=scale))

    return jax.pure_callback(run, jax.ShapeDtypeStruct(q.shape, q.dtype),
                             q, kv_pages, rows, pages)


def test_the_kernel_path_gives_the_plain_paths_decode_logits(monkeypatch):
    """The decode program through the kernel's inputs (the interleaved
    leaf, each lane's pages in order, its fresh row written first), with
    the shipped reference in the kernel's place, gives the plain path's
    logits at every served token; the plain path is the CPU's own, and
    the program says which it took."""
    prompts = [prompt(9, 31), prompt(18, 32)]

    def fresh():
        # a spec of its own: the programs are built once a spec
        return lfm2_moe_paged_spec(adapter.program_config(CFG),
                                   adapter.program_params(CFG, SEED))

    with server(fresh()) as srv:
        want_toks, want = logits_served(srv, prompts, 12)
    assert attention.last_decode_program().counts() == (0, 2, "backend cpu")
    monkeypatch.setattr(paged_attend, "kernel_refusal", lambda *a: None)
    monkeypatch.setattr(paged_attend, "paged_kernel",
                        lambda: _reference_kernel)
    with server(fresh()) as srv:
        toks, got = logits_served(srv, prompts, 12)
    assert attention.last_decode_program().counts() == (2, 0, None)
    assert toks == want_toks
    # the two reads weigh the rows in another order and the kernel's
    # reference rounds its weighted rows to bfloat16: 0.02 of a deviation
    # apart; a row read twice or lost moves the logits by tenths
    for g, w in zip(got, want):
        assert off_by(g, w) < 0.05


@pytest.mark.parametrize("dtype,heads,kv_heads,head_dim,why", [
    ("bfloat16", 32, 8, 64, None),        # the cell: a head of 64, G 4
    ("bfloat16", 128, 8, 128, None),      # Command A+'s
    ("float32", 32, 8, 64, None),         # G 4 pads to 8 rows
    ("bfloat16", 28, 4, 128, None),       # SmallThinker's 7 pads to 16
    ("bfloat16", 32, 8, 32, "head size 32"),
    ("bfloat16", 32, 8, 96, "head size 96"),
    ("float16", 32, 8, 64, "dtype float16"),
    ("bfloat16", 30, 8, 64, "heads 30 over 8"),
])
def test_kernel_refusal_on_a_tpu(dtype, heads, kv_heads, head_dim, why):
    """On a TPU the decode kernel takes the cell's head of 64 (one
    128-lane column ``[k_h | v_h]`` a head) and its group of 4 query
    heads (padded to whole sublane tiles), and still refuses another
    head size, a leaf in another dtype and heads that do not group."""
    import jax.numpy as jnp
    monkey = paged_attend.jax.default_backend
    try:
        paged_attend.jax.default_backend = lambda: "tpu"
        assert paged_attend.kernel_refusal(
            jnp.dtype(dtype), heads, kv_heads, head_dim) == why
    finally:
        paged_attend.jax.default_backend = monkey


def test_kv_pages_read_sum_is_a_count_by_hand(spec, pc, monkeypatch):
    """One decode step of four lanes over a table of 64 entries (two
    spans of 32, blocks of 4), three active at positions 0, 150 and 22
    and one idle: the kernel path counts each active lane's own pages on
    each of the 2 attention layers, ``p // 4 + 1``; the plain path every
    entry it gathered, as many spans as the longest lane fills (150 rows
    of 128 a span: 2) for each active lane."""
    import jax
    import jax.numpy as jnp
    S = 4
    io = {"tokens": np.array([5, 17, 40, 3], np.int32),
          "positions": np.array([0, 150, 22, 9], np.int32),
          "active": np.array([True, True, True, False]),
          "write_off": np.array([0, 2, 2, 1], np.int32),
          "tables": np.zeros((S, 64), np.int32),
          "write_block": np.array([1, 2, 3, 0], np.int32)}
    leaf = tuple(jnp.zeros((8, BS, 2 * 2 * 16), jnp.bfloat16)
                 for _ in pc.attention_layers)
    state = (tuple(jnp.zeros((S + 1, 2, 64), jnp.bfloat16)
                   for _ in pc.conv_layers),)

    def read(kernel):
        if kernel:
            monkeypatch.setattr(paged_attend, "kernel_refusal",
                                lambda *a: None)
            monkeypatch.setattr(paged_attend, "paged_kernel",
                                lambda: _reference_kernel)
        _, decode_fn = lfm2_moe_paged_spec(pc, spec.params()).make_fns(BS,
                                                                       64)
        _, _, nxt, _, _ = jax.jit(decode_fn)(spec.params(), leaf, (), io,
                                             state)
        return dict(zip(PROGRAM_COUNTERS, np.asarray(nxt)[S:].tolist()))

    plain = read(False)["kv_pages_read_sum"]
    assert attention.last_decode_program().counts() == (0, 2, "backend cpu")
    kernel = read(True)["kv_pages_read_sum"]
    assert attention.last_decode_program().counts() == (2, 0, None)
    assert kernel == 2 * sum(p // 4 + 1 for p in (0, 150, 22)) == 90
    assert plain == 2 * 3 * 2 * 32


# -- the cell's size, with nothing allocated --------------------------------
def test_the_cells_parameters_pool_and_state_with_nothing_made():
    """The configuration at 16 layers: 5,399,129,024 parameters by the
    program's own leaf shapes (10.80 GB in bfloat16), as the counts and
    the reference give them; a pool of 64 slots at their worst over the 4
    attention layers, 2.42 GB; a state of 12 x 2 x 2,048 bfloat16 a lane,
    98,304 bytes."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    pc = Lfm2MoeConfig.from_dict(cfg)
    n = sum(int(np.prod(s)) for s in lfm2_moe_param_shapes(pc).values())
    assert n == 5_399_129_024 == counts.param_count(cfg)
    drawn = sum(int(np.prod(ref.kind_shape(cfg, k))) for k in ref.TOP_KINDS)
    drawn += sum(int(np.prod(ref.kind_shape(cfg, k)))
                 for i in range(16) for k in ref.layer_kinds(cfg, i))
    assert drawn == n
    assert pc.attention_layers == (2, 6, 10, 14)
    assert len(pc.conv_layers) == 12 and pc.head_dim == 64
    blocks = 64 * 4608 // 16 + 1
    pool = blocks * 16 * 4 * 2 * 8 * 64 * 2
    assert pool == pytest.approx(2.42e9, rel=0.01)
    assert 12 * 2 * 2048 * 2 == 98_304
    assert 2 * n + pool < 0.8 * 16_909_336_064


def test_the_counts_expect_the_experts_the_router_touches():
    """``counts.experts_touched`` takes the published rule's expectation
    under the seeded draw's statistics: the reference's own router and
    bias at the published width (32 experts, top-4, hidden 2,048; unit-RMS
    rows) touches as many experts a run as it expects, within 5%."""
    import jax
    import jax.numpy as jnp
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    seen = {n: [] for n in (1, 8, 64)}
    for seed in (1, 2**31 + 3):
        for layer in (3, 4, 5):
            wr = ref.draw(cfg, seed, "router", layer)
            b = ref.draw(cfg, seed, "router_bias", layer)
            m = jax.random.normal(jax.random.PRNGKey(seed + layer),
                                  (1024, 2048), jnp.float32)
            s = jax.nn.sigmoid(m @ wr.astype(jnp.float32))
            chosen = np.asarray(jax.lax.top_k(
                s + b.astype(jnp.float32), 4)[1])
            for n in seen:
                seen[n] += [len(np.unique(chosen[i:i + n]))
                            for i in range(0, 1024, n)]
    for n in seen:
        assert counts.experts_touched(cfg, n) == pytest.approx(
            np.mean(seen[n]), rel=0.05), n
