"""Mixture-of-Experts + expert parallelism tests (new TPU-native
capability — no reference analogue; Switch/GShard recipe with static
capacity-based dispatch). Runs on the virtual 8-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.parallel import (
    EXPERT_AXIS, expert_parallel_specs, init_moe_params, moe_ffn,
    moe_train_step, switch_gating)


def _params(rng, d=8, f=16, e=4):
    return init_moe_params(rng, d, f, e)


def test_single_expert_equals_dense_ffn():
    """E=1 with ample capacity must reduce EXACTLY to gate*ffn(x)."""
    rng = np.random.default_rng(0)
    d, f = 8, 16
    p = _params(rng, d, f, e=1)
    x = jnp.asarray(rng.normal(size=(12, d)), jnp.float32)
    y, aux = moe_ffn(x, p["gate_w"], p["w_in"], p["w_out"],
                     capacity_factor=2.0)
    dense = jnp.matmul(jax.nn.gelu(jnp.matmul(x, p["w_in"][0])),
                       p["w_out"][0])
    # top-1 gate prob over a single expert is exactly 1
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                               rtol=1e-5, atol=1e-6)
    assert aux == pytest.approx(1.0)    # E * (1 * 1)


def test_routing_sends_tokens_to_argmax_expert():
    d, e = 4, 3
    gate_w = jnp.eye(d, e)              # token argmax dim -> expert
    x = jnp.asarray(np.eye(d, dtype=np.float32)[[0, 1, 2, 0]]) * 3.0
    dispatch, combine, aux = switch_gating(x, gate_w, capacity=4)
    assigned = np.asarray(dispatch.sum(axis=2).argmax(axis=1))
    np.testing.assert_array_equal(assigned, [0, 1, 2, 0])
    # second token routed to expert 0 takes slot 1
    assert float(dispatch[3, 0, 1]) == 1.0


def test_capacity_overflow_drops_tokens():
    d, e = 4, 2
    gate_w = jnp.zeros((d, e)).at[:, 0].set(1.0)   # everyone -> expert 0
    x = jnp.ones((6, d), jnp.float32)
    dispatch, combine, aux = switch_gating(x, gate_w, capacity=2)
    kept = float(dispatch.sum())
    assert kept == 2.0                  # capacity caps the queue
    # dropped tokens produce zero output rows
    rng = np.random.default_rng(1)
    p = _params(rng, d, 8, e)
    p["gate_w"] = gate_w
    y, _ = moe_ffn(x, p["gate_w"], p["w_in"], p["w_out"],
                   capacity_factor=2 * e / 6.0)    # capacity=2
    assert np.abs(np.asarray(y)[2:]).sum() < np.abs(np.asarray(y)[:2]).sum() \
        or np.allclose(np.asarray(y)[2:], 0)


def test_aux_loss_prefers_balance():
    d, e = 4, 2
    # positive tokens so the collapsed gate really routes EVERY token to
    # expert 0 (a linear gate has no bias; signed inputs would flip it)
    x = jnp.asarray(np.abs(np.random.default_rng(2).normal(size=(32, d))),
                    jnp.float32)
    balanced = jnp.asarray([[4.0, -4], [-4, 4], [4, -4], [-4, 4]],
                           jnp.float32)  # (d=4, e=2), splits tokens
    collapsed = jnp.zeros((d, e)).at[:, 0].set(4.0)
    *_, aux_b = switch_gating(x, balanced, capacity=32)
    *_, aux_c = switch_gating(x, collapsed, capacity=32)
    assert float(aux_c) > float(aux_b)


def test_expert_parallel_matches_single_device():
    """EP over the 8-device CPU mesh: sharded experts, GSPMD all-to-alls
    — numerics equal to the unsharded computation."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    rng = np.random.default_rng(3)
    d, f, e, n = 8, 16, 4, 32
    p = _params(rng, d, f, e)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y_ref, aux_ref = moe_ffn(x, p["gate_w"], p["w_in"], p["w_out"])

    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, (EXPERT_AXIS,))
    specs = expert_parallel_specs()
    with mesh:
        p_sharded = {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in p.items()}
        fn = jax.jit(lambda pp, xx: moe_ffn(
            xx, pp["gate_w"], pp["w_in"], pp["w_out"],
            expert_sharded=True))
        y_ep, aux_ep = fn(p_sharded, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    assert float(aux_ep) == pytest.approx(float(aux_ref), rel=1e-5)


def test_moe_training_learns_and_shards():
    """A data x expert mesh trains the MoE head; loss decreases and
    numerics match the single-device trajectory."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    rng = np.random.default_rng(4)
    d, f, e, n = 8, 16, 2, 64
    params = _params(rng, d, f, e)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    tgt = jnp.asarray(np.tanh(np.asarray(x) @ rng.normal(size=(d, d))),
                      jnp.float32)

    # single-device trajectory
    p1 = jax.tree_util.tree_map(jnp.copy, params)
    losses = []
    for _ in range(5):
        p1, l = moe_train_step(p1, x, tgt)
        losses.append(float(l))
    assert losses[-1] < losses[0]

    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("data", EXPERT_AXIS))
    specs = expert_parallel_specs()
    with mesh:
        p2 = {k: jax.device_put(jnp.copy(v), NamedSharding(mesh, specs[k]))
              for k, v in params.items()}
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        ts = jax.device_put(tgt, NamedSharding(mesh, P("data", None)))
        step = jax.jit(lambda p, a, b: moe_train_step(
            p, a, b, expert_sharded=True))
        for i in range(5):
            p2, l2 = step(p2, xs, ts)
    assert float(l2) == pytest.approx(losses[-1], rel=1e-4)


def test_grouped_dispatch_matches_ungrouped_at_ample_capacity():
    """GShard-style grouping: with capacity ample enough that no group
    drops tokens, G>1 equals G=1 for a single expert, and runs with the
    (G,S,E,C) dispatch for many experts."""
    rng = np.random.default_rng(6)
    d, f = 8, 16
    p = _params(rng, d, f, e=1)
    x = jnp.asarray(rng.normal(size=(32, d)), jnp.float32)
    y1, _ = moe_ffn(x, p["gate_w"], p["w_in"], p["w_out"],
                    capacity_factor=2.0, n_groups=1)
    y4, _ = moe_ffn(x, p["gate_w"], p["w_in"], p["w_out"],
                    capacity_factor=2.0, n_groups=4)
    np.testing.assert_allclose(np.asarray(y4), np.asarray(y1),
                               rtol=1e-5, atol=1e-6)
    p8 = _params(rng, d, f, e=4)
    y8, aux = moe_ffn(x, p8["gate_w"], p8["w_in"], p8["w_out"],
                      capacity_factor=4.0, n_groups=4)
    assert y8.shape == (32, d) and np.isfinite(float(aux))
    with pytest.raises(ValueError, match="divisible"):
        moe_ffn(x, p["gate_w"], p["w_in"], p["w_out"], n_groups=5)


# ----------------------------------------------------------------------
# the dropless top-k layer (parallel/moe.py topk_route, dropless_topk_ffn)
from deeplearning4j_tpu.parallel import dropless_topk_ffn, topk_route


def _reglu_experts(rng, d=8, f=12, e=8):
    return (jnp.asarray(rng.normal(size=(e, d, f)) * 0.3, jnp.float32),
            jnp.asarray(rng.normal(size=(e, d, f)) * 0.3, jnp.float32),
            jnp.asarray(rng.normal(size=(e, f, d)) * 0.3, jnp.float32))


def _token_loop(x, idx, w, gate, up, down):
    """The layer as its equations read: token by token, choice by
    choice."""
    out = np.zeros(x.shape, np.float64)
    x, gate, up, down = (np.asarray(a, np.float64)
                         for a in (x, gate, up, down))
    for n in range(x.shape[0]):
        for e, we in zip(np.asarray(idx[n]), np.asarray(w[n])):
            h = np.maximum(x[n] @ gate[e], 0.0) * (x[n] @ up[e])
            out[n] += float(we) * (h @ down[e])
    return out


def test_topk_route_takes_the_k_largest_with_a_softmax_over_them():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(10, 8)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(8, 6)), jnp.float32)
    idx, w = topk_route(x, router, 3)
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    for n in range(10):
        order = np.argsort(-logits[n])[:3]
        assert sorted(np.asarray(idx[n])) == sorted(order)
        e = np.exp(logits[n][np.asarray(idx[n])] - logits[n].max())
        np.testing.assert_allclose(np.asarray(w[n]), e / e.sum(), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 1.0, rtol=1e-6)


def test_dropless_layer_drops_nothing_when_one_expert_takes_half():
    """Expert 0 is the first choice of half the tokens, 16 of 32, four
    times an even share; the Switch layer at its default capacity would
    keep 5 of them. Every pair is served, as a loop over tokens gives
    it."""
    rng = np.random.default_rng(4)
    n, d, e, k = 32, 8, 8, 2
    gate, up, down = _reglu_experts(rng, d, 12, e)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = np.asarray(rng.normal(size=(d, e)), np.float32)
    idx, w = topk_route(x, jnp.asarray(router), k)
    idx = np.asarray(idx).copy()
    idx[:n // 2, 0] = 0
    idx[:n // 2, 1] = np.where(idx[:n // 2, 1] == 0, 1, idx[:n // 2, 1])
    idx = jnp.asarray(idx)
    y, served = jax.jit(dropless_topk_ffn)(x, idx, w, gate, up, down)
    assert int(served[0]) >= n // 2 and int(served.sum()) == n * k
    np.testing.assert_allclose(np.asarray(y),
                               _token_loop(x, idx, w, gate, up, down),
                               rtol=1e-4, atol=1e-5)


def test_the_parts_of_four_shares_add_up_to_the_uncut_layer():
    """Four chips holding two experts each route over all eight and give
    their own experts' part; the parts add up to what one chip holding
    all eight gives, and each serves only its own pairs."""
    rng = np.random.default_rng(5)
    n, d, e, k = 24, 8, 8, 3
    gate, up, down = _reglu_experts(rng, d, 12, e)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    idx, w = topk_route(x, jnp.asarray(rng.normal(size=(d, e)),
                                       jnp.float32), k)
    whole, served = dropless_topk_ffn(x, idx, w, gate, up, down)
    parts, counts = zip(*(
        dropless_topk_ffn(x, idx, w, gate[a:a + 2], up[a:a + 2],
                          down[a:a + 2], first_expert=a)
        for a in range(0, e, 2)))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.concatenate(counts), np.asarray(served))
    np.testing.assert_allclose(np.asarray(whole),
                               _token_loop(x, idx, w, gate, up, down),
                               rtol=1e-4, atol=1e-5)


def test_a_token_that_is_not_valid_is_served_by_no_expert():
    rng = np.random.default_rng(6)
    gate, up, down = _reglu_experts(rng)
    x = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
    idx, w = topk_route(x, jnp.asarray(rng.normal(size=(8, 8)),
                                       jnp.float32), 2)
    valid = jnp.asarray([True, False, True, True, False, False])
    y, served = dropless_topk_ffn(x, idx, w, gate, up, down, valid=valid)
    assert int(served.sum()) == 3 * 2
    assert not np.asarray(y)[~np.asarray(valid)].any()
    want = _token_loop(x, idx, w, gate, up, down)
    np.testing.assert_allclose(np.asarray(y)[np.asarray(valid)],
                               want[np.asarray(valid)], rtol=1e-4,
                               atol=1e-5)


# ----------------------------------------------------------------------
# sigmoid routing with a correction bias, SiLU experts, a shared expert
from deeplearning4j_tpu.parallel import sigmoid_bias_route


def _silu(v):
    return v / (1.0 + np.exp(-v))


def _route_loop(x, router, bias, k, scale):
    """The rule as it reads, token by token, in float64: the k largest
    of ``sigmoid + bias``; weights the chosen sigmoids WITHOUT the bias
    over their sum, times the scale."""
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(router, np.float64))))
    idx, w, moved = [], [], []
    for n in range(s.shape[0]):
        pick = np.argsort(-(s[n] + np.asarray(bias, np.float64)))[:k]
        bare = set(np.argsort(-s[n])[:k])
        idx.append(pick)
        w.append(scale * s[n][pick] / (s[n][pick].sum() + 1e-20))
        moved.append(sum(int(e) not in bare for e in pick))
    return np.stack(idx), np.stack(w), np.asarray(moved)


def _biased_router(seed=7, n=40, d=8, e=8):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e)) * 0.4, jnp.float32)
    # a bias large enough to change choices: one expert pushed up, one down
    bias = jnp.asarray(rng.normal(size=e) * 0.1, jnp.float32
                       ).at[2].add(0.4).at[5].add(-0.4)
    return x, router, bias


def test_the_sigmoid_router_chooses_with_the_bias_and_weighs_without_it():
    x, router, bias = _biased_router()
    idx, w, moved = sigmoid_bias_route(x, router, bias, 3, scale=1.8)
    want_idx, want_w, want_moved = _route_loop(x, router, bias, 3, 1.8)
    got_order = np.argsort(np.asarray(idx), axis=1)
    want_order = np.argsort(want_idx, axis=1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(idx), got_order, 1),
        np.take_along_axis(want_idx, want_order, 1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), got_order, 1),
        np.take_along_axis(want_w, want_order, 1), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(moved), want_moved)
    # the weights sum to the scale, whatever the bias
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 1.8, rtol=1e-6)
    # the bias CHANGES the choice ...
    bare_idx, bare_w, none = sigmoid_bias_route(
        x, router, jnp.zeros_like(bias), 3, scale=1.8)
    assert int(moved.sum()) > 0 and not int(none.sum())
    changed = [n for n in range(x.shape[0]) if set(
        np.asarray(idx[n])) != set(np.asarray(bare_idx[n]))]
    assert changed and len(changed) == int((np.asarray(moved) > 0).sum())
    # ... and does NOT enter a weight: a token whose choice it left alone
    # has the bare router's weights to the digit
    same = [n for n in range(x.shape[0]) if n not in changed]
    assert same
    for n in same:
        by_expert = lambda i, v: np.asarray(v[n])[  # noqa: E731
            np.argsort(np.asarray(i[n]))]
        np.testing.assert_array_equal(by_expert(idx, w),
                                      by_expert(bare_idx, bare_w))


@pytest.mark.parametrize("fault", ["bias_in_the_weights",
                                   "bias_out_of_the_choice"])
def test_the_router_test_tells_a_wrong_rule(fault):
    """The two ways to get the rule wrong, each against the loop: the
    comparison above would fail on either."""
    x, router, bias = _biased_router()
    want_idx, want_w, _ = _route_loop(x, router, bias, 3, 1.8)
    s = jax.nn.sigmoid(x @ router)
    if fault == "bias_in_the_weights":
        top, idx = jax.lax.top_k(s + bias, 3)
        w = 1.8 * top / top.sum(axis=1, keepdims=True)
        order = np.argsort(np.asarray(idx), axis=1)
        got = np.take_along_axis(np.asarray(w), order, 1)
        want = np.take_along_axis(want_w, np.argsort(want_idx, axis=1), 1)
        assert np.abs(got - want).max() > 1e-2
    else:
        _, idx = jax.lax.top_k(s, 3)
        assert any(set(np.asarray(idx[n])) != set(want_idx[n])
                   for n in range(x.shape[0]))


def test_four_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """Four chips holding two SiLU experts each route over all eight by
    the sigmoid rule and give their own experts' part; the shared expert
    is what every chip computes alike, counted ONCE: the sum is the
    uncut layer as a loop over tokens gives it."""
    rng = np.random.default_rng(8)
    n, d, f, e, k = 24, 8, 12, 8, 3
    gate, up, down = _reglu_experts(rng, d, f, e)
    sg, su, sd = (a[0] for a in _reglu_experts(rng, d, f, 1))
    x, router, bias = _biased_router(9, n, d, e)
    idx, w, _ = sigmoid_bias_route(x, router, bias, k, scale=1.8)

    def shared(v):
        return (jax.nn.silu(v @ sg) * (v @ su)) @ sd

    whole, served = dropless_topk_ffn(x, idx, w, gate, up, down,
                                      activation=jax.nn.silu)
    parts, counts = zip(*(
        dropless_topk_ffn(x, idx, w, gate[a:a + 2], up[a:a + 2],
                          down[a:a + 2], first_expert=a,
                          activation=jax.nn.silu)
        for a in range(0, e, 2)))
    np.testing.assert_array_equal(np.concatenate(counts), np.asarray(served))
    assert int(served.sum()) == n * k
    x64, g64, u64, d64 = (np.asarray(a, np.float64)
                          for a in (x, gate, up, down))
    want = np.asarray(shared(x), np.float64)
    for t in range(n):
        for ex, we in zip(np.asarray(idx[t]), np.asarray(w[t])):
            h = _silu(x64[t] @ g64[ex]) * (x64[t] @ u64[ex])
            want[t] += float(we) * (h @ d64[ex])
    np.testing.assert_allclose(np.asarray(sum(parts) + shared(x)), want,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole + shared(x)), want,
                               rtol=1e-4, atol=1e-5)
    # counted four times it is not the layer
    assert np.abs(np.asarray(sum(p + shared(x) for p in parts))
                  - want).max() > 1e-2


def test_a_caller_who_names_no_activation_gets_relu_as_before():
    """SmallThinker's call lowers to the text it lowered to: the default
    is the same function object's trace as naming ``jax.nn.relu``."""
    rng = np.random.default_rng(10)
    gate, up, down = _reglu_experts(rng)
    x = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
    idx, w = topk_route(x, jnp.asarray(rng.normal(size=(8, 8)),
                                       jnp.float32), 2)
    plain = jax.jit(dropless_topk_ffn).lower(x, idx, w, gate, up, down)
    named = jax.jit(lambda *a: dropless_topk_ffn(
        *a, activation=jax.nn.relu)).lower(x, idx, w, gate, up, down)
    strip = lambda t: "\n".join(l for l in t.as_text().splitlines()  # noqa
                                if "module @" not in l)
    assert strip(plain) == strip(named)
    y, _ = dropless_topk_ffn(x, idx, w, gate, up, down)
    np.testing.assert_allclose(np.asarray(y),
                               _token_loop(x, idx, w, gate, up, down),
                               rtol=1e-4, atol=1e-5)
    swi, _ = dropless_topk_ffn(x, idx, w, gate, up, down,
                               activation=jax.nn.silu)
    assert np.abs(np.asarray(swi) - np.asarray(y)).max() > 1e-3


# ----------------------------------------------------------------------
# the grouped product for many rows (JAX's TPU kernel, interpreted here)
from deeplearning4j_tpu.parallel import tiled_grouped_dot


def test_the_tiled_grouped_product_is_ragged_dot_for_many_rows():
    """256 rows over 4 groups, one of them empty, 29 rows behind the last
    group: the kernel (interpreted on the CPU) gives ``ragged_dot``'s rows
    for every row that is in a group; what it leaves behind the last
    group is finite, and ``dropless_topk_ffn`` puts zeros there."""
    rng = np.random.default_rng(11)
    m, k, n = 256, 256, 512
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(4, k, n)) * 0.05, jnp.bfloat16)
    sizes = jnp.asarray([100, 0, 37, 90], jnp.int32)
    got = np.asarray(tiled_grouped_dot(x, w, sizes))
    want = np.asarray(jax.lax.ragged_dot(
        x, w, sizes, preferred_element_type=jnp.float32))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    np.testing.assert_allclose(got[:227], want[:227], rtol=1e-5, atol=1e-5)


def test_the_layer_takes_a_named_grouped_product():
    """The layer with the kernel named (interpreted where there is no
    TPU, as here) gives the layer with ``ragged_dot``, invalid tokens and
    all; shapes that fill no whole tile are refused."""
    rng = np.random.default_rng(12)
    n, d, f, e, k = 128, 512, 512, 4, 2
    gate, up, down = (jnp.asarray(a, jnp.bfloat16)
                      for a in _reglu_experts(rng, d, f, e))
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16)
    idx, w = topk_route(x, jnp.asarray(rng.normal(size=(d, e)),
                                       jnp.float32), k)
    valid = jnp.asarray(rng.random(n) > 0.1)
    plain, served = dropless_topk_ffn(x, idx, w, gate, up, down,
                                      valid=valid, activation=jax.nn.silu)
    tiled, served_t = dropless_topk_ffn(
        x, idx, w, gate, up, down, valid=valid, activation=jax.nn.silu,
        grouped=tiled_grouped_dot)
    np.testing.assert_array_equal(np.asarray(served), np.asarray(served_t))
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(plain),
                               rtol=2e-2, atol=2e-2 * float(
                                   np.abs(np.asarray(plain)).max()))
    assert not np.asarray(tiled)[~np.asarray(valid)].any()
    for rows, cols in ((100, 512), (128, 500)):
        with pytest.raises(ValueError, match="whole tiles"):
            tiled_grouped_dot(x[:rows], up[:, :, :cols],
                              jnp.zeros(e, jnp.int32))


# ----------------------------------------------------------------------
# a router without a correction bias, and a chip that holds a few of the
# experts it routes over (Command A+ at one chip's share of a layer)
def test_the_sigmoid_router_without_a_bias_takes_the_k_largest_sigmoids():
    """``bias=None``: the k largest sigmoids, weighed as the rule says;
    what a bias of zeros chooses, with nothing added to the scores and
    ``moved`` 0."""
    x, router, bias = _biased_router(13)
    idx, w, moved = sigmoid_bias_route(x, router, None, 3)
    want_idx, want_w, _ = _route_loop(x, router, np.zeros(8), 3, 1.0)
    order = np.argsort(np.asarray(idx), axis=1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(idx), order, 1),
        np.sort(want_idx, axis=1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, 1),
        np.take_along_axis(want_w, np.argsort(want_idx, axis=1), 1),
        rtol=1e-5)
    assert moved.shape == (x.shape[0],) and not np.asarray(moved).any()
    zero_idx, zero_w, _ = sigmoid_bias_route(x, router, jnp.zeros(8), 3)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(zero_idx))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(zero_w))

    def adds(b):
        jaxpr = jax.make_jaxpr(
            lambda v: sigmoid_bias_route(v, router, b, 3))(x)
        return sum(e.primitive.name == "add" for e in jaxpr.eqns)
    assert adds(None) < adds(jnp.zeros(8))


@pytest.mark.parametrize("first,held,grouped", [
    (0, 2, None), (6, 2, None), (4, 4, None), (2, 2, "tiled")])
def test_a_chip_that_holds_few_of_the_routed_experts_gives_their_part(
        first, held, grouped):
    """16 experts under a sigmoid router without a bias, 4 a token; a
    chip holds ``held`` of them from ``first``: its part is, token by
    token, the chosen experts it holds and no other, whatever the others
    were chosen, and it serves exactly the pairs routed to its experts.
    With the tiled grouped product (interpreted here) the pairs routed
    elsewhere, most of the rows, ride behind the last held expert and
    give nothing."""
    rng = np.random.default_rng(14 + first)
    n, e, k = 64, 16, 4
    d = f = 512 if grouped else 8
    gate, up, down = (jnp.asarray(a, jnp.float32)
                      for a in _reglu_experts(rng, d, f, e))
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    idx, w, _ = sigmoid_bias_route(
        x, jnp.asarray(rng.normal(size=(d, e)) * 0.3, jnp.float32), None, k)
    mine = slice(first, first + held)
    y, served = dropless_topk_ffn(
        x, idx, w, gate[mine], up[mine], down[mine], first_expert=first,
        activation=jax.nn.silu,
        grouped=tiled_grouped_dot if grouped else None)
    i64 = np.asarray(idx)
    in_share = (i64 >= first) & (i64 < first + held)
    assert 0 < int(served.sum()) == int(in_share.sum()) < n * k
    np.testing.assert_array_equal(
        np.asarray(served),
        np.bincount(i64[in_share] - first, minlength=held))
    x64, g64, u64, d64 = (np.asarray(a, np.float64)
                          for a in (x, gate, up, down))
    want = np.zeros((n, d))
    for t in range(n):
        for ex, we in zip(i64[t], np.asarray(w[t])):
            if first <= ex < first + held:
                h = _silu(x64[t] @ g64[ex]) * (x64[t] @ u64[ex])
                want[t] += float(we) * (h @ d64[ex])
    tol = 2e-2 * np.abs(want).max() if grouped else 1e-5
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=tol)
    # a token that chose none of the held experts gets nothing here
    assert not np.asarray(y)[~in_share.any(axis=1)].any()
