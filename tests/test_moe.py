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


@pytest.mark.parametrize("family,bias,scale", [("glm", True, 1.8),
                                               ("cmda", False, 1.0)])
def test_the_divisors_epsilon_leaves_the_routers_that_were_bit_for_bit(
        family, bias, scale):
    """The epsilon in the chosen weights' divisor is an argument (LFM2's
    code adds 1e-6); its default is the 1e-20 GLM-4.7-Flash's router
    (with its bias) and Command A+'s (without) were written with: both
    route bit for bit as that rule does, in the same lowered program."""
    x, router, b = _biased_router()
    b = b if bias else None

    def before(x, router, b):
        # the rule as written before the epsilon was an argument
        s = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(s if b is None else s + b, 3)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        return idx, scale * chosen / (jnp.sum(chosen, axis=-1,
                                              keepdims=True) + 1e-20)

    idx, w, _ = sigmoid_bias_route(x, router, b, 3, scale)
    want_idx, want_w = before(x, router, b)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(want_w))
    text = jax.jit(lambda x, r: sigmoid_bias_route(x, r, b, 3, scale)
                   ).lower(x, router).as_text()
    named = jax.jit(lambda x, r: sigmoid_bias_route(x, r, b, 3, scale,
                                                    eps=1e-20)
                    ).lower(x, router).as_text()
    assert text == named
    # LFM2's divisor: the weights of the loop's rule with 1e-6
    got = np.asarray(sigmoid_bias_route(x, router, b, 3, scale,
                                        eps=1e-6)[1])
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(router, np.float64))))
    picked = np.take_along_axis(s, np.asarray(idx), axis=1)
    np.testing.assert_allclose(
        got, scale * picked / (picked.sum(1, keepdims=True) + 1e-6),
        rtol=1e-5)


# ----------------------------------------------------------------------
# the experts streamed at a decode call's size (parallel/moe.py
# expert_stream_ffn; the kernel interpreted here)
import importlib

from deeplearning4j_tpu.parallel import moe as moe_mod


def _sorted_path(monkeypatch):
    """The layer as it ran before the streamed product: every call sorts
    its pairs through ``ragged_dot``."""
    monkeypatch.setattr(moe_mod, "streams_experts", lambda tokens: False)


def _through_kernel(monkeypatch):
    """Streamed experts through the kernel, as on a TPU (interpreted
    here), where the CPU computes its sums in XLA."""
    monkeypatch.setattr(moe_mod, "stream_kernel", lambda: True)


#: scaled-down decode calls: (tokens, width d, expert width f, routed
#: experts, k, first held, held, activation, how the pairs are drawn)
STREAM_CASES = {
    # SmallThinker: 8 lanes, top-6 of 64, ReGLU
    "smallthinker": (8, 256, 768, 16, 6, 0, 16, jax.nn.relu, "router"),
    # GLM-4.7-Flash: 8 lanes, top-4 of 64, SwiGLU
    "glm": (8, 256, 384, 16, 4, 0, 16, jax.nn.silu, "router"),
    # Command A+: 32 lanes, top-8 of 128, this chip holds 4 from the 8th
    "cmda": (32, 256, 256, 32, 8, 8, 4, jax.nn.silu, "router"),
    # LFM2-8B-A1B: 64 lanes, top-4 of 32; a width of 7 tiles of 128
    "lfm2": (64, 128, 896, 8, 4, 0, 8, jax.nn.silu, "router"),
    "one_token": (1, 128, 256, 8, 2, 0, 8, jax.nn.silu, "router"),
    "one_expert_takes_every_token": (24, 128, 256, 8, 2, 0, 8,
                                     jax.nn.relu, "first_is_0"),
    "half_the_experts_untouched": (16, 128, 256, 8, 2, 0, 8, jax.nn.silu,
                                   "even_only"),
    # narrower than a lane tile: the whole width a step
    "narrow_expert": (8, 32, 48, 8, 2, 0, 8, jax.nn.silu, "router"),
}


def _stream_case(name, dtype):
    n, d, f, e, k, first, held, act, draw = STREAM_CASES[name]
    rng = np.random.default_rng(30 + sorted(STREAM_CASES).index(name))
    gate, up, down = (jnp.asarray(a, dtype)
                      for a in _reglu_experts(rng, d, f, held))
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    idx, w = topk_route(x, jnp.asarray(rng.normal(size=(d, e)),
                                       jnp.float32), k)
    idx = np.asarray(idx).copy()
    if draw == "first_is_0":
        idx[:, 1] = np.where(idx[:, 1] == 0, idx[:, 0], idx[:, 1])
        idx[:, 0] = 0
        idx[:, 1] = np.where(idx[:, 1] == 0, 1, idx[:, 1])
    elif draw == "even_only":
        idx = 2 * (np.argsort(rng.random((n, e // 2)), axis=1)[:, :k])
    valid = jnp.asarray(rng.random(n) > 0.25).at[0].set(True)
    return (x, jnp.asarray(idx, jnp.int32), w, gate, up, down), \
        dict(first_expert=first, valid=valid, activation=act)


@pytest.mark.parametrize("product", ["kernel", "xla"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(STREAM_CASES))
def test_the_streamed_experts_give_the_sorted_grouped_products(
        name, dtype, product, monkeypatch):
    """A decode-sized call streams the experts, through the kernel (a
    TPU's; interpreted here) or its sums in XLA (the CPU's), and gives
    what the pairs sorted through ``ragged_dot`` gave, to the order of
    float32 sums (in bfloat16 the activation's product rounds to the
    weights' dtype on both paths): held experts from ``first_expert``,
    idle lanes, experts nobody chose, one expert taking every token, one
    token; the tokens each expert served are the same count."""
    args, kw = _stream_case(name, dtype)
    assert moe_mod.streams_experts(args[0].shape[0])
    if product == "kernel":
        _through_kernel(monkeypatch)
    streamed, served = jax.jit(
        lambda *a: dropless_topk_ffn(*a, **kw))(*args)
    text = str(jax.make_jaxpr(
        lambda *a: dropless_topk_ffn(*a, **kw))(*args))
    assert ("expert_stream_ffn" in text) == (product == "kernel")
    assert "ragged_dot" not in text
    _sorted_path(monkeypatch)
    want, served_sorted = dropless_topk_ffn(*args, **kw)
    np.testing.assert_array_equal(np.asarray(served),
                                  np.asarray(served_sorted))
    idx, valid = np.asarray(args[1]), np.asarray(kw["valid"])
    local = idx - kw["first_expert"]
    held = args[3].shape[0]
    mine = (local >= 0) & (local < held) & valid[:, None]
    np.testing.assert_array_equal(
        np.asarray(served), np.bincount(local[mine], minlength=held))
    if name == "half_the_experts_untouched":
        assert not np.asarray(served)[1::2].any()
    if name == "one_expert_takes_every_token":
        assert int(served[0]) == int(valid.sum())
    scale = float(np.abs(np.asarray(want)).max())
    assert scale > 0
    tol = 1e-6 if dtype == jnp.float32 else 2e-3
    np.testing.assert_allclose(np.asarray(streamed), np.asarray(want),
                               rtol=0, atol=tol * scale)
    assert not np.asarray(streamed)[~valid].any()


@pytest.mark.parametrize("tile", [128, 896])
def test_any_tile_of_the_width_gives_the_same_experts(tile, monkeypatch):
    """The width read a grid step is the kernel's own business: an
    expert of 896 read in 7 tiles or in one gives the same sums to
    float32's order."""
    args, kw = _stream_case("lfm2", jnp.float32)
    x, idx, w, gate, up, down = args
    held, n, k = gate.shape[0], x.shape[0], idx.shape[1]
    mine = kw["valid"][:, None] & (idx < held)
    flat = jnp.where(mine, idx, held).reshape(-1)
    tokens = jnp.zeros(held + 1, jnp.int32).at[flat].add(1)[:held]
    cols = jnp.zeros((n, held + 1), jnp.float32).at[
        jnp.arange(n)[:, None], flat.reshape(n, k)].add(
            jnp.where(mine, w, 0.0))[:, :held]
    want = moe_mod._expert_major(x, cols, tokens, gate, up, down,
                                 jax.nn.silu)
    monkeypatch.setattr(moe_mod, "STREAM_TILE", tile)
    assert moe_mod._stream_tile(896, x.shape[1], 4) == tile
    got = moe_mod.expert_stream_ffn(x, cols, tokens, gate, up, down,
                                    jax.nn.silu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-6 * float(np.abs(want).max()))


def test_the_call_shape_picks_the_product(monkeypatch):
    """Up to ``STREAM_TOKENS`` tokens (a decode step's lanes) the experts
    stream (through the kernel on a TPU); a 512-token prefill chunk keeps
    ``ragged_dot``, and the tiled product where its caller names it; the
    tile of the width fills the VMEM it is given and divides the width."""
    assert not moe_mod.stream_kernel()          # the CPU: its sums in XLA
    _through_kernel(monkeypatch)
    assert all(moe_mod.streams_experts(n) for n in (1, 8, 32, 64, 128))
    assert not any(moe_mod.streams_experts(n) for n in (129, 512, 3072))
    rng = np.random.default_rng(20)
    d, e, k = 512, 4, 2
    gate, up, down = (jnp.asarray(a, jnp.bfloat16)
                      for a in _reglu_experts(rng, d, 512, e))

    def text(n, grouped=None):
        x = jnp.zeros((n, d), jnp.bfloat16)
        idx = jnp.zeros((n, k), jnp.int32).at[:, 1].set(1)
        return str(jax.make_jaxpr(lambda *a: dropless_topk_ffn(
            *a, grouped=grouped))(x, idx, jnp.ones((n, k)), gate, up, down))
    for n, stream, ragged, tiled in (
            (64, True, False, False), (128, True, False, False),
            (512, False, True, False)):
        t = text(n)
        assert ("expert_stream_ffn" in t, "ragged_dot" in t,
                "gmm" in t) == (stream, ragged, tiled)
    t = text(512, tiled_grouped_dot)
    assert "gmm" in t and "ragged_dot" not in t \
        and "expert_stream_ffn" not in t
    assert "expert_stream_ffn" in text(64, tiled_grouped_dot)
    # the four families' widths, bfloat16: (d, f) -> the tile
    for (dd, f), want in (((2560, 768), 384), ((2048, 1536), 512),
                          ((4096, 4096), 512), ((2048, 1792), 256),
                          ((32, 48), 48)):
        t = moe_mod._stream_tile(f, dd, 2)
        assert t == want and f % t == 0
        assert t == f or 2 * 3 * dd * t * 2 <= moe_mod.STREAM_TILE_BYTES


#: each MoE family: its test module (the tiny configuration) and its
#: paged spec
FAMILY_SPECS = {
    "smallthinker": ("test_smallthinker", "smallthinker",
                     "smallthinker_paged_spec"),
    "glm_moe_lite": ("test_glm_moe_lite", "glm_moe_lite",
                     "glm_moe_lite_paged_spec"),
    "cohere2_moe": ("test_cohere2_moe", "cohere2_moe",
                    "cohere2_moe_paged_spec"),
    "lfm2_moe": ("test_lfm2_moe", "lfm2_moe", "lfm2_moe_paged_spec"),
}


@pytest.mark.parametrize("family", sorted(FAMILY_SPECS))
def test_a_family_counts_streamed_layers_at_decode_and_not_at_a_chunk(
        family, monkeypatch):
    """A request of 200 tokens through the family's server: its prefill
    chunk of 256 tokens sorts its pairs (traced with 256 rows), every
    decode step of 3 lanes streams the experts of every expert layer
    (through the kernel, as on a TPU; interpreted here), and
    ``moe_stream_layer_steps`` counts them: as many as
    ``moe_layer_steps``."""
    from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
    _through_kernel(monkeypatch)
    tests, zoo, build = FAMILY_SPECS[family]
    mod = importlib.import_module(tests)
    cfg = dict(mod.CFG, max_position_embeddings=1024)
    spec = getattr(importlib.import_module("deeplearning4j_tpu.zoo." + zoo),
                   build)(mod.adapter.program_config(cfg),
                          mod.adapter.program_params(cfg, mod.SEED))
    assert "moe_stream_layer_steps" in spec.program_counters
    # the rows each path was traced with
    rows = {"_streamed": [], "_sorted_grouped": []}
    for path in rows:
        real = getattr(moe_mod, path)
        monkeypatch.setattr(moe_mod, path,
                            lambda x, *a, real=real, path=path: (
                                rows[path].append(x.shape[0]),
                                real(x, *a))[1])
    prompt = np.random.default_rng(1).integers(
        0, cfg["vocab_size"], 200).astype(np.int32)
    with PagedGenerativeServer(spec, max_slots=3, block_size=4,
                               max_seq_len=256, buckets=[8, 256],
                               warmup=False) as srv:
        srv.submit(prompt, max_new_tokens=4).result(timeout=300)
        c = dict(srv.metrics.counters)
    assert c["decode_steps"] > 0
    assert c["moe_stream_layer_steps"] == c["moe_layer_steps"] > 0
    assert set(rows["_streamed"]) == {3}
    assert set(rows["_sorted_grouped"]) == {256}
