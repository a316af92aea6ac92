"""``benchmark/reference/gpt2.py`` against ``zoo/gpt.py`` at a tiny size
on the CPU, on seeded weights: prefill and then decoding through the
block pool and its tables against the reference's full forward, and one
training step's loss and gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import TINY
from benchmark.adapters import gpt2 as adapter
from benchmark.reference import gpt2 as ref

SEED = 2**31 + 3


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(TINY, SEED), adapter.program_params(TINY, SEED)


def test_weights_are_a_function_of_the_seed(weights):
    again = ref.init_params(TINY, SEED)
    other = ref.init_params(TINY, SEED + 1)
    for k, v in weights[0].items():
        assert v.dtype == jnp.float32
        assert (np.asarray(v) == np.asarray(again[k])).all()
        assert (np.asarray(v) != np.asarray(other[k])).any()


def test_program_layout_holds_the_same_numbers(weights):
    stacked, prog = weights
    assert (np.asarray(prog["h1/mlp/fc/kernel"])
            == np.asarray(stacked["mlp.c_fc.w"][1])).all()
    # the fused projection: GPT-2's [Q|K|V] columns, per head in the
    # program; head 2's keys are the same numbers in both
    H, A = TINY["n_embd"], TINY["n_head"]
    D = H // A
    w = np.asarray(stacked["attn.c_attn.w"][0])
    k_ref = w[:, H + 2 * D:H + 3 * D]
    k_prog = np.asarray(prog["h0/attn/qkv/kernel"])[:, 2 * 3 * D + D:
                                                     2 * 3 * D + 2 * D]
    assert (k_ref == k_prog).all()


def test_prefill_then_paged_decode_follows_the_full_forward(weights):
    from deeplearning4j_tpu.zoo.gpt import gpt_paged_decode_fns
    stacked, prog = weights
    pc = adapter.program_config(TINY)
    BS, MAXB, NB = 8, 6, 9
    prefill, decode, _ = gpt_paged_decode_fns(pc, BS, MAXB)
    shape = (pc.num_layers, NB, pc.num_heads, BS, pc.head_size)
    kc = jnp.zeros(shape, jnp.float32)
    vc = jnp.zeros(shape, jnp.float32)
    rng = np.random.default_rng(0)
    n, steps = 13, 9
    seq = rng.integers(0, TINY["vocab_size"], n + steps).astype(np.int32)
    table = np.array([3, 5, 1, 0, 0, 0], np.int32)   # scattered blocks
    padded = np.zeros(16, np.int32)
    padded[:n] = seq[:n]
    kc, vc, _, lg = jax.jit(prefill)(
        prog, kc, vc, {"tokens": padded, "length": np.int32(n),
                       "hist": np.int32(0), "table": table})
    got = [np.asarray(lg)]
    dec = jax.jit(decode)
    for j in range(steps):
        pos = n + j
        io = {"tokens": np.array([seq[pos], 0], np.int32),
              "positions": np.array([pos, 0], np.int32),
              "active": np.array([True, False]),
              "tables": np.stack([table, np.zeros(MAXB, np.int32)]),
              "write_block": np.array([table[pos // BS], 0], np.int32),
              "write_off": np.array([pos % BS, 0], np.int32)}
        kc, vc, _, lg = dec(prog, kc, vc, io)
        got.append(np.asarray(lg)[0])
    want = np.asarray(ref.logits(stacked, jnp.asarray(seq[None]), TINY))[0]
    np.testing.assert_allclose(np.stack(got), want[n - 1:], atol=2e-5)


def test_served_gaps_are_nought_for_the_references_own_choice(weights):
    stacked, _ = weights
    prompt = np.arange(5, dtype=np.int32)
    seq = list(prompt)
    for _ in range(6):                  # greedy by the reference itself
        lg = ref.logits(stacked, jnp.asarray(np.array([seq], np.int32)),
                        TINY)
        seq.append(int(np.argmax(np.asarray(lg)[0, -1])))
    served = seq[5:]
    gaps, = ref.served_gaps(stacked, TINY, [(prompt, served)], 16)
    assert gaps.shape == (6,) and gaps.max() == 0.0
    altered = list(served)
    altered[3] = (altered[3] + 1) % TINY["vocab_size"]
    gaps, = ref.served_gaps(stacked, TINY, [(prompt, altered)], 16)
    assert gaps[3] > 0 and (gaps[:3] == 0).all()


def test_one_training_steps_loss_and_gradients(weights):
    from deeplearning4j_tpu.zoo.gpt import build_gpt
    stacked, prog = weights
    B, S = 4, 16
    rng = np.random.default_rng(1)
    rows = rng.integers(0, TINY["vocab_size"], (B, S + 1)).astype(np.int32)
    ids, tgt = rows[:, :-1], rows[:, 1:]
    sd = build_gpt(adapter.program_config(TINY), batch=B, seq_len=S)
    for name, arr in prog.items():
        sd.set_arr_for_var(name, arr)
    ph = {"input_ids": ids, "targets": tgt}
    loss = float(np.asarray(sd.output(ph, ["loss"])["loss"].to_numpy()))
    grads = sd.calculate_gradients(ph)
    want_loss, want = ref.loss_and_grads(stacked, ids, tgt, TINY,
                                         rows_per_block=2)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    np.testing.assert_allclose(
        grads["h1/mlp/proj/kernel"].to_numpy(),
        np.asarray(want["mlp.c_proj.w"][1]), rtol=2e-3, atol=1e-7)
    np.testing.assert_allclose(grads["wte"].to_numpy(),
                               np.asarray(want["wte"]), rtol=2e-3,
                               atol=1e-7)
    # the fused projection's bias, laid out per head in the program
    H, A = TINY["n_embd"], TINY["n_head"]
    b = np.asarray(want["attn.c_attn.b"][0]).reshape(3, A, H // A)
    np.testing.assert_allclose(
        grads["h0/attn/qkv/bias"].to_numpy().reshape(A, 3, H // A),
        b.transpose(1, 0, 2), rtol=2e-3, atol=1e-7)


def test_adam_is_the_stated_form():
    p = {"w": jnp.asarray([1.0, -2.0], jnp.float32)}
    g = {"w": jnp.asarray([0.5, -0.25], jnp.float32)}
    z = {"w": jnp.zeros(2, jnp.float32)}
    new, m, v = ref.adam_step(p, g, dict(z), {"w": jnp.zeros(2)}, 1,
                              lr=1e-2, b1=0.9, b2=0.999, eps=1e-8)
    # after one step m/(1-b1) = g and v/(1-b2) = g^2: the step is lr*sign
    np.testing.assert_allclose(np.asarray(new["w"]), [0.99, -1.99],
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(m["w"]), [0.05, -0.025],
                               rtol=1e-6)
