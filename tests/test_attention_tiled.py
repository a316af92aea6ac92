"""The tiled attention kernel compiled for a described TPU v5e, at the
widths the chip runs: no chip is needed, nothing runs, and what the
chip's compiler would refuse (a block that does not fit the VMEM, a
layout it cannot tile, a kernel under a mesh) is refused here.

The topology is described inside a fixture, so that only the worker
that runs this file loads the TPU's library; keep every test that
compiles for the chip in this one file.
"""
import re

import numpy as np
import pytest

from deeplearning4j_tpu.monitor.attention import AttentionSites
from deeplearning4j_tpu.ops import nn_ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """The code under test asks ``jax.default_backend()``, which is the
    CPU here: the test answers for the chip the program is lowered for."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.enable_x64(False):     # the chip runs 32-bit, the tests 64
        yield


def _step(q, k, v, do):
    """Attention as a train step runs it in a remat region: forward,
    recomputed forward, backward."""
    import jax
    out, vjp = jax.vjp(jax.checkpoint(
        lambda q, k, v: nn_ops._tiled_causal_attention(
            q, k, v, q.shape[-1] ** -0.5)), q, k, v)
    return (out,) + vjp(do)


@pytest.mark.parametrize("shape,dtype", [
    ((16, 16, 1024, 64), "bfloat16"),    # medium_train
    ((16, 12, 512, 128), "bfloat16"),    # chip_smoke.py's GPT_MEDIUM
    ((2, 4, 384, 64), "float32"),        # a tile of 384
    ((1, 4, 4096, 128), "float32"),      # the most VMEM: f32, 128, 1024
])
def test_the_kernel_compiles_for_a_v5e(one_chip, as_tpu, shape, dtype):
    import jax
    x = jax.ShapeDtypeStruct(shape, np.dtype(dtype) if dtype != "bfloat16"
                             else jax.numpy.bfloat16, sharding=one_chip)
    text = jax.jit(_step).lower(x, x, x, x).compile().as_text()
    # forward, forward with its row statistics, and ONE backward kernel
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 3
    b, h, s, _ = shape
    assert f"[{b},{h},{s},{s}]" not in text


def test_head_size_256_does_not_fit_and_is_not_taken(one_chip, as_tpu):
    """Why ``_TILED_HEAD_SIZES`` stops at 128: at blocks of 1024 the
    chip's compiler runs out of VMEM for 256."""
    import jax
    assert 256 not in nn_ops._TILED_HEAD_SIZES
    x = jax.ShapeDtypeStruct((2, 4, 2048, 256), jax.numpy.bfloat16,
                             sharding=one_chip)
    with pytest.raises(Exception, match="vmem"):
        jax.jit(_step).lower(x, x, x, x).compile()


def test_under_a_mesh_the_kernel_is_refused_and_the_op_goes_plain(
        topo, as_tpu):
    """A Pallas call has no partitioning rule: the chip's compiler
    refuses the kernel under a mesh, which is why a step traced for more
    than one device takes the plain path."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    x = jax.ShapeDtypeStruct((4, 4, 256, 64), jax.numpy.bfloat16,
                             sharding=NamedSharding(mesh,
                                                    P("data", "model")))
    sdpa = nn_ops.scaled_dot_product_attention

    def attend(devices):
        def fn(q, k, v):
            with nn_ops.attention_trace_scope(
                    AttentionSites(devices=devices)):
                return sdpa(q, k, v, causal=True)
        return jax.jit(fn).lower(x, x, x)

    with pytest.raises(Exception, match="cannot be automatically "
                                        "partitioned"):
        attend(1).compile()
    assert "tpu_custom_call" not in attend(4).compile().as_text()


def test_a_gpt_step_at_the_cells_widths_holds_no_score_matrix(one_chip,
                                                              as_tpu):
    """`medium_train`'s step cut to two layers (hidden 1024, 16 heads of
    64, batch 16 x 1024, bf16 compute, remat on), lowered for one v5e:
    both sites take the kernel and no ``[16, 16, 1024, 1024]`` array of
    any dtype is in the optimised program."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.autodiff import MixedPrecision, TrainingConfig
    from deeplearning4j_tpu.learning.updaters import Adam
    from deeplearning4j_tpu.zoo.gpt import GPTConfig, build_gpt
    cfg = GPTConfig(vocab_size=2048, hidden_size=1024, num_layers=2,
                    num_heads=16, intermediate_size=4096, max_seq_len=1024)
    sd = build_gpt(cfg, batch=16, seq_len=1024)
    sd.training_config = TrainingConfig(
        updater=Adam(1e-4), mixed_precision=MixedPrecision(),
        data_set_feature_mapping=["input_ids"],
        data_set_label_mapping=["targets"])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = {n: sds(a) for n, a in sd.trainable_params().items()}
    state = jax.tree_util.tree_map(
        sds, jax.eval_shape(sd.training_config.updater.init, params))
    ph = {n: jax.ShapeDtypeStruct((16, 1024), jnp.int32, sharding=one_chip)
          for n in ("input_ids", "targets")}
    body, _ = sd._build_step_body()
    text = jax.jit(body).lower(
        params, {}, state, jax.ShapeDtypeStruct((), jnp.int32,
                                                sharding=one_chip),
        {n: sds(a) for n, a in sd.constants_map().items()}, ph,
        jax.random.key(0)).compile().as_text()
    assert sd.attention_sites.counts() == (2, 0, None)
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 6
    assert "[16,16,1024,1024]" not in text


@pytest.mark.parametrize("entries,window", [(672, None), (257, 4096)])
def test_the_paged_decode_kernel_compiles_for_a_v5e(one_chip, as_tpu,
                                                    entries, window):
    """Command A+'s decode read at the cell's shapes (32 lanes, 128 query
    heads over 8 K/V heads of 128, the pool's interleaved leaf in blocks
    of 16, bf16): the global table's 672 entries, the ring's 257 under
    the window of 4,096. The leaf is read in place: no copy of it into
    another layout (JAX's shipped kernel, over the same leaf as its
    ``[pages, 16, 16, 128]``, gets one: the last lines)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import \
        ragged_paged_attention
    from deeplearning4j_tpu.zoo import paged_attend
    nb = 32 * (672 if window is None else 289) + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    args = (sds((32, 128, 128), jnp.bfloat16),
            sds((nb, 16, 2048), jnp.bfloat16),
            sds((32, entries), jnp.int32), sds((32,), jnp.int32))
    relayout = rf"= bf16\[({nb}|{2 * nb}),[0-9,]+\]\S* (copy|fusion)"
    text = jax.jit(
        lambda q, leaf, pages, rows: paged_attend.paged_decode(
            q, leaf, pages, rows, 128 ** -0.5, window)).lower(
        *args).compile().as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 1
    assert not re.search(relayout, text)
    shipped = jax.jit(
        lambda q, leaf, pages, rows: ragged_paged_attention(
            q, leaf.reshape(nb, 16, 16, 128), rows, pages,
            jnp.arange(33, dtype=jnp.int32), jnp.full((1,), 32, jnp.int32),
            sm_scale=128 ** -0.5, sliding_window=window,
            num_kv_pages_per_block=16, num_queries_per_block=1)).lower(
        *args).compile().as_text()
    assert re.search(relayout, shipped)


def test_the_cells_decode_program_reads_its_pages_in_place(one_chip,
                                                           as_tpu):
    """The whole decode program of `cmda_mixed_closed` (4 layers at the
    published widths, 16 experts held, 32 lanes, the global table at its
    top rung), lowered for one v5e: every layer takes the decode kernel,
    no table is gathered (no ``[32, 672, 16, ...]`` or ``[32, 257, 16,
    ...]`` array) and no leaf is copied, so the temporaries stay under
    0.5 GB (0.29; the gathered tables took 1.60 GB by the same compile,
    the shipped kernel's copy of every leaf into its layout 1.42)."""
    import json
    import os
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.monitor import attention
    from deeplearning4j_tpu.zoo import cohere2_moe as zoo
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "command-a-plus-05-2026.json")) as f:
        pc = zoo.Cohere2MoeConfig.from_dict(json.load(f))
    S = 32
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    params = {n: sds(s, jnp.bfloat16)
              for n, s in zoo.cohere2_moe_param_shapes(pc).items()}
    kc = tuple(sds(((32 * 289 if w else 32 * 672) + 1, 16, 2048),
                   jnp.bfloat16) for w in pc.window_layout)
    io = {"tokens": sds((S,), jnp.int32), "positions": sds((S,), jnp.int32),
          "write_off": sds((S,), jnp.int32), "active": sds((S,), jnp.bool_),
          "tables.global": sds((S, 672), jnp.int32),
          "tables.window": sds((S, 257), jnp.int32),
          "write_block.global": sds((S,), jnp.int32),
          "write_block.window": sds((S,), jnp.int32)}
    _, decode_fn = zoo.cohere2_moe_paged_decode_fns(pc, 16, 672)
    compiled = jax.jit(decode_fn, donate_argnums=(1, 2)).lower(
        params, kc, (), io).compile()
    assert attention.last_decode_program().counts() == (4, 0, None)
    text = compiled.as_text()
    assert len(re.findall(r"paged_decode_attention[.0-9]* = ", text)) == 4
    assert "[32,672,16," not in text and "[32,257,16," not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
