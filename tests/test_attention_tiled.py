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
