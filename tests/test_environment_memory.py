"""Environment flag catalog + memory observability tests (reference:
ND4JSystemProperties / Environment.h toggles; AllocationsTracker)."""
import os

import numpy as np
import pytest

from deeplearning4j_tpu import environment, memory
from deeplearning4j_tpu.environment import PROPERTIES, Environment
from deeplearning4j_tpu.memory import (
    AllocationsTracker, MemoryWatermark, device_memory_report, snapshot)


@pytest.fixture(autouse=True)
def _clean_env():
    env = environment()
    env.reset()
    saved = {s.key: os.environ.get(s.key) for s in PROPERTIES.values()}
    yield
    env.reset()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def test_catalog_defaults_and_describe():
    env = environment()
    assert env.is_verbose() is False
    assert env.default_dtype() == "float32"
    d = env.describe()
    for name in PROPERTIES:
        assert name in d
    assert env.platform() in ("cpu", "tpu", "gpu")
    assert env.device_count() >= 1


def test_env_var_resolution_and_override_precedence():
    os.environ["DL4J_TPU_VERBOSE"] = "true"
    env = environment()
    assert env.is_verbose() is True
    env.set("verbose", False)            # programmatic beats env var
    assert env.is_verbose() is False
    env.reset("verbose")
    assert env.is_verbose() is True


def test_unknown_property_rejected():
    with pytest.raises(KeyError):
        environment().get("bogus")
    with pytest.raises(KeyError):
        environment().set("bogus", 1)


def test_singleton_identity():
    assert environment() is Environment.get_instance()


def test_debug_flag_defaults_nan_panic():
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.learning.updaters import Sgd
    assert TrainingConfig(updater=Sgd(0.1)).nan_panic is False
    environment().set("debug", True)
    try:
        assert TrainingConfig(updater=Sgd(0.1)).nan_panic is True
    finally:
        environment().reset("debug")


def test_memory_snapshot_and_report():
    import jax.numpy as jnp
    keep = jnp.ones((256, 256), jnp.float32) + 0     # live device buffer
    states = snapshot()
    assert states and all(s.bytes_in_use >= 0 for s in states)
    rpt = device_memory_report()
    assert "MiB in use" in rpt
    assert memory.live_array_count() > 0
    del keep


def test_memory_watermark_context():
    import jax.numpy as jnp
    with MemoryWatermark() as wm:
        x = jnp.zeros((512, 512), jnp.float32) + 1.0
        x.block_until_ready()
    assert wm.peak_bytes >= 0
    assert "watermark" in wm.report()


def test_allocations_tracker_accounting():
    t = AllocationsTracker.get_instance()
    t.reset()
    t.allocate("workspace", 1024)
    t.allocate("workspace", 1024)
    t.release("workspace", 512)
    assert t.bytes_tracked("workspace") == 1536
    assert t.totals() == {"workspace": 1536}
    t.reset()
    assert t.totals() == {}


def test_verbose_compile_logging(capsys):
    from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
    from deeplearning4j_tpu.learning.updaters import Sgd
    environment().set("verbose", True)
    try:
        sd = SameDiff()
        x = sd.placeholder("x", shape=(None, 4))
        w = sd.var("w", value=np.ones((4, 2)))
        y = x.mmul(w, name="y")
        loss = y.square().mean(name="loss")
        loss.mark_as_loss()
        sd.training_config = TrainingConfig(
            updater=Sgd(0.01), data_set_feature_mapping=["x"],
            data_set_label_mapping=[])
        sd.make_train_step()
        out = capsys.readouterr().out
        assert "compiling train step" in out
    finally:
        environment().reset("verbose")
