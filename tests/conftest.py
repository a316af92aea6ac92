"""Test harness configuration.

Reference parity: the reference runs its consolidated platform-tests module
against a backend selected by property (SURVEY.md §4). Here tests run on the
CPU backend with a virtual 8-device mesh so multi-chip sharding logic is
exercised without TPU hardware (XLA --xla_force_host_platform_device_count),
exactly how multi-device code must be CI-tested for TPU.
"""
import os

# Force CPU, set before jax is imported: unit tests run on the virtual
# 8-device CPU mesh whatever accelerator the machine has (the chip is
# exercised by chip_smoke.py, not by pytest).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
# The reference treats DOUBLE/INT64 as first-class dtypes; enable 64-bit on
# the CPU test backend. TPU runs keep jax's 32-bit defaults (MXU-friendly).
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax

jax.config.update("jax_enable_x64", True)

import signal

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


class ServedBatches:
    """Every padded batch a ``ParallelInference`` really ran, so that a
    test can put a served row beside the model's output FOR THE SAME
    PADDED BATCH: on the CPU the last bit of a row follows the batch's
    shape and the row's place in it, so a row served from a batch and
    ``net.output`` of that row alone differ by an ulp or two. Install it
    before any other wrapper of ``_execute``."""

    def __init__(self, pi):
        self.batches = []
        inner = pi._execute

        def recording(features, real_rows=None):
            outs = inner(features, real_rows=real_rows)
            self.batches.append(np.array(features[0], copy=True))
            return outs

        pi._execute = recording

    def directs(self, net, x):
        """``net.output`` of each recorded batch that held ``x``, cut
        to ``x``'s rows; the batch that ran last comes first (a request
        is answered from the last batch it was in)."""
        x = np.asarray(x)
        rows, want = x.shape[0], x.tobytes()
        outs, given = {}, set()     # one net.output a distinct batch
        for feats in reversed(self.batches):
            batch = feats.tobytes()
            for off in range(feats.shape[0] - rows + 1):
                if feats[off:off + rows].tobytes() != want \
                        or (batch, off) in given:
                    continue
                given.add((batch, off))
                if batch not in outs:
                    outs[batch] = net.output(feats).to_numpy()
                yield outs[batch][off:off + rows]

    def direct(self, net, x):
        for out in self.directs(net, x):
            return out
        raise AssertionError("no executed batch held these rows")


@pytest.fixture
def served_batches():
    return ServedBatches


@pytest.fixture(scope="session")
def lively():
    """``lively(spec)``: a generative or paged spec of the tiny GPT over
    other parameters. The seeded tiny model with its tied head only
    repeats its last token, whatever its caches and positions hold. With
    noise on every product's weights and the positional embedding eight
    times as large, the greedy next token follows the position and the
    whole context, so a row written or read in the wrong place changes
    the tokens. The programs are the spec's own; the copy compiles them
    once more."""
    import dataclasses

    def make(spec, seed=5):
        rng = np.random.default_rng(seed)
        params = {}
        for name, a in spec.params().items():
            a = np.asarray(a)
            if name == "wpe":
                a = a * 8
            elif a.ndim == 2 and name != "wte":
                a = a + rng.normal(0, 0.35, a.shape).astype(a.dtype)
            params[name] = a
        return dataclasses.replace(spec, params=lambda: params)

    return make


CHAOS_DEFAULT_TIMEOUT = 120


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Individually timeout-guard @pytest.mark.chaos tests: fault
    injection that wedges a run (a retry loop that never converges, a
    signal handler that deadlocks) must fail ONE test, not hang tier-1.
    SIGALRM-based, so it interrupts even a blocked main thread; chaos
    tests run on the main thread (pytest default) as required."""
    marker = item.get_closest_marker("chaos")
    if marker is None or not hasattr(signal, "SIGALRM"):
        return (yield)
    timeout = int(marker.kwargs.get("timeout", CHAOS_DEFAULT_TIMEOUT))

    def _expired(signum, frame):
        raise TimeoutError(
            f"chaos test exceeded its {timeout}s timeout guard")

    prev = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(timeout)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)
