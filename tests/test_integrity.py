"""integrity/ — stall watchdog, silent-corruption fingerprints,
checkpoint scrubbing (the non-raising-failure rail).

Covers the PR-4/PR-8 clean-path discipline (fingerprints + watchdog
armed vs off are bit-identical on the fused, per-step and scanned
tiers), pins each chaos injector to its typed error, and drives the
composite chaos e2e: one FaultTolerantFit run survives a stalled
dispatch, a param bit-flip and a rotten newest checkpoint, finishing
bit-identical to an uninterrupted run.
"""
import json
import os
import subprocess
import sys
import time
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.autodiff import (SameDiff, ScoreIterationListener,
                                         TrainingConfig)
from deeplearning4j_tpu.checkpoint import (CheckpointManager, Scrubber,
                                           capture_training_state)
from deeplearning4j_tpu.checkpoint import manifest as ckpt_manifest
from deeplearning4j_tpu.dataset.iterators import (ArrayDataSetIterator,
                                                  DeviceCachedIterator)
from deeplearning4j_tpu.faults import (ChaosMonkey, FaultTolerantFit,
                                       RetryPolicy, SilentCorruptionError,
                                       TrainingStalledError,
                                       retryable_errors)
from deeplearning4j_tpu.integrity import (StallWatchdog,
                                          check_replica_agreement,
                                          dump_all_stacks, np_fingerprint,
                                          np_leaf_fingerprint,
                                          state_fingerprint,
                                          tree_fingerprint,
                                          verify_state_stamp)
from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.ui.stats import StatsStorage


def _mlp(fused_steps=4, fingerprints=False, replay_every=0, lr=1e-2,
         accum_steps=1):
    rng = np.random.default_rng(0)
    sd = SameDiff()
    x = sd.placeholder("x", shape=(-1, 8))
    w0 = sd.var("w0", value=rng.normal(0, .1, (8, 16)).astype(np.float32))
    b0 = sd.var("b0", value=np.zeros(16, np.float32))
    h = sd.nn.relu(x.mmul(w0).add(b0))
    w1 = sd.var("w1", value=rng.normal(0, .1, (16, 2)).astype(np.float32))
    logits = h.mmul(w1)
    labels = sd.placeholder("labels", shape=(-1, 2))
    sd.loss.softmax_cross_entropy(logits, labels, name="loss")
    sd.set_loss_variables(["loss"])
    sd.training_config = TrainingConfig(
        updater=Adam(lr), data_set_feature_mapping=["x"],
        data_set_label_mapping=["labels"], fused_steps=fused_steps,
        accum_steps=accum_steps, fingerprints=fingerprints,
        fingerprint_replay_every=replay_every)
    return sd


def _data(n=128, seed=1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    Y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
    return X, Y


def _quiet():
    return ScoreIterationListener(print_every=10 ** 9,
                                  print_fn=lambda *a: None)


def _params(sd):
    return {n: np.asarray(a) for n, a in sd.trainable_params().items()}


def _fast_watchdog(**kw):
    kw.setdefault("k", 4.0)
    kw.setdefault("floor_s", 0.15)
    kw.setdefault("grace_s", 0.4)
    kw.setdefault("poll_s", 0.02)
    kw.setdefault("min_samples", 2)
    return StallWatchdog(**kw)


# ---------------------------------------------------------------------------
# the digest itself

class TestFingerprintDigest:
    def test_host_device_parity_across_dtypes(self, rng):
        arrs = [rng.normal(size=(5, 7)).astype(np.float32),
                rng.normal(size=(3,)).astype(np.float64),
                rng.integers(0, 255, (4, 4)).astype(np.uint8),
                rng.normal(size=(2, 3)).astype(np.float16),
                np.array([True, False, True]),
                rng.integers(-5, 5, (6,)).astype(np.int32),
                rng.integers(-5, 5, (2,)).astype(np.int64)]
        host = np_fingerprint(arrs)
        import jax.numpy as jnp
        dev = int(jax.device_get(
            tree_fingerprint([jnp.asarray(a) for a in arrs])))
        assert host == dev

    def test_single_bit_flip_always_changes_digest(self, rng):
        a = rng.normal(size=(4, 4)).astype(np.float32)
        base = np_leaf_fingerprint(a)
        flat = a.copy().view(np.uint8).reshape(-1)
        # a u32 word-sum mod 2^32 changes by ±2^b on ANY single-bit
        # flip — exhaustively true, spot-check a spread of positions
        for pos in (0, 7, 13, 31, 64, flat.size * 8 - 1):
            b = a.copy()
            v = b.view(np.uint8).reshape(-1)
            v[pos // 8] ^= np.uint8(1 << (pos % 8))
            assert np_leaf_fingerprint(b) != base, f"bit {pos} silent"

    def test_order_independence(self, rng):
        leaves = [rng.normal(size=(3, 3)).astype(np.float32)
                  for _ in range(5)]
        assert np_fingerprint(leaves) == np_fingerprint(leaves[::-1])

    def test_empty_and_scalar_leaves(self):
        assert np_fingerprint([np.empty((0,), np.float32)]) == 0
        s = np.float32(1.5)
        assert np_leaf_fingerprint(s) == \
            int(np.asarray(s).view(np.uint32))


# ---------------------------------------------------------------------------
# clean-path bit-identity (the PR-4/PR-8 discipline)

class TestCleanPathBitIdentity:
    def _run(self, tier, fingerprints, watchdog):
        sd = _mlp(fused_steps=4 if tier == "windowed" else 1,
                  fingerprints=fingerprints,
                  accum_steps=2 if tier == "accum" else 1)
        if tier == "accum":
            sd.training_config.fused_steps = 4
        X, Y = _data()
        it = DeviceCachedIterator(X, Y, batch_size=16) \
            if tier == "scanned" else ArrayDataSetIterator(X, Y,
                                                           batch_size=16)
        listeners = [] if tier == "scanned" else [_quiet()]
        if watchdog:
            with _fast_watchdog(grace_s=60.0, floor_s=60.0):
                h = sd.fit(it, epochs=2, listeners=listeners)
        else:
            h = sd.fit(it, epochs=2, listeners=listeners)
        return _params(sd), h, sd

    @pytest.mark.parametrize("tier", ["windowed", "per_step", "scanned",
                                      "accum"])
    def test_rail_on_is_bit_identical(self, tier):
        p_off, h_off, _ = self._run(tier, False, False)
        p_on, h_on, sd = self._run(tier, True, True)
        for n in p_off:
            assert np.array_equal(p_off[n], p_on[n]), n
        assert h_off.final_loss() == h_on.final_loss()
        if tier == "scanned":
            assert sd.last_fit_stats["tier"] == "scanned_epoch"
        # the rail actually ran: a boundary digest was produced
        assert sd._device_fingerprint is not None

    def test_all_tiers_agree_on_boundary_digest(self):
        """Fused, per-step and scanned tiers end at the same params —
        their device digests must agree bit-for-bit (cross-validates
        the in-window digest against the separate per-step program)."""
        fps = {}
        for tier in ("windowed", "per_step", "scanned"):
            _, _, sd = self._run(tier, True, False)
            fps[tier] = sd._device_fingerprint["fp"]
        assert len(set(fps.values())) == 1, fps

    def test_probe_windows_do_not_change_math(self):
        p_base, _, _ = self._run("windowed", True, False)
        sd = _mlp(fused_steps=4, fingerprints=True, replay_every=1)
        X, Y = _data()
        sd.fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=2,
               listeners=[_quiet()])
        assert sd.last_fit_stats["replay_probes"] > 0
        for n, v in p_base.items():
            assert np.array_equal(v, _params(sd)[n]), n


# ---------------------------------------------------------------------------
# capture stamping + restore re-verification

class TestCaptureAndRestoreStamp:
    def _trained(self, tmp_path, fingerprints=True):
        sd = _mlp(fingerprints=fingerprints)
        X, Y = _data()
        sd.fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=1,
               listeners=[_quiet()])
        mgr = CheckpointManager(tmp_path, keep_last_n=10,
                                async_write=False)
        return sd, mgr

    def test_capture_stamps_verified(self, tmp_path):
        sd, mgr = self._trained(tmp_path)
        mgr.save(8, model=sd, blocking=True)
        _, state = mgr.restore_latest()
        stamp = state.metadata["integrity"]
        assert stamp["verified"] is True
        assert stamp["fingerprint"] == stamp["device_fingerprint"] \
            == state_fingerprint(state)
        assert verify_state_stamp(state) is True
        mgr.close()

    def test_capture_mismatch_raises_typed(self, tmp_path):
        sd, mgr = self._trained(tmp_path)
        # corrupt the host-side state AFTER the device digest was taken
        # (what a bad D2H copy looks like)
        name = sorted(sd.trainable_params())[0]
        host = np.asarray(sd._arrays[name]).copy()
        host.view(np.uint8).reshape(-1)[3] ^= 1
        import jax.numpy as jnp
        sd._arrays[name] = jnp.asarray(host)
        with pytest.raises(SilentCorruptionError) as ei:
            capture_training_state(sd)
        assert ei.value.check == "capture"
        mgr.close()

    def test_unstamped_checkpoints_restore_as_before(self, tmp_path):
        sd, mgr = self._trained(tmp_path, fingerprints=False)
        mgr.save(8, model=sd, blocking=True)
        _, state = mgr.restore_latest()
        assert "integrity" not in state.metadata
        assert verify_state_stamp(state) is None
        mgr.close()

    def test_restore_reverifies_stamp(self, tmp_path):
        """Rot that the sha256 manifest can no longer witness (payload
        AND manifest rewritten) still fails typed at restore — and the
        verified-only walk lands on an older intact step."""
        sd, mgr = self._trained(tmp_path)
        mgr.save(8, model=sd, blocking=True)
        mgr.save(16, model=sd, blocking=True)
        d = mgr.step_dir(16)
        p = os.path.join(d, "arrays.npz")
        with np.load(p) as npz:
            arrays = {k: npz[k].copy() for k in npz.files}
        first = sorted(arrays)[0]
        arrays[first].view(np.uint8).reshape(-1)[3] ^= 1
        np.savez(p, **arrays)                  # valid npz, wrong bits
        ckpt_manifest.write_manifest(d)        # adversarial re-hash
        with pytest.raises(SilentCorruptionError):
            mgr.restore(16)
        with pytest.raises(SilentCorruptionError):
            mgr.restore_latest()
        step, _ = mgr.restore_latest(verified_only=True)
        assert step == 8
        assert mgr.latest_verified_step() == 8
        mgr.close()

    def test_retryable_hierarchy(self):
        types = retryable_errors()
        assert SilentCorruptionError in types
        assert TrainingStalledError in types


# ---------------------------------------------------------------------------
# replay probe + chaos corruption injectors

class TestReplayProbeAndBitflip:
    @pytest.mark.chaos
    def test_probe_catches_self_consistent_sdc(self):
        """refingerprint=True: device state and its digest agree but
        differ from a correct replay — only the probe can see it."""
        sd = _mlp(fingerprints=True, replay_every=1)
        X, Y = _data()
        chaos = ChaosMonkey(0)
        with chaos.bitflip_param(at_call=3):
            with pytest.raises(SilentCorruptionError) as ei:
                sd.fit(ArrayDataSetIterator(X, Y, batch_size=16),
                       epochs=1, listeners=[_quiet()])
        assert ei.value.check == "replay_probe"
        assert chaos.log[-1]["event"] == "param_bit_flipped"
        assert chaos.log[-1]["refingerprint"] is True

    @pytest.mark.chaos
    def test_capture_catches_transfer_corruption(self, tmp_path):
        """refingerprint=False: the in-program digest is intact, the
        returned bytes are not — the capture check sees it and the
        recovery driver rolls back to a VERIFIED checkpoint."""
        sd = _mlp(fingerprints=True)
        X, Y = _data()
        storage = StatsStorage()
        mgr = CheckpointManager(tmp_path, keep_last_n=10,
                                async_write=False)
        ftf = FaultTolerantFit(
            sd, mgr, policy=RetryPolicy(max_retries=2, backoff_base=0.0),
            checkpoint_every_n_iterations=4, stats_storage=storage,
            sleep=lambda s: None)
        chaos = ChaosMonkey(1)
        with chaos.bitflip_param(at_call=3, refingerprint=False):
            h = ftf.fit(ArrayDataSetIterator(X, Y, batch_size=16),
                        epochs=2)
        assert np.isfinite(h.final_loss())
        assert ftf.rollbacks >= 1
        rb = [r for r in storage.of_type("faults")
              if r["event"] == "rollback"]
        assert rb and all(r["verified_only"] for r in rb)
        fault = [r for r in storage.of_type("faults")
                 if r["event"] == "fault"][0]
        assert fault["cause"] == "silent_corruption"
        mgr.close()

    @pytest.mark.chaos
    def test_fingerprints_off_is_genuinely_silent(self):
        """The negative control: without the rail, the same bit flip
        trains through unnoticed — finite loss, corrupted timeline."""
        sd = _mlp(fingerprints=False)
        X, Y = _data()
        chaos = ChaosMonkey(0)
        with chaos.bitflip_param(at_call=1):
            h = sd.fit(ArrayDataSetIterator(X, Y, batch_size=16),
                       epochs=1, listeners=[_quiet()])
        assert np.isfinite(h.final_loss())      # nothing raised
        clean = _mlp(fingerprints=False)
        clean.fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=1,
                  listeners=[_quiet()])
        assert any(not np.array_equal(_params(sd)[n], _params(clean)[n])
                   for n in _params(sd))        # but the bits diverged


class TestReplicaAgreement:
    def test_replicated_params_agree(self):
        from jax.sharding import (Mesh, NamedSharding,
                                  PartitionSpec as P)
        devs = jax.devices()[:4]
        repl = NamedSharding(Mesh(np.array(devs), ("dp",)), P())
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        assert check_replica_agreement(
            {"w": jax.device_put(a, repl)}) == []

    def test_desynced_replica_raises(self):
        from jax.sharding import (Mesh, NamedSharding,
                                  PartitionSpec as P)
        devs = jax.devices()[:4]
        repl = NamedSharding(Mesh(np.array(devs), ("dp",)), P())
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        parts = [jax.device_put(a.copy(), d) for d in devs]
        bad = a.copy()
        bad.view(np.uint8).reshape(-1)[5] ^= 1
        parts[2] = jax.device_put(bad, devs[2])
        arr = jax.make_array_from_single_device_arrays(a.shape, repl,
                                                       parts)
        with pytest.raises(SilentCorruptionError) as ei:
            check_replica_agreement({"w": arr})
        assert ei.value.check == "replica_agreement"
        detail = check_replica_agreement({"w": arr}, raise_=False)
        assert detail[0]["array"] == "w"

    def test_host_arrays_short_circuit(self):
        # un-sharded host values have no addressable shards: no-op
        assert check_replica_agreement({"w": np.ones(3)}) == []


# ---------------------------------------------------------------------------
# stall watchdog

class TestStallWatchdog:
    def test_noop_guard_when_uninstalled(self):
        from deeplearning4j_tpu.integrity.watchdog import guard
        with guard("window_dispatch"):
            pass                                # shared null context

    def test_adaptive_deadline_and_compile_grace(self):
        wd = _fast_watchdog(k=10.0, floor_s=0.01, grace_s=5.0,
                            min_samples=3)
        # under min_samples → grace
        assert wd.deadline_for("b") == 5.0
        for v in (0.1, 0.1, 0.1):
            wd._percentiles.setdefault(
                "b", __import__(
                    "deeplearning4j_tpu.monitor.steptime",
                    fromlist=["RollingPercentiles"]
                ).RollingPercentiles(8)).add(v)
        assert wd.deadline_for("b") == pytest.approx(1.0)
        # a first (compiling) dispatch always gets the grace
        assert wd.deadline_for("b", first=True) == 5.0

    @pytest.mark.chaos
    def test_stall_raises_typed_with_forensics(self):
        from deeplearning4j_tpu.integrity.watchdog import guard
        storage = StatsStorage()
        wd = _fast_watchdog(storage=storage, min_samples=1,
                            floor_s=0.05, k=2.0)
        with wd:
            with guard("x"):
                time.sleep(0.002)
            with pytest.raises(TrainingStalledError) as ei:
                with guard("x"):
                    time.sleep(0.5)
        e = ei.value
        assert e.boundary == "x" and e.waited_s > e.deadline_s
        assert any(s["name"] for s in e.forensics["stacks"])
        prov = e.provenance()
        assert prov["cause"] == "stall" and prov["boundary"] == "x"
        events = [r["event"] for r in storage.of_type("faults")]
        assert events.count("stall") == 1
        forens = storage.of_type("integrity")
        assert forens and forens[0]["event"] == "stall_forensics"

    def test_stall_flips_health_until_recovered(self):
        from deeplearning4j_tpu.monitor.server import health_snapshot
        storage = StatsStorage()
        storage.put({"type": "faults", "event": "stall", "t": time.time(),
                     "boundary": "window_dispatch"})
        snap = health_snapshot(storage)
        assert snap["healthy"] is False
        assert snap["fault_state"] == "recovering"
        storage.put({"type": "faults", "event": "recovered",
                     "t": time.time()})
        assert health_snapshot(storage)["healthy"] is True

    def test_in_flight_exception_not_masked(self):
        from deeplearning4j_tpu.integrity.watchdog import guard
        wd = _fast_watchdog(min_samples=1, floor_s=0.05, k=2.0,
                            forensics=False)
        with wd:
            with guard("y"):
                time.sleep(0.002)
            with pytest.raises(ValueError):
                with guard("y"):
                    time.sleep(0.3)
                    raise ValueError("the real failure")

    @pytest.mark.chaos
    def test_stalled_dispatch_recovered_by_ftf(self, tmp_path):
        sd = _mlp()
        X, Y = _data()
        chaos = ChaosMonkey(0)
        storage = StatsStorage()
        mgr = CheckpointManager(tmp_path, async_write=False)
        ftf = FaultTolerantFit(
            sd, mgr, policy=RetryPolicy(max_retries=2, backoff_base=0.0),
            checkpoint_every_n_iterations=4, stats_storage=storage,
            sleep=lambda s: None)
        with _fast_watchdog(storage=storage):
            ftf.fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=1)
            with chaos.stalled_dispatch(delay_s=1.0, at_call=1):
                h = ftf.fit(ArrayDataSetIterator(X, Y, batch_size=16),
                            epochs=1)
        assert np.isfinite(h.final_loss())
        events = [r["event"] for r in storage.of_type("faults")]
        assert "stall" in events and "recovered" in events
        assert ftf.rollbacks == 1
        fault = [r for r in storage.of_type("faults")
                 if r["event"] == "fault"][0]
        assert fault["cause"] == "stall"
        mgr.close()


class TestStacksRoute:
    def test_dump_all_stacks_sees_this_thread(self):
        stacks = dump_all_stacks()
        me = [s for s in stacks if s["name"] == "MainThread"]
        assert me and any("dump_all_stacks" in ln or "test_" in ln
                          for ln in me[0]["stack"])

    def test_stacks_route_serves_json(self):
        from deeplearning4j_tpu.monitor.server import serve
        server = serve(storage=StatsStorage())
        try:
            body = json.loads(urllib.request.urlopen(
                server.url + "/stacks", timeout=10).read())
            assert body["threads"]
            index = urllib.request.urlopen(server.url + "/",
                                           timeout=10).read().decode()
            assert "/stacks" in index
        finally:
            server.close()


# ---------------------------------------------------------------------------
# checkpoint scrubber + restore-path memo

class TestScrubber:
    def _tree(self, tmp_path, steps=(4, 8, 12)):
        sd = _mlp(fingerprints=True)
        X, Y = _data()
        sd.fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=1,
               listeners=[_quiet()])
        mgr = CheckpointManager(tmp_path, keep_last_n=10,
                                async_write=False)
        for s in steps:
            mgr.save(s, model=sd, blocking=True)
        return sd, mgr

    def test_scrub_clean_tree(self, tmp_path):
        _, mgr = self._tree(tmp_path)
        storage = StatsStorage()
        rep = Scrubber(mgr, storage=storage).scrub_once()
        assert rep["scanned"] == 3 and rep["rotten"] == 0
        assert storage.of_type("integrity")[-1]["event"] == "scrub"
        mgr.close()

    @pytest.mark.chaos
    def test_rot_quarantined_aside_with_typed_record(self, tmp_path):
        _, mgr = self._tree(tmp_path)
        ChaosMonkey(0).rot_checkpoint(tmp_path, step=8)
        storage = StatsStorage()
        rep = Scrubber(mgr, storage=storage).scrub_once()
        assert rep["rotten"] == 1 and rep["quarantined"] == [8]
        rotten_dir = os.path.join(str(tmp_path), "step_00000008.rotten")
        assert os.path.isdir(rotten_dir)
        with open(os.path.join(rotten_dir, "ROTTEN.json")) as fh:
            rec = json.load(fh)
        assert rec["step"] == 8 and rec["problems"]
        # the quarantined name is invisible to restore/retention/gc
        assert mgr.all_steps() == [4, 12]
        assert mgr.restore_latest()[0] == 12
        assert mgr.gc_uncommitted() == []
        ev = [r["event"] for r in storage.of_type("integrity")]
        assert "checkpoint_quarantined" in ev
        mgr.close()

    @pytest.mark.chaos
    def test_rotten_newest_never_lands_mid_recovery(self, tmp_path):
        """The acceptance property: after a scrub, a rollback cannot
        land on bit-rot — and even WITHOUT a scrub, restore_latest's
        own verification skips it."""
        _, mgr = self._tree(tmp_path)
        ChaosMonkey(0).rot_checkpoint(tmp_path)      # newest = 12
        step, _ = mgr.restore_latest()
        assert step == 8
        mgr.close()

    @pytest.mark.chaos
    def test_re_rot_keeps_first_forensics(self, tmp_path):
        """A step that rots again after a re-save quarantines to
        .rotten.2 — the first incident's evidence stays untouched."""
        sd, mgr = self._tree(tmp_path, steps=(8,))
        ChaosMonkey(0).rot_checkpoint(tmp_path, step=8)
        sc = Scrubber(mgr)
        sc.scrub_once()
        first = os.path.join(str(tmp_path), "step_00000008.rotten")
        with open(os.path.join(first, "ROTTEN.json")) as fh:
            t_first = json.load(fh)["quarantined_t"]
        mgr.save(8, model=sd, blocking=True)           # re-save
        ChaosMonkey(1).rot_checkpoint(tmp_path, step=8)
        sc.scrub_once()
        second = first + ".2"
        assert os.path.isdir(first) and os.path.isdir(second)
        with open(os.path.join(first, "ROTTEN.json")) as fh:
            assert json.load(fh)["quarantined_t"] == t_first
        mgr.close()

    def test_rate_limit_sleeps_off_surplus(self, tmp_path):
        _, mgr = self._tree(tmp_path)
        slept = []
        sc = Scrubber(mgr, max_mb_per_s=1e-3,          # absurdly slow
                      sleep=lambda s: slept.append(s))
        sc.scrub_once()
        assert slept and sum(slept) > 0
        mgr.close()

    def test_background_cycles(self, tmp_path):
        _, mgr = self._tree(tmp_path)
        sc = Scrubber(mgr, interval_s=0.01)
        with sc:
            deadline = time.monotonic() + 5
            while sc.cycles < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
        assert sc.cycles >= 2
        mgr.close()

    def test_cli_exit_codes(self, tmp_path):
        from deeplearning4j_tpu.checkpoint.__main__ import main
        _, mgr = self._tree(tmp_path)
        mgr.close()
        assert main(["scrub", str(tmp_path)]) == 0
        ChaosMonkey(0).rot_checkpoint(tmp_path, step=8)
        assert main(["scrub", str(tmp_path)]) == 1
        assert main(["scrub", str(tmp_path / "nope")]) == 2
        assert main([]) == 2
        # --quarantine moves it aside; the tree is then clean again
        assert main(["scrub", str(tmp_path), "--quarantine"]) == 1
        assert main(["scrub", str(tmp_path)]) == 0

    def test_cli_subprocess_entrypoint(self, tmp_path):
        _, mgr = self._tree(tmp_path, steps=(4,))
        mgr.close()
        r = subprocess.run(
            [sys.executable, "-m", "deeplearning4j_tpu.checkpoint",
             "scrub", str(tmp_path), "--json"],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr
        rep = json.loads(r.stdout)
        assert rep["type"] == "integrity" and rep["scanned"] == 1


class TestRestoreMemo:
    def _hash_counter(self, monkeypatch):
        calls = {"n": 0}
        orig = ckpt_manifest.sha256_file

        def counting(path, chunk=1 << 20):
            calls["n"] += 1
            return orig(path, chunk)

        monkeypatch.setattr(ckpt_manifest, "sha256_file", counting)
        return calls

    def test_repeat_restores_skip_rehash(self, tmp_path, monkeypatch):
        sd = _mlp()
        X, Y = _data()
        sd.fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=1,
               listeners=[_quiet()])
        mgr = CheckpointManager(tmp_path, async_write=False)
        for s in (4, 8):
            mgr.save(s, model=sd, blocking=True)
        calls = self._hash_counter(monkeypatch)
        mgr.restore_latest()
        first = calls["n"]
        assert first > 0
        # the recovery-loop case: repeated rollbacks over unchanged
        # committed files must not re-hash on the critical path
        mgr.restore_latest()
        mgr.restore(8)
        assert calls["n"] == first
        mgr.close()

    def test_memo_expires_after_ttl(self, tmp_path, monkeypatch):
        """Media rot bypasses the filesystem (no mtime change), so
        memo entries expire: a restore after the TTL re-hashes even an
        unchanged dir."""
        sd = _mlp()
        X, Y = _data()
        sd.fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=1,
               listeners=[_quiet()])
        mgr = CheckpointManager(tmp_path, async_write=False,
                                verify_memo_ttl_s=0.0)
        mgr.save(4, model=sd, blocking=True)
        mgr.restore_latest()
        calls = self._hash_counter(monkeypatch)
        mgr.restore_latest()            # TTL 0: always expired
        assert calls["n"] > 0
        mgr.close()

    def test_memo_invalidates_on_change(self, tmp_path, monkeypatch):
        sd = _mlp()
        X, Y = _data()
        sd.fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=1,
               listeners=[_quiet()])
        mgr = CheckpointManager(tmp_path, async_write=False)
        mgr.save(4, model=sd, blocking=True)
        mgr.save(8, model=sd, blocking=True)
        mgr.restore_latest()
        calls = self._hash_counter(monkeypatch)
        ChaosMonkey(0).rot_checkpoint(tmp_path)        # newest = 8
        step, _ = mgr.restore_latest()
        assert step == 4                # re-hashed, caught, skipped
        assert calls["n"] > 0
        mgr.close()

    def test_scrubber_feeds_memo(self, tmp_path, monkeypatch):
        sd = _mlp()
        X, Y = _data()
        sd.fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=1,
               listeners=[_quiet()])
        mgr = CheckpointManager(tmp_path, async_write=False)
        mgr.save(4, model=sd, blocking=True)
        Scrubber(mgr).scrub_once()
        calls = self._hash_counter(monkeypatch)
        mgr.restore_latest()            # scrub already verified it
        assert calls["n"] == 0
        mgr.close()


# ---------------------------------------------------------------------------
# observability plumbing

class TestIntegrityObservability:
    def test_fold_integrity_metrics(self):
        from deeplearning4j_tpu.monitor.registry import MetricsRegistry
        reg = MetricsRegistry()
        reg.fold_integrity({"type": "integrity", "event": "scrub",
                            "scanned": 3, "rotten": 1, "bytes": 1024,
                            "seconds": 0.5, "quarantined": [8]})
        reg.fold_integrity({"type": "integrity",
                            "event": "checkpoint_quarantined", "step": 8})
        reg.fold_integrity({"type": "integrity",
                            "event": "stall_forensics", "waited_s": 1.2})
        text = reg.to_prometheus_text()
        assert "integrity_scrub_cycles_total 1" in text
        assert "integrity_rotten_total 1" in text
        assert "integrity_quarantined_total 1" in text
        assert "integrity_stalls_total 1" in text
        assert "integrity_last_rotten_step 8" in text

    def test_report_renders_integrity_panel(self):
        from deeplearning4j_tpu.ui.report import render_report
        storage = StatsStorage()
        storage.put({"type": "faults", "event": "stall", "t": time.time(),
                     "boundary": "window_dispatch", "waited_s": 1.5,
                     "deadline_s": 0.5, "threads": 3})
        storage.put({"type": "integrity", "event": "scrub",
                     "t": time.time(), "scanned": 3, "rotten": 1,
                     "quarantined": [8], "bytes": 4096, "seconds": 0.1})
        storage.put({"type": "integrity",
                     "event": "checkpoint_quarantined", "t": time.time(),
                     "step": 8, "problems": ["arrays.npz: sha256 "
                                             "mismatch"],
                     "quarantined_to": "/x/step_00000008.rotten"})
        html = render_report(storage)
        assert "Integrity" in html and "window_dispatch" in html
        assert "checkpoint scrubber" in html
        assert "unrendered record types" not in html


# ---------------------------------------------------------------------------
# the composite chaos e2e (acceptance)

class TestIntegrityChaosE2E:
    @pytest.mark.chaos
    def test_survives_stall_bitflip_and_rotten_checkpoint(self, tmp_path):
        """ONE FaultTolerantFit run survives a stalled dispatch, a
        param bit-flip and a rotten NEWEST checkpoint — and finishes
        bit-identical (params and final loss) to an uninterrupted
        run."""
        X, Y = _data()

        clean = _mlp(fingerprints=False)
        h_clean = clean.fit(ArrayDataSetIterator(X, Y, batch_size=16),
                            epochs=4, listeners=[_quiet()])

        sd = _mlp(fingerprints=True)
        chaos = ChaosMonkey(7)
        storage = StatsStorage()
        mgr = CheckpointManager(tmp_path, keep_last_n=16,
                                async_write=False)
        # epoch-boundary checkpoints: a rollback target is always a
        # whole-epoch boundary, so every retry replays complete epochs
        # and the healed run is bit-identical to the uninterrupted one
        ftf = FaultTolerantFit(
            sd, mgr, policy=RetryPolicy(max_retries=2, backoff_base=0.0,
                                        quarantine_corrupt=False),
            checkpoint_every_n_epochs=1, stats_storage=storage,
            sleep=lambda s: None)
        it = ArrayDataSetIterator(X, Y, batch_size=16)   # 8 steps/epoch
        with _fast_watchdog(storage=storage):
            # epoch 0: clean (warms the watchdog's percentiles and
            # commits verified rollback targets)
            ftf.fit(it, epochs=1)
            # epoch 1: a wedged dispatch that eventually un-wedges
            with chaos.stalled_dispatch(delay_s=1.0, at_call=1):
                ftf.fit(it, epochs=1)
            # epoch 2: silent corruption of the dispatched params —
            # on the epoch's LAST window (at_call=2 of 2), the boundary
            # whose digest the epoch-end capture verifies; an earlier
            # flip trains through device-side and is the replay probe's
            # case, pinned in TestReplayProbeAndBitflip
            with chaos.bitflip_param(at_call=2, refingerprint=False):
                ftf.fit(it, epochs=1)
            # epoch 3: the newest committed checkpoint rots on disk;
            # a poisoned batch then forces a rollback that MUST skip it
            chaos.rot_checkpoint(tmp_path)
            poisoned = chaos.poison_batches(it, at_step=2)
            h = ftf.fit(poisoned, epochs=1)
        assert sd.training_config.epoch_count == 4
        assert ftf.rollbacks >= 3
        events = [r["event"] for r in storage.of_type("faults")]
        assert "stall" in events
        assert "recovered" in events
        causes = {r.get("cause") for r in storage.of_type("faults")
                  if r["event"] == "fault"}
        assert {"stall", "silent_corruption"} <= causes
        # bit-identical to the uninterrupted run
        assert h.final_loss() == h_clean.final_loss()
        for n, v in _params(clean).items():
            assert np.array_equal(v, _params(sd)[n]), n
        mgr.close()
