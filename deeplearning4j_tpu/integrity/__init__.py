"""integrity/ — detect→diagnose→recover for failures that DON'T raise.

The faults/ rail (divergence sentinel, rollback-and-retry), serving
resilience and the datapipe plane all key on exceptions; at fleet scale
the dominant remaining class raises nothing: wedged dispatches and
collectives that hang forever, silent data corruption that flips a
param bit without tripping the isfinite sentinel, and checkpoint
bit-rot discovered mid-rollback. This package closes that gap,
composed WITH the existing substrate rather than beside it:

- ``watchdog``    — :class:`StallWatchdog`: a daemon heartbeat thread
  arming an adaptive deadline (k × rolling-p50, compile grace) around
  every blocking device boundary the tracer already names (window
  dispatch, flush device_get, serving exec, checkpoint capture); on
  expiry it dumps all-thread stacks + the active memory plan + an HBM
  snapshot into a typed ``TrainingStalledError``, publishes
  ``{"type": "faults", "event": "stall"}`` and flips ``/healthz`` to
  503. A recoverable stall is retryable under ``FaultTolerantFit``'s
  normal rollback budget.
- ``fingerprint`` — device-side bitwise fingerprints of params +
  optimizer state (a uint32 word-sum emitted by the compiled window
  exactly like the PR-4 sentinel carry — one extra int per window),
  checked at flush boundaries: device-vs-host at checkpoint capture,
  fingerprint-stamped checkpoints re-verified at restore, a periodic
  replay probe (re-dispatch from a stashed carry, compare digests),
  and cross-replica agreement under DP sharding. Mismatch raises
  ``SilentCorruptionError``; ``FaultTolerantFit`` answers by rolling
  back to the last fingerprint-VERIFIED checkpoint.
- the checkpoint scrubber lives with its subsystem
  (``checkpoint.Scrubber``): rate-limited background re-hashing of
  committed step dirs against their manifests, quarantining rotten
  steps aside so ``restore_latest`` never lands on bit-rot mid-
  recovery. ``python -m deeplearning4j_tpu.checkpoint scrub <dir>``
  is the offline fleet-side CLI.

Arm it: ``TrainingConfig.fingerprints = True`` (+
``fingerprint_replay_every`` / ``fingerprint_replica_every``), a
``StallWatchdog(...).install()`` (or context manager) around the run,
and a ``checkpoint.Scrubber(manager)`` next to long-retention trees.
Clean-path training with the whole rail armed is bit-identical to
rail-off (tested); its cost on the chip: not measured (ROADMAP D5).
See docs/fault_tolerance.md "Non-raising failures".
"""
from deeplearning4j_tpu.checkpoint.scrub import Scrubber
from deeplearning4j_tpu.faults.errors import (SilentCorruptionError,
                                              TrainingStalledError)
from deeplearning4j_tpu.integrity.fingerprint import (
    check_probes, check_replica_agreement, make_fingerprint_fn,
    np_fingerprint, np_leaf_fingerprint, replica_fingerprints,
    state_fingerprint, tree_fingerprint, verify_state_stamp)
from deeplearning4j_tpu.integrity.watchdog import (StallWatchdog,
                                                   dump_all_stacks, guard)

__all__ = ["Scrubber", "SilentCorruptionError", "StallWatchdog",
           "TrainingStalledError", "check_probes",
           "check_replica_agreement", "dump_all_stacks", "guard",
           "make_fingerprint_fn", "np_fingerprint",
           "np_leaf_fingerprint", "replica_fingerprints",
           "state_fingerprint", "tree_fingerprint",
           "verify_state_stamp"]
