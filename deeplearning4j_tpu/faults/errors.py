"""Structured fault hierarchy for the training stack.

Every error the detect → decide → recover loop routes on carries machine-
readable provenance (absolute step, epoch, batch index, cause tag) so the
recovery driver — and a postmortem reading ``{"type": "faults"}`` stats
records — can answer *where* and *why* without parsing message strings.

Reference parity: the reference signals failure with bare
``ND4JIllegalStateException`` / ``RuntimeException`` from deep inside the
executor (DefaultOpExecutioner NAN_PANIC, FailureTestingListener); the
caller learns "something broke" but not at which iteration of which
epoch. Here the fault rail is typed end-to-end.
"""
from __future__ import annotations

from typing import Any, Dict, Optional


class FaultError(RuntimeError):
    """Base for all structured training-stack faults.

    ``provenance()`` returns the machine-readable view used for
    ``'faults'`` stats records and recovery decisions.
    """

    cause_tag: str = "fault"

    def __init__(self, message: str, *, step: Optional[int] = None,
                 epoch: Optional[int] = None,
                 batch_index: Optional[int] = None,
                 cause: Optional[str] = None,
                 value: Optional[float] = None):
        super().__init__(message)
        self.step = step
        self.epoch = epoch
        self.batch_index = batch_index
        self.cause = cause or self.cause_tag
        self.value = value

    def provenance(self) -> Dict[str, Any]:
        return {"error": type(self).__name__, "cause": self.cause,
                "step": self.step, "epoch": self.epoch,
                "batch_index": self.batch_index, "value": self.value}


class TrainingDivergedError(FaultError, ArithmeticError):
    """Training left the healthy regime: non-finite loss or gradient
    (device sentinel, ``TrainingConfig.sentinel``), a host-side loss
    spike, or a plateau watcher firing. Also an ``ArithmeticError`` so
    callers already catching ``NumericsException``-style numerics
    failures see it."""

    cause_tag = "divergence"


class DataPipelineError(FaultError):
    """A data loader/iterator failed: a worker-thread exception
    (``AsyncDataSetIterator``'s poisoned sentinel), a retry budget
    exhausted (``faults.RetryingIterator``), or a corrupt batch that
    could not be quarantined. ``batch_index`` is the index of the batch
    (within the current pass) that failed to materialize."""

    cause_tag = "data_pipeline"


class ShardCorruptError(DataPipelineError):
    """A data shard failed integrity verification: the bytes on disk do
    not match the ``ShardManifest`` (sha256/size/record-count mismatch,
    truncation, an unreadable npz) or the manifest itself is torn.
    RETRYABLE (⊂ :class:`DataPipelineError`): flaky NFS can serve bad
    bytes once and good bytes on the re-read, so the sharded reader
    retries within its budget before the shard is quarantined.
    ``shard`` names the shard file and ``offset`` the first affected
    record offset within it (None = whole-shard damage)."""

    cause_tag = "shard_corrupt"

    def __init__(self, message: str, *, shard: Optional[str] = None,
                 offset: Optional[int] = None, **kw):
        super().__init__(message, **kw)
        self.shard = shard
        self.offset = offset

    def provenance(self) -> Dict[str, Any]:
        out = super().provenance()
        out["shard"] = self.shard
        out["offset"] = self.offset
        return out


class TransientDeviceError(FaultError):
    """A device/runtime error believed transient (injected by the chaos
    harness; real runs map backend runtime errors onto the same retry
    path via ``retryable_errors()``)."""

    cause_tag = "device"


class FaultBudgetExhaustedError(FaultError):
    """The recovery driver's retry budget ran out. The model has been
    rolled back to the last committed checkpoint and a final checkpoint
    is committed — the run aborted *cleanly*; ``__cause__`` is the last
    underlying fault."""

    cause_tag = "budget_exhausted"


class TrainingStalledError(FaultError):
    """A blocking device boundary (window dispatch, flush device_get,
    serving exec, checkpoint capture) exceeded its adaptive stall
    deadline (integrity/watchdog.py) — the non-raising failure class:
    a wedged collective, a hung host↔device transfer, a lost device.
    RETRYABLE: a stall that eventually un-wedges (transient network
    partition, a straggling peer that recovers) heals through the
    normal rollback path; a permanent wedge never returns from the
    blocking call, but the watchdog has already published the
    ``{"type": "faults", "event": "stall"}`` record, flipped
    ``/healthz`` to 503, and dumped forensics for the supervisor that
    will eventually kill the process.

    ``forensics`` carries all-thread stacks, an HBM snapshot and the
    active compiled-program memory plan captured AT EXPIRY (while the
    boundary was still wedged), not at raise time."""

    cause_tag = "stall"

    def __init__(self, message: str, *, boundary: Optional[str] = None,
                 waited_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 forensics: Optional[Dict[str, Any]] = None, **kw):
        super().__init__(message, **kw)
        self.boundary = boundary
        self.waited_s = waited_s
        self.deadline_s = deadline_s
        self.forensics = dict(forensics or {})

    def provenance(self) -> Dict[str, Any]:
        out = super().provenance()
        out["boundary"] = self.boundary
        out["waited_s"] = self.waited_s
        out["deadline_s"] = self.deadline_s
        return out


class SilentCorruptionError(FaultError):
    """Bitwise state divergence that raised nothing: a replay probe's
    fingerprint mismatch (SDC/nondeterminism inside a dispatch), a
    device-vs-host fingerprint mismatch at checkpoint capture (a
    corrupted device→host copy), cross-replica fingerprint disagreement
    under DP sharding, or a checkpoint whose fingerprint stamp no
    longer matches its payload at restore (integrity/fingerprint.py).
    RETRYABLE — but ``faults.FaultTolerantFit`` answers it by rolling
    back to the last *fingerprint-verified* checkpoint rather than
    merely the newest (docs/fault_tolerance.md "Non-raising
    failures")."""

    cause_tag = "silent_corruption"

    def __init__(self, message: str, *, check: Optional[str] = None,
                 expected: Optional[int] = None,
                 actual: Optional[int] = None, **kw):
        super().__init__(message, **kw)
        self.check = check
        self.expected = expected
        self.actual = actual

    def provenance(self) -> Dict[str, Any]:
        out = super().provenance()
        out["check"] = self.check
        out["expected"] = self.expected
        out["actual"] = self.actual
        return out


def retryable_errors() -> tuple:
    """Exception classes the recovery driver treats as recoverable:
    the structured fault hierarchy, numerics panics from the fit tiers,
    checkpoint-write failures (``CheckpointError`` — which covers
    ``TopologyChangedError``/``ShardCountMismatchError``, the elastic
    topology-change signals routed through resharded restore), and the
    backend's runtime errors (preemption / transient device loss
    surface there)."""
    types = [TrainingDivergedError, DataPipelineError, TransientDeviceError,
             TrainingStalledError, SilentCorruptionError]
    from deeplearning4j_tpu.autodiff.samediff import NumericsException
    types.append(NumericsException)
    from deeplearning4j_tpu.checkpoint.manager import CheckpointError
    types.append(CheckpointError)
    from jax.errors import JaxRuntimeError
    types.append(JaxRuntimeError)
    return tuple(types)
