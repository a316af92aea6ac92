"""faults/ — the robustness layer: detect → decide → recover.

- ``errors``    : structured fault hierarchy (step/epoch/batch provenance)
- ``sentinels`` : device-side divergence sentinel semantics + host-side
  loss-spike / plateau watchers (the per-layer ``LayerHealthWatcher``
  lives in monitor/tensorstats.py — it rides the in-graph tensor
  statistics — and is re-exported here next to its siblings)
- ``recovery``  : FaultTolerantFit — rollback-and-retry training over
  the checkpoint/ manager, bounded backoff, clean abort
- ``iterators`` : RetryingIterator — loader retry + corrupt-batch
  quarantine for the data pipeline
- ``chaos``     : deterministic seed-driven fault injection (NaN grads,
  loader exceptions, torn checkpoint commits, SIGTERM mid-window,
  host loss / topology shrink for elastic-resume drills)

See docs/fault_tolerance.md and docs/elastic_training.md.
"""
from deeplearning4j_tpu.checkpoint.manager import (ShardCountMismatchError,
                                                   TopologyChangedError)
from deeplearning4j_tpu.faults.chaos import (ChaosMonkey, FileBarrier,
                                             HostKiller, HostLossInjector,
                                             TornShard)
from deeplearning4j_tpu.faults.errors import (DataPipelineError,
                                              FaultBudgetExhaustedError,
                                              FaultError,
                                              ShardCorruptError,
                                              SilentCorruptionError,
                                              TrainingDivergedError,
                                              TrainingStalledError,
                                              TransientDeviceError,
                                              retryable_errors)
from deeplearning4j_tpu.faults.iterators import RetryingIterator
from deeplearning4j_tpu.faults.recovery import FaultTolerantFit, RetryPolicy
from deeplearning4j_tpu.faults.sentinels import (LossSpikeWatcher,
                                                 PlateauWatcher)
from deeplearning4j_tpu.monitor.tensorstats import LayerHealthWatcher

__all__ = ["ChaosMonkey", "DataPipelineError", "FaultBudgetExhaustedError",
           "FaultError", "FaultTolerantFit", "FileBarrier", "HostKiller",
           "HostLossInjector", "LayerHealthWatcher", "LossSpikeWatcher",
           "PlateauWatcher", "RetryPolicy", "RetryingIterator",
           "ShardCorruptError", "ShardCountMismatchError",
           "SilentCorruptionError", "TornShard", "TopologyChangedError",
           "TrainingDivergedError", "TrainingStalledError",
           "TransientDeviceError", "retryable_errors"]
