"""monitor/ — the observability spine: trace spans, a unified metrics
registry, and per-window step-time attribution.

Reference parity: the deeplearning4j-ui stats pipeline answers "how is
training going"; this subsystem answers "where did the time go" —
always-on, cheap, and unified across training (fused windows and the
per-step tier), serving, checkpointing and the fault rail:

- :mod:`monitor.trace` — a thread-safe ring-buffered span tracer with
  a near-zero-cost disabled path and Chrome/Perfetto trace export; the
  hot paths are permanently instrumented (window executor stages,
  serving request lifecycle, checkpoint commits, rollback/retry).
- :mod:`monitor.registry` — labeled counters/gauges/histograms folding
  every subsystem's counters into one namespace, with Prometheus text
  export and ``{"type": "metrics"}`` StatsStorage records.
- :mod:`monitor.steptime` — per-window data-wait/dispatch/flush
  breakdowns computed from spans at existing flush boundaries (no
  extra device syncs; clean runs stay bit-identical), rolling
  percentiles, and a straggler watcher.
- :mod:`monitor.tensorstats` — in-graph per-layer gradient/update/
  param summaries (norms, nonfinite counts, log2-magnitude histograms)
  sampled inside the compiled step, folded into the scan carry like
  the divergence sentinel; plus the dead/exploding-layer watcher.
- :mod:`monitor.attention` — which path (tiled kernel or plain) each
  attention site of the train step traced last took, and the first
  reason for a plain one: a trace-time fact no span can show.
- :mod:`monitor.server` — the live telemetry HTTP endpoint
  (``monitor.serve(port=0)``): /metrics, /healthz, /readyz, /report,
  /trace, /stats over a stdlib ThreadingHTTPServer, loopback-bound.

See docs/observability.md.
"""
from deeplearning4j_tpu.monitor import memstats
from deeplearning4j_tpu.monitor.registry import MetricsRegistry
from deeplearning4j_tpu.monitor.server import (TelemetryServer,
                                               health_snapshot, serve)
from deeplearning4j_tpu.monitor.steptime import (MonitorListener,
                                                 RollingPercentiles,
                                                 StragglerWatcher,
                                                 window_rows)
from deeplearning4j_tpu.monitor.tensorstats import (LayerHealthWatcher,
                                                    TensorStatsConfig)
from deeplearning4j_tpu.monitor.trace import (TRACER, Span, Tracer,
                                              disable_tracing,
                                              enable_tracing, get_tracer)

__all__ = ["TRACER", "Span", "Tracer", "get_tracer", "enable_tracing",
           "disable_tracing", "MetricsRegistry", "MonitorListener",
           "RollingPercentiles", "StragglerWatcher", "window_rows",
           "TensorStatsConfig", "LayerHealthWatcher", "TelemetryServer",
           "serve", "health_snapshot"]
