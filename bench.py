"""Benchmarks: BASELINE.md target configs on one TPU chip.

Prints ONE JSON line (driver contract): the headline metric
{"metric", "value", "unit", "vs_baseline"} plus a "configs" dict with all
measured configs (step-time ms, samples/sec, MFU estimate each).

Configs (BASELINE.md):
1. lenet_mnist      — MultiLayerNetwork.fit(), batch 128 (zoo LeNet)
2. samediff_mlp     — SameDiff graph-autodiff MLP train step, batch 128
3. resnet50         — zoo ResNet-50, 224x224 ImageNet shapes, batch 128,
                      bf16 mixed precision (f32 master params)

All base configs train through the scanned whole-epoch step (one device
dispatch per epoch) with device-cached data — the same code path fit()
takes for any listener-free DeviceCachedIterator run. The *_listener
configs attach a ScoreIterationListener and run the fused-window tier
(fused_steps=8, docs/training_performance.md) — the production path —
and additionally report dispatches_per_epoch.

The reference publishes no benchmark numbers (BASELINE.json
"published": {}), so vs_baseline is null — an honest "no measured
reference baseline exists", not a self-granted parity.

Throughput = steady-state training samples/sec (PerformanceListener
definition, reference optimize/listeners/PerformanceListener.java:46-118).
MFU estimate = achieved matmul+conv FLOPs (3x forward for fwd+bwd) over
the device's bf16 peak from the one peak-rate table
(monitor/memstats.py, keyed by device_kind); forward FLOPs counted
analytically.

The run refuses any backend but ``tpu``, stamps ``platform`` /
``device_kind`` / ``device_count`` into its JSON, and exits non-zero
when any config raises — a number printed here is a number a chip
produced. One process holds a chip: ``cold_start`` spawns fresh-process
probes, so it runs FIRST, before this process initialises a backend.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _require_tpu() -> dict:
    """The device stamp every result carries — or a refusal: a
    throughput or an MFU from a CPU run is not a measurement of this
    system. Initialises the backend (this process then holds the chip)."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"bench.py: refusing to run on backend {backend!r}: these "
            f"are device measurements and there is no CPU fallback")
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _peak_flops() -> float:
    """bf16 peak FLOP/s of this device from the one table; a device
    kind that is not listed is an error where a utilization is printed."""
    from deeplearning4j_tpu.monitor import memstats
    peak = memstats.peak_flops()
    if peak is None:
        import jax
        raise RuntimeError(
            f"device kind {jax.devices()[0].device_kind!r} is not in the "
            f"peak-rate table (monitor/memstats.py); add it with its "
            f"source before printing a utilization")
    return peak


def _median_rate(fit_fn, n_samples, trials=3):
    rates = []
    for _ in range(trials):
        t0 = time.perf_counter()
        fit_fn()
        rates.append(n_samples / (time.perf_counter() - t0))
    return sorted(rates)[trials // 2]


def _dispatch_stats(sd):
    """dispatches_per_epoch + tier from the fit dispatch accounting."""
    st = getattr(sd, "last_fit_stats", None) or {}
    out = {}
    if "dispatches_per_epoch" in st:
        out["dispatches_per_epoch"] = st["dispatches_per_epoch"]
        out["tier"] = st.get("tier")
    return out


def _memory_stats():
    """Per-model memory trajectory: HBM peak after the
    run (the watermark the run needed) plus the active compiled
    program's plan bytes/flops when one was captured
    (monitor/memstats.py) — memory is tracked next to throughput."""
    from deeplearning4j_tpu import memory
    from deeplearning4j_tpu.monitor import memstats
    snap = memory.snapshot()
    out = {"hbm_peak_bytes": max((s.peak_bytes or s.bytes_in_use)
                                 for s in snap),
           "hbm_headroom_bytes": int(memstats.projected_headroom(snap))}
    plan = memstats.PLANS.active_plan()
    if plan is not None:
        out["plan_program"] = plan.label
        out["plan_total_bytes"] = int(plan.total_bytes)
        if plan.temp_bytes is not None:
            out["plan_temp_bytes"] = int(plan.temp_bytes)
        if plan.flops_per_step is not None:
            out["plan_gflops_per_step"] = round(
                plan.flops_per_step / 1e9, 3)
    return out


def bench_lenet(batch=128, listener=False, fused_steps=1):
    """BASELINE config 1 — plus the ``lenet_listener`` variant: a
    ScoreIterationListener attached (forcing off the scanned tier, as
    any production run with score/checkpoint listeners is) and
    ``fused_steps=8`` fused windows, tracking the listener-path
    throughput."""
    from deeplearning4j_tpu.autodiff import ScoreIterationListener
    from deeplearning4j_tpu.dataset import DeviceCachedIterator, load_mnist
    from deeplearning4j_tpu.zoo import LeNet

    X, y = load_mnist(train=True, n_synthetic=2048)
    Y = np.eye(10, dtype=np.float32)[y]
    n = (len(X) // batch) * batch
    net = LeNet(height=28, width=28, channels=1).build()
    it = DeviceCachedIterator(X, Y, batch_size=batch)
    listeners = [ScoreIterationListener(print_every=10 ** 9,
                                        print_fn=lambda *a: None)] \
        if listener else []
    fit = lambda epochs: net.fit(it, epochs=epochs, listeners=listeners,
                                 fused_steps=fused_steps)
    fit(2)                                      # warmup/compile
    epochs = 6
    sps = _median_rate(lambda: fit(epochs), epochs * n)
    # fwd conv+matmul FLOPs per image (LeNet 28x28: conv1 20x5x5 @28x28,
    # conv2 50x20x5x5 @14x14, fc 2450x500, out 500x10)
    fwd_flops = 2 * (20 * 5 * 5 * 1 * 28 * 28 + 50 * 5 * 5 * 20 * 14 * 14
                     + 2450 * 500 + 500 * 10)
    return {"samples_per_sec": round(sps, 1),
            "step_time_ms": round(1000.0 * batch / sps, 3),
            "mfu_est": round(3 * fwd_flops * sps / _peak_flops(), 5),
            "batch": batch, **_dispatch_stats(net.samediff),
            **_memory_stats()}


def _build_mlp_sd(hidden=(512, 256), fused_steps=1, sentinel=False,
                  seed=0, tensorstats=None, analyze=True,
                  fingerprints=False):
    """The BASELINE config-2 MLP graph (784 -> hidden -> 10, softmax CE,
    Adam 1e-3) — shared by bench_samediff_mlp and the cold-start child
    probe so the restart metric measures the same program the throughput
    benchmark does."""
    from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
    from deeplearning4j_tpu.learning.updaters import Adam

    rng = np.random.default_rng(seed)
    sd = SameDiff()
    x = sd.placeholder("x", shape=(-1, 784))
    cur, n_in = x, 784
    for i, h in enumerate(hidden):
        w = sd.var(f"w{i}", value=rng.normal(0, 0.05, (n_in, h)).astype(np.float32))
        b = sd.var(f"b{i}", value=np.zeros(h, np.float32))
        cur = sd.nn.relu(cur.mmul(w).add(b), name=f"h{i}")
        n_in = h
    w = sd.var("w_out", value=rng.normal(0, 0.05, (n_in, 10)).astype(np.float32))
    b = sd.var("b_out", value=np.zeros(10, np.float32))
    logits = cur.mmul(w).add(b, name="logits")
    labels = sd.placeholder("labels", shape=(-1, 10))
    sd.loss.softmax_cross_entropy(logits, labels, name="loss")
    sd.set_loss_variables(["loss"])
    builder = (TrainingConfig.builder()
               .updater(Adam(learning_rate=1e-3))
               .data_set_feature_mapping("x")
               .data_set_label_mapping("labels")
               .fused_steps(fused_steps)
               .sentinel(sentinel)
               .analyze(analyze))
    if tensorstats is not None:
        builder.tensorstats(tensorstats)
    if fingerprints:
        builder.fingerprints(True)
    sd.training_config = builder.build()
    return sd


def bench_samediff_mlp(batch=128, hidden=(512, 256), listener=False,
                       fused_steps=1, sentinel=False,
                       monitor_storage=None, tensorstats=None,
                       monitor_memory=True, analyze=True,
                       fingerprints=False):
    """BASELINE config 2: SameDiff MLP via the graph-autodiff train path
    (reference TrainingSession.java:74). ``listener``/``fused_steps``
    give the listener-path variant (see bench_lenet); ``sentinel`` arms
    the device-side divergence sentinel (docs/fault_tolerance.md);
    ``monitor_storage`` attaches a monitor.MonitorListener publishing
    steptime/metrics records into it; ``tensorstats`` (True or a
    TensorStatsConfig) arms the in-graph per-layer statistics
    (docs/observability.md)."""
    from deeplearning4j_tpu.autodiff import ScoreIterationListener

    rng = np.random.default_rng(0)
    sd = _build_mlp_sd(hidden=hidden, fused_steps=fused_steps,
                       sentinel=sentinel, tensorstats=tensorstats,
                       analyze=analyze, fingerprints=fingerprints)

    from deeplearning4j_tpu.dataset import DeviceCachedIterator
    n = 2048
    X = rng.normal(size=(n, 784)).astype(np.float32)
    Y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, n)]
    it = DeviceCachedIterator(X, Y, batch_size=batch)

    listeners = [ScoreIterationListener(print_every=10 ** 9,
                                        print_fn=lambda *a: None)] \
        if listener else []
    sd.fit(it, epochs=2, listeners=listeners)   # warmup/compile
    if monitor_storage is not None:
        # attached AFTER warmup so the steptime records describe
        # steady state — the one-time XLA compile happens inside the
        # warmup windows' dispatch spans and must not inflate the
        # published dispatch share
        from deeplearning4j_tpu.monitor import MonitorListener
        listeners = listeners + [MonitorListener(monitor_storage,
                                                 memory=monitor_memory)]
    epochs = 6
    sps = _median_rate(lambda: sd.fit(it, epochs=epochs,
                                      listeners=listeners), epochs * n)
    fwd_flops = 2 * (784 * hidden[0] + hidden[0] * hidden[1]
                     + hidden[1] * 10)
    return {"samples_per_sec": round(sps, 1),
            "step_time_ms": round(1000.0 * batch / sps, 3),
            "mfu_est": round(3 * fwd_flops * sps / _peak_flops(), 5),
            "batch": batch, **_dispatch_stats(sd), **_memory_stats()}


def bench_sentinel_overhead(batch=128, fused_steps=8, repeats=2):
    """Cost of the divergence rail (faults/, docs/fault_tolerance.md):
    the fused-window listener config with the device sentinel off vs on.
    The sentinel adds one finiteness reduction per step inside the scan
    and one int32 per window — the acceptance bar is ≤5% steps/s.

    Run-to-run jitter can exceed the effect size (its spread on the
    chip is not measured yet), so each flag is measured ``repeats`` times interleaved and
    the best rate per flag is compared (the min-overhead estimator for
    a one-sided cost)."""
    best = {False: 0.0, True: 0.0}
    for _ in range(repeats):
        for flag in (False, True):
            r = bench_samediff_mlp(batch=batch, listener=True,
                                   fused_steps=fused_steps, sentinel=flag)
            best[flag] = max(best[flag], r["samples_per_sec"])
    overhead = (best[False] - best[True]) / best[False] * 100.0 \
        if best[False] else 0.0
    return {"samples_per_sec": best[True],
            "samples_per_sec_sentinel_off": best[False],
            "step_time_ms": round(1000.0 * batch / best[True], 3)
            if best[True] else 0.0,
            "sentinel_overhead_pct": round(overhead, 2),
            "batch": batch, "fused_steps": fused_steps}


def bench_tensorstats_overhead(batch=128, fused_steps=8, repeats=2):
    """Cost of the in-graph tensor-statistics rail (monitor/
    tensorstats.py, docs/observability.md): the fused-window listener
    config with per-layer grad/update/param summaries off vs on at the
    default sampling cadence. The stats compute under a lax.cond only
    on sampled steps (1-in-every_n), plus two small extra carry
    outputs per window and their share of the flush's device_get —
    the acceptance bar is ≤3% steps/s. Same best-of-``repeats``
    interleaved estimator as sentinel_overhead (run-to-run jitter can
    exceed the effect size)."""
    from deeplearning4j_tpu.monitor import TensorStatsConfig

    cfg = TensorStatsConfig()          # the default cadence under test
    best = {False: 0.0, True: 0.0}
    for _ in range(repeats):
        for flag in (False, True):
            r = bench_samediff_mlp(batch=batch, listener=True,
                                   fused_steps=fused_steps,
                                   tensorstats=cfg if flag else None)
            best[flag] = max(best[flag], r["samples_per_sec"])
    overhead = (best[False] - best[True]) / best[False] * 100.0 \
        if best[False] else 0.0
    return {"samples_per_sec": best[True],
            "samples_per_sec_tensorstats_off": best[False],
            "step_time_ms": round(1000.0 * batch / best[True], 3)
            if best[True] else 0.0,
            "tensorstats_overhead_pct": round(overhead, 2),
            "every_n": cfg.every_n, "families": list(cfg.families),
            "batch": batch, "fused_steps": fused_steps}


def bench_integrity_overhead(batch=128, fused_steps=8, repeats=2):
    """Cost of the integrity rail (integrity/, docs/fault_tolerance.md
    "Non-raising failures"): the fused-window K=8 listener config with
    state fingerprints + an armed StallWatchdog on vs both off. The
    fingerprint adds ONE uint32 word-sum of params/optimizer state per
    window (computed once on the post-scan carry) and its share of the
    flush's device_get; the watchdog adds one guard (a deadline
    register/unregister under a lock) around every dispatch and flush.
    Replay probes / replica checks are cadence knobs benchmarked as
    off (their cost is 1/N redispatches by construction). Acceptance
    bar ≤2% steps/s; same best-of-``repeats`` interleaved estimator as
    sentinel_overhead (jitter can exceed the effect size)."""
    from deeplearning4j_tpu.integrity import StallWatchdog

    best = {False: 0.0, True: 0.0}
    for _ in range(repeats):
        for flag in (False, True):
            if flag:
                wd = StallWatchdog(k=8.0, floor_s=5.0, grace_s=120.0)
                with wd:
                    r = bench_samediff_mlp(batch=batch, listener=True,
                                           fused_steps=fused_steps,
                                           fingerprints=True)
            else:
                r = bench_samediff_mlp(batch=batch, listener=True,
                                       fused_steps=fused_steps)
            best[flag] = max(best[flag], r["samples_per_sec"])
    overhead = (best[False] - best[True]) / best[False] * 100.0 \
        if best[False] else 0.0
    return {"samples_per_sec": best[True],
            "samples_per_sec_integrity_off": best[False],
            "step_time_ms": round(1000.0 * batch / best[True], 3)
            if best[True] else 0.0,
            "integrity_overhead_pct": round(overhead, 2),
            "batch": batch, "fused_steps": fused_steps}


def bench_analyze_overhead(batch=128, fused_steps=8, repeats=2):
    """Cost of the pre-compile static analyzer (analyze/,
    docs/static_analysis.md) on the warm dispatch path: the
    fused-window listener config with TrainingConfig.analyze on vs
    off. The analysis runs ONCE per graph version, before the first
    compile — warm fits pay a cache-key dict lookup — so the bar is
    ~0% (noise). The one-time analysis wall cost is reported
    separately (analysis_seconds). Same best-of-``repeats``
    interleaved estimator as the other rail probes."""
    best = {False: 0.0, True: 0.0}
    for _ in range(repeats):
        for flag in (False, True):
            r = bench_samediff_mlp(batch=batch, listener=True,
                                   fused_steps=fused_steps,
                                   analyze=flag)
            best[flag] = max(best[flag], r["samples_per_sec"])
    overhead = (best[False] - best[True]) / best[False] * 100.0 \
        if best[False] else 0.0
    # the one-time pre-compile cost, measured directly
    from deeplearning4j_tpu.analyze import analyze_training
    sd = _build_mlp_sd(fused_steps=fused_steps)
    rep = analyze_training(sd, has_listeners=True)
    return {"samples_per_sec": best[True],
            "samples_per_sec_analyze_off": best[False],
            "step_time_ms": round(1000.0 * batch / best[True], 3)
            if best[True] else 0.0,
            "analyze_overhead_pct": round(overhead, 2),
            "analysis_seconds": round(rep.seconds, 4),
            "rules_run": rep.rules_run,
            "findings": sum(rep.counts().values()),
            "batch": batch, "fused_steps": fused_steps}


def bench_memory_overhead(batch=128, fused_steps=8, repeats=2):
    """Cost of the HBM telemetry rail (monitor/memstats.py,
    docs/observability.md "Memory observability"): the fused-window
    K=8 listener path with a MonitorListener whose memory telemetry
    (per-flush {"type": "memory"} records + plan capture + the MFU
    gauge) is on vs off. The on-path additions are pure host work at
    flush boundaries the host already syncs on — one PJRT counter read
    per device (or a live-array walk on CPU), a dict of tagged totals,
    and one registry gauge set — the acceptance bar is ≤2% steps/s.
    Same best-of-``repeats`` interleaved estimator as
    sentinel_overhead (run-to-run jitter can exceed the effect
    size). Clean runs are bit-identical on vs off
    (tests/test_memory_obs.py)."""
    from deeplearning4j_tpu.monitor import memstats
    from deeplearning4j_tpu.ui.stats import StatsStorage

    # the capture switch is process-global (main() arms it for the
    # whole run; MonitorListener arms it too): the off leg must really
    # run without it, and the ENTRY state must be restored afterwards —
    # leaving it off would strip plan capture (and misattribute stale
    # plans) from every config that runs after this one
    was_enabled = memstats.plan_capture_enabled()
    best = {False: 0.0, True: 0.0}
    try:
        for _ in range(repeats):
            for flag in (False, True):
                if flag:
                    memstats.enable_plan_capture()
                else:
                    memstats.disable_plan_capture()
                r = bench_samediff_mlp(batch=batch, listener=True,
                                       fused_steps=fused_steps,
                                       monitor_storage=StatsStorage(),
                                       monitor_memory=flag)
                best[flag] = max(best[flag], r["samples_per_sec"])
    finally:
        if was_enabled:
            memstats.enable_plan_capture()
        else:
            memstats.disable_plan_capture()
    overhead = (best[False] - best[True]) / best[False] * 100.0 \
        if best[False] else 0.0
    return {"samples_per_sec": best[True],
            "samples_per_sec_memory_off": best[False],
            "step_time_ms": round(1000.0 * batch / best[True], 3)
            if best[True] else 0.0,
            "memory_overhead_pct": round(overhead, 2),
            "batch": batch, "fused_steps": fused_steps,
            **_memory_stats()}


def bench_tracer_overhead(batch=128, fused_steps=8, repeats=2):
    """Cost of the observability rail (monitor/, docs/observability.md):
    the fused-window listener config with span tracing off vs on. The
    disabled path adds one no-op attribute check per span site (bar:
    unmeasurable, guarded ≤1% analytically in tests/test_monitor.py);
    enabled tracing adds two clock reads + a locked ring append per
    span, ~5 spans per K-step window — the acceptance bar is ≤3%
    steps/s. Same best-of-``repeats`` interleaved estimator as
    sentinel_overhead (run-to-run jitter can exceed the effect
    size).

    Also reports the measured step-time breakdown — the aggregate of
    the monitored run's {"type": "steptime"} records: where the wall
    time of a fused listener-path step actually goes (data-wait vs
    dispatch vs flush)."""
    from deeplearning4j_tpu.monitor import disable_tracing, enable_tracing
    from deeplearning4j_tpu.ui.stats import StatsStorage

    best = {False: 0.0, True: 0.0}
    for _ in range(repeats):
        for flag in (False, True):
            if flag:
                enable_tracing(reset=True)
            else:
                disable_tracing()
            try:
                r = bench_samediff_mlp(batch=batch, listener=True,
                                       fused_steps=fused_steps)
            finally:
                disable_tracing()
            best[flag] = max(best[flag], r["samples_per_sec"])
    overhead = (best[False] - best[True]) / best[False] * 100.0 \
        if best[False] else 0.0
    # one monitored (traced + MonitorListener) run for the breakdown —
    # not part of the timed comparison
    storage = StatsStorage()
    enable_tracing(reset=True)
    try:
        bench_samediff_mlp(batch=batch, listener=True,
                           fused_steps=fused_steps,
                           monitor_storage=storage)
    finally:
        disable_tracing()
    recs = [r for r in storage.of_type("steptime")
            if r.get("event") != "straggler"]
    wall = sum(r.get("wall_s", 0.0) for r in recs) or 1.0
    breakdown = {f"{stage}_pct": round(
        100.0 * sum(r.get(f"{stage}_s", 0.0) for r in recs) / wall, 2)
        for stage in ("data_wait", "dispatch", "flush", "other")}
    breakdown["step_ms_p50"] = recs[-1].get("step_ms_p50") if recs else None
    breakdown["steps"] = sum(r.get("steps", 0) for r in recs)
    return {"samples_per_sec": best[True],
            "samples_per_sec_tracing_off": best[False],
            "step_time_ms": round(1000.0 * batch / best[True], 3)
            if best[True] else 0.0,
            "tracer_overhead_pct": round(overhead, 2),
            "steptime_breakdown": breakdown,
            "batch": batch, "fused_steps": fused_steps}


def bench_serving_resilience_overhead(n_requests=768, concurrency=8,
                                      repeats=2):
    """Cost of the serving resilience rail (serving/resilience.py,
    docs/serving.md "Resilience"): closed-loop throughput through the
    BATCHED path with admission control + circuit breaker + supervised
    workers on vs off. The healthy-path additions are one breaker
    acquire per batch, one rolling-percentile insert per exec, one
    admission estimate per submit, and the per-request finite-output
    scan — the acceptance bar is ≤3% req/s. Same best-of-``repeats``
    interleaved estimator as sentinel_overhead (run-to-run jitter
    exceeds the effect size)."""
    from deeplearning4j_tpu.learning.updaters import Adam
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.serving import (InferenceMode, LoadGenerator,
                                            ParallelInference)

    n_in = 64

    def build_server(flag):
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer(n_out=256, activation="tanh"))
                .layer(OutputLayer(n_out=10, loss_function="MCXENT"))
                .set_input_type(InputType.feed_forward(n_in))
                .build())
        net = MultiLayerNetwork(conf).init()
        return ParallelInference(net, mode=InferenceMode.BATCHED,
                                 workers=2, max_batch_size=32,
                                 max_delay_ms=1.0, max_queue_len=1024,
                                 resilience=flag)

    best = {False: 0.0, True: 0.0}
    for _ in range(repeats):
        for flag in (False, True):
            pi = build_server(flag)
            try:
                lg = LoadGenerator(
                    pi, lambda rng, i: rng.normal(size=(2, n_in))
                    .astype(np.float32), seed=3)
                lg.run_closed(n_requests=max(64, n_requests // 4),
                              concurrency=concurrency)   # warmup/compile
                res = lg.run_closed(n_requests=n_requests,
                                    concurrency=concurrency)
            finally:
                pi.shutdown()
            best[flag] = max(best[flag], res.throughput_rps)
    overhead = (best[False] - best[True]) / best[False] * 100.0 \
        if best[False] else 0.0
    return {"throughput_rps": round(best[True], 1),
            "throughput_rps_resilience_off": round(best[False], 1),
            "resilience_overhead_pct": round(overhead, 2),
            "n_requests": n_requests, "concurrency": concurrency}


def bench_generative(n_requests=32, max_slots=8, max_seq_len=160,
                     prompt_len=(2, 16), new_tokens=None,
                     concurrency=32, seed=11):
    """Continuous-batching generative serving (serving/generative.py,
    ROADMAP item 1): a seeded mixed prompt/output-length
    trace driven through a GPT decode server twice — ``admit=
    "continuous"`` (step-boundary admission into free KV slots) vs
    ``admit="static"`` (the wait-for-full-batch baseline, a new wave
    only when every slot is free). Same trace, same compiled programs;
    the acceptance bar is continuous ≥ 2x static tokens/sec on mixed
    lengths. Reports tokens/sec/chip, p50/p99 TTFT, p50 inter-token
    latency and slot occupancy, all from the shared
    ``GenerativeLoadGenerator`` driver."""
    from deeplearning4j_tpu.serving.generative import GenerativeServer
    from deeplearning4j_tpu.serving.loadgen import GenerativeLoadGenerator
    from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                            gpt_generative_spec)

    # big enough that decode compute (not host scheduling) dominates
    # the CPU smoke wall clock; on-chip the step ratio is the binding
    # quantity and it runs 2.5-3x (decode_steps in the sub-dicts)
    cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=4,
                    num_heads=8, intermediate_size=512,
                    max_seq_len=max_seq_len)
    sd = build_gpt(cfg, batch=2, seq_len=8, seed=0)
    spec = gpt_generative_spec(sd, cfg)
    if new_tokens is None:
        # long-tailed output lengths (the distribution continuous
        # batching exists for): mostly short answers, a 20% tail of
        # long generations that would hold a static batch hostage
        def new_tokens(rng):
            return int(rng.integers(2, 9)) if rng.random() < 0.8 \
                else int(rng.integers(80, 129))
    out = {}
    for mode in ("continuous", "static"):
        srv = GenerativeServer(spec, max_slots=max_slots,
                               max_seq_len=max_seq_len, admit=mode,
                               warmup=True)
        try:
            lg = GenerativeLoadGenerator(srv, seed=seed,
                                         prompt_len=prompt_len,
                                         new_tokens=new_tokens)
            res = lg.run_closed(n_requests=n_requests,
                                concurrency=concurrency)
        finally:
            srv.shutdown()
        rec = srv.metrics.to_record()
        out[mode] = {
            "tokens_per_sec": round(res.tokens_per_sec, 1),
            "ttft_p50_ms": round(res.ttft_percentile(50), 3),
            "ttft_p99_ms": round(res.ttft_percentile(99), 3),
            "intertoken_p50_ms": round(res.intertoken_percentile(50), 3),
            "slot_occupancy": rec["generative"]["slot_occupancy"],
            "decode_steps": rec["generative"]["decode_steps"],
            "n_ok": res.n_ok,
            "compiles": rec["counters"]["compiles"],
            "warmup_compiles": rec["counters"]["warmup_compiles"]}
    cont, stat = out["continuous"], out["static"]
    speedup = cont["tokens_per_sec"] / stat["tokens_per_sec"] \
        if stat["tokens_per_sec"] else 0.0
    return {"samples_per_sec": cont["tokens_per_sec"],   # tokens/sec/chip
            "tokens_per_sec": cont["tokens_per_sec"],
            "ttft_p50_ms": cont["ttft_p50_ms"],
            "ttft_p99_ms": cont["ttft_p99_ms"],
            "intertoken_p50_ms": cont["intertoken_p50_ms"],
            "slot_occupancy": cont["slot_occupancy"],
            "static_tokens_per_sec": stat["tokens_per_sec"],
            "static_slot_occupancy": stat["slot_occupancy"],
            "continuous_vs_static_speedup": round(speedup, 2),
            "max_slots": max_slots, "n_requests": n_requests,
            "continuous": cont, "static": stat}


def bench_serving_paged(n_requests=32, dense_slots=4, max_seq_len=256,
                        block_size=16, prompt_len=(2, 16),
                        concurrency=16, seed=13):
    """Paged KV vs dense slabs at EQUAL HBM (serving/paged/, ISSUE 16).

    The dense server preallocates ``max_seq`` KV rows per slot, so its
    concurrent capacity at a fixed HBM budget is ``budget /
    (max_seq_row_bytes)`` regardless of how short requests actually
    are. The paged server spends the SAME budget as a block pool and
    reserves each request's own worst case, so a mixed-length trace
    (mostly short chats, a 20% long tail) fits several times the
    concurrent requests — the acceptance bar is >= 4x. Also records
    the prefix-caching TTFT win (a repeated prompt prefills only its
    suffix: hit TTFT ~ one decode step, vs the cold full-prompt
    prefill) and the tp=2 greedy bit-identity bit."""
    import jax

    from deeplearning4j_tpu.serving.generative import (GenerativeServer,
                                                       greedy_decode)
    from deeplearning4j_tpu.serving.loadgen import GenerativeLoadGenerator
    from deeplearning4j_tpu.serving.paged import (PagedGenerativeServer,
                                                  blocks_for_tokens)
    from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                            gpt_generative_spec,
                                            gpt_paged_spec)
    cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=4,
                    num_heads=8, intermediate_size=512,
                    max_seq_len=max_seq_len)
    sd = build_gpt(cfg, batch=2, seq_len=8, seed=0)
    dense_spec = gpt_generative_spec(sd, cfg)
    paged_spec = gpt_paged_spec(sd, cfg)
    # the shared budget: what the SMALL dense deployment preallocates
    dense_bytes = 2 * int(np.prod(
        dense_spec.kv_shape(dense_slots, max_seq_len))) * 4

    def new_tokens(rng):
        # mostly short answers, a 20% long tail (same shape as the
        # continuous-batching bench, scaled into this max_seq)
        return int(rng.integers(2, 9)) if rng.random() < 0.8 \
            else int(rng.integers(64, 97))

    # -- concurrent capacity at equal HBM (worst-case commitment) ------
    rng = np.random.default_rng(seed)
    trace = [(int(rng.integers(prompt_len[0], prompt_len[1] + 1)),
              new_tokens(rng)) for _ in range(max(n_requests, 64))]
    bytes_per_block = 2 * int(np.prod(
        paged_spec.kv_shape(1, block_size))) * 4
    pool_capacity = dense_bytes // bytes_per_block - 1   # null block
    committed = admitted = 0
    for p, n in trace:
        need = blocks_for_tokens(min(p + n, max_seq_len), block_size)
        if committed + need > pool_capacity:
            break
        committed += need
        admitted += 1
    capacity_ratio = admitted / dense_slots if dense_slots else 0.0

    # -- same trace through both servers at the same HBM budget --------
    out = {}
    servers = {
        "dense": lambda: GenerativeServer(
            dense_spec, max_slots=dense_slots, max_seq_len=max_seq_len,
            warmup=True),
        "paged": lambda: PagedGenerativeServer(
            paged_spec, max_slots=concurrency, max_seq_len=max_seq_len,
            block_size=block_size, kv_hbm_bytes=dense_bytes,
            warmup=True)}
    for name, build in servers.items():
        srv = build()
        try:
            lg = GenerativeLoadGenerator(srv, seed=seed,
                                         prompt_len=prompt_len,
                                         new_tokens=new_tokens)
            res = lg.run_closed(n_requests=n_requests,
                                concurrency=concurrency)
        finally:
            srv.shutdown()
        rec = srv.metrics.to_record()
        out[name] = {
            "tokens_per_sec": round(res.tokens_per_sec, 1),
            "ttft_p50_ms": round(res.ttft_percentile(50), 3),
            "n_ok": res.n_ok, "n_rejected": res.n_rejected,
            "kv_bytes": srv.kv_slab_bytes,
            "compiles": rec["counters"]["compiles"]}
        if name == "paged":
            out[name]["pool_occupancy"] = rec["paged"]["pool_occupancy"]
            out[name]["blocks_per_request"] = \
                rec["paged"]["blocks_per_request"]

    # -- prefix-hit TTFT: repeat prompt prefills only its suffix -------
    prompt = (np.arange(64, dtype=np.int32) * 5) % cfg.vocab_size
    srv = PagedGenerativeServer(paged_spec, max_slots=4,
                                max_seq_len=max_seq_len,
                                block_size=block_size,
                                kv_hbm_bytes=dense_bytes, warmup=True)
    try:
        def ttft(h):
            t0 = time.perf_counter()
            next(iter(h.tokens(timeout=60)))
            dt = (time.perf_counter() - t0) * 1000.0
            h.result(timeout=60)
            return dt
        ttft_cold = ttft(srv.submit(prompt, max_new_tokens=8))
        ttft_hit = ttft(srv.submit(prompt, max_new_tokens=8))
        step_p50 = srv.metrics.exec_ms.summary()["p50"]
        hit_rate = srv.metrics.to_record()["paged"]["prefix_hit_rate"]
    finally:
        srv.shutdown()

    # -- tp=2 greedy bit-identity (the mesh exists on 2+ devices) ------
    tp_match = None
    if len(jax.devices()) >= 2:
        tp_srv = PagedGenerativeServer(paged_spec, max_slots=4,
                                       max_seq_len=max_seq_len,
                                       block_size=block_size,
                                       kv_hbm_bytes=dense_bytes,
                                       tp=2, warmup=True)
        try:
            probes = [(np.arange(L, dtype=np.int32) * 3) % cfg.vocab_size
                      for L in (3, 17, 40)]
            got = [tp_srv.submit(p, max_new_tokens=8).result(timeout=120)
                   for p in probes]
        finally:
            tp_srv.shutdown()
        tp_match = got == [greedy_decode(dense_spec, p, 8,
                                         max_seq_len=max_seq_len)
                           for p in probes]

    return {"samples_per_sec": out["paged"]["tokens_per_sec"],
            "tokens_per_sec": out["paged"]["tokens_per_sec"],
            "dense_tokens_per_sec": out["dense"]["tokens_per_sec"],
            "kv_budget_bytes": dense_bytes,
            "dense_concurrent_capacity": dense_slots,
            "paged_concurrent_capacity": admitted,
            "capacity_ratio_equal_hbm": round(capacity_ratio, 2),
            "pool_blocks": pool_capacity,
            "block_size": block_size,
            "ttft_cold_ms": round(ttft_cold, 3),
            "ttft_prefix_hit_ms": round(ttft_hit, 3),
            "decode_step_p50_ms": round(step_p50, 3),
            "ttft_hit_vs_step": round(ttft_hit / step_p50, 2)
            if step_p50 else None,
            "prefix_hit_rate": hit_rate,
            "tp2_greedy_match": tp_match,
            "n_requests": n_requests,
            "dense": out["dense"], "paged": out["paged"]}


def bench_serving_speculative(n_requests=24, max_slots=4, max_seq_len=256,
                              speculate_k=8, n_draft_layers=1,
                              prompt_len=(2, 16), concurrency=8, seed=19):
    """Speculative decoding vs plain decode on one seeded skewed trace
    (serving/generative.py ``draft_spec=``, ISSUE 18).

    Self-speculative pairing: the target's DEEP layers get their
    residual-out projections (``attn/proj``, ``mlp/proj``) zeroed, so
    those blocks are identity on the residual stream and the
    ``n_draft_layers``-deep draft computes the target's exact logits.
    Acceptance then sits at ~1.0, measuring the mechanism's ceiling —
    every drafted token rides the ONE batched verify dispatch — rather
    than any particular draft model's quality; the acceptance bar is
    speculative >= 1.5x plain tokens/sec on the mixed-length trace.
    The geometry matters: with a 1-of-8-layers draft and K=8, a round
    costs ~2 target-step-equivalents (8 cheap drafts + one verify,
    whose window rides the weight bytes one decode step already moves)
    and lands ~K tokens — the plain path pays K full steps. Also
    records the temp-0 bit-identity bit (speculation must emit EXACTLY
    the non-speculative greedy tokens) and both servers'
    traffic-compile counts (0 after warmup)."""
    import dataclasses as _dc

    from deeplearning4j_tpu.serving.generative import (GenerativeServer,
                                                       greedy_decode)
    from deeplearning4j_tpu.serving.loadgen import GenerativeLoadGenerator
    from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                            gpt_generative_spec)

    cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=8,
                    num_heads=8, intermediate_size=512,
                    max_seq_len=max_seq_len)
    sd = build_gpt(cfg, batch=2, seq_len=8, seed=0)
    for i in range(int(n_draft_layers), cfg.num_layers):
        for part in ("attn/proj", "mlp/proj"):
            for leaf in ("kernel", "bias"):
                n = f"h{i}/{part}/{leaf}"
                sd._arrays[n] = np.zeros_like(np.asarray(sd._arrays[n]))
    spec = gpt_generative_spec(sd, cfg)
    draft = gpt_generative_spec(
        sd, _dc.replace(cfg, num_layers=int(n_draft_layers)))

    def new_tokens(rng):
        # the skewed trace continuous batching + speculation both live
        # for: mostly short answers, a 20% tail of long generations
        return int(rng.integers(2, 9)) if rng.random() < 0.8 \
            else int(rng.integers(80, 129))

    out = {}
    builds = {
        "plain": lambda: GenerativeServer(
            spec, max_slots=max_slots, max_seq_len=max_seq_len,
            warmup=True),
        "speculative": lambda: GenerativeServer(
            spec, max_slots=max_slots, max_seq_len=max_seq_len,
            draft_spec=draft, speculate_k=speculate_k, warmup=True)}
    for name, build in builds.items():
        srv = build()
        try:
            lg = GenerativeLoadGenerator(srv, seed=seed,
                                         prompt_len=prompt_len,
                                         new_tokens=new_tokens)
            res = lg.run_closed(n_requests=n_requests,
                                concurrency=concurrency)
        finally:
            srv.shutdown()
        rec = srv.metrics.to_record()
        gen = rec["generative"]
        out[name] = {
            "tokens_per_sec": round(res.tokens_per_sec, 1),
            "intertoken_p50_ms": round(res.intertoken_percentile(50), 3),
            "decode_steps": gen["decode_steps"],
            "n_ok": res.n_ok,
            "compiles": rec["counters"]["compiles"],
            "warmup_compiles": rec["counters"]["warmup_compiles"]}
        if name == "speculative":
            out[name]["acceptance_rate"] = gen["draft_acceptance_rate"]
            out[name]["spec_rounds"] = gen["spec_rounds"]
            out[name]["draft_rejected"] = gen["draft_rejected"]

    # temp-0 bit-identity: the acceptance criterion of the change
    probes = [(np.arange(L, dtype=np.int32) * 7) % cfg.vocab_size
              for L in (3, 11, 29)]
    srv = GenerativeServer(spec, max_slots=2, max_seq_len=max_seq_len,
                           draft_spec=draft, speculate_k=speculate_k,
                           warmup=True)
    try:
        got = [srv.submit(p, max_new_tokens=12).result(timeout=120)
               for p in probes]
    finally:
        srv.shutdown()
    greedy_match = got == [greedy_decode(spec, p, 12,
                                         max_seq_len=max_seq_len)
                           for p in probes]

    speedup = (out["speculative"]["tokens_per_sec"]
               / out["plain"]["tokens_per_sec"]) \
        if out["plain"]["tokens_per_sec"] else 0.0
    return {"samples_per_sec": out["speculative"]["tokens_per_sec"],
            "tokens_per_sec": out["speculative"]["tokens_per_sec"],
            "plain_tokens_per_sec": out["plain"]["tokens_per_sec"],
            "speculative_speedup": round(speedup, 2),
            "acceptance_rate": out["speculative"]["acceptance_rate"],
            "speculate_k": speculate_k,
            "draft_layers": int(n_draft_layers),
            "greedy_bit_identical": greedy_match,
            "n_requests": n_requests,
            "plain": out["plain"], "speculative": out["speculative"]}


def bench_serving_quant(n_requests=24, max_slots=8, max_seq_len=256,
                        block_size=16, prompt_len=(2, 16),
                        concurrency=8, seed=23):
    """int8 weight + KV quantization at equal slab bytes (zoo/gpt.py
    ``quantize_weights``/``quantize_kv``, ISSUE 18).

    The paged pool is sized in BYTES, and with ISSUE 18 the server
    derives bytes-per-block from the spec's ``kv_dtype`` itemsize —
    int8 KV quarters the bytes per block, so the SAME ``kv_hbm_bytes``
    budget holds ~4x the f32 token capacity (acceptance bar >= 1.9x,
    read from the live servers' pool sizes, not arithmetic). Also
    reports f32-vs-int8 decode throughput on one seeded trace and the
    greedy-token agreement between the two servers on probe prompts
    (quantization is lossy; the delta is published, not gated)."""
    from deeplearning4j_tpu.serving.loadgen import GenerativeLoadGenerator
    from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
    from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                            gpt_paged_spec)

    cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=4,
                    num_heads=8, intermediate_size=512,
                    max_seq_len=max_seq_len)
    sd = build_gpt(cfg, batch=2, seq_len=8, seed=0)
    specs = {"f32": gpt_paged_spec(sd, cfg),
             "int8": gpt_paged_spec(sd, cfg, quantize_weights=True,
                                    quantize_kv=True)}
    # one fixed byte budget for both servers: 49 f32 blocks' worth
    # (48 usable + the null block), so the int8 pool's size shows the
    # dtype-aware sizing rather than a bigger grant
    f32_block_bytes = 2 * int(np.prod(
        specs["f32"].kv_shape(1, block_size))) * 4
    kv_budget = 49 * f32_block_bytes

    out = {}
    toks = {}
    probes = [(np.arange(L, dtype=np.int32) * 7) % cfg.vocab_size
              for L in (3, 11, 29)]
    for name, spec in specs.items():
        srv = PagedGenerativeServer(spec, max_slots=max_slots,
                                    max_seq_len=max_seq_len,
                                    block_size=block_size,
                                    kv_hbm_bytes=kv_budget, warmup=True)
        try:
            toks[name] = [srv.submit(p, max_new_tokens=10)
                          .result(timeout=120) for p in probes]
            lg = GenerativeLoadGenerator(srv, seed=seed,
                                         prompt_len=prompt_len,
                                         new_tokens=(4, 24))
            res = lg.run_closed(n_requests=n_requests,
                                concurrency=concurrency)
        finally:
            srv.shutdown()
        rec = srv.metrics.to_record()
        out[name] = {
            "tokens_per_sec": round(res.tokens_per_sec, 1),
            "pool_blocks": rec["paged"]["num_blocks"],
            "token_capacity": rec["paged"]["num_blocks"] * block_size,
            "kv_bytes": srv.kv_slab_bytes,
            "n_ok": res.n_ok,
            "compiles": rec["counters"]["compiles"]}
    agree = float(np.mean([a == b
                           for s8, s32 in zip(toks["int8"], toks["f32"])
                           for a, b in zip(s8, s32)]))
    ratio = (out["int8"]["token_capacity"] / out["f32"]["token_capacity"]
             if out["f32"]["token_capacity"] else 0.0)
    return {"samples_per_sec": out["int8"]["tokens_per_sec"],
            "tokens_per_sec": out["int8"]["tokens_per_sec"],
            "f32_tokens_per_sec": out["f32"]["tokens_per_sec"],
            "kv_budget_bytes": kv_budget,
            "token_capacity_ratio_equal_bytes": round(ratio, 2),
            "greedy_token_agreement": round(agree, 4),
            "block_size": block_size,
            "n_requests": n_requests,
            "f32": out["f32"], "int8": out["int8"]}


def bench_serving_fleet(n_replicas=3, n_requests=48, rate_rps=40.0,
                        ttft_slo_ms=2000.0, block_size=8, seed=17):
    """Fleet chaos drill + affinity win (serving/fleet/, ISSUE 17).

    One open-loop repeated-prefix trace against a 3-replica fleet
    while (a) a replica is KILLED mid-traffic (no drain) and (b) a
    rolling canaried deploy reloads the survivors — the acceptance bar
    is ZERO failed healthy requests and fleet p99 TTFT inside the SLO
    through both events. Then the affinity column: the SAME trace
    routed with prefix affinity vs uniformly at random, scored on the
    replicas' actual prefix-cache hit rate — affinity must beat
    random (it concentrates each shared prefix on its rendezvous home,
    so the cache warms once instead of once per replica)."""
    import threading
    from types import SimpleNamespace

    from deeplearning4j_tpu.serving.fleet import (FleetReplica,
                                                  FleetRouter,
                                                  RollingDeploy)
    from deeplearning4j_tpu.serving.loadgen import FleetLoadGenerator
    from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
    from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                            gpt_paged_spec)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, intermediate_size=128, max_seq_len=64)
    sd = build_gpt(cfg, batch=2, seq_len=8, seed=0)
    spec = gpt_paged_spec(sd, cfg)     # shared -> one compile set

    def replica(name, warm=False):
        return FleetReplica(name, server=PagedGenerativeServer(
            spec, max_slots=4, block_size=block_size, max_seq_len=64,
            warmup=warm))

    pool = [(np.arange(block_size, dtype=np.int32) * k + k)
            % cfg.vocab_size for k in (1, 3)]

    def loadgen(front_door, gen_seed):
        return FleetLoadGenerator(front_door,
                                  vocab_size=cfg.vocab_size,
                                  seed=gen_seed, prompt_len=(1, 8),
                                  new_tokens=(2, 8), prefix_pool=pool,
                                  prefix_p=0.75)

    # -- the drill: kill + rolling reload under open-loop load ---------
    replicas = [replica(f"r{i}", warm=(i == 0))
                for i in range(n_replicas)]
    router = FleetRouter(replicas, retry_budget=4,
                         poll_interval_s=0.05)
    deploy_report = {}

    def mid_run():
        replicas[-1].kill()            # no drain: the chaos kill
        deploy_report.update(RollingDeploy(
            router, probes=[(np.arange(6, dtype=np.int32), 4, None)],
            drain_timeout_s=60.0).run(canary="r0"))
    chaos = threading.Timer(0.3, mid_run)
    chaos.start()
    res = loadgen(router.generate, seed).run_open(
        n_requests=n_requests, rate_rps=rate_rps)
    chaos.join()
    rec = router.metrics.to_record()
    for r in replicas:
        if r.alive:
            r.stop(drain=True)

    # -- affinity vs random placement, scored on REAL prefix hits ------
    def prefix_hit_rate(route_random):
        reps = [replica(f"h{i}") for i in range(n_replicas)]
        rt = FleetRouter(reps, poll_interval_s=0.05)
        rng = np.random.default_rng(seed + 1)

        def random_door(prompt, max_new_tokens=16, timeout_ms=None):
            rep = reps[int(rng.integers(len(reps)))]
            h = rep.submit(prompt, max_new_tokens=max_new_tokens,
                           timeout_ms=timeout_ms)
            return SimpleNamespace(tokens=h.result(), replica=rep.name,
                                   retries=0, routed="random",
                                   ttft_ms=None, intertoken_ms=[])
        door = random_door if route_random else rt.generate
        r = loadgen(door, seed + 2).run_open(n_requests=32,
                                             rate_rps=rate_rps)
        hits = sum(rep.server.metrics.counters["prefix_hits"]
                   for rep in reps)
        lookups = sum(rep.server.metrics.counters["prefix_lookups"]
                      for rep in reps)
        for rep in reps:
            rep.stop(drain=True)
        return (hits / lookups if lookups else 0.0), r.n_failed
    affinity_rate, aff_failed = prefix_hit_rate(route_random=False)
    random_rate, rnd_failed = prefix_hit_rate(route_random=True)

    ttft_p99 = res.ttft_percentile(99)
    return {"samples_per_sec": round(res.tokens_per_sec, 1),
            "tokens_per_sec": round(res.tokens_per_sec, 1),
            "n_replicas": n_replicas,
            "n_requests": n_requests,
            "rate_rps": rate_rps,
            "n_ok": res.n_ok,
            # the acceptance bar: nothing healthy failed through a
            # kill AND a rolling reload
            "n_failed_through_chaos": res.n_failed + aff_failed
            + rnd_failed,
            "retries_absorbed": res.retries_total,
            "deploy_ok": bool(deploy_report.get("ok")),
            "deploy_rolled": deploy_report.get("rolled"),
            "ttft_p50_ms": round(res.ttft_percentile(50), 3),
            "ttft_p99_ms": round(ttft_p99, 3),
            "ttft_slo_ms": ttft_slo_ms,
            "ttft_p99_within_slo": bool(ttft_p99 <= ttft_slo_ms),
            "affinity_prefix_hit_rate": round(affinity_rate, 4),
            "random_prefix_hit_rate": round(random_rate, 4),
            "affinity_beats_random": bool(affinity_rate > random_rate),
            "replica_deaths_seen":
                rec["counters"]["replica_deaths_seen"],
            "fleet_affinity_hit_rate":
                rec["fleet"]["affinity_hit_rate"]}


def bench_serving_durability(n_requests=24, rate_rps=60.0, block_size=8,
                             kill_after=5, seed=23):
    """Durable generative requests drill (serving/fleet/durable.py,
    ISSUE 19).

    Three legs. (1) Mid-stream kill: a replica is killed after
    ``kill_after`` streamed tokens and the router resumes the request
    on a survivor from the emitted prefix — the bar is tokens_salvaged
    > 0, an exactly-once stream (the streamed sequence IS the final
    result, zero dedup drops), and final output bit-identical to an
    uninterrupted run, greedy AND seeded-sampled. (2) Router
    kill-and-restart: with a write-ahead journal armed and a zero
    retry budget the same kill strands the request; a fresh router
    replays the journal and must finish it bit-identically, exactly
    once. (3) The journal's price: open-loop throughput with the
    fsync'd journal armed vs without."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.faults.chaos import ChaosMonkey
    from deeplearning4j_tpu.serving.fleet import (FleetReplica,
                                                  FleetRouter,
                                                  FleetUnavailableError,
                                                  RequestJournal)
    from deeplearning4j_tpu.serving.loadgen import FleetLoadGenerator
    from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
    from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                            gpt_paged_spec)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, intermediate_size=128, max_seq_len=64)
    sd = build_gpt(cfg, batch=2, seq_len=8, seed=0)
    spec = gpt_paged_spec(sd, cfg)     # shared -> one compile set

    def replica(name):
        return FleetReplica(name, server=PagedGenerativeServer(
            spec, max_slots=4, block_size=block_size, max_seq_len=64,
            warmup=False))

    prompt = [3, 1, 4, 1, 5]
    n_new = 24

    def baseline(**kw):
        rep = replica("base")
        try:
            return rep.submit(prompt, max_new_tokens=n_new,
                              **kw).result(timeout=120)
        finally:
            rep.stop(drain=False)

    # -- leg 1: kill a replica mid-stream, greedy and sampled ----------
    def kill_drill(**kw):
        reps = [replica(f"r{i}") for i in range(2)]
        router = FleetRouter(reps, retry_budget=3, affinity=False,
                             poll_interval_s=0.0)
        ChaosMonkey(seed=seed).kill_mid_stream(reps[0],
                                               after_tokens=kill_after)
        streamed = []
        try:
            res = router.generate(prompt, max_new_tokens=n_new,
                                  on_token=streamed.append, **kw)
        finally:
            for r in reps:
                if r.alive:
                    r.stop(drain=False)
        return {"tokens": res.tokens, "streamed": streamed,
                "resumes": res.resumes,
                "tokens_salvaged": res.tokens_salvaged,
                "dedup_drops":
                    router.durability.counters["dedup_drops"]}
    greedy = kill_drill()
    sampled_kw = dict(temperature=0.8, top_k=16, seed=seed)
    sampled = kill_drill(**sampled_kw)
    greedy_identical = greedy["tokens"] == baseline()
    sampled_identical = sampled["tokens"] == baseline(**sampled_kw)
    exactly_once = (greedy["streamed"] == greedy["tokens"]
                    and sampled["streamed"] == sampled["tokens"]
                    and greedy["dedup_drops"] == 0
                    and sampled["dedup_drops"] == 0)

    # -- leg 2: kill the only replica, restart the router, replay -----
    jdir = tempfile.mkdtemp(prefix="dl4j_durable_journal_")
    try:
        journal = RequestJournal(jdir, flush_every=2)
        r0 = replica("r0")
        router1 = FleetRouter([r0], retry_budget=0, affinity=False,
                              poll_interval_s=0.0, journal=journal)
        ChaosMonkey(seed=seed).kill_mid_stream(r0,
                                               after_tokens=kill_after)
        try:
            router1.generate(prompt, max_new_tokens=n_new)
            stranded = False
        except FleetUnavailableError:
            stranded = True
        finally:
            if r0.alive:
                r0.stop(drain=False)
        open_entries = journal.incomplete()
        r1 = replica("r1")
        router2 = FleetRouter([r1], affinity=False, poll_interval_s=0.0)
        try:
            recovered = router2.recover(journal)
            second_pass = router2.recover()
        finally:
            r1.stop(drain=False)
        replay_identical = (len(recovered) == 1
                            and next(iter(recovered.values())).tokens
                            == baseline())
        recovery = {
            "stranded_open_entries": len(open_entries),
            "journal_tokens_salvaged":
                router2.durability.counters["tokens_salvaged"],
            "replay_bit_identical": bool(stranded and replay_identical),
            "replay_exactly_once": bool(len(recovered) == 1
                                        and second_pass == {}
                                        and not journal.incomplete())}
        journal.close()

        # -- leg 3: the journal's price under open-loop load -----------
        def throughput(jn):
            reps = [replica(f"t{i}") for i in range(2)]
            rt = FleetRouter(reps, poll_interval_s=0.05, journal=jn)
            res = FleetLoadGenerator(
                rt.generate, vocab_size=cfg.vocab_size, seed=seed,
                prompt_len=(1, 8), new_tokens=(2, 8)).run_open(
                    n_requests=n_requests, rate_rps=rate_rps)
            for r in reps:
                r.stop(drain=True)
            return res
        throughput(None)               # discard: pays the bucket compiles
        bare = throughput(None)
        journal2 = RequestJournal(os.path.join(jdir, "load"))
        journaled = throughput(journal2)
        fsync_p99 = journal2.metrics.to_dict()["journal_fsync_ms"]["p99"]
        journal2.close()
    finally:
        shutil.rmtree(jdir, ignore_errors=True)

    overhead = (bare.tokens_per_sec / journaled.tokens_per_sec
                if journaled.tokens_per_sec else 0.0)
    return {"samples_per_sec": round(journaled.tokens_per_sec, 1),
            "tokens_per_sec": round(journaled.tokens_per_sec, 1),
            "bare_tokens_per_sec": round(bare.tokens_per_sec, 1),
            "journal_overhead_x": round(overhead, 3),
            "journal_fsync_p99_ms": round(fsync_p99, 3),
            "n_failed": bare.n_failed + journaled.n_failed,
            # the acceptance bars
            "tokens_salvaged": greedy["tokens_salvaged"]
            + sampled["tokens_salvaged"],
            "resumes": greedy["resumes"] + sampled["resumes"],
            "exactly_once_stream": bool(exactly_once),
            "greedy_bit_identical": bool(greedy_identical),
            "sampled_bit_identical": bool(sampled_identical),
            **recovery}


def bench_reqtrace_overhead(n_replicas=2, n_requests=32, concurrency=4,
                            repeats=2, block_size=8, seed=29):
    """Cost of the request-tracing + SLO rail (monitor/reqtrace.py,
    ISSUE 20): the fleet loadgen closed loop with span
    tracing + per-request waterfall assembly + SLO tracking ON vs the
    whole rail OFF (tracer disabled, ``slo=False``/``reqtrace=False``
    router). Same best-of-``repeats`` interleaved estimator as
    tracer_overhead; the acceptance bar is ≤3% tokens/sec (the PR-5
    discipline — observability must never become the workload). Also
    records how many traces the run kept and the worst-TTFT waterfall's
    breakdown (where the slowest request's first token went)."""
    from deeplearning4j_tpu.monitor import disable_tracing, enable_tracing
    from deeplearning4j_tpu.serving.fleet import FleetReplica, FleetRouter
    from deeplearning4j_tpu.serving.loadgen import FleetLoadGenerator
    from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
    from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                            gpt_paged_spec)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, intermediate_size=128, max_seq_len=64)
    sd = build_gpt(cfg, batch=2, seq_len=8, seed=0)
    spec = gpt_paged_spec(sd, cfg)     # shared -> one compile set

    def run(traced):
        reps = [FleetReplica(f"t{i}", server=PagedGenerativeServer(
            spec, max_slots=4, block_size=block_size, max_seq_len=64,
            warmup=False)) for i in range(n_replicas)]
        if traced:
            enable_tracing(reset=True)
            rt = FleetRouter(reps, poll_interval_s=0.05,
                             trace_sample=1.0)
        else:
            disable_tracing()
            rt = FleetRouter(reps, poll_interval_s=0.05,
                             slo=False, reqtrace=False)
        try:
            res = FleetLoadGenerator(
                rt.generate, vocab_size=cfg.vocab_size, seed=seed,
                prompt_len=(1, 8), new_tokens=(2, 8)).run_closed(
                    n_requests=n_requests, concurrency=concurrency)
        finally:
            disable_tracing()
            for r in reps:
                r.stop(drain=True)
        return res, rt

    run(False)                         # discard: pays the bucket compiles
    best = {False: 0.0, True: 0.0}
    traced_router = None
    traced_res = None
    for _ in range(repeats):
        for flag in (False, True):
            res, rt = run(flag)
            if res.tokens_per_sec > best[flag]:
                best[flag] = res.tokens_per_sec
                if flag:
                    traced_router, traced_res = rt, res
    overhead = (best[False] - best[True]) / best[False] * 100.0 \
        if best[False] else 0.0
    kept = traced_router.reqtrace.summaries() if traced_router else []
    worst = None
    slo_sub = None
    if traced_router is not None and traced_router.slo is not None:
        slo_sub = traced_router.slo.to_dict()
        worst_list = slo_sub.get("worst_traces") or []
        if worst_list:
            worst = worst_list[0]
    return {"samples_per_sec": round(best[True], 1),
            "tokens_per_sec": round(best[True], 1),
            "tokens_per_sec_untraced": round(best[False], 1),
            "reqtrace_overhead_pct": round(overhead, 2),
            "n_requests": n_requests,
            "concurrency": concurrency,
            "sampled_traces_kept": len(kept),
            "worst_ttft_waterfall": worst,
            "slo_ttft_attainment": (slo_sub or {}).get(
                "objectives", {}).get("ttft_ms", {}).get("attainment"),
            "slo_attainment_loadgen_2s": round(
                traced_res.slo_attainment(2000.0), 4)
            if traced_res is not None else None}


def bench_disk_stream(batch=128, fused_steps=8, n=2048, shard_size=512,
                      worker_counts=(1, 2, 4)):
    """Disk-backed streaming training vs the device-cached window bench
    (datapipe/, docs/data_pipeline.md — the ROADMAP item-4 acceptance
    bar: within ~5% of cached). The BASELINE config-2 MLP trains
    through ``StreamingDataPipeline`` — sha256-verified shard reads +
    supervised parallel prefetch feeding the fused-window stager — at
    several prefetch-worker counts (the scaling column), against the
    same model fed from ``DeviceCachedIterator``. One monitored run
    reports the per-flush data-wait fraction (the number that says
    whether the prefetch actually hides the disk)."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.autodiff import ScoreIterationListener
    from deeplearning4j_tpu.datapipe import (StreamingDataPipeline,
                                             write_dataset)
    from deeplearning4j_tpu.monitor import (MonitorListener,
                                            disable_tracing,
                                            enable_tracing)
    from deeplearning4j_tpu.ui.stats import StatsStorage

    cached = bench_samediff_mlp(batch=batch, listener=True,
                                fused_steps=fused_steps)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, 784)).astype(np.float32)
    Y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, n)]
    ds_dir = tempfile.mkdtemp(prefix="dl4j_disk_stream_")
    try:
        write_dataset(os.path.join(ds_dir, "ds"), X, Y,
                      shard_size=shard_size, overwrite=True)
        path = os.path.join(ds_dir, "ds")
        per_workers = {}
        epochs = 6
        for workers in worker_counts:
            sd = _build_mlp_sd(fused_steps=fused_steps)
            listeners = [ScoreIterationListener(print_every=10 ** 9,
                                                print_fn=lambda *a: None)]
            pipe = StreamingDataPipeline(path, batch_size=batch,
                                         shuffle=False,
                                         n_workers=workers)
            sd.fit(pipe, epochs=2, listeners=listeners)   # warmup
            sps = _median_rate(lambda: sd.fit(pipe, epochs=epochs,
                                              listeners=listeners),
                               epochs * n)
            per_workers[str(workers)] = round(sps, 1)
        best_workers, best = max(per_workers.items(),
                                 key=lambda kv: kv[1])
        # one monitored (traced) run at the best worker count for the
        # per-flush data-wait fraction — not part of the timing
        storage = StatsStorage()
        sd = _build_mlp_sd(fused_steps=fused_steps)
        pipe = StreamingDataPipeline(path, batch_size=batch,
                                     shuffle=False,
                                     n_workers=int(best_workers))
        listeners = [ScoreIterationListener(print_every=10 ** 9,
                                            print_fn=lambda *a: None)]
        sd.fit(pipe, epochs=2, listeners=listeners)       # warmup
        enable_tracing(reset=True)
        try:
            sd.fit(pipe, epochs=2,
                   listeners=listeners + [MonitorListener(storage)])
        finally:
            disable_tracing()
        waits = [r["data_wait_frac"] for r in storage.of_type("datapipe")
                 if r.get("data_wait_frac") is not None]
        cached_sps = cached.get("samples_per_sec", 0.0)
        gap = (cached_sps - best) / cached_sps * 100.0 if cached_sps \
            else 0.0
        return {"samples_per_sec": best,
                "samples_per_sec_cached": cached_sps,
                "disk_vs_cached_pct": round(gap, 2),
                "workers_best": int(best_workers),
                "samples_per_sec_by_workers": per_workers,
                "data_wait_frac_per_flush": [round(w, 4)
                                             for w in waits[-12:]],
                "data_wait_frac_mean": round(
                    float(np.mean(waits)), 4) if waits else None,
                "shards": (n + shard_size - 1) // shard_size,
                "shard_size": shard_size, "batch": batch,
                "fused_steps": fused_steps}
    finally:
        shutil.rmtree(ds_dir, ignore_errors=True)


def bench_resnet50(batch=128, steps=32, image=224, mixed_precision=True):
    """BASELINE config 3: zoo ResNet-50 training step, ImageNet shapes,
    bf16 mixed precision (f32 master params) at MXU-saturating batch."""
    from deeplearning4j_tpu.autodiff import MixedPrecision
    from deeplearning4j_tpu.nn import ComputationGraph
    from deeplearning4j_tpu.zoo import ResNet50

    from deeplearning4j_tpu.dataset import DeviceCachedIterator
    rng = np.random.default_rng(0)
    conf = ResNet50(height=image, width=image, channels=3,
                    num_classes=1000).conf()
    if mixed_precision:
        conf.mixed_precision = MixedPrecision()
    net = ComputationGraph(conf).init()
    n = batch * steps
    X = rng.normal(size=(n, 3, image, image)).astype(np.float32)
    Y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, n)]
    it = DeviceCachedIterator(X, Y, batch_size=batch)
    net.fit(it, epochs=1)                       # warmup/compile
    sps = _median_rate(lambda: net.fit(it, epochs=2), 2 * n)
    # ResNet-50 fwd FLOPs/image: 4.1e9 at 224x224; conv FLOPs scale with
    # spatial area for other image sizes
    fwd_flops = 4.1e9 * (image / 224.0) ** 2
    return {"samples_per_sec": round(sps, 1),
            "step_time_ms": round(1000.0 * batch / sps, 3),
            "mfu_est": round(3 * fwd_flops * sps / _peak_flops(), 5),
            "batch": batch,
            "precision": "bf16_mixed" if mixed_precision else "f32",
            **_memory_stats()}


def bench_bert_base(batch=16, seq_len=128, steps=16, mixed_precision=True):
    # steps=16: a 4-step epoch measures the per-dispatch host cost and
    # its jitter more than the model; 16 steps per epoch amortize it
    """BASELINE config 4: BERT-base imported from a frozen TF GraphDef,
    fine-tune step (pooled-output classifier, softmax-CE, Adam)."""
    from deeplearning4j_tpu.autodiff import MixedPrecision, TrainingConfig
    from deeplearning4j_tpu.dataset import DeviceCachedIterator
    from deeplearning4j_tpu.learning.updaters import Adam
    from deeplearning4j_tpu.zoo.bert import BERT_BASE, bert_base

    sd = bert_base(BERT_BASE, batch=batch, seq_len=seq_len, num_labels=2)
    sd.training_config = TrainingConfig(
        updater=Adam(2e-5),
        data_set_feature_mapping=["input_ids", "input_mask",
                                  "token_type_ids"],
        data_set_label_mapping=["labels"],
        mixed_precision=MixedPrecision() if mixed_precision else None)
    rng = np.random.default_rng(0)
    n = batch * steps
    ids = rng.integers(0, BERT_BASE.vocab_size, (n, seq_len)).astype(np.int32)
    mask = np.ones((n, seq_len), np.int32)
    tt = np.zeros((n, seq_len), np.int32)
    labels = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
    it = DeviceCachedIterator([ids, mask, tt], [labels], batch_size=batch)
    sd.fit(it, epochs=1)                        # warmup/compile
    sps = _median_rate(lambda: sd.fit(it, epochs=2), 2 * n)
    # fwd matmul FLOPs per example: per layer qkv+attn-out (8h^2/token) +
    # ffn (16h^2/token) + attention scores/context (4*s*h/token)
    h, L, s = BERT_BASE.hidden_size, BERT_BASE.num_layers, seq_len
    fwd_flops = L * (24 * s * h * h + 4 * s * s * h)
    return {"samples_per_sec": round(sps, 1),
            "step_time_ms": round(1000.0 * batch / sps, 3),
            "mfu_est": round(3 * fwd_flops * sps / _peak_flops(), 5),
            "batch": batch, "seq_len": seq_len,
            "precision": "bf16_mixed" if mixed_precision else "f32",
            **_memory_stats()}


def bench_gpt_medium(batch=16, seq_len=512, steps=8, mixed_precision=True,
                     ce_tail_dtype=None):
    """Compute-dense flagship: GPT-medium-class decoder LM (h=1536, 16
    layers, ffn 6144, vocab 32k, ~510M params), seq 512, per-layer remat
    (sd.remat_scope), weight-tied head, sparse CE. This is the config
    where MXU saturation is actually reachable — matmul-dominated,
    bf16, one fused attention op per layer.

    ``ce_tail_dtype="bfloat16"`` (the gpt_medium_bf16_ce config) keeps
    the [B,S,32k] log-softmax tail in bf16 instead of f32; the
    per-token losses still reduce in f32."""
    from deeplearning4j_tpu.autodiff import MixedPrecision, TrainingConfig
    from deeplearning4j_tpu.dataset import DeviceCachedIterator
    from deeplearning4j_tpu.learning.updaters import Adam
    from deeplearning4j_tpu.zoo.gpt import GPT_MEDIUM, build_gpt

    cfg = GPT_MEDIUM
    sd = build_gpt(cfg, batch=batch, seq_len=seq_len)
    sd.training_config = TrainingConfig(
        updater=Adam(1e-4),
        data_set_feature_mapping=["input_ids"],
        data_set_label_mapping=["targets"],
        mixed_precision=MixedPrecision(softmax_dtype=ce_tail_dtype)
        if mixed_precision else None)
    rng = np.random.default_rng(0)
    n = batch * steps
    ids = rng.integers(0, cfg.vocab_size, (n, seq_len)).astype(np.int32)
    tgt = rng.integers(0, cfg.vocab_size, (n, seq_len)).astype(np.int32)
    it = DeviceCachedIterator([ids], [tgt], batch_size=batch)
    sd.fit(it, epochs=1)                        # warmup/compile
    sps = _median_rate(lambda: sd.fit(it, epochs=2), 2 * n)
    h, L, f, S, V = (cfg.hidden_size, cfg.num_layers, cfg.intermediate_size,
                     seq_len, cfg.vocab_size)
    # fwd matmul FLOPs/token: qkv+proj (8h^2) + mlp (4*h*f) + tied head
    # (2hV); attention scores+context 4*S*h
    fwd_flops = S * (L * (8 * h * h + 4 * h * f + 4 * S * h) + 2 * h * V)
    return {"samples_per_sec": round(sps, 2),
            "step_time_ms": round(1000.0 * batch / sps, 3),
            "tokens_per_sec": round(sps * seq_len, 1),
            "mfu_est": round(3 * fwd_flops * sps / _peak_flops(), 5),
            "batch": batch, "seq_len": seq_len,
            "precision": "bf16_mixed" if mixed_precision else "f32",
            # the CE-tail knob rides MixedPrecision; without it the tail
            # is plain f32 regardless of what was requested
            "ce_tail_dtype": (ce_tail_dtype or "float32")
            if mixed_precision else "float32",
            **_memory_stats()}


# -- cold start: fresh-process first-compile vs warm-restart ------------
# (compilecache/, docs/cold_start.md — restart-to-first-step is a
# tracked metric alongside throughput)

def _cold_start_probe(model: str, t0: float = None) -> dict:
    """One restart probe: build the model, AOT-precompile, fit one short
    epoch; returns phase timings + compile accounting, counted from
    ``t0`` (default: now). Meant for a FRESH process. The persistent cache is wherever the environment
    places it ($JAX_COMPILATION_CACHE_DIR and the admission knobs, set
    by the parent) — nothing here names a directory. Run once against an
    empty cache = cold start; again against the now-populated one = warm
    restart."""
    if t0 is None:
        t0 = time.perf_counter()
    from deeplearning4j_tpu.compilecache import (COMPILE_STATS,
                                                 install_compile_watcher)
    install_compile_watcher()
    from deeplearning4j_tpu.autodiff import (MixedPrecision,
                                             ScoreIterationListener,
                                             TrainingConfig)
    from deeplearning4j_tpu.dataset import DeviceCachedIterator
    from deeplearning4j_tpu.learning.updaters import Adam
    t_import = time.perf_counter()

    rng = np.random.default_rng(0)
    listeners = []
    if model == "samediff_mlp":
        # the BASELINE config-2 graph (same builder as
        # bench_samediff_mlp) on the production (fused-window +
        # listener) tier: precompile covers K=8 plus the pow2 tails
        sd = _build_mlp_sd(fused_steps=8)
        batch, n = 128, 1024
        X = rng.normal(size=(n, 784)).astype(np.float32)
        Y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, n)]
        it = DeviceCachedIterator(X, Y, batch_size=batch)
        listeners = [ScoreIterationListener(print_every=10 ** 9,
                                            print_fn=lambda *a: None)]
        precompile = lambda: sd.precompile(batch_size=batch)
    elif model in ("gpt_medium", "gpt_tiny"):
        from deeplearning4j_tpu.zoo.gpt import (GPT_MEDIUM, GPT_TINY,
                                                build_gpt)
        cfg, batch, seq_len = (GPT_MEDIUM, 16, 512) \
            if model == "gpt_medium" else (GPT_TINY, 4, 32)
        sd = build_gpt(cfg, batch=batch, seq_len=seq_len)
        sd.training_config = TrainingConfig(
            updater=Adam(1e-4),
            data_set_feature_mapping=["input_ids"],
            data_set_label_mapping=["targets"],
            mixed_precision=MixedPrecision())
        steps = 2
        n = batch * steps
        ids = rng.integers(0, cfg.vocab_size, (n, seq_len)) \
            .astype(np.int32)
        tgt = rng.integers(0, cfg.vocab_size, (n, seq_len)) \
            .astype(np.int32)
        it = DeviceCachedIterator([ids], [tgt], batch_size=batch)
        # the listener-free device-cached fit takes the scanned tier
        precompile = lambda: sd.precompile(epoch_steps=steps,
                                           tiers=("epoch",))
    else:
        raise SystemExit(f"unknown cold-start model {model!r}")
    t_build = time.perf_counter()
    info = precompile()
    t_pre = time.perf_counter()
    sd.fit(it, epochs=1, listeners=listeners)
    t_fit = time.perf_counter()
    snap = COMPILE_STATS.snapshot()
    return {
        "model": model,
        "import_s": round(t_import - t0, 4),
        "build_s": round(t_build - t_import, 4),
        "precompile_s": round(t_pre - t_build, 4),
        "first_fit_s": round(t_fit - t_pre, 4),
        "restart_to_first_step_s": round(t_fit - t0, 4),
        "backend_compiles": int(snap["backend_compiles"]),
        "cache_hits": int(snap["cache_hits"]),
        "cache_misses": int(snap["cache_misses"]),
        "precompile": info}


def bench_cold_start(models=None, timeout_s=900):
    """Restart-to-first-step per model, cold (empty persistent cache)
    vs warm (the cache the cold run just populated) — each probe a
    FRESH python process, because in-process jit caches would fake the
    warmth a real restart does not have. The headline
    ``warm_restart_speedup`` is cold/warm restart time; acceptance for
    gpt_medium is ≥5x (the XLA compile dominates its cold start).
    Override models via $DL4J_BENCH_COLD_START_MODELS (comma list).

    A chip belongs to one process, so this parent must not hold one
    when it starts a probe: it raises if a JAX backend is already up
    (``main`` runs it before anything else). The probes' cache is the
    fixed-name subdirectory ``<cache>/cold_start/<model>`` of wherever
    the environment places the compile cache, handed down through
    $JAX_COMPILATION_CACHE_DIR and emptied before the cold probe."""
    import shutil
    import subprocess

    from jax._src import xla_bridge

    from deeplearning4j_tpu.environment import environment
    if xla_bridge._backends:
        raise RuntimeError(
            "cold_start spawns fresh-process probes that need the chip, "
            "and this process already initialised a JAX backend and "
            "holds it — run cold_start first (bench.py does) or as its "
            "own invocation: python bench.py cold_start")
    if models is None:
        env_models = os.environ.get("DL4J_BENCH_COLD_START_MODELS")
        models = tuple(env_models.split(",")) if env_models \
            else ("samediff_mlp", "gpt_medium")
    here = os.path.abspath(__file__)
    base = environment().compilation_cache_dir()
    out = {}
    for model in models:
        probe_cache = os.path.join(base, "cold_start", model)
        shutil.rmtree(probe_cache, ignore_errors=True)
        # cache everything: the probe's restart time must not depend on
        # which compiles cleared jax's default 1 s admission threshold
        probe_env = dict(os.environ,
                         JAX_COMPILATION_CACHE_DIR=probe_cache,
                         JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
                         JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        runs = {}
        for phase in ("cold", "warm"):
            proc = subprocess.run(
                [sys.executable, here, "_cold_start_child", model],
                capture_output=True, text=True, timeout=timeout_s,
                cwd=os.path.dirname(here), env=probe_env)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"{model} {phase} probe failed: {proc.stderr[-800:]}")
            runs[phase] = json.loads(proc.stdout.strip().splitlines()[-1])
        cold_t = runs["cold"]["restart_to_first_step_s"]
        warm_t = runs["warm"]["restart_to_first_step_s"]
        out[model] = {
            "cold": runs["cold"], "warm": runs["warm"],
            "warm_restart_speedup": round(cold_t / warm_t, 2),
            "warm_cache_hits": runs["warm"]["cache_hits"],
            "warm_miss_compiles": max(
                0, runs["warm"]["backend_compiles"]
                - runs["warm"]["cache_hits"])}
    # headline = gpt_medium (the model the >=5x acceptance bar names —
    # its cold start is compile-dominated), else the first model run
    headline_model = "gpt_medium" if "gpt_medium" in out else models[0]
    return {"models": out,
            "warm_restart_speedup":
                out[headline_model]["warm_restart_speedup"],
            "headline_model": headline_model}


REGISTRY = (("lenet_mnist", bench_lenet),
            ("samediff_mlp", bench_samediff_mlp),
            # listener-path tiers (fused windows, K=8): the
            # production configuration
            ("lenet_listener",
             lambda: bench_lenet(listener=True, fused_steps=8)),
            ("samediff_mlp_listener",
             lambda: bench_samediff_mlp(listener=True,
                                        fused_steps=8)),
            # the fault rail's cost stays visible: fused-window
            # steps/s with divergence sentinels on vs off
            ("sentinel_overhead", bench_sentinel_overhead),
            # the tensorstats rail's cost (in-graph per-layer
            # grad/update/param summaries at default cadence,
            # ≤3% bar)
            ("tensorstats_overhead", bench_tensorstats_overhead),
            # the HBM telemetry rail's cost (per-flush memory
            # records + plan capture + MFU gauge, ≤2% bar) +
            # the hbm_peak/plan-bytes trajectory
            ("memory_overhead", bench_memory_overhead),
            # the static analyzer's warm-path cost (~0: it
            # runs once per graph version, pre-compile) +
            # its one-time wall seconds (analyze/)
            ("analyze_overhead", bench_analyze_overhead),
            # the observability rail's cost + the step-time
            # breakdown (where fused listener-path wall time
            # goes)
            ("tracer_overhead", bench_tracer_overhead),
            # the serving resilience rail's cost (admission +
            # breaker + supervision on the batched path, ≤3%
            # bar)
            ("serving_resilience_overhead",
             bench_serving_resilience_overhead),
            # continuous-batching generative serving vs the
            # static wait-for-full-batch baseline on one
            # seeded mixed-length trace (tokens/sec/chip,
            # p50/p99 TTFT, inter-token p50, slot occupancy —
            # serving/generative.py)
            ("generative", bench_generative),
            # paged KV vs dense at equal HBM: concurrent
            # capacity ratio (≥4x bar), prefix-hit TTFT vs
            # decode-step p50, tp=2 greedy bit-identity
            # (serving/paged/)
            ("serving_paged", bench_serving_paged),
            # fleet chaos drill: kill a replica + rolling
            # reload under open-loop load (zero failed healthy
            # requests, p99 TTFT inside the SLO) and the
            # affinity-vs-random prefix-hit-rate column
            # (serving/fleet/)
            ("serving_fleet", bench_serving_fleet),
            # durable requests: mid-stream-kill salvage +
            # exactly-once stream + bit-identity (greedy AND
            # sampled), router kill/restart journal replay,
            # and the fsync'd journal's throughput price
            # (serving/fleet/durable.py)
            ("serving_durability", bench_serving_durability),
            # the request-tracing + SLO rail's cost on the
            # fleet loadgen closed loop (trace tagging +
            # waterfall assembly + SLO windows, ≤3% bar) plus
            # kept-trace count and the worst-TTFT waterfall
            # (monitor/reqtrace.py)
            ("reqtrace_overhead", bench_reqtrace_overhead),
            # speculative decoding vs plain decode on the
            # skewed trace: acceptance-ceiling self-draft,
            # >= 1.5x tokens/sec bar, temp-0 bit-identity bit
            # (serving/generative.py draft_spec)
            ("serving_speculative", bench_serving_speculative),
            # int8 weights + KV: paged-pool token capacity at
            # equal slab bytes (>= 1.9x bar, ~4x expected) +
            # f32-vs-int8 throughput and greedy-token
            # agreement (zoo/gpt.py quantize_*)
            ("serving_quant", bench_serving_quant),
            # the integrity rail's cost (state fingerprints +
            # stall-watchdog guards on the fused K=8 listener
            # path, ≤2% bar)
            ("integrity_overhead", bench_integrity_overhead),
            # disk-backed streaming vs the cached-window bench
            # (datapipe/, ~5% bar) + data-wait per flush +
            # prefetch-worker scaling,
            ("disk_stream", bench_disk_stream),
            # cold-start: fresh-process first-compile vs
            # warm-cache restart per model (compilecache/)
            ("cold_start", bench_cold_start),
            ("resnet50", bench_resnet50),
            ("bert_base", bench_bert_base),
            ("gpt_medium", bench_gpt_medium),
            # the CE-tail precision lever on the flagship LM
            # (MixedPrecision.softmax_dtype)
            ("gpt_medium_bf16_ce",
             lambda: bench_gpt_medium(ce_tail_dtype="bfloat16")))


def main(argv=None):
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "_cold_start_child":
        t0 = time.perf_counter()    # backend start-up is restart time
        stamp = _require_tpu()
        print(json.dumps({**_cold_start_probe(argv[1], t0), **stamp}))
        return
    only = set(argv) or None     # `bench.py cold_start` runs a subset
    names = [name for name, _ in REGISTRY]
    if only:
        # an unknown name running NOTHING with a success-shaped zero
        # result would let a typo'd CI invocation report 0 forever
        unknown = only - set(names)
        if unknown:
            raise SystemExit(
                f"unknown bench config(s) {sorted(unknown)}; "
                f"have {sorted(names)}")
    todo = [(name, fn) for name, fn in REGISTRY
            if not only or name in only]
    configs = {}
    # cold_start's fresh-process probes need the chip, and a chip
    # belongs to one process: it runs before this process touches a
    # device (bench_cold_start refuses otherwise); the probes check the
    # backend themselves
    if any(name == "cold_start" for name, _ in todo):
        configs["cold_start"] = bench_cold_start()
    stamp = _require_tpu()
    # capture a memory plan for every compiled train program so the
    # per-model hbm/plan trajectory is recorded (same lowering, one
    # compile either way — the child cold-start probes stay untouched
    # so their numbers remain comparable across rounds)
    from deeplearning4j_tpu.monitor import memstats
    memstats.enable_plan_capture()
    # any config that raises ends the run with a traceback and a
    # non-zero exit: there is no partial, success-shaped result
    for name, fn in todo:
        if name == "cold_start":
            continue
        # per-config plan attribution: _memory_stats() reads the ACTIVE
        # plan, which must not be a stale one from the previous config
        memstats.PLANS.reset()
        configs[name] = fn()
    headline = configs.get("resnet50", {}).get("samples_per_sec")
    print(json.dumps({
        "metric": "resnet50_train_throughput",
        "value": headline,      # null unless resnet50 was among the run
        "unit": "samples/sec/chip",
        "vs_baseline": None,    # reference publishes no numbers
        **stamp,
        "configs": configs,
    }))


if __name__ == "__main__":
    main()
