"""Set-up says where its seconds went (ISSUE 37).

The phases of a start that are not the compiler's are spans opened
through ``COMPILE_STATS.span``: a tracer span and, on the same two
edges, the span's self time into a counter. Pinned here, on the CPU with
the ring on, a tiny paged server and a tiny scanned fit:

- (a) the new spans nest as ``SPAN_CATALOG`` describes them;
- (b) each new counter is the sum of its spans: ``build_seconds`` the
  self times of the phases, ``plan_analyze_seconds`` the
  ``compile.plan_analyze`` spans, ``cache_load_seconds`` the
  ``compile.backend`` markers with ``cache_hit``;
- (c) a jitted function that calls a jitted function reads
  ``trace_seconds`` as the outer trace's wall time, not both events;
- (d) build, trace, lower, backend-compile and plan-analysis seconds
  partition the wall time under ``serving.build``, and under
  ``fit.build`` and the first ``fit.dispatch``: no second twice;
- (e) ``warmup_report["programs"]`` has one row a program built, the
  rows sum to what the warm-up added to ``COMPILE_STATS``, a second
  warm-up of the same shapes adds none, and ``seconds`` is the span's
  length;
- (f) a fit that has run adds nothing: no phase, no build second;
- (g) with the ring off the counters still move, and the module that
  holds them names no jax at its top.
"""
import numpy as np
import pytest

from deeplearning4j_tpu.compilecache import (COMPILE_STATS,
                                             install_compile_watcher)
from deeplearning4j_tpu.compilecache import cache as cache_mod
from deeplearning4j_tpu.environment import environment
from deeplearning4j_tpu.monitor import trace as trace_mod
from deeplearning4j_tpu.monitor.trace import (SPAN_CATALOG, TRACER,
                                              disable_tracing,
                                              enable_tracing)
from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
from deeplearning4j_tpu.zoo.gpt import GPTConfig, build_gpt, gpt_paged_spec

CFG = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                intermediate_size=64, max_seq_len=32)

#: the five counters that partition a start's wall time
PARTITION = ("build_seconds", "trace_seconds", "lower_seconds",
             "backend_compile_seconds", "plan_analyze_seconds")
#: spans of ``COMPILE_STATS.span`` (``fit.dispatch`` is one where
#: ``first`` is set) and the counter each one's self time goes to
PHASES = {"serving.build": "build_seconds",
          "serving.build.params": "build_seconds",
          "serving.build.pool": "build_seconds",
          "serving.warmup": "build_seconds",
          "model.build": "build_seconds",
          "fit.build": "build_seconds",
          "fit.dispatch": "build_seconds",
          "compile.precompile": "build_seconds",
          "compile.plan_analyze": "plan_analyze_seconds"}
MARKERS = {"compile.trace": "trace_seconds",
           "compile.lower": "lower_seconds",
           "compile.backend": "backend_compile_seconds"}
#: child -> the parents it may sit under, as SPAN_CATALOG's comments say
SETUP_TREE = {
    "serving.build": {None},
    "serving.build.params": {"serving.build", "serving.build.pool"},
    "serving.build.pool": {"serving.build"},
    "serving.warmup": {"serving.build", None},
    "compile.precompile": {"serving.warmup", None},
    "compile.plan_analyze": {"serving.warmup", None},
    "model.build": {None, "model.build", "serving.build.pool"},
    "fit.build": {None, "fit"},
    "fit.dispatch": {"fit"},
}


@pytest.fixture(autouse=True)
def _ring():
    """The ring on; plan capture as a fresh process has it, off (a
    ``MonitorListener`` of an earlier test file arms it for the life of
    the process, and an armed capture builds a fit's program under
    ``compile.plan_capture``, ahead of the first dispatch)."""
    from deeplearning4j_tpu.monitor import memstats
    armed = memstats.plan_capture_enabled()
    memstats.disable_plan_capture()
    install_compile_watcher()
    enable_tracing(reset=True)
    yield
    disable_tracing()
    if armed:
        memstats.enable_plan_capture()


def is_phase(sp):
    return sp.name in PHASES and (sp.name != "fit.dispatch"
                                  or sp.args.get("first"))


def self_times(spans):
    """``{counter: seconds}`` from the ring alone: every phase's length
    less the phases and markers right under it, and every marker's
    length."""
    by_sid = {sp.sid: sp for sp in spans}
    own = {sp.sid: sp.dur for sp in spans if is_phase(sp)}
    out = dict.fromkeys(set(PHASES.values()) | set(MARKERS.values()), 0.0)
    for sp in spans:
        if not (is_phase(sp) or sp.name in MARKERS):
            continue
        if sp.name in MARKERS:
            out[MARKERS[sp.name]] += sp.dur
        up = by_sid.get(sp.parent)
        while up is not None and not is_phase(up):
            up = by_sid.get(up.parent)
        if up is not None:
            own[up.sid] -= sp.dur
    for sid, seconds in own.items():
        out[PHASES[by_sid[sid].name]] += seconds
    return out


def within(spans, roots):
    """The spans that lie inside one of ``roots``, on its thread."""
    return [sp for sp in spans
            if any(sp.tid == r.tid and sp.t0 >= r.t0
                   and sp.t0 + sp.dur <= r.t0 + r.dur for r in roots)]


def parents(spans):
    by_sid = {sp.sid: sp for sp in spans}
    return [(sp, by_sid[sp.parent].name if sp.parent in by_sid else None)
            for sp in spans]


def tiny_spec():
    """A spec of its own each time: dispatchers and their executables
    are memoized on the spec, and a test wants to build them."""
    sd = build_gpt(CFG, batch=2, seq_len=8, seed=0)
    return gpt_paged_spec(sd, CFG)


def tiny_server(spec, **kw):
    return PagedGenerativeServer(spec, max_slots=4, max_seq_len=32,
                                 block_size=8, buckets=[8, 16],
                                 start=False, **kw)


@pytest.fixture()
def started():
    """``(server, what its start added to COMPILE_STATS, the ring)``:
    the model, the spec and the constructor with its warm-up."""
    mark = COMPILE_STATS.mark()
    srv = tiny_server(tiny_spec())
    delta, spans = COMPILE_STATS.delta(mark), TRACER.spans()
    yield srv, delta, spans
    srv.shutdown(drain=False)


@pytest.fixture()
def cache_env(tmp_path):
    env = environment()
    env.set("compilation_cache_dir", str(tmp_path / "xla_cache"))
    env.set("compilation_cache_min_entry_size", -1)
    env.set("compilation_cache_min_compile_time", 0.0)
    try:
        yield
    finally:
        env.reset("compilation_cache_dir")
        env.reset("compilation_cache_min_entry_size")
        env.reset("compilation_cache_min_compile_time")


def tiny_fit():
    from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
    from deeplearning4j_tpu.dataset import DeviceCachedIterator
    from deeplearning4j_tpu.learning.updaters import Adam
    rng = np.random.default_rng(0)
    sd = SameDiff()
    x = sd.placeholder("x", shape=(-1, 4))
    w = sd.var("w", value=rng.normal(0, 0.1, (4, 3)).astype(np.float32))
    labels = sd.placeholder("labels", shape=(-1, 3))
    sd.loss.softmax_cross_entropy(x.mmul(w, name="logits"), labels,
                                  name="loss")
    sd.set_loss_variables(["loss"])
    sd.training_config = (TrainingConfig.builder().updater(Adam(1e-2))
                          .data_set_feature_mapping("x")
                          .data_set_label_mapping("labels").build())
    X = rng.normal(size=(32, 4)).astype(np.float32)
    Y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]
    return sd, DeviceCachedIterator(X, Y, batch_size=8)


# ----------------------------------------------------------------------
# (a) the tree

@pytest.mark.parametrize("name", sorted(SETUP_TREE))
def test_the_new_spans_are_cataloged(name):
    assert name in SPAN_CATALOG
    # no arg of a start's span that nothing reads (ISSUE 37, removals)
    want = {"model.build": ("family",), "compile.precompile": ("target",),
            "compile.plan_analyze": ("target",),
            "fit.dispatch": ("epoch", "first", "trace_s", "lower_s",
                             "backend_s", "cache_hit")}
    assert SPAN_CATALOG[name][1] == want.get(name, ())


def test_a_servers_start_nests_as_cataloged(started):
    _, _, spans = started
    seen = {}
    for sp, parent in parents(spans):
        if sp.name in SETUP_TREE:
            assert parent in SETUP_TREE[sp.name], (sp.name, parent)
            seen[sp.name] = seen.get(sp.name, 0) + 1
        elif sp.name in MARKERS:
            # jax's events fire where a program is built or an array made
            assert parent in ("compile.precompile", "serving.build.pool",
                              "model.build", "serving.build.params")
    # decode and two buckets, each with its plan read once
    assert seen["compile.precompile"] == seen["compile.plan_analyze"] == 3
    assert seen["serving.build"] == seen["serving.warmup"] == 1
    assert seen["serving.build.pool"] == seen["serving.build.params"] == 1
    # the graph, the spec, and the spec's programs under the pool
    fams = [sp.args["family"] for sp in spans if sp.name == "model.build"]
    assert fams == ["gpt"] * 3
    targets = [sp.args["target"] for sp in spans
               if sp.name == "compile.precompile"]
    assert targets == ["paged_decode_s4r0", "paged_prefill_b8",
                       "paged_prefill_b16"]
    assert targets == [sp.args["target"] for sp in spans
                       if sp.name == "compile.plan_analyze"]


# ----------------------------------------------------------------------
# (b) a counter is its spans

@pytest.mark.parametrize("key", sorted(set(PHASES.values())
                                       | set(MARKERS.values())))
def test_each_counter_is_the_sum_of_its_spans(started, key):
    _, delta, spans = started
    assert delta[key] > 0
    assert self_times(spans)[key] == pytest.approx(delta[key], rel=0.05)


def test_precompiles_counts_the_programs_built_ahead(started):
    srv, delta, _ = started
    assert delta["precompiles"] == 3 == len(srv.warmup_report["programs"])
    assert delta["backend_compiles"] >= delta["precompiles"]


def test_cache_load_seconds_is_the_markers_with_a_hit(cache_env):
    cold = COMPILE_STATS.mark()
    tiny_server(tiny_spec()).shutdown(drain=False)
    first = COMPILE_STATS.delta(cold)
    assert first["cache_load_seconds"] == 0 == first["cache_hits"]
    enable_tracing(reset=True)
    warm = COMPILE_STATS.mark()
    srv = tiny_server(tiny_spec())
    srv.shutdown(drain=False)
    d = COMPILE_STATS.delta(warm)
    hits = [sp for sp in TRACER.spans()
            if sp.name == "compile.backend" and sp.args.get("cache_hit")]
    assert len(hits) == d["cache_hits"] >= 3
    assert sum(sp.dur for sp in hits) == pytest.approx(
        d["cache_load_seconds"], rel=1e-6)
    assert 0 < d["cache_load_seconds"] <= d["backend_compile_seconds"]
    assert all(row["cache_hit"] for row in srv.warmup_report["programs"])


# ----------------------------------------------------------------------
# (c) a trace inside a trace

def test_a_jit_called_from_a_jit_reads_the_outer_traces_wall_time():
    import jax
    import jax.numpy as jnp
    from jax import monitoring

    @jax.jit
    def inner(x):
        for i in range(300):
            x = jnp.sin(x) * (1.0 + i)
        return x

    @jax.jit
    def outer(x):
        return inner(x) + 1.0

    events = []

    def listen(event, duration, **kw):
        if event.endswith("jaxpr_trace_duration"):
            events.append(float(duration))

    monitoring.register_event_duration_secs_listener(listen)
    try:
        mark = COMPILE_STATS.mark()
        outer.trace(jnp.ones((4,), jnp.float32))
        got = COMPILE_STATS.delta(mark)["trace_seconds"]
    finally:
        from jax._src import monitoring as _m
        _m.unregister_event_duration_listener(listen)
    # jax told of both traces, and the outer one holds the inner one
    assert len(events) >= 2 and max(events) > 0.02
    assert sum(events) > 1.6 * max(events)
    assert got == pytest.approx(max(events), rel=0.05)
    # the markers take their seconds back too
    marks = [sp.dur for sp in TRACER.spans() if sp.name == "compile.trace"]
    assert sum(marks) == pytest.approx(got, rel=1e-6)


def test_an_interval_takes_back_what_closed_inside_it_and_no_more():
    """The rule by itself, on a thread of its own: two events side by
    side keep their seconds, one around them takes theirs back, a phase
    around all three takes the whole."""
    import threading
    import time
    got = {}

    def run():
        mark = COMPILE_STATS.mark()
        with COMPILE_STATS.span("model.build", cat="model",
                                family="test") as phase:
            t0 = time.perf_counter()
            time.sleep(0.02)
            a = cache_mod._own_seconds(0.02)
            time.sleep(0.03)
            b = cache_mod._own_seconds(0.03)
            time.sleep(0.01)
            whole = time.perf_counter() - t0
            c = cache_mod._own_seconds(whole)
        got.update(a=a, b=b, c=c, whole=whole, dur=phase.dur,
                   build=COMPILE_STATS.delta(mark)["build_seconds"])

    t = threading.Thread(target=run)
    t.start()
    t.join()
    assert got["a"] == 0.02 and got["b"] == 0.03
    assert got["c"] == pytest.approx(got["whole"] - 0.05, abs=1e-9)
    assert got["build"] == pytest.approx(got["dur"] - got["whole"],
                                         abs=1e-9)


# ----------------------------------------------------------------------
# (d) the partition

def test_five_counters_partition_a_servers_build(started):
    _, _, spans = started
    (build,) = [sp for sp in spans if sp.name == "serving.build"]
    got = self_times(within(spans, [build]))
    assert sum(got[k] for k in PARTITION) == pytest.approx(build.dur,
                                                           rel=0.05)
    assert got["trace_seconds"] <= build.dur


def test_five_counters_partition_a_first_fit():
    sd, it = tiny_fit()
    mark = COMPILE_STATS.mark()
    sd.fit(it, epochs=2)
    delta, spans = COMPILE_STATS.delta(mark), TRACER.spans()
    for sp, parent in parents(spans):
        if sp.name in ("fit.build", "fit.dispatch"):
            assert parent in SETUP_TREE[sp.name]
    builds = [sp for sp in spans if sp.name == "fit.build"]
    assert [p for sp, p in parents(spans)
            if sp.name == "fit.build"] == [None, "fit"]
    dispatches = [sp for sp in spans if sp.name == "fit.dispatch"]
    assert [sp.args.get("first") for sp in dispatches] == [1, None]
    assert [sp.args["epoch"] for sp in dispatches] == [0, 1]
    first = dispatches[0]
    # the program's row rides on the dispatch that built it
    assert first.args["backend_s"] > 0 and first.args["trace_s"] > 0
    assert first.args["lower_s"] > 0 and first.args["cache_hit"] in (0, 1)
    # (what fit.stage and fit.sync compile on a first fit, the copies
    # and the updater's start, is counted too, and lies under no phase)
    under = sum(sp.dur for sp in builds) + first.dur
    got = self_times(within(spans, builds + [first]))
    assert sum(got[k] for k in PARTITION) == pytest.approx(under, rel=0.05)
    assert got["build_seconds"] == pytest.approx(delta["build_seconds"],
                                                 rel=0.05)
    assert got["trace_seconds"] <= under
    assert sum(delta[k] for k in PARTITION) >= 0.95 * under


# ----------------------------------------------------------------------
# (e) the table of programs

def test_the_rows_sum_to_what_the_warm_up_added(cache_env):
    srv = tiny_server(tiny_spec(), warmup=False)
    try:
        assert srv.warmup_report is None
        enable_tracing(reset=True)
        mark = COMPILE_STATS.mark()
        report = srv.warmup()
        delta = COMPILE_STATS.delta(mark)
        rows = report["programs"]
        assert [r["label"] for r in rows] == [
            "paged_decode_s4r0", "paged_prefill_b8", "paged_prefill_b16"]
        assert all(set(r) == {"label", "trace_s", "lower_s", "backend_s",
                              "cache_hit", "plan_analyze_s"} for r in rows)
        for col, key in (("trace_s", "trace_seconds"),
                         ("lower_s", "lower_seconds"),
                         ("backend_s", "backend_compile_seconds"),
                         ("plan_analyze_s", "plan_analyze_seconds")):
            assert all(r[col] > 0 for r in rows)
            assert sum(r[col] for r in rows) == pytest.approx(delta[key],
                                                              rel=1e-9)
        assert report["backend_compiles"] == delta["backend_compiles"] == 3
        assert report["cache_misses"] == 3 and report["cache_hits"] == 0
        assert not any(r["cache_hit"] for r in rows)
        # one clock: the report's seconds are the span's length
        (span,) = [sp for sp in TRACER.spans()
                   if sp.name == "serving.warmup"]
        assert report["seconds"] == pytest.approx(span.dur, abs=2e-3)
        assert report["seconds"] >= sum(
            r["trace_s"] + r["lower_s"] + r["backend_s"]
            + r["plan_analyze_s"] for r in rows)
        # the same shapes again: nothing is built, no row
        again = srv.warmup()
        assert again["programs"] == [] and again["backend_compiles"] == 0
        assert COMPILE_STATS.delta(mark)["precompiles"] == 3
    finally:
        srv.shutdown(drain=False)


def test_a_spec_with_counters_has_a_row_for_the_program_that_cuts_them():
    from test_evabyte import CFG as EVA, SEED

    from benchmark.adapters import evabyte as adapter
    from deeplearning4j_tpu.zoo.evabyte import evabyte_paged_spec
    spec = evabyte_paged_spec(adapter.program_config(EVA),
                              adapter.program_params(EVA, SEED))
    srv = PagedGenerativeServer(spec, max_slots=3, block_size=4,
                                max_seq_len=256, buckets=[8],
                                warmup=False, start=False)
    try:
        mark = COMPILE_STATS.mark()
        report = srv.warmup()
        delta = COMPILE_STATS.delta(mark)
        labels = [r["label"] for r in report["programs"]]
        assert "paged_cut_tokens_s3" in labels
        assert labels[-1] == "paged_prefill_b8"
        assert len(labels) == delta["precompiles"] \
            == delta["backend_compiles"] == report["backend_compiles"]
        assert sum(r["backend_s"] for r in report["programs"]) == \
            pytest.approx(delta["backend_compile_seconds"], rel=1e-9)
        assert sum(r["trace_s"] for r in report["programs"]) == \
            pytest.approx(delta["trace_seconds"], rel=1e-9)
        fams = {sp.args["family"] for sp in TRACER.spans()
                if sp.name == "model.build"}
        assert fams == {"evabyte"}
    finally:
        srv.shutdown(drain=False)


def test_precompile_returns_the_scanned_fits_row():
    sd, it = tiny_fit()
    mark = COMPILE_STATS.mark()
    info = sd.precompile(batch_size=8, epoch_steps=4, tiers=["epoch"])
    delta = COMPILE_STATS.delta(mark)
    (row,) = info["programs"]
    assert row["label"] == "epoch_4" and info["compiled"] == 1
    assert row["backend_s"] == pytest.approx(
        delta["backend_compile_seconds"], rel=1e-9)
    assert row["plan_analyze_s"] == pytest.approx(
        delta["plan_analyze_seconds"], rel=1e-9) and row["trace_s"] > 0
    assert delta["precompiles"] == 1
    # the fit then builds nothing: its first dispatch runs the program
    mark = COMPILE_STATS.mark()
    sd.fit(it, epochs=1)
    assert COMPILE_STATS.delta(mark)["backend_compiles"] == 0
    (first,) = [sp for sp in TRACER.spans() if sp.name == "fit.dispatch"]
    assert first.args["first"] == 1 and first.args["backend_s"] == 0


def test_a_batch_servers_warm_up_has_the_table_too():
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.serving import ParallelInference
    conf = (NeuralNetConfiguration.builder().seed(0).list()
            .layer(DenseLayer(n_out=8))
            .layer(OutputLayer(n_out=3, activation="softmax"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf)
    net.init()
    mark = COMPILE_STATS.mark()
    with ParallelInference(net, max_batch_size=4,
                           warmup_buckets=(1, 4)) as srv:
        report = srv.warmup_report
        assert [r["label"] for r in report["programs"]] == [
            "output_b1", "output_b4"]
        assert srv.warmup(buckets=(1, 4))["programs"] == []
    delta = COMPILE_STATS.delta(mark)
    assert delta["precompiles"] == 2
    assert sum(r["backend_s"] for r in report["programs"]) == \
        pytest.approx(delta["backend_compile_seconds"], rel=1e-9)
    warm = [sp for sp in TRACER.spans() if sp.name == "serving.warmup"]
    assert len(warm) == 2 and all(not sp.args for sp in warm)
    assert report["seconds"] == pytest.approx(warm[0].dur, abs=2e-3)


# ----------------------------------------------------------------------
# (f) a steady fit pays nothing

def test_a_fit_that_has_run_opens_no_phase_and_counts_no_second():
    sd, it = tiny_fit()
    sd.fit(it, epochs=1)
    enable_tracing(reset=True)
    mark = COMPILE_STATS.mark()
    sd.fit(it, epochs=2)
    assert COMPILE_STATS.delta(mark) == dict.fromkeys(
        COMPILE_STATS.snapshot(), 0)
    names = [sp.name for sp in TRACER.spans()]
    assert "fit.build" not in names and names.count("fit.dispatch") == 2
    assert not any(sp.args.get("first") for sp in TRACER.spans())
    # a graph that changed is a graph not run: the phases come back
    sd.var("unused", value=np.zeros(2, np.float32))
    mark = COMPILE_STATS.mark()
    sd.fit(it, epochs=1)
    assert COMPILE_STATS.delta(mark)["build_seconds"] > 0
    assert [sp.name for sp in TRACER.spans()].count("fit.build") == 2


# ----------------------------------------------------------------------
# (g) ring off, and the import

def test_with_the_ring_off_the_counters_move_and_no_span_is_made():
    disable_tracing()
    ring = TRACER.mark()
    mark = COMPILE_STATS.mark()
    srv = tiny_server(tiny_spec())
    srv.shutdown(drain=False)
    delta = COMPILE_STATS.delta(mark)
    assert TRACER.mark() == ring
    assert all(delta[k] > 0 for k in PARTITION)
    assert delta["precompiles"] == 3
    assert srv.warmup_report["seconds"] > 0
    assert len(srv.warmup_report["programs"]) == 3
    phase = COMPILE_STATS.span("serving.build", cat="serving")
    assert phase._span is trace_mod._NULL_SPAN


def test_the_counters_module_names_no_jax_at_its_top():
    """``monitor/trace.py`` imports no jax until a span asks whether a
    profiler session is open (tests/test_trace_clock.py); the module
    that holds the counters adds none at import either: jax is named
    inside the functions that configure the cache and listen to it."""
    import ast
    with open(cache_mod.__file__) as fh:
        tree = ast.parse(fh.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    names = [a.name for n in top if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in top
                                  if isinstance(n, ast.ImportFrom)]
    assert names and not any(n.split(".")[0] == "jax" for n in names)


# ----------------------------------------------------------------------
# /metrics and the report

def test_the_new_counters_reach_metrics_and_the_report():
    from deeplearning4j_tpu.monitor import MetricsRegistry
    from deeplearning4j_tpu.ui.report import render_report
    from deeplearning4j_tpu.ui.stats import StatsStorage
    tiny_server(tiny_spec()).shutdown(drain=False)
    reg = MetricsRegistry()
    reg.fold_compile(COMPILE_STATS)
    text = reg.to_prometheus_text()
    for line in ("dl4j_compile_build_seconds",
                 "dl4j_compile_plan_analyze_seconds",
                 "dl4j_compile_cache_load_seconds",
                 "dl4j_compile_precompiles_total",
                 "dl4j_compile_trace_seconds"):
        assert line in text
    storage = StatsStorage()
    rec = COMPILE_STATS.publish(storage)
    assert set(rec) >= set(PARTITION) | {"precompiles",
                                         "cache_load_seconds"}
    html = render_report(storage)
    assert "building models" in html and "programs built ahead" in html
