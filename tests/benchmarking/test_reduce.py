"""The trace reduction on a hand-built plane set with known answers, and
the operations-and-bytes functions against hand counts."""
import json
import os

import pytest

from bench_tiny import REPO, TINY
from benchmark import reduce
from benchmark.counts import gpt2 as counts

PLANES = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ("%copy.1 = f32[4,8]{1,0} copy(x)", 0.0, 1.0),
            ("%fusion.3 = bf16[16]{0} fusion(y)", 1.5, 0.5),
            ("%copy.2 = f32[4,8]{1,0} copy(x)", 3.0, 1.0)]},
        {"name": "XLA Modules", "events": [
            ("jit_decode_fn(123)", 0.0, 2.0),
            ("jit_prefill_fn(5)", 3.0, 1.0)]}]},
    {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": []}]},
    {"name": "/host:CPU", "lines": [{"name": "worker", "events": [
        ("np.asarray(jax.Array)", 0.9, 0.7),
        ("outer", 0.0, 4.0),
        ("PjitFunction(jit_x)", 2.0, 0.9)]}]},
]


def test_busy_share_is_the_union_of_operations():
    busy, window = reduce.busy_seconds(PLANES)
    assert (busy, window) == (2.5, 4.0)


def test_overlapping_operations_count_once():
    planes = [{"name": "/device:TPU:0", "lines": [{
        "name": "XLA Ops", "events": [("a", 0.0, 2.0), ("b", 1.0, 2.0)]}]}]
    assert reduce.busy_seconds(planes) == (3.0, 3.0)


def test_a_trace_with_no_device_operation_gives_nothing():
    assert reduce.busy_seconds(PLANES[2:]) is None
    assert reduce.module_seconds(PLANES[2:]) == {}


def test_program_time_is_its_modules_device_time():
    assert reduce.module_seconds(PLANES) == {"jit_decode_fn": (2.0, 1),
                                             "jit_prefill_fn": (1.0, 1)}


def test_a_program_run_that_an_edge_cuts_through_is_left_out():
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [("a", 0.0, 5.0)]},
        {"name": "XLA Modules", "events": [
            ("jit_f(1)", 0.5, 1.0), ("jit_f(1)", 1.5, 1.0),
            ("jit_f(1)", 2.5, 1.0), ("jit_f(1)", 3.5, 1.0)]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            (reduce.WINDOW_EVENT, 1.0, 3.0)]}]}]
    assert reduce.module_seconds(planes) == {"jit_f": (2.0, 2)}


def test_the_window_is_the_hosts_annotation_and_its_idle_edges_count():
    """Idle time before the first device operation and after the last
    one is inside the traced span and counts as idle."""
    planes = PLANES + [{"name": "/host:CPU", "lines": [{
        "name": "main", "events": [(reduce.WINDOW_EVENT, -1.0, 6.0)]}]}]
    assert reduce.trace_window(planes) == (-1.0, 5.0)
    assert reduce.busy_seconds(planes) == (2.5, 6.0)
    gaps = dict(reduce.idle_gaps(planes))
    # the edges' 2 s are idle and the annotation itself names no gap
    assert sum(gaps.values()) == pytest.approx(3.5)
    assert reduce.WINDOW_EVENT not in gaps


def _arith_record(**over):
    """Two requests of 10 prompt tokens: one prefilled in the span, and
    three decode tokens in it between them."""
    rec = {"peaks": {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9},
           "counts": counts, "config": TINY,
           "requests": [
               {"prompt_len": 10, "token_t": [0.5, 1.5, 2.5]},
               {"prompt_len": 10, "token_t": [1.2, 2.2, 9.0]}],
           "trace": {"t_start": 1.0, "t_stop": 3.0, "busy_s": 0.5,
                     "window_s": 2.0,
                     "modules": {"jit_decode_fn": {"seconds": 0.25,
                                                   "runs": 2}}}}
    rec.update(over)
    return rec


def test_step_mfu_is_all_the_spans_work_over_the_devices_busy_time():
    from benchmark.readers import arith
    rec = _arith_record()
    # decode tokens at 1.5, 2.5 (contexts 11, 12) and 2.2 (context 11);
    # one prefill, whose first token fell at 1.2
    flops = counts.decode_flops(TINY, [11, 12, 11]) \
        + counts.prefill_flops(TINY, [10])
    got = arith.read(rec, {"work": "serve", "bound": "mfu"})
    assert got == pytest.approx(100 * flops / 0.5 / 1e9)
    # no trace, no number: never a share worked out from the host clock
    assert arith.read(_arith_record(trace=None),
                      {"work": "serve", "bound": "mfu"}) is None
    assert arith.read(_arith_record(peaks=None),
                      {"work": "serve", "bound": "mfu"}) is None


def test_a_roofline_is_the_least_time_over_the_programs_device_time():
    from benchmark.readers import arith
    rec = _arith_record()
    spec = {"work": "decode", "bound": "roofline",
            "program": "jit_decode_fn"}
    ctx = [11, 12, 11]
    least = max(counts.decode_flops(TINY, ctx),
                counts.decode_bytes(TINY, 2, ctx)) / 1e9
    assert arith.read(rec, spec) == pytest.approx(100 * least / 0.25)
    # a program that is not in the trace leaves its roofline silent
    assert arith.read(rec, dict(spec, program="jit_other")) is None


def test_train_mfu_counts_the_traced_fits_only():
    from benchmark.readers import arith
    rec = _arith_record(train={"seq_len": 32, "traced_tokens": 1000,
                               "tokens": 99999})
    got = arith.read(rec, {"work": "train", "bound": "mfu"})
    assert got == pytest.approx(
        100 * counts.train_flops_per_token(TINY, 32) * 1000 / 0.5 / 1e9)
    rec["train"]["traced_tokens"] = 0
    assert arith.read(rec, {"work": "train", "bound": "mfu"}) is None


def test_top_operations_are_named_by_opcode_type_and_shape():
    assert reduce.top_device_ops(PLANES) == [["copy_f32_4_8", 2.0],
                                             ["fusion_bf16_16", 0.5]]
    assert reduce.op_label("fusion.123") == "fusion"


def test_idle_gaps_are_named_by_the_innermost_host_event():
    gaps = dict(reduce.idle_gaps(PLANES))
    assert gaps == {"np.asarray_jax.Array_": 0.5, "outer": 1.0}


def test_short_gaps_are_summed_into_one_entry():
    planes = [{"name": "/device:TPU:0", "lines": [{
        "name": "XLA Ops", "events": [("a", 0.0, 1.0),
                                      ("b", 1.00001, 1.0)]}]}]
    (name, seconds), = reduce.idle_gaps(planes)
    assert name == "shorter_gaps_1" and seconds == pytest.approx(1e-5)


def _published(name):
    with open(os.path.join(REPO, "benchmark", "configs", name)) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name,millions", [("gpt2-xl.json", 1557.6),
                                           ("gpt2-medium.json", 354.8)])
def test_published_parameter_counts_fall_out(name, millions):
    assert round(counts.param_count(_published(name)) / 1e6, 1) == millions


def test_tiny_counts_by_hand():
    V, P, H, L, I = 256, 64, 64, 2, 128
    layer = (H * 3 * H + 3 * H) + (H * H + H) + (H * I + I) + (I * H + H) \
        + 4 * H
    assert counts.param_count(TINY) == V * H + P * H + L * layer + 2 * H
    mm = L * (4 * H * H + 2 * H * I) + V * H
    assert counts.matmul_params(TINY) == mm
    # two tokens, attending 10 and 20 positions
    assert counts.decode_flops(TINY, [10, 20]) == \
        2 * mm * 2 + 4 * L * H * 30
    weights = counts.param_count(TINY) * 4
    assert counts.decode_bytes(TINY, 1, [10, 20]) == \
        weights + 2 * L * H * 4 * (30 + 2)
    n = 12
    assert counts.prefill_flops(TINY, [n]) == \
        2 * (mm - V * H) * n + 2 * V * H + 4 * L * H * n * (n + 1) / 2
    assert counts.prefill_bytes(TINY, 1, [n]) == \
        weights + 2 * L * H * 4 * n
    assert counts.train_flops_per_token(TINY, 32) == \
        6 * mm + 12 * L * H * 32


def test_bytes_follow_the_files_dtypes():
    half = dict(TINY, kv_dtype="bfloat16")
    full = counts.decode_bytes(TINY, 0, [100])
    assert counts.decode_bytes(half, 0, [100]) == full / 2


def test_counts_import_nothing_of_the_program():
    import ast
    for mod in ("counts/gpt2.py", "reduce.py", "reference/gpt2.py",
                "readers/arith.py"):
        with open(os.path.join(REPO, "benchmark", mod)) as fh:
            tree = ast.parse(fh.read())
        names = [n.module or "" for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)]
        names += [a.name for n in ast.walk(tree)
                  if isinstance(n, ast.Import) for a in n.names]
        assert not [n for n in names if n.startswith("deeplearning4j")], mod
