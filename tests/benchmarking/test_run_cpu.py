"""The harness on the CPU at a tiny size: generators are functions of
the seed; a run without a chip prints no result; the rest of a run goes
through on files the tests add themselves; a timed path broken
underneath comes out as not correct; and the arithmetic that pins why a
median of gaps was replaced by their mean."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import serve, train
from benchmark.generators import closed_loop, token_batches
from benchmark.readers import requests as req_reader
from benchmark.readers import value as value_reader

from bench_tiny import CHAT, CPU_STAMP, REPO, TINY, TRAIN


# ----------------------------------------------------------------------
# generators
def test_closed_loop_is_a_function_of_the_seed():
    a = closed_loop.generate(CHAT, TINY, 2**31 + 7)
    b = closed_loop.generate(CHAT, TINY, 2**31 + 7)
    c = closed_loop.generate(CHAT, TINY, 5)
    assert all((x["prompt"] == y["prompt"]).all()
               and x["max_new_tokens"] == y["max_new_tokens"]
               for x, y in zip(a, b))
    assert any(len(x["prompt"]) != len(y["prompt"])
               or (x["prompt"] != y["prompt"]).any() for x, y in zip(a, c))


def _pairs(reqs):
    return [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]


def test_every_round_of_every_seed_is_the_same_mix_in_another_order():
    """What a window serves does not hang on the seed or on how far
    down the list a faster program gets: every round is the same set."""
    n = CHAT["round"]
    a = _pairs(closed_loop.generate(CHAT, TINY, 1))
    b = _pairs(closed_loop.generate(CHAT, TINY, 2))
    assert len(a) == n * closed_loop.ROUNDS
    first = sorted(a[:n])
    for reqs in (a, b):
        assert all(sorted(reqs[i:i + n]) == first
                   for i in range(0, len(reqs), n))
    assert a[:n] != b[:n] and a[:n] != a[n:2 * n]
    lo, hi = CHAT["prompt_len"]["min"], CHAT["prompt_len"]["max"]
    assert all(lo <= p <= hi for p, _ in first)


def test_the_chat_mix_has_its_sources_means_and_fits_its_server():
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "chat_closed.json")) as fh:
        mix = json.load(fh)
    assert "arXiv:2309.06180" in mix["source"]
    one = _pairs(closed_loop.generate(mix, {"vocab_size": 50257},
                                      7))[:mix["round"]]
    prompts, outputs = zip(*one)
    # the round's means are the source's, to rounding
    assert np.mean(prompts) == pytest.approx(19.31, rel=0.01)
    assert np.mean(outputs) == pytest.approx(58.45, rel=0.01)
    # no length sits on a clip, none outgrows the pool or the buckets
    for spec, got in ((mix["prompt_len"], prompts),
                      (mix["output_len"], outputs)):
        assert spec["min"] < min(got) and max(got) < spec["max"]
    srv = mix["server"]
    assert max(prompts) <= max(srv["buckets"])
    assert max(p + o for p, o in one) <= srv["max_seq_len"]
    assert len(set(one)) == mix["round"]


def test_an_unknown_length_distribution_is_refused():
    with pytest.raises(ValueError):
        closed_loop.round_lengths({"dist": "uniform", "min": 1,
                                   "max": 2}, 4)


def test_token_batches_are_a_function_of_the_seed_and_rows_differ():
    ids, tgt = token_batches.generate(TRAIN, TINY, 3_000_000_000)
    ids2, _ = token_batches.generate(TRAIN, TINY, 3_000_000_000)
    other, _ = token_batches.generate(TRAIN, TINY, 4)
    assert (ids == ids2).all() and (ids != other).any()
    assert ids.shape == (16, 32) and (ids[:, 1:] == tgt[:, :-1]).all()
    assert len({row.tobytes() for row in ids}) == len(ids)


# ----------------------------------------------------------------------
# metric arithmetic
def _record(token_times, submit=0.0, window=(0.0, 100.0)):
    return {"window": window, "window_s": window[1] - window[0],
            "requests": [{"submit_t": submit, "token_t": list(t),
                          "prompt_len": 10} for t in token_times]}


def test_a_stall_moves_the_mean_gap_by_its_share_and_not_the_median():
    steady = [[1.0 + 0.05 * i for i in range(41)] for _ in range(4)]
    stalled = [list(t) for t in steady]
    for t in stalled:                  # one 2 s stall after token 20
        for i in range(21, 41):
            t[i] += 2.0
    mean = {"stat": "gap_ms", "reduce": "mean"}
    median = {"stat": "gap_ms", "reduce": "percentile", "q": 50}
    base = req_reader.read(_record(steady), mean)
    assert base == pytest.approx(50.0)
    # 2000 ms more over 40 gaps: the mean moves by 50 ms
    assert req_reader.read(_record(stalled), mean) == \
        pytest.approx(base + 2000.0 / 40)
    assert req_reader.read(_record(stalled), median) == \
        pytest.approx(req_reader.read(_record(steady), median))


def test_gaps_and_first_tokens_count_by_the_window_they_fall_in():
    rec = _record([[1.0, 2.0, 3.0], [9.0, 11.0]], window=(1.5, 10.0))
    # gaps ending at 2.0 and 3.0 count; the one ending at 11.0 does not
    assert req_reader.gaps_ms(rec) == [1000.0, 1000.0]
    # only the second request's first token is inside
    assert req_reader.ttft_ms(rec) == [9000.0]
    unfinished = _record([[]])
    assert req_reader.read(unfinished, {"stat": "ttft_ms",
                                        "reduce": "mean"}) is None


def test_value_reader_gives_nothing_where_there_is_nothing_to_read():
    rec = {"counters": {"a": 3.0, "b": 1.0, "zero": 0.0}, "n": 2}
    share = {"num": ["counters.a"], "den": ["counters.a", "counters.b"],
             "scale": 100}
    assert value_reader.read(rec, share) == 75.0
    assert value_reader.read(rec, {"num": ["counters.a"],
                                   "den": ["counters.b"],
                                   "times": ["n"]}) == 1.5
    assert value_reader.read(rec, {"num": ["counters.missing"]}) is None
    assert value_reader.read(rec, {"num": ["counters.a"],
                                   "den": ["counters.zero"]}) is None


def test_judge_needs_a_limit_for_every_number_and_fails_what_is_missing():
    ok, table = harness.judge({"g": 0.1}, {"g": 0.2})
    assert ok and table == {"g": {"value": 0.1, "limit": 0.2}}
    assert not harness.judge({"g": 0.3}, {"g": 0.2})[0]
    assert not harness.judge({"g": None}, {"g": 0.2})[0]
    assert not harness.judge({"g": float("nan")}, {"g": 0.2})[0]
    with pytest.raises(harness.BenchFailure):
        harness.judge({"g": 0.1}, {})


# ----------------------------------------------------------------------
# the command itself
def _run_cli(root, *args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
             JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    e.update(env or {})
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--root", root, *args],
        cwd=REPO, env=e, capture_output=True, text=True, timeout=600)


def test_without_a_chip_the_command_fails_and_prints_no_result(tiny_root):
    p = _run_cli(tiny_root, "--workload", "tiny_serve", "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no fallback" in p.stderr


def test_an_unknown_workload_fails(tiny_root):
    p = _run_cli(tiny_root, "--workload", "nope", "--seed", "1",
                 "--seconds", "1", "--trace", "0", "--dry")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_dry_run_prints_the_result_line_for_files_the_test_added(tiny_root):
    p = _run_cli(tiny_root, "--workload", "tiny_serve", "--seed",
                 str(2**31 + 11), "--seconds", "1.5", "--trace", "0",
                 "--dry")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    # the new metric over a known reader, and the copied ones
    assert set(line["metrics"]) == {"ttft_p75_ms", "tpot_mean_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["compared"]["widest_gap"]["limit"] == 2e-5
    tail = p.stderr.strip().splitlines()[-3:]
    assert tail[-1] == "correct: True"
    assert tail[0].startswith("compared widest_gap:")


# ----------------------------------------------------------------------
# the rest of a run, in this process, with the timed path broken
@pytest.fixture(scope="module")
def cells(tiny_root):
    return {n: harness.Cell(tiny_root, n)
            for n in ("tiny_serve", "tiny_fit")}


def _verdict(cell, compared):
    return harness.judge(compared, cell.limits)[0]


def test_serving_run_is_correct_and_reads_its_metrics(cells):
    cell = cells["tiny_serve"]
    record, compared, _ = serve.run(cell, 2**31 + 5, 1.5, False, CPU_STAMP)
    assert _verdict(cell, compared), compared
    got = harness.read_metrics(cell, record, trace=True)
    assert 0 < got["kv_pool_held_share.tpot"]["value"] <= 100
    assert record["attempted"] >= 8 and record["failed"] == 0


def test_a_token_altered_where_it_is_produced_is_not_correct(cells):
    cell = cells["tiny_serve"]

    def alter(server):
        real, n = server._resolve_token, [0]

        def resolve(req, device_tok, logits_row):
            n[0] += 1
            tok = real(req, device_tok, logits_row)
            return (tok + 1) % TINY["vocab_size"] if n[0] % 5 == 0 else tok
        server._resolve_token = resolve
        return server

    _, compared, _ = serve.run(cell, 2**31 + 5, 1.5, False, CPU_STAMP,
                               wrap_server=alter)
    assert compared["widest_gap"] > 10 * cell.limits["widest_gap"]
    assert not _verdict(cell, compared)


def test_the_lower_precision_control_is_not_correct(cells):
    """The control: the reference put in the program's place with its
    matrix products' operands in float8, one precision below the
    bfloat16 operands that the configuration's default matmul precision
    means on the chip; read at the same prompts and positions."""
    cell = harness.Cell(cells["tiny_serve"].root, "tiny_serve")
    # at 256 logits a flip is rare: read some thousands of tokens
    cell.traffic = dict(cell.traffic, check={"sample": 400})
    _, rows, _ = serve.offer(cell, 77, 1.0, False, CPU_STAMP)
    pad = cell.traffic["server"]["max_seq_len"]
    sound = cell.adapter.check_served(cell.config, 77, rows, pad)
    control = cell.adapter.check_served(cell.config, 77, rows, pad,
                                        control="float8")
    assert sound["widest_gap"] <= cell.limits["widest_gap"]
    assert control["widest_gap"] > 10 * cell.limits["widest_gap"]


def test_training_run_is_correct(cells):
    cell = cells["tiny_fit"]
    record, compared, _ = train.run(cell, 2**31 + 5, 0.5, False, CPU_STAMP)
    assert _verdict(cell, compared), compared
    got = harness.read_metrics(cell, record, trace=False)
    assert got["train_tok_s"]["value"] > 0
    assert record["train"]["tokens"] == record["train"]["fits"] * 4 * 4 * 32


class _Wrapped:
    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(cells):
    cell = cells["tiny_fit"]

    class Frozen(_Wrapped):
        def fit(self):
            sd = self._inner.sd
            before = dict(sd.trainable_params())
            self._inner.fit()
            for n, a in before.items():
                sd.set_arr_for_var(n, a)

    _, compared, _ = train.run(cell, 9, 0.2, False, CPU_STAMP,
                               wrap_trainer=Frozen)
    assert compared["change_gap"] == pytest.approx(1.0)
    assert not _verdict(cell, compared)


def test_half_of_the_batch_left_out_is_not_correct(cells):
    cell = cells["tiny_fit"]

    def halve(trainer):
        from deeplearning4j_tpu.dataset import DeviceCachedIterator
        B = TRAIN["batch"]
        ids = np.asarray(trainer.it.X).copy()
        tgt = np.asarray(trainer.it.Y).copy()
        for i in range(0, len(ids), B):     # the mean over the first half
            ids[i + B // 2:i + B] = ids[i:i + B // 2]
            tgt[i + B // 2:i + B] = tgt[i:i + B // 2]
        trainer.it = DeviceCachedIterator([ids], [tgt], batch_size=B)
        return trainer

    _, compared, _ = train.run(cell, 9, 0.2, False, CPU_STAMP,
                               wrap_trainer=halve)
    assert not _verdict(cell, compared), compared


def test_the_float8_control_is_not_correct(cells):
    """The training control: the reference in the program's place one
    precision below the bfloat16 the job states."""
    cell = cells["tiny_fit"]
    ids, tgt = cell.generator.generate(cell.traffic, cell.config, 9)
    B = TRAIN["batch"]
    batches = [(ids[i:i + B], tgt[i:i + B]) for i in range(0, len(ids), B)]
    ref = cell.adapter.reference_training(cell.config, cell.traffic, 9,
                                          batches)
    ctl = cell.adapter.reference_training(cell.config, cell.traffic, 9,
                                          batches, mode="float8")
    ctl["loss"] = sum(ctl["losses"]) / len(ctl["losses"])
    assert not _verdict(cell, train.compare(ctl, ref))
