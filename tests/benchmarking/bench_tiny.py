"""A benchmark root of the tests' own, made of NEW files only: a tiny
GPT-2 configuration, traffic files of the known kinds, metric files over
the known readers, limits, and a BENCHMARK.json that names them. That
the package runs it unedited is the proof that a later PR adds a cell, a
configuration, a traffic mix or a metric with files and entries alone."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {"family": "gpt2", "activation_function": "gelu_new",
        "initializer_range": 0.02, "layer_norm_epsilon": 1e-5,
        "n_embd": 64, "n_head": 4, "n_inner": 128, "n_layer": 2,
        "n_positions": 64, "vocab_size": 256,
        "param_dtype": "float32", "kv_dtype": "float32"}

CHAT = {"kind": "closed_loop", "clients": 4, "round": 8,
        "lengths_seed": 1,
        "prompt_len": {"dist": "lognormal", "mean": 12, "sigma": 0.6,
                       "min": 4, "max": 30},
        "output_len": {"dist": "lognormal", "mean": 8, "sigma": 0.5,
                       "min": 4, "max": 16},
        "server": {"max_slots": 4, "block_size": 8, "max_seq_len": 48,
                   "buckets": [8, 16, 32]},
        "check": {"sample": 6}}

TRAIN = {"kind": "token_batches", "batch": 4, "seq_len": 32,
         "steps_per_fit": 4, "compute_dtype": "bfloat16",
         "optimizer": {"name": "adam", "learning_rate": 1e-4,
                       "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
         "reference_rows": 2, "check": {}}


def write_root(root: str) -> str:
    data = os.path.join(root, "bench_data")
    for sub in ("configs", "traffic", "metrics", "limits"):
        os.makedirs(os.path.join(data, sub))

    def put(rel, obj):
        with open(os.path.join(data, rel), "w") as fh:
            json.dump(obj, fh)

    put("configs/tiny.json", TINY)
    put("traffic/tiny_chat.json", CHAT)
    put("traffic/tiny_train.json", TRAIN)
    # two metrics copied from the package's files, one that is new
    for name in ("tpot_mean_ms", "setup_s", "train_tok_s",
                 "kv_pool_held_share.tpot"):
        shutil.copy(os.path.join(REPO, "benchmark", "metrics",
                                 name + ".json"),
                    os.path.join(data, "metrics", name + ".json"))
    put("metrics/ttft_p75_ms.json",
        {"reader": "requests",
         "params": {"stat": "ttft_ms", "reduce": "percentile", "q": 75}})
    # set from readings at this size on the CPU: the program reads 0 to
    # 2e-6 and the bfloat16 control 2e-4 to 3e-3 (serving), 1.2e-5 / 0.003 / 0.014 (training)
    put("limits/tiny_serve.json", {"widest_gap": 2e-5,
                                   "requests_failed": 0})
    put("limits/tiny_fit.json", {"loss_gap": 1e-3, "moment_gap": 0.05,
                                 "change_gap": 0.1})
    bench = {
        "command": ["python3", "-m", "benchmark.run"],
        "paths": ["bench_data"], "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench_data/configs/tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [
            {"name": "tiny_serve", "config": "tiny",
             "traffic": "tiny_chat", "chips": 1, "why": "test"},
            {"name": "tiny_fit", "config": "tiny",
             "traffic": "tiny_train", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "ttft_p75_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny_serve"]},
            {"name": "tpot_mean_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny_serve"]},
            {"name": "train_tok_s", "unit": "tok/s", "better": "higher",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny_fit"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": [
            {"name": "kv_pool_held_share.tpot", "unit": "%",
             "better": "higher", "source": "program_counter",
             "layer": "KV memory tier", "moves": "tpot_mean_ms",
             "workloads": ["tiny_serve"]}],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


CPU_STAMP = {"platform": "cpu", "kind": "cpu", "count": 1}

#: the scheduler's metrics that every serving cell lists (PR 34)
SCHED_METRICS = ("sched_host_share.tpot", "decode_launch_ms",
                 "decode_ahead_share.tpot")


def check_sched_metrics(got: dict, counters: dict) -> None:
    """A traced line's scheduler metrics against the record's counters:
    the counters are every family's server's, not one cell's."""
    assert set(SCHED_METRICS) <= set(got)
    ahead = 100.0 * counters["decode_ahead_steps"] / counters["decode_steps"]
    assert abs(got["decode_ahead_share.tpot"]["value"] - ahead) < 1e-9
    assert 0 < got["sched_host_share.tpot"]["value"] < 100
    assert got["decode_launch_ms"]["value"] > 0
