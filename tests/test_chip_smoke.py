"""The bring-up rails (ISSUE 21): chip_smoke.py's legs at GPT_TINY on the
CPU mesh, and the places where a run could hide its device — importing
the package must not take the chip, chip_smoke.py must refuse the CPU,
and there is one peak-rate table that nothing in the environment can
override. (The benchmark's own refusals are in tests/benchmarking/.)
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)        # chip_smoke.py lives at the root

import chip_smoke       # noqa: E402

from deeplearning4j_tpu.monitor import memstats      # noqa: E402
from deeplearning4j_tpu.zoo.gpt import GPT_TINY      # noqa: E402

TRAIN_KW = dict(batch=4, seq_len=32, steps=2, epochs=3)
SERVE_KW = dict(max_slots=4, block_size=4, max_seq_len=64,
                prompt_lens=(3, 9, 20, 40, 24, 30), shared_prefix_len=16,
                max_new_tokens=8)


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        timeout=300, cwd=REPO,
        env=dict(os.environ, PYTHONPATH=REPO, **env))


@pytest.fixture(scope="module")
def tiny_run():
    sd, train = chip_smoke.train_leg(GPT_TINY, **TRAIN_KW)
    return sd, train, chip_smoke.serve_leg(sd, GPT_TINY, **SERVE_KW)


def test_smoke_legs_at_gpt_tiny(tiny_run):
    """The same train and serve functions ``__main__`` runs at
    GPT_MEDIUM, with the same checks (they raise SmokeFailure):
    falling finite losses from ln(vocab), exact token budgets, a
    prefix hit, zero compiles after warmup."""
    _, train, serve = tiny_run
    assert train["tier"] == "scanned_epoch"
    assert train["last_loss"] < train["first_loss"]
    # the tally of the traced step's attention sites: all plain here
    assert train["attention_sites"] == {
        "devices": 1, "kernel": 0, "plain": GPT_TINY.num_layers,
        "first_reason": "backend cpu"}
    assert serve["tokens_delivered"] == 6 * 8
    assert serve["compiles_after_warmup"] == 0
    assert serve["prefix_blocks_hit"] >= 4
    # exact on the CPU; on the chip it depends on the weights (PERF.md)
    assert serve["greedy_matches_reference"]
    assert serve["reference_divergence"] is None
    json.dumps({"train": train, "serve": serve})     # JSON-clean report


def test_four_chip_gate_passes_ties_and_fails_anything_else(
        tiny_run, monkeypatch):
    """Two layouts over the same weights may part only where the model's
    own logits are undecided: a twin that leaves for a token far from
    the top fails the run, however late; the sampled request (1) is
    not gated."""
    sd, _, s1 = tiny_run
    s2 = {"tokens": [list(t) for t in s1["tokens"]]}
    s2["tokens"][1] = [0] * 8                   # the sampled request
    out = chip_smoke.same_greedy_streams(sd, GPT_TINY, SERVE_KW, s1, s2,
                                         "t")
    assert [out[i]["equal"] for i in (0, 2, 3, 4, 5)] == [8] * 5
    assert all(v["parted"] is None for v in out.values())
    s2["tokens"][3][6] = 0                      # far from any tie
    with pytest.raises(chip_smoke.SmokeFailure, match="away from any tie"):
        chip_smoke.same_greedy_streams(sd, GPT_TINY, SERVE_KW, s1, s2, "t")
    # the same parting under a threshold it clears: reported, not fatal
    monkeypatch.setattr(chip_smoke, "TIE_GAP_STD", 100.0)
    out = chip_smoke.same_greedy_streams(sd, GPT_TINY, SERVE_KW, s1, s2,
                                         "t")
    parted = out[3]["parted"]
    assert out[3]["equal"] == parted["at"] == 6
    assert parted["tokens"] == [s1["tokens"][3][6], 0]
    assert 0.05 < parted["gap_over_std"] < 100.0


@pytest.mark.slow
def test_four_chip_placement_on_the_virtual_mesh():
    """--four-chip's body on four of the eight virtual CPU devices:
    KV slabs on two devices, the same greedy streams at tp=2 as at tp=1
    for untrained and trained weights (it raises otherwise), parameters
    on four devices and the one-device losses."""
    out = chip_smoke.four_chip(GPT_TINY, TRAIN_KW, SERVE_KW)
    four = out["four_chip"]
    assert four["serve_tp2"]["kv_slab_devices"] == 2
    assert four["serve_tp2_untrained"]["kv_slab_devices"] == 2
    assert four["train_2x2"]["attention_sites"]["devices"] == 4
    assert four["train_2x2"]["attention_sites"]["kernel"] == 0
    for key in ("tp2_vs_tp1", "tp2_vs_tp1_untrained"):
        assert [four[key][i]["equal"] for i in (0, 2, 3, 4, 5)] == [8] * 5
    json.dumps(out)


def test_the_last_stdout_line_is_the_verdict_and_nothing_else(capsys):
    """The driver parses the LAST line of stdout and accepts exactly
    ``{"ok", "device": {"platform", "kind", "count"}}``; the readings
    are the line before it (the first chip check refused a verdict that
    also carried them)."""
    stamp = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    chip_smoke.finish(stamp, GPT_TINY, {"train": {"wall_s": 1.0}})
    readings, last = capsys.readouterr().out.splitlines()
    assert last == json.dumps({"ok": True, "device": stamp})
    verdict = json.loads(last)
    assert sorted(verdict) == ["device", "ok"] and verdict["ok"] is True
    assert sorted(verdict["device"]) == ["count", "kind", "platform"]
    assert type(verdict["device"]["count"]) is int
    readings = json.loads(readings)
    assert readings["claim"] is None and readings["train"] == {"wall_s": 1.0}
    assert "ok" not in readings
    # and the stamp is JAX's own, in those words
    import jax
    assert chip_smoke.device_stamp() == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}


def test_chip_smoke_refuses_the_cpu():
    proc = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert "backend 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_import_and_analyze_cli_initialise_no_backend():
    """A chip belongs to one process: a parent that only imports the
    package or runs the analyzer CLI must not have taken it."""
    code = (
        "import sys, runpy\n"
        "import deeplearning4j_tpu\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, 'import initialised a backend'\n"
        "sys.argv = ['analyze', '--help']\n"
        "try:\n"
        "    runpy.run_module('deeplearning4j_tpu.analyze',"
        " run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert e.code == 0\n"
        "assert not xla_bridge._backends, 'analyze --help did'\n"
        "print('no backend')\n")
    proc = _run(["-c", code])
    assert proc.returncode == 0, proc.stderr[-800:]
    assert proc.stdout.strip().endswith("no backend")


def test_peak_rate_table_is_the_only_source(monkeypatch):
    assert memstats.peak_flops("TPU v5 lite") == 197e12
    assert memstats.peak_flops("TPU v5e") == 197e12
    assert memstats.peak_flops("NVIDIA H100") is None
    assert memstats.peak_flops() is None            # the test CPU
    monkeypatch.setenv("DL4J_PEAK_FLOPS", "1e15")   # the removed override
    assert memstats.peak_flops() is None
    assert memstats.peak_flops("TPU v5 lite") == 197e12
