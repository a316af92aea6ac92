"""Training UI stats pipeline: StatsListener -> StatsStorage -> HTML.

Reference parity: BaseStatsListener.java:58 collection families (score,
performance, histograms, update ratios, memory) and FileStatsStorage
persistence; the dashboard is a static HTML artifact instead of the
Vertx server (VertxUIServer.java:78).
"""
import json
import os

import numpy as np

from deeplearning4j_tpu.ui import (StatsListener, StatsStorage,
                                   render_report, write_report)


def _train_with_listener(tmp_path, epochs=4):
    from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
    from deeplearning4j_tpu.learning.updaters import Adam

    rng = np.random.default_rng(0)
    sd = SameDiff()
    x = sd.placeholder("x", shape=(-1, 8))
    w = sd.var("w", value=rng.standard_normal((8, 4)).astype(np.float32))
    b = sd.var("b", value=np.zeros(4, np.float32))
    y = x.mmul(w).add(b, name="pred")
    t = sd.placeholder("t", shape=(-1, 4))
    loss = sd.invoke("mean_sqerr_loss", [y, t], name="loss")
    sd.set_loss_variables([loss])
    sd.training_config = TrainingConfig(
        updater=Adam(1e-2), data_set_feature_mapping=["x"],
        data_set_label_mapping=["t"])
    X = rng.standard_normal((64, 8)).astype(np.float32)
    W0 = rng.standard_normal((8, 4)).astype(np.float32)
    Y = X @ W0
    st = StatsStorage(str(tmp_path / "stats.jsonl"))
    lst = StatsListener(st, frequency=2)
    batches = [([X[i:i + 16]], [Y[i:i + 16]]) for i in range(0, 64, 16)]
    sd.fit(batches, epochs=epochs, listeners=[lst])
    st.close()
    return sd, st


class TestStatsPipeline:
    def test_collects_all_families(self, tmp_path):
        _, st = _train_with_listener(tmp_path)
        types = {r["type"] for r in st.records}
        assert {"meta", "score", "perf", "params", "end"} <= types
        scores = st.of_type("score")
        assert len(scores) == 16                    # 4 epochs x 4 batches
        assert scores[0]["loss"] > scores[-1]["loss"]

    def test_param_stats_and_update_ratio(self, tmp_path):
        _, st = _train_with_listener(tmp_path)
        params = st.of_type("params")
        assert len(params) == 4
        last = params[-1]["params"]
        assert set(last) == {"w", "b"}
        ent = last["w"]
        assert len(ent["hist"]) == 16
        assert ent["norm"] > 0
        # epochs after the first have update stats
        assert "update_ratio" in ent and ent["update_ratio"] > 0

    def test_jsonl_persistence_roundtrip(self, tmp_path):
        _, st = _train_with_listener(tmp_path)
        loaded = StatsStorage.load(str(tmp_path / "stats.jsonl"))
        assert len(loaded.records) == len(st.records)
        assert loaded.of_type("score")[0]["loss"] == \
            st.of_type("score")[0]["loss"]

    def test_html_report_artifact(self, tmp_path):
        _, st = _train_with_listener(tmp_path)
        out = write_report(st, str(tmp_path / "report.html"),
                           title="mlp run")
        html = open(out, encoding="utf-8").read()
        assert html.startswith("<!doctype html>")
        assert "score vs iteration" in html
        assert "Update : parameter ratios" in html
        assert html.count("<svg") >= 4     # score, perf, ratios, hists
        assert "mlp run" in html
        # every param appears in the stats table
        assert ">w<" in html and ">b<" in html

    def test_report_on_empty_storage(self):
        html = render_report(StatsStorage())
        assert "no data" in html

    def test_concurrent_writers_do_not_tear(self, tmp_path):
        """ISSUE-5 satellite: the async checkpoint writer, serving
        workers and the window stager publish concurrently — records
        must not drop and JSONL lines must not interleave."""
        import threading
        path = str(tmp_path / "concurrent.jsonl")
        st = StatsStorage(path)
        n_threads, n_puts = 8, 250

        def writer(tid):
            for i in range(n_puts):
                st.put({"type": "x", "writer": tid, "i": i,
                        "pad": "p" * 50})

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st.close()
        assert len(st.records) == n_threads * n_puts
        lines = [l for l in open(path, encoding="utf-8") if l.strip()]
        assert len(lines) == n_threads * n_puts
        seen = set()
        for line in lines:
            rec = json.loads(line)          # a torn line would not parse
            assert rec["pad"] == "p" * 50
            seen.add((rec["writer"], rec["i"]))
        assert len(seen) == n_threads * n_puts   # no record lost

    def test_load_keeps_persisting(self, tmp_path):
        """ISSUE-5 satellite: a loaded storage must keep appending to
        its source file — load() used to drop the path, silently
        turning persistence off after a restart."""
        path = str(tmp_path / "s.jsonl")
        st = StatsStorage(path)
        st.put({"type": "score", "iter": 0, "loss": 1.0})
        st.close()
        loaded = StatsStorage.load(path)
        assert loaded.path == path
        loaded.put({"type": "score", "iter": 1, "loss": 0.5})
        loaded.close()
        again = StatsStorage.load(path, persist=False)
        assert again.path is None           # explicit read-only opt-out
        assert [r["iter"] for r in again.of_type("score")] == [0, 1]


class TestZooModelReport:
    def test_lenet_training_produces_browsable_report(self, tmp_path):
        """The UI's 'done' criterion: training a zoo model
        produces a browsable report with PerformanceListener-style
        numbers in it."""
        from deeplearning4j_tpu.dataset import load_mnist
        from deeplearning4j_tpu.zoo import LeNet

        X, y = load_mnist(train=True, n_synthetic=128)
        Y = np.eye(10, dtype=np.float32)[y]
        net = LeNet(height=28, width=28, channels=1).build()
        st = StatsStorage(str(tmp_path / "lenet.jsonl"))
        lst = StatsListener(st, frequency=1)
        batches = [([X[i:i + 32]], [Y[i:i + 32]])
                   for i in range(0, 128, 32)]
        net.fit(batches, epochs=2, listeners=[lst])
        st.close()
        out = write_report(st, str(tmp_path / "lenet.html"))
        html = open(out, encoding="utf-8").read()
        assert "throughput" in html
        perf = st.of_type("perf")
        assert perf and perf[-1]["batches_per_sec"] > 0


class TestEpochStatsSingleTransfer:
    """Satellite (ISSUE 8): StatsListener.on_epoch_end computes its
    histograms/moments in float32 with ONE device→host copy per param
    — no float64 upcast doubling the epoch-boundary stall and peak
    host memory. The record schema is unchanged."""

    class _FakeSD:
        def __init__(self, params):
            self._params = params

        def trainable_params(self):
            return self._params

    def test_no_float64_upcast(self, monkeypatch):
        import jax.numpy as jnp

        seen_dtypes = []
        orig_hist = np.histogram

        def spy_hist(a, *args, **kw):
            seen_dtypes.append(np.asarray(a).dtype)
            return orig_hist(a, *args, **kw)

        monkeypatch.setattr(np, "histogram", spy_hist)
        st = StatsStorage()
        lst = StatsListener(st)
        sd = self._FakeSD({"w": jnp.arange(12, dtype=jnp.float32)})
        lst.on_epoch_end(sd, 0, 0.5)
        lst.on_epoch_end(sd, 1, 0.4)
        assert seen_dtypes and all(d == np.float32 for d in seen_dtypes)
        rec = st.of_type("params")[-1]["params"]["w"]
        # schema unchanged: plain floats + histogram + update stats
        assert isinstance(rec["mean"], float) and isinstance(
            rec["norm"], float)
        assert rec["update_norm"] == 0.0
        json.dumps(rec)

    def test_bfloat16_params_histogram(self):
        import jax.numpy as jnp

        st = StatsStorage()
        lst = StatsListener(st)
        sd = self._FakeSD(
            {"w": jnp.linspace(-1, 1, 64).astype(jnp.bfloat16)})
        lst.on_epoch_end(sd, 0, 0.1)
        ent = st.of_type("params")[-1]["params"]["w"]
        assert sum(ent["hist"]) == 64
        assert np.isfinite(ent["mean"])
