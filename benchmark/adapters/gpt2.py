"""The GPT-2 family under test: a configuration file becomes the
program's own ``GPTConfig``; seeded weights (``benchmark.reference.gpt2``,
GPT-2's own names and ``[Q|K|V]`` layout) are laid out as ``zoo/gpt.py``
wants them; a ``PagedGenerativeServer`` or a ``SameDiff`` graph is stood
up the way a user would. This is the only module of the benchmark that
imports the program under test.

An adapter gives the drivers :func:`build_server` with
:func:`server_counters` and :func:`check_served`, and
:func:`build_trainer` with :func:`reference_training`.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import gpt2 as ref

#: reference kind -> program leaf (``h{i}/`` is prefixed for layer kinds)
_NAMES = {
    "wte": "wte", "wpe": "wpe", "ln_f.g": "ln_f/gamma",
    "ln_f.b": "ln_f/beta",
    "ln_1.g": "ln_1/gamma", "ln_1.b": "ln_1/beta",
    "attn.c_attn.w": "attn/qkv/kernel", "attn.c_attn.b": "attn/qkv/bias",
    "attn.c_proj.w": "attn/proj/kernel", "attn.c_proj.b": "attn/proj/bias",
    "ln_2.g": "ln_2/gamma", "ln_2.b": "ln_2/beta",
    "mlp.c_fc.w": "mlp/fc/kernel", "mlp.c_fc.b": "mlp/fc/bias",
    "mlp.c_proj.w": "mlp/proj/kernel", "mlp.c_proj.b": "mlp/proj/bias",
}


def program_config(cfg: dict):
    """The file's sizes as the program's ``GPTConfig``."""
    from deeplearning4j_tpu.zoo.gpt import GPTConfig
    V, P, H, L, A, I = ref.sizes(cfg)
    if cfg.get("activation_function", "gelu_new") != "gelu_new":
        raise ValueError("zoo/gpt.py computes gelu_new only")
    return GPTConfig(vocab_size=V, hidden_size=H, num_layers=L,
                     num_heads=A, intermediate_size=I, max_seq_len=P,
                     initializer_range=float(
                         cfg.get("initializer_range", 0.02)),
                     layer_norm_eps=float(
                         cfg.get("layer_norm_epsilon", 1e-5)),
                     remat=True, tie_embeddings=True)


def _to_program_layout(x, kind: str, A: int):
    """``zoo/gpt.py`` keeps the fused projection's columns per head,
    ``[q_a | k_a | v_a]``; GPT-2 keeps ``[Q | K | V]``."""
    if kind == "attn.c_attn.w":
        L, H, _ = x.shape
        return x.reshape(L, H, 3, A, H // A).transpose(0, 1, 3, 2, 4) \
            .reshape(L, H, 3 * H)
    if kind == "attn.c_attn.b":
        L, H3 = x.shape
        H = H3 // 3
        return x.reshape(L, 3, A, H // A).transpose(0, 2, 1, 3) \
            .reshape(L, H3)
    return x


@functools.partial(jax.jit, static_argnames=("cfg", "kind"))
def _layer_leaves(key, cfg, kind):
    x = _to_program_layout(ref._draw(key, cfg, kind), kind,
                           int(cfg["n_head"]))
    return tuple(x[i] for i in range(x.shape[0]))


def program_params(cfg: dict, seed: int) -> dict:
    """The seeded weights under the program's names, one array a leaf,
    drawn on the device a kind at a time."""
    fz, key = ref._frozen(cfg), ref.seed_key(seed)
    out = {}
    for kind in ref.ALL_KINDS:
        if kind in ref.TOP_KINDS:
            out[_NAMES[kind]] = ref.init_kind(cfg, seed, kind)
            continue
        for i, leaf in enumerate(_layer_leaves(key, fz, kind)):
            out[f"h{i}/{_NAMES[kind]}"] = leaf
    return out


# ----------------------------------------------------------------------
# serving
def build_server(cfg: dict, server: dict, seed: int):
    """``PagedGenerativeServer`` over the seeded weights: the programs,
    pool and scheduler of ``gpt_paged_spec``, with the weights handed over
    on the device instead of pulled from a training graph. Warms the
    cell's own buckets only."""
    from deeplearning4j_tpu.serving.paged import (PagedGenerativeServer,
                                                  PagedGenerativeSpec)
    from deeplearning4j_tpu.zoo.gpt import (gpt_paged_decode_fns,
                                            gpt_param_names)
    pc = program_config(cfg)
    if cfg["param_dtype"] != "float32":
        raise ValueError("this adapter serves float32 parameters only")
    params = program_params(cfg, seed)
    missing = set(gpt_param_names(pc)) ^ set(params)
    if missing:
        raise ValueError(f"parameter names differ: {sorted(missing)[:4]}")
    spec = PagedGenerativeSpec(
        params=lambda: params,
        make_fns=lambda bs, maxb: gpt_paged_decode_fns(pc, bs, maxb),
        kv_shape=lambda nb, bs: (pc.num_layers, int(nb), pc.num_heads,
                                 int(bs), pc.head_size),
        vocab_size=pc.vocab_size, max_seq_len=pc.max_seq_len,
        num_heads=pc.num_heads, kv_dtype=str(cfg["kv_dtype"]))
    return PagedGenerativeServer(
        spec, max_slots=int(server["max_slots"]),
        block_size=int(server["block_size"]),
        max_seq_len=int(server["max_seq_len"]),
        buckets=[int(b) for b in server["buckets"]], warmup=True)


def server_counters(srv) -> dict:
    """The program's own counters, as plain numbers (exact sums, not its
    histograms' buckets)."""
    m = srv.metrics
    with m._lock:
        c = dict(m.counters)
        c["prefill_ms_sum"] = float(m.prefill_ms.total_ms)
        c["decode_ms_sum"] = float(m.exec_ms.total_ms)
    c["num_blocks"] = int(m.num_blocks)
    return c


def check_served(cfg: dict, seed: int, rows, pad_to: int,
                 control: str | None = None):
    """Widest and mean gap of the served tokens under the reference
    (see ``reference.gpt2.served_gaps``), with the weights drawn anew
    from the seed."""
    params = ref.init_params(cfg, seed)
    gaps = ref.served_gaps(params, cfg, rows, pad_to, control=control)
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"tokens": int(flat.size),
            "widest_gap": float(flat.max()) if flat.size else None,
            "mean_gap": float(flat.mean()) if flat.size else None,
            "parted": int((flat > 0).sum())}


# ----------------------------------------------------------------------
# training
class Trainer:
    """``build_gpt`` + ``TrainingConfig`` + a ``DeviceCachedIterator``,
    as ``chip_smoke.py`` trains: one object that set-up drives through
    its first ``fit`` and the window goes on calling."""

    def __init__(self, cfg: dict, job: dict, seed: int, ids, targets):
        from deeplearning4j_tpu.autodiff import (MixedPrecision,
                                                 TrainingConfig)
        from deeplearning4j_tpu.dataset import DeviceCachedIterator
        from deeplearning4j_tpu.learning.updaters import Adam
        from deeplearning4j_tpu.zoo.gpt import build_gpt
        self.cfg, self.job, self.seed = cfg, job, int(seed)
        pc = program_config(cfg)
        opt = job["optimizer"]
        if opt["name"] != "adam" or job["compute_dtype"] != "bfloat16":
            raise ValueError("this adapter trains with Adam under bf16 "
                             "mixed precision only")
        B, S = int(job["batch"]), int(job["seq_len"])
        t = time.monotonic()
        self.sd = build_gpt(pc, batch=B, seq_len=S,
                            seed=self.seed & 0x7FFFFFFF)
        self.build_graph_s = time.monotonic() - t
        for name, arr in program_params(cfg, seed).items():
            self.sd.set_arr_for_var(name, arr)
        self.sd.training_config = TrainingConfig(
            updater=Adam(float(opt["learning_rate"]),
                         beta1=float(opt["beta1"]),
                         beta2=float(opt["beta2"]),
                         epsilon=float(opt["epsilon"])),
            data_set_feature_mapping=["input_ids"],
            data_set_label_mapping=["targets"],
            mixed_precision=MixedPrecision())
        self.it = DeviceCachedIterator([ids], [targets], batch_size=B)
        self.steps_per_fit = ids.shape[0] // B
        self.tokens_per_fit = self.steps_per_fit * B * S
        self.losses = []

    def fit(self):
        """One ``SameDiff.fit`` over the cached batches; returns when the
        device has finished it."""
        hist = self.sd.fit(self.it, epochs=1)
        jax.block_until_ready(list(self.sd.trainable_params().values()))
        self.losses.extend(float(l) for l in hist.loss_curve.losses)

    def readings(self) -> dict:
        """What the program holds now, as norms per leaf: Adam's first
        moment and the parameters' change from the seeded start. Keyed
        ``(kind, layer)`` like the reference's."""
        A = int(self.cfg["n_head"])
        start = program_params(self.cfg, self.seed)
        now = self.sd.trainable_params()
        state = self.sd._updater_state
        if state is None:
            raise RuntimeError("fit left no updater state")
        names = sorted(now)
        m_n, d_n = _norms([state[n][0] for n in names],
                          [now[n] for n in names],
                          [start[n] for n in names])
        del start
        inv = {v: k for k, v in _NAMES.items()}
        out = {"moment_norms": {}, "change_norms": {}}
        for n, mn, dn in zip(names, np.asarray(m_n), np.asarray(d_n)):
            if n.startswith("h") and "/" in n and n[1].isdigit():
                layer, rest = n.split("/", 1)
                key = (inv[rest], int(layer[1:]))
            else:
                key = (inv[n], None)
            out["moment_norms"][key] = float(mn)
            out["change_norms"][key] = float(dn)
        return out

    def close(self):
        self.sd = self.it = None
        gc.collect()


@jax.jit
def _norms(moments, now, start):
    n2 = lambda x: jnp.sqrt(jnp.sum(jnp.square(          # noqa: E731
        x.astype(jnp.float32))))
    return (jnp.stack([n2(m) for m in moments]),
            jnp.stack([n2(a - b) for a, b in zip(now, start)]))


def build_trainer(cfg: dict, job: dict, seed: int, ids, targets) -> Trainer:
    return Trainer(cfg, job, seed, ids, targets)


def reference_training(cfg: dict, job: dict, seed: int, batches,
                       mode: str = "float32") -> dict:
    """The plain reference over the same batches, keyed like
    :meth:`Trainer.readings`."""
    out = ref.train_steps(cfg, seed, batches, job["optimizer"],
                          rows_per_block=int(job.get("reference_rows", 2)),
                          mode=mode)
    flat = {"losses": out["losses"]}
    for what in ("moment_norms", "change_norms"):
        flat[what] = {}
        for kind, v in out[what].items():
            v = np.asarray(v)
            if v.ndim == 0:
                flat[what][(kind, None)] = float(v)
            else:
                for i, x in enumerate(v):
                    flat[what][(kind, i)] = float(x)
    return flat
