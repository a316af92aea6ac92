"""The LFM2-8B-A1B family (``lfm2_moe``) under test: a configuration file
becomes the program's own ``Lfm2MoeConfig``; the seeded weights of
``benchmark.reference.lfm2_moe`` (drawn again, leaf by leaf, so that
program and reference hold the same bfloat16 values) go under the
program's names; a ``PagedGenerativeServer`` is stood up the way a user
would. This is the only module of the configuration that imports the
program under test.

The family is served only: :func:`build_server`, :func:`server_counters`
and :func:`check_served`, as ``adapters/gpt2.py`` gives them.
"""
from __future__ import annotations

import numpy as np

# the same server class, so the same counters
from benchmark.adapters.gpt2 import server_counters  # noqa: F401
from benchmark.reference import lfm2_moe as ref

#: reference kind -> program leaf (``h{i}/`` is prefixed for layer kinds)
_NAMES = {"embed": "embed", "norm_f": "norm_f", "norm_op": "norm_op",
          "norm_ffn": "norm_ffn", "conv_in": "conv/in", "conv_w": "conv/w",
          "conv_out": "conv/out", "q": "attn/q", "k": "attn/k",
          "v": "attn/v", "o": "attn/o", "q_norm": "attn/q_norm",
          "k_norm": "attn/k_norm", "mlp_gate": "mlp/gate",
          "mlp_up": "mlp/up", "mlp_down": "mlp/down", "router": "router",
          "router_bias": "router_bias", "gate": "experts/gate",
          "up": "experts/up", "down": "experts/down"}

#: the least margins by which :func:`check_served` also reports the
#: widest gap of the positions kept (read, never judged: the readings
#: ``reference.CLEAR_MARGIN`` is set from)
MARGINS = (0.0, 0.001, 0.002, 0.004, 0.008, 0.016)


def program_config(cfg: dict):
    from deeplearning4j_tpu.zoo.lfm2_moe import Lfm2MoeConfig
    return Lfm2MoeConfig.from_dict(cfg)


def program_params(cfg: dict, seed: int) -> dict:
    """The seeded weights under the program's names, made on the
    device."""
    out = {_NAMES[k]: ref.draw(cfg, seed, k) for k in ref.TOP_KINDS}
    for i in range(int(cfg["num_hidden_layers"])):
        for k in ref.layer_kinds(cfg, i):
            out[f"h{i}/{_NAMES[k]}"] = ref.draw(cfg, seed, k, i + 1)
    return out


def build_server(cfg: dict, server: dict, seed: int):
    """``PagedGenerativeServer`` over the seeded weights: the scheduler,
    pool, per-lane state store, ladder, chunked prefill and dispatch that
    serve the other families, with ``lfm2_moe_paged_spec``'s programs.
    Warms the cell's own buckets only."""
    from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
    from deeplearning4j_tpu.zoo.lfm2_moe import lfm2_moe_paged_spec
    if cfg["param_dtype"] != "bfloat16" or cfg["kv_dtype"] != "bfloat16":
        raise ValueError("the seeded parameters are bfloat16, and the "
                         "program caches K, V and the state in their dtype")
    spec = lfm2_moe_paged_spec(program_config(cfg), program_params(cfg, seed))
    return PagedGenerativeServer(
        spec, max_slots=int(server["max_slots"]),
        block_size=int(server["block_size"]),
        max_seq_len=int(server["max_seq_len"]),
        buckets=[int(b) for b in server["buckets"]], warmup=True)


def check_served(cfg: dict, seed: int, rows, pad_to: int,
                 control: str | None = None):
    """Widest and mean gap of the served tokens under the reference
    (``reference.lfm2_moe.served_gaps``), with the weights drawn anew from
    the seed, over the positions where every router of the reference chose
    by a clear margin (``ref.CLEAR_MARGIN``): ``tokens`` of them,
    ``excused`` the others, whose widest gap is ``widest_excused``
    (reported, never judged); ``by_margin``, for each of :data:`MARGINS`,
    the positions kept at that least margin and their widest gap."""
    gaps, least = ref.served_gaps(cfg, seed, rows, pad_to, control=control)
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    margin = np.concatenate(least) if least else np.zeros(0)
    clear = margin >= ref.CLEAR_MARGIN
    kept, rest = flat[clear], flat[~clear]
    return {"tokens": int(kept.size), "excused": int(rest.size),
            "widest_gap": float(kept.max()) if kept.size else None,
            "mean_gap": float(kept.mean()) if kept.size else None,
            "parted": int((kept > 0).sum()),
            "widest_excused": float(rest.max()) if rest.size else None,
            "by_margin": {str(m): [int((margin >= m).sum()), float(
                flat[margin >= m].max(initial=0.0))] for m in MARGINS}}
