"""The Command A+ family (``cohere2_moe``) under test: a configuration
file becomes the program's own ``Cohere2MoeConfig``; the seeded weights
of ``benchmark.reference.cohere2_moe`` (drawn again, leaf by leaf, so
that program and reference hold the same bfloat16 values) go under the
program's names, the four shared experts side by side as the one product
the program runs; a ``PagedGenerativeServer`` is stood up the way a user
would. This is the only module of the configuration that imports the
program under test.

The family is served only: :func:`build_server`, :func:`server_counters`
and :func:`check_served`, as ``adapters/gpt2.py`` gives them.
"""
from __future__ import annotations

import numpy as np

# the same server class, so the same counters
from benchmark.adapters.gpt2 import server_counters  # noqa: F401
from benchmark.reference import cohere2_moe as ref

#: reference kind -> program leaf (``h{i}/`` is prefixed for layer kinds)
_NAMES = {"embed": "embed", "norm_f": "norm_f", "norm": "norm",
          "q": "attn/q", "k": "attn/k", "v": "attn/v", "o": "attn/o",
          "router": "router", "gate": "experts/gate", "up": "experts/up",
          "down": "experts/down", "shared_gate": "shared/gate",
          "shared_up": "shared/up", "shared_down": "shared/down"}


def program_config(cfg: dict):
    from deeplearning4j_tpu.zoo.cohere2_moe import Cohere2MoeConfig
    return Cohere2MoeConfig.from_dict(cfg)


def _side_by_side(x, kind: str):
    """The reference's shared experts ``[S, in, out]`` as one product:
    ``[H, S * F]`` for gate and up, ``[S * F, H]`` for down."""
    S, a, b = x.shape
    if kind == "shared_down":
        return x.reshape(S * a, b)
    return x.transpose(1, 0, 2).reshape(a, S * b)


def program_params(cfg: dict, seed: int) -> dict:
    """The seeded weights under the program's names, made on the
    device."""
    out = {_NAMES[k]: ref.draw(cfg, seed, k) for k in ref.TOP_KINDS}
    for i in range(int(cfg["num_hidden_layers"])):
        for k in ref.LAYER_KINDS:
            x = ref.draw(cfg, seed, k, i + 1)
            out[f"h{i}/{_NAMES[k]}"] = _side_by_side(x, k) \
                if k in ref.SHARED_KINDS else x
    return out


def build_server(cfg: dict, server: dict, seed: int):
    """``PagedGenerativeServer`` over the seeded weights: the scheduler,
    two-tier pool, ladder, chunked prefill and dispatch that serve the
    other families, with ``cohere2_moe_paged_spec``'s programs. Warms the
    cell's own buckets only."""
    from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
    from deeplearning4j_tpu.zoo.cohere2_moe import cohere2_moe_paged_spec
    if cfg["param_dtype"] != "bfloat16" or cfg["kv_dtype"] != "bfloat16":
        raise ValueError("the seeded parameters are bfloat16, and the "
                         "program caches K and V in their dtype")
    spec = cohere2_moe_paged_spec(program_config(cfg),
                                  program_params(cfg, seed))
    return PagedGenerativeServer(
        spec, max_slots=int(server["max_slots"]),
        block_size=int(server["block_size"]),
        max_seq_len=int(server["max_seq_len"]),
        buckets=[int(b) for b in server["buckets"]], warmup=True)


def check_served(cfg: dict, seed: int, rows, pad_to: int,
                 control: str | None = None):
    """Widest and mean gap of the served tokens under the reference
    (``reference.cohere2_moe.served_gaps``), with the weights drawn anew
    from the seed, over the positions where no router of the reference
    stood at a near-tie of a held expert (``ref.CLEAR_MARGIN``):
    ``tokens`` of them, ``excused`` the others, whose widest gap is
    ``widest_excused`` (reported, never judged)."""
    gaps, least = ref.served_gaps(cfg, seed, rows, pad_to, control=control)
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    clear = (np.concatenate(least) if least else np.zeros(0)) \
        >= ref.CLEAR_MARGIN
    kept, rest = flat[clear], flat[~clear]
    return {"tokens": int(kept.size), "excused": int(rest.size),
            "widest_gap": float(kept.max()) if kept.size else None,
            "mean_gap": float(kept.mean()) if kept.size else None,
            "parted": int((kept > 0).sum()),
            "widest_excused": float(rest.max()) if rest.size else None}
