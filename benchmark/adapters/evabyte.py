"""The EvaByte family (``evabyte``) under test: a configuration file
becomes the program's own ``EvaByteConfig``; the seeded weights of
``benchmark.reference.evabyte`` (drawn again, leaf by leaf, so that
program and reference hold the same bfloat16 values) go under the
program's names; a ``PagedGenerativeServer`` is stood up the way a user
would. This is the only module of the configuration that imports the
program under test, and it imports it where it is named: no other cell
pays for it.

The family is served only: :func:`build_server`, :func:`server_counters`
and :func:`check_served`, as ``adapters/gpt2.py`` gives them.
"""
from __future__ import annotations

import numpy as np

# the same server class, so the same counters
from benchmark.adapters.gpt2 import server_counters  # noqa: F401
from benchmark.reference import evabyte as ref

#: reference kind -> program leaf (``h{i}/`` is prefixed for layer kinds)
_NAMES = {"embed": "embed", "norm_f": "norm_f", "head": "lm_head",
          "norm_1": "norm_1", "q": "attn/q", "k": "attn/k", "v": "attn/v",
          "o": "attn/o", "phi": "attn/phi", "mu": "attn/mu",
          "norm_2": "norm_2", "gate": "mlp/gate", "up": "mlp/up",
          "down": "mlp/down"}


def program_config(cfg: dict):
    from deeplearning4j_tpu.zoo.evabyte import EvaByteConfig
    return EvaByteConfig.from_dict(cfg)


def program_params(cfg: dict, seed: int) -> dict:
    """The seeded weights under the program's names, made on the
    device."""
    out = {_NAMES[k]: ref.draw(cfg, seed, k) for k in ref.TOP_KINDS}
    for i in range(int(cfg["num_hidden_layers"])):
        for k in ref.LAYER_KINDS:
            out[f"h{i}/{_NAMES[k]}"] = ref.draw(cfg, seed, k, i + 1)
    return out


def build_server(cfg: dict, server: dict, seed: int):
    """``PagedGenerativeServer`` over the seeded weights: the scheduler,
    pool, ladder, chunked prefill and the decode loop ahead of its sync
    that serve the other families, with ``evabyte_paged_spec``'s programs
    and two stores a layer on two tiers. Warms the cell's own buckets
    only."""
    from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
    from deeplearning4j_tpu.zoo.evabyte import evabyte_paged_spec
    if cfg["param_dtype"] != "bfloat16" or cfg["kv_dtype"] != "bfloat16":
        raise ValueError("the seeded parameters are bfloat16, and the "
                         "program caches its rows in their dtype")
    spec = evabyte_paged_spec(program_config(cfg),
                              program_params(cfg, seed))
    return PagedGenerativeServer(
        spec, max_slots=int(server["max_slots"]),
        block_size=int(server["block_size"]),
        max_seq_len=int(server["max_seq_len"]),
        buckets=[int(b) for b in server["buckets"]], warmup=True)


def check_served(cfg: dict, seed: int, rows, pad_to: int,
                 control: str | None = None):
    """Widest and mean gap of the served tokens under the reference
    (see ``reference.evabyte.served_gaps``), with the weights drawn anew
    from the seed. There is no router: every sampled position is
    judged."""
    gaps = ref.served_gaps(cfg, seed, rows, pad_to, control=control)
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"tokens": int(flat.size),
            "widest_gap": float(flat.max()) if flat.size else None,
            "mean_gap": float(flat.mean()) if flat.size else None,
            "parted": int((flat > 0).sum())}
