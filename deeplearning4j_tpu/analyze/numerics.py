"""Numerics hazard passes.

Runs over the :class:`~deeplearning4j_tpu.analyze.graphpass.GraphFacts`
of a *policy walk*: when the TrainingConfig carries a MixedPrecision
policy, the abstract interpretation casts params/constants/placeholders
to the compute dtype exactly like the train step's trace does
(``SameDiff._build_step_parts``), so the dtypes inspected here are the
dtypes XLA will run — not the f32 the graph was declared in.

Three hazard families (tentpole pass 3):
- low-precision accumulation: a loss op whose scalar output is bf16/f16
  (the accumulation ate the training signal), or any large reduction
  accumulating in bf16/f16;
- non-finite-prone patterns: ``log``/``divide`` with no positivity /
  zero guard between the value and the op;
- policy hints: the f32 CE tail (bf16 compute with
  ``MixedPrecision.softmax_dtype`` unset).
"""
from __future__ import annotations

import math
from typing import List

import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.analyze.findings import Finding, finding
from deeplearning4j_tpu.analyze.graphpass import (GraphFacts, _LOWP,
                                                  provenance_chain)
from deeplearning4j_tpu.ops import registry

#: reduction ops whose accumulator follows the input dtype
_REDUCE_OPS = {"reduce_sum", "reduce_mean", "cumsum"}

#: minimum reduced-element count before a bf16 accumulator is flagged
#: (bf16 has an 8-bit mantissa: once the running sum is ~256x a term,
#: additions round to nothing — small reductions like pooling windows
#: are fine)
LOWP_REDUCTION_MIN_ELEMENTS = 4096

#: ops whose outputs are strictly positive — a log/div over them needs
#: no guard
_POSITIVE_OPS = {"exp", "sigmoid", "softplus"}

#: softmax-CE loss ops the ce_tail_f32 hint applies to
_SOFTMAX_CE_OPS = {"softmax_cross_entropy", "softmax_cross_entropy_loss",
                   "sparse_softmax_cross_entropy"}


def _const_array(sd, name: str):
    from deeplearning4j_tpu.autodiff.variable import VariableType
    v = sd._vars.get(name)
    if v is not None and v.var_type == VariableType.CONSTANT:
        return sd._arrays.get(name)
    return None


def _guarded(sd, name: str, positive: bool) -> bool:
    """Is variable ``name`` safe to log (positive=True: needs > 0) or
    divide by (positive=False: needs != 0)? Walks ONE producer hop —
    the idioms this recognizes are the repo's own guard patterns
    (``x.div(norm.add(eps))``, ``maximum(x, eps)``, clip attrs)."""
    const = _const_array(sd, name)
    if const is not None:
        a = np.asarray(const)
        if a.size == 0:
            return False
        return bool((a > 0).all() if positive else (a != 0).all())
    prod = sd._producer.get(name)
    if prod is None:
        return False                       # raw placeholder/param
    node = sd._ops[prod]
    if node.op in _POSITIVE_OPS:
        return True
    if node.op in ("maximum", "add"):
        # guarded when one side is a constant that enforces the bound
        # (add of a positive eps bounds away from zero only when the
        # other operand is nonnegative — accepted: it is THE idiom)
        for i in node.inputs:
            ca = _const_array(sd, i)
            if ca is not None and np.asarray(ca).size \
                    and (np.asarray(ca) > 0).all():
                return True
        return False
    if node.op in ("clip", "clip_by_value"):
        lo = node.attrs.get("min", node.attrs.get("clip_value_min"))
        try:
            return lo is not None and float(lo) > 0
        except (TypeError, ValueError):
            return False
    if node.op in ("softmax",) and not positive:
        # softmax rows are nonzero in exact math; denominator use is
        # the normalization idiom
        return True
    return False


def check_nonfinite_prone(sd, facts: GraphFacts) -> List[Finding]:
    out: List[Finding] = []
    for opn in facts.live_ops:
        node = sd._ops[opn]
        if node.op == "log" and node.inputs:
            x = node.inputs[0]
            if not _guarded(sd, x, positive=True):
                out.append(finding(
                    "numerics.unguarded_log", opn,
                    f"op {opn!r} takes log({x}) with no positivity "
                    f"guard between them",
                    fix_hint="log(maximum(x, eps)) or clip first — a "
                             "single 0 poisons the loss with -inf",
                    provenance=provenance_chain(sd, [x], facts.env)))
        elif node.op == "divide" and len(node.inputs) >= 2:
            den = node.inputs[1]
            if not _guarded(sd, den, positive=False):
                out.append(finding(
                    "numerics.unguarded_div", opn,
                    f"op {opn!r} divides by {den!r} with no zero "
                    f"guard",
                    fix_hint="divide by (x + eps) or maximum(x, eps)",
                    provenance=provenance_chain(sd, [den], facts.env)))
    return out


def check_lowp_accumulation(sd, facts: GraphFacts) -> List[Finding]:
    """bf16/f16 accumulations: loss ops whose scalar output stayed
    low-precision under the policy walk, and large reductions whose
    input AND output are low-precision (the accumulator follows)."""
    out: List[Finding] = []
    for opn in facts.live_ops:
        node = sd._ops[opn]
        try:
            o = registry.get_op(node.op)
        except KeyError:
            continue
        out_av = facts.env.get(node.outputs[0]) if node.outputs else None
        if out_av is None:
            continue
        if o.category == "loss":
            if out_av.ndim == 0 and out_av.dtype in _LOWP:
                out.append(finding(
                    "numerics.lowp_loss_accum", opn,
                    f"loss op {opn!r} ({node.op}) reduces to a "
                    f"{out_av.dtype} scalar under the compute-dtype "
                    f"policy — the per-example sum loses the training "
                    f"signal past ~256 terms",
                    fix_hint="reduce with an f32 accumulator "
                             "(jnp.sum(..., dtype=jnp.float32)); the "
                             "built-in loss ops already do"))
            continue
        if node.op in _REDUCE_OPS:
            in_av = facts.env.get(node.inputs[0]) if node.inputs else None
            if in_av is None or in_av.dtype not in _LOWP \
                    or out_av.dtype not in _LOWP:
                continue
            reduced = (math.prod(in_av.shape)
                       // max(1, math.prod(out_av.shape)))
            if reduced >= LOWP_REDUCTION_MIN_ELEMENTS:
                out.append(finding(
                    "numerics.lowp_reduction", opn,
                    f"op {opn!r} ({node.op}) reduces {reduced} "
                    f"elements in {in_av.dtype} — the accumulator "
                    f"rounds away the tail of the sum",
                    fix_hint="pass dtype=jnp.float32 to the reduction "
                             "(XLA still reads bf16 inputs at full "
                             "rate)",
                    provenance=provenance_chain(
                        sd, node.inputs[:1], facts.env)))
    return out


def check_ce_tail_policy(sd, facts: GraphFacts, mp) -> List[Finding]:
    """The f32 CE tail as a hint: bf16 compute, a softmax-CE
    loss in the live graph, and no softmax_dtype policy — the
    [batch..., vocab] f32 tail is the step's largest tensor."""
    if mp is None or getattr(mp, "softmax_dtype", None) is not None:
        return []
    cdt = str(getattr(mp, "compute_dtype", "")).lower()
    if cdt not in ("bfloat16", "bf16", "float16", "f16", "half"):
        return []
    out: List[Finding] = []
    for opn in facts.live_ops:
        node = sd._ops[opn]
        if node.op in _SOFTMAX_CE_OPS:
            in_av = facts.env.get(node.inputs[0]) if node.inputs else None
            vocab = in_av.shape[-1] if in_av is not None and in_av.ndim \
                else "?"
            out.append(finding(
                "numerics.ce_tail_f32", opn,
                f"loss op {opn!r} ({node.op}) runs its log-softmax "
                f"tail in f32 under bf16 compute (vocab {vocab}) — "
                f"the largest f32 tensor in the step",
                fix_hint="MixedPrecision(softmax_dtype='bfloat16') "
                         "keeps the tail bf16; the scalar loss still "
                         "accumulates f32 "
                         "(docs/training_performance.md)"))
    return out


__all__ = ["check_nonfinite_prone", "check_lowp_accumulation",
           "check_ce_tail_policy", "LOWP_REDUCTION_MIN_ELEMENTS"]
