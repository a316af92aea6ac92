"""Findings, the rule catalog, and the analysis report.

Reference parity: the role of DL4J's ``OpValidation`` / SameDiff
shape-inference checks (L3 of the PAPER.md layer map) — user errors
surface as *named graph diagnostics* before anything native runs. Here
"native" is XLA: a wrong shape, dtype hazard or bad config otherwise
dies inside jit with a traceback that names none of the user's
variables. Every check the analyzer runs is a :class:`Rule` in
:data:`RULES`; every hit is a :class:`Finding` carrying the rule id,
severity, the offending variable/op and its producer chain, and a fix
hint. ``docs/static_analysis.md`` is the human-readable catalog
(tests/test_analyze.py asserts the two stay in sync, and that every
rule has a seeded-defect test the analyzer catches).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Sequence, Tuple

#: severity levels, most severe first. "error" findings make
#: ``strict`` mode raise :class:`GraphAnalysisError` BEFORE any XLA
#: compile; "warn" is a real hazard that may still be intended; "info"
#: is hygiene / a perf hint.
SEVERITIES = ("error", "warn", "info")


@dataclasses.dataclass(frozen=True)
class Rule:
    rule_id: str
    severity: str
    summary: str


def _catalog(*rules: Rule) -> Dict[str, Rule]:
    out: Dict[str, Rule] = {}
    for r in rules:
        if r.severity not in SEVERITIES:
            raise ValueError(f"{r.rule_id}: bad severity {r.severity!r}")
        if r.rule_id in out:
            raise ValueError(f"duplicate rule id {r.rule_id}")
        out[r.rule_id] = r
    return out


#: The rule catalog. Adding a rule here without a seeded-defect test in
#: tests/test_analyze.py (and a row in docs/static_analysis.md) fails
#: the suite — the catalog IS the contract.
RULES: Dict[str, Rule] = _catalog(
    # -- graph passes (analyze/graphpass.py) ----------------------------
    Rule("graph.shape_mismatch", "error",
         "an op's input shapes/dtypes cannot compose (abstract "
         "jax.eval_shape of the op body fails)"),
    Rule("graph.undefined_input", "error",
         "an op consumes a variable that does not exist or is an ARRAY "
         "with no producing op"),
    Rule("graph.invalid_loss", "error",
         "a loss variable is missing from the graph, is not an op "
         "output, or has a non-floating dtype"),
    Rule("graph.unused_placeholder", "warn",
         "a placeholder is declared but not consumed by any op "
         "contributing to the requested outputs"),
    Rule("graph.name_shadowing", "warn",
         "two placeholders share a base name (auto-suffixed _N) — data "
         "fed by name silently reaches only one of them"),
    Rule("graph.dead_op", "warn",
         "a recorded loss op contributes to none of the requested "
         "outputs — a forgotten loss_variables entry trains nothing, "
         "silently"),
    Rule("graph.state_alias", "error",
         "a state-var update source is missing or aliases the state "
         "var itself (the update would be a no-op or crash at trace)"),
    # -- numerics passes (analyze/numerics.py) --------------------------
    Rule("numerics.lowp_loss_accum", "warn",
         "a loss op reduces to its scalar in bf16/f16 under the "
         "compute-dtype policy — the accumulation loses the training "
         "signal (force an f32 accumulator)"),
    Rule("numerics.lowp_reduction", "warn",
         "a large reduction (>= 4096 elements) accumulates in "
         "bf16/f16 — rounding absorbs the tail of the sum"),
    Rule("numerics.unguarded_log", "warn",
         "log() over a value with no positivity guard (clip/maximum/"
         "+eps) — 0 or negative inputs produce -inf/NaN"),
    Rule("numerics.unguarded_div", "warn",
         "division by a value with no zero guard (+eps/maximum/"
         "nonzero constant) — a zero denominator produces inf/NaN"),
    Rule("numerics.ce_tail_f32", "info",
         "bf16 compute with the softmax-CE tail left in f32 — on a "
         "large vocab this is the single largest f32 tensor in the "
         "step (set MixedPrecision.softmax_dtype)"),
    # -- config/composition passes (analyze/configpass.py) --------------
    Rule("config.mapping_unknown", "error",
         "data_set_feature/label_mapping names a variable that does "
         "not exist or is not a placeholder"),
    Rule("config.mapping_incomplete", "warn",
         "a placeholder the loss depends on is in neither feature nor "
         "label mapping — tuple batches cannot feed it"),
    Rule("config.cadence_misalignment", "warn",
         "fused_steps is not a multiple of accum_steps — window "
         "boundaries land mid-accumulation-cycle "
         "(docs/training_performance.md)"),
    Rule("config.donation_conflict", "error",
         "a requested output (loss variable) is a parameter/state/"
         "constant — the donated buffer would be read after the step "
         "invalidates it, and it carries no gradient"),
    Rule("config.sharding_invalid", "error",
         "the ShardingSpec cannot bind: axis sizes don't divide the "
         "device count or a matched parameter dim "
         "(ShardingSpec.validate)"),
    Rule("config.sharding_unmatched_rule", "warn",
         "an explicit ShardingRule matches zero parameters — the "
         "intended layout silently degrades to the preset/replication"),
    Rule("config.chaos_armed", "warn",
         "a faults/chaos injection spec is still armed on the "
         "TrainingConfig — deterministic faults will fire in this fit"),
    Rule("config.tensorstats_unobserved", "warn",
         "tensorstats is configured but this fit has no listeners — "
         "stats are silently skipped, and attaching listeners later "
         "retraces the step program"),
    # -- serving/config passes (analyze/servingpass.py) -----------------
    Rule("serving.dense_kv_exceeds_headroom", "warn",
         "a generative serving config's dense KV slab estimate "
         "(max_slots x max_seq rows) exceeds the device headroom "
         "guard — construction would be refused; paged KV "
         "(serving/paged) sizes by tokens actually held"),
    Rule("serving.fleet_slo_unreachable", "warn",
         "a fleet capacity plan (replicas x slots x p99 decode-step "
         "estimate) cannot meet its TTFT SLO at the stated arrival "
         "rate — queues grow without bound under Little's law and "
         "every request is eventually shed or late"),
    Rule("serving.speculation_misconfig", "error",
         "a speculative-decoding draft/target pairing is broken "
         "(vocab or max_seq mismatch — the server would refuse it at "
         "construction) or pointless (draft at least as large as the "
         "target, demoted to a warning: verification still yields the "
         "target's exact tokens, just no speedup)"),
)


@dataclasses.dataclass
class Finding:
    """One diagnostic: which rule, how severe, what it names.

    ``subject`` is the user-facing variable/op/config-field name;
    ``provenance`` is the producer chain ("var <- op ... ") that turns
    "XLA failed" into "YOUR variable, defined here, fed this op".
    """
    rule_id: str
    severity: str
    subject: str
    message: str
    fix_hint: str = ""
    provenance: Tuple[str, ...] = ()

    def to_json(self) -> dict:
        return {"rule_id": self.rule_id, "severity": self.severity,
                "subject": self.subject, "message": self.message,
                "fix_hint": self.fix_hint,
                "provenance": list(self.provenance)}

    def render(self) -> str:
        lines = [f"[{self.severity:<5}] {self.rule_id}: {self.subject} — "
                 f"{self.message}"]
        for p in self.provenance:
            lines.append(f"    {p}")
        if self.fix_hint:
            lines.append(f"    fix: {self.fix_hint}")
        return "\n".join(lines)


def finding(rule_id: str, subject: str, message: str, fix_hint: str = "",
            provenance: Sequence[str] = (),
            severity: str = "") -> Finding:
    """Build a Finding for a cataloged rule. Severity comes from the
    catalog by default; a pass may pass ``severity=`` to DEMOTE a
    dual-severity rule's hit (e.g. ``serving.speculation_misconfig``:
    a broken pairing is an error, a merely-pointless one a warning) —
    never to escalate past the catalog, which states the worst case."""
    rule = RULES[rule_id]
    if severity and severity not in SEVERITIES:
        raise ValueError(f"{rule_id}: bad severity override {severity!r}")
    if severity and SEVERITIES.index(severity) < \
            SEVERITIES.index(rule.severity):
        raise ValueError(
            f"{rule_id}: override {severity!r} escalates past the "
            f"cataloged {rule.severity!r}")
    return Finding(rule_id=rule_id, severity=severity or rule.severity,
                   subject=subject, message=message, fix_hint=fix_hint,
                   provenance=tuple(provenance))


class GraphAnalysisError(RuntimeError):
    """Strict-mode verdict: error-severity findings exist, raised
    BEFORE any XLA compile is attempted. ``.report`` carries the full
    :class:`AnalysisReport`; the message renders the error findings."""

    def __init__(self, report: "AnalysisReport"):
        self.report = report
        errs = report.errors()
        msg = (f"static analysis found {len(errs)} error(s) "
               f"(strict mode; docs/static_analysis.md):\n"
               + "\n".join(f.render() for f in errs))
        super().__init__(msg)


class GraphAnalysisWarning(UserWarning):
    """Non-strict mode surfaces error-severity findings as this
    warning category and proceeds (the compile will usually fail with
    a better-located message than XLA's)."""


@dataclasses.dataclass
class AnalysisReport:
    """Everything one analyzer run produced, plus provenance of the
    run itself (context, wall seconds, graph size)."""
    findings: List[Finding] = dataclasses.field(default_factory=list)
    context: str = "fit"            # fit | precompile | serving | cli
    n_vars: int = 0
    n_ops: int = 0
    rules_run: int = 0
    seconds: float = 0.0

    def add(self, f: Finding) -> None:
        self.findings.append(f)

    def extend(self, fs: Sequence[Finding]) -> None:
        self.findings.extend(fs)

    def by_severity(self, severity: str) -> List[Finding]:
        return [f for f in self.findings if f.severity == severity]

    def errors(self) -> List[Finding]:
        return self.by_severity("error")

    def warnings(self) -> List[Finding]:
        return self.by_severity("warn")

    def counts(self) -> Dict[str, int]:
        return {s: len(self.by_severity(s)) for s in SEVERITIES}

    def raise_if_errors(self) -> None:
        if self.errors():
            raise GraphAnalysisError(self)

    def to_record(self, max_findings: int = 100) -> dict:
        """The ``{"type": "analysis"}`` ui/stats record (schema in the
        ui/stats.py module docstring; rendered by ui/report's "Static
        analysis" panel, folded by MetricsRegistry.fold_analysis)."""
        return {"type": "analysis", "t": time.time(),
                "context": self.context,
                "graph": {"vars": self.n_vars, "ops": self.n_ops},
                "rules_run": self.rules_run,
                "seconds": round(self.seconds, 4),
                "counts": self.counts(),
                "findings": [f.to_json()
                             for f in self.findings[:max_findings]],
                "truncated": max(0, len(self.findings) - max_findings)}

    def render(self) -> str:
        head = (f"static analysis ({self.context}): {self.n_ops} ops / "
                f"{self.n_vars} vars, {self.rules_run} rules in "
                f"{self.seconds:.3f}s — "
                + ", ".join(f"{n} {s}" for s, n in self.counts().items()))
        if not self.findings:
            return head + "\nclean — no findings."
        order = {s: i for i, s in enumerate(SEVERITIES)}
        ranked = sorted(self.findings, key=lambda f: order[f.severity])
        return head + "\n" + "\n".join(f.render() for f in ranked)


__all__ = ["SEVERITIES", "Rule", "RULES", "Finding", "finding",
           "AnalysisReport", "GraphAnalysisError", "GraphAnalysisWarning"]
