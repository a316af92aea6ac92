"""A number, or a ratio of sums of numbers, found in the run's record by
path: the program's counters, the memory statistics, the compile
statistics, the training tally. ``scale * sum(num) / (sum(den) *
prod(times))``; nothing where a path is missing or the divisor is 0."""
from __future__ import annotations


def lookup(record, path: str):
    """``a.b|c.d`` names alternatives: the first that resolves (two
    families that call one size by two keys)."""
    first, _, rest = path.partition("|")
    node = record
    for part in first.split("."):
        if not isinstance(node, dict) or part not in node \
                or node[part] is None:
            return lookup(record, rest) if rest else None
        node = node[part]
    return node


def _sum(record, paths):
    vals = [lookup(record, p) for p in paths]
    return None if any(v is None for v in vals) else float(sum(vals))


def read(record, params):
    num = _sum(record, params["num"])
    if num is None:
        return None
    den = 1.0
    if "den" in params:
        den = _sum(record, params["den"])
        if not den:
            return None
    for p in params.get("times", []):
        v = lookup(record, p)
        if not v:
            return None
        den *= float(v)
    return float(params.get("scale", 1.0)) * num / den
