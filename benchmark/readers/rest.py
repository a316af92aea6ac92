"""What is left of a number once the numbers that account for parts of
it are taken away, each found in the run's record by path
(``value.lookup``): ``of - sum(less) - sum(less_if_there)``. Nothing
where ``of`` or one of ``less`` is missing (a program that has no such
counter: the rest would claim its seconds); a path of ``less_if_there``
that is missing counts 0 (a traffic file without a warm-in)."""
from __future__ import annotations

from benchmark.readers.value import lookup


def read(record, params):
    whole = lookup(record, params["of"])
    parts = [lookup(record, p) for p in params.get("less", [])]
    if whole is None or any(v is None for v in parts):
        return None
    parts += [lookup(record, p) or 0.0
              for p in params.get("less_if_there", [])]
    return float(whole) - float(sum(parts))
