"""The device's idle share of the traced window: 1 minus the union of
the intervals in which an operation ran, over the window."""
from __future__ import annotations


def read(record, params):
    tr = record.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
