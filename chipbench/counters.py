"""Reading the program's counters: differences over the window.

The program's registry (``rocalphago_tpu/obs/registry.py``) keys a
metric by name plus labels (``serve_eval_batches_total{size="16"}``).
A reader asks for a family by name; labelled members are summed.
"""

from __future__ import annotations


def _family(section: dict, name: str) -> list:
    return [v for k, v in section.items()
            if k == name or k.startswith(name + "{")]


def counter_delta(before: dict, after: dict, name: str):
    """Growth of a counter family between two registry snapshots;
    None when the program has no such counter."""
    b = _family(before.get("counters", {}), name)
    a = _family(after.get("counters", {}), name)
    if not a:
        return None
    return sum(a) - sum(b)


def histogram_delta(before: dict, after: dict, name: str):
    """``(sum, count)`` growth of a histogram family; None when the
    program has no such histogram."""
    b = _family(before.get("histograms", {}), name)
    a = _family(after.get("histograms", {}), name)
    if not a:
        return None
    return (sum(h["sum"] for h in a) - sum(h["sum"] for h in b),
            sum(h["count"] for h in a) - sum(h["count"] for h in b))
