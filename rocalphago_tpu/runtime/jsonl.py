"""Tolerant JSONL reading (stdlib-only — safe for light scripts).

A process killed mid-``write`` leaves AT MOST one torn trailing line
in a line-buffered JSONL stream (``io.metrics.MetricsLogger`` emits
whole lines through a ``buffering=1`` handle; ``tests/
test_runtime.py`` pins the at-most-one-torn-line invariant), so a
reader that skips undecodable lines loses at most the final
in-flight record instead of crashing. ``scripts/zero_curve.py``
and ``scripts/obs_report.py`` read crash-prone logs through this.
"""

from __future__ import annotations

import json


def read_jsonl(path: str, on_error: str = "skip") -> list:
    """One dict per well-formed line of ``path``.

    ``on_error``: "skip" (default) drops undecodable or non-object
    lines; "raise" propagates the decode error (for writers that
    must be exact)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                if on_error == "raise":
                    raise
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


def iter_jsonl(f, on_error: str = "skip"):
    """Streaming form over an open file object."""
    for line in f:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            if on_error == "raise":
                raise
            continue
        if isinstance(rec, dict):
            yield rec
