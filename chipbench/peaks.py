"""The table of peaks (``peaks.json``), keyed by ``device_kind``."""

from __future__ import annotations

import json
import os


def peak(device: dict, key: str = "bf16_flops_per_s"):
    """Peak of the attached chip from ``peaks.json``. None off the
    TPU (a utilization against a host CPU "peak" means nothing); a
    TPU that is not in the table is an error, never a default."""
    if device["platform"] != "tpu":
        return None
    with open(os.path.join(os.path.dirname(__file__),
                           "peaks.json")) as f:
        table = json.load(f)
    kind = device["kind"]
    if kind not in table:
        raise KeyError(f"no peaks recorded for device_kind {kind!r}: "
                       "add it to chipbench/peaks.json with its source")
    return table[kind][key]
