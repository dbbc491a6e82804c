"""One way to measure: ``BENCHMARK.json`` + ``chipbench/`` is the
repo's only yardstick (PERF.md), and these tests hold the rules that
keep it so — no number without a chip, one table of chip peaks, and a
README whose commands exist."""

import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_benchmark_refuses_without_a_chip(cell):
    """Every cell of the manifest, off-TPU: exit 3, nothing on stdout
    — no fallback may print a CPU number under a device metric's name
    (how the driver's old captures came to be CPU figures)."""
    proc = subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "'cpu'" in proc.stderr and "No fallback" in proc.stderr


def _python_files():
    skip = {"chipbench", "chiprun_out", "__pycache__"}
    for d, dirs, files in os.walk(REPO):
        dirs[:] = [x for x in dirs
                   if not x.startswith(".") and x not in skip]
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(d, f)


def test_chip_peaks_live_only_with_the_benchmark():
    """``chipbench/peaks.json`` (read by ``chipbench/peaks.py``, which
    errors on an unknown device) is the one table of chip peaks: an
    MFU or roofline share computed against a second copy can drift
    from the one the ledger's numbers used."""
    marks = ("197e12", "_TPU_BF16_PEAK", "bf16_peak")
    hits = []
    for path in _python_files():
        if os.path.samefile(path, __file__):
            continue
        with open(path, errors="replace") as f:
            text = f.read()
        hits += [f"{os.path.relpath(path, REPO)}: {m}"
                 for m in marks if m in text]
    assert not hits, hits


def test_readme_commands_name_files_of_the_tree():
    """Every ``python <file>``, ``python -m <module>`` and
    ``bash <file>`` in a fenced block of README.md exists: a file of
    the checkout, a module of it, or (``-m pytest``) an installed
    tool."""
    with open(os.path.join(REPO, "README.md")) as f:
        blocks = re.findall(r"^```[^\n]*\n(.*?)^```", f.read(),
                            flags=re.S | re.M)
    commands = re.findall(
        r"\b(python3?|bash)\s+(-m\s+)?([\w./-]+)", "\n".join(blocks))
    assert len(commands) >= 20, commands    # the parse found them
    missing = []
    for tool, dash_m, target in commands:
        if not dash_m:
            ok = os.path.isfile(os.path.join(REPO, target))
        elif os.path.exists(os.path.join(REPO, target.split(".")[0])):
            base = os.path.join(REPO, *target.split("."))
            ok = (os.path.isfile(base + ".py")
                  or os.path.isfile(os.path.join(base, "__init__.py")))
        else:
            ok = importlib.util.find_spec(target.split(".")[0]) \
                is not None
        if not ok:
            missing.append(f"{tool} {dash_m}{target}")
    assert not missing, missing
