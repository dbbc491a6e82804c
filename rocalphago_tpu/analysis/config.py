"""jaxlint configuration: the ``[tool.jaxlint]`` block in
pyproject.toml.

Recognized keys (all optional — defaults lint the whole repo):

* ``include`` — list of repo-relative files/dirs to lint;
* ``exclude`` — list of repo-relative prefixes to drop;
* ``disable`` — list of rule ids switched off globally;
* ``baseline`` — path of the committed baseline file;
* ``docs.observability`` / ``docs.resilience`` / ``docs.knobs`` —
  where the inventory rules find their documented tables;
* ``report_modules`` — files whose metric-name *consumers* are
  checked against the produced set (obs_report drift).

Python 3.10 has no ``tomllib``, so a minimal single-table parser
handles exactly the value shapes above (strings, string lists,
booleans); ``tomllib`` is used when available.
"""

from __future__ import annotations

import dataclasses
import os
import re

DEFAULT_INCLUDE = ("rocalphago_tpu", "scripts", "tests")
DEFAULT_EXCLUDE = ()


@dataclasses.dataclass
class LintConfig:
    include: tuple = DEFAULT_INCLUDE
    exclude: tuple = DEFAULT_EXCLUDE
    disable: tuple = ()
    baseline: str = ".jaxlint-baseline.json"
    docs_observability: str = "docs/OBSERVABILITY.md"
    docs_resilience: str = "docs/RESILIENCE.md"
    docs_knobs: str = "docs/KNOBS.md"
    docs_serving: str = "docs/SERVING.md"
    docs_gateway: str = "docs/GATEWAY.md"
    docs_replaynet: str = "docs/REPLAYNET.md"
    docs_rollout: str = "docs/ROLLOUT.md"
    report_modules: tuple = ("scripts/obs_report.py",)
    #: module whose ``ServePool.stats`` dict is the serve-probe
    #: block producer (diffed against docs_serving's JSON schema)
    serve_probe_module: str = "rocalphago_tpu/serve/sessions.py"
    #: module whose ``GatewayServer.stats`` dict is the gateway-probe
    #: block producer (diffed against docs_gateway's JSON schema)
    gateway_probe_module: str = "rocalphago_tpu/gateway/server.py"
    #: module whose ``ReplayService.stats`` dict is the replaynet
    #: probe producer (diffed against docs_replaynet's JSON schema)
    replaynet_probe_module: str = "rocalphago_tpu/replaynet/server.py"
    #: module whose ``RolloutRouter.stats`` dict is the router probe
    #: producer (diffed against docs_rollout's JSON schema)
    router_probe_module: str = "rocalphago_tpu/rollout/router.py"
    #: module whose ``CanaryController.stats`` dict is the canary
    #: probe producer (diffed against docs_rollout's JSON schema)
    canary_probe_module: str = "rocalphago_tpu/rollout/canary.py"


_KEY_MAP = {
    "include": "include", "exclude": "exclude", "disable": "disable",
    "baseline": "baseline",
    "docs.observability": "docs_observability",
    "docs.resilience": "docs_resilience",
    "docs.knobs": "docs_knobs",
    "docs.serving": "docs_serving",
    "docs.gateway": "docs_gateway",
    "docs.replaynet": "docs_replaynet",
    "docs.rollout": "docs_rollout",
    "report_modules": "report_modules",
    "serve_probe_module": "serve_probe_module",
    "gateway_probe_module": "gateway_probe_module",
    "replaynet_probe_module": "replaynet_probe_module",
    "router_probe_module": "router_probe_module",
    "canary_probe_module": "canary_probe_module",
}


def _mini_toml_table(text: str, table: str) -> dict:
    """Parse one ``[table]`` of simple ``key = value`` lines; value
    shapes: basic string, list of basic strings, true/false."""
    out: dict = {}
    lines = text.splitlines()
    in_table = False
    buf = None  # (key, accumulated) while a list spans lines
    for raw in lines:
        line = raw.strip()
        if line.startswith("["):
            in_table = line == f"[{table}]"
            buf = None
            continue
        if not in_table or not line or line.startswith("#"):
            continue
        if buf is not None:
            buf = (buf[0], buf[1] + " " + line)
            if "]" in line:
                out[buf[0]] = buf[1]
                buf = None
            continue
        m = re.match(r'(?:"([^"]+)"|([A-Za-z0-9_.\-]+))\s*=\s*(.*)$', line)
        if not m:
            continue
        key = m.group(1) or m.group(2)
        val = m.group(3).strip()
        if val.startswith("[") and "]" not in val:
            buf = (key, val)
            continue
        out[key] = val
    parsed = {}
    for key, val in out.items():
        val = val.split("#")[0].strip() if not val.startswith("[") \
            else val
        if val.startswith("["):
            inner = val[val.index("[") + 1:val.rindex("]")]
            parsed[key] = [s for s in re.findall(r'"([^"]*)"', inner)]
        elif val.startswith('"'):
            parsed[key] = val.strip('"')
        elif val in ("true", "false"):
            parsed[key] = val == "true"
        else:
            parsed[key] = val
    return parsed


def _read_jaxlint_table(pyproject_path: str) -> dict:
    try:
        with open(pyproject_path, "rb") as f:
            data = f.read()
    except OSError:
        return {}
    try:
        import tomllib  # Python >= 3.11
        return (tomllib.loads(data.decode("utf-8"))
                .get("tool", {}).get("jaxlint", {}))
    except ImportError:
        return _mini_toml_table(data.decode("utf-8"), "tool.jaxlint")


def load_config(root: str) -> LintConfig:
    """Config from ``<root>/pyproject.toml``; defaults when the block
    (or the file) is absent."""
    table = _read_jaxlint_table(os.path.join(root, "pyproject.toml"))
    cfg = LintConfig()
    for toml_key, attr in _KEY_MAP.items():
        if toml_key in table:
            val = table[toml_key]
            if isinstance(getattr(cfg, attr), tuple):
                val = tuple(val) if isinstance(val, list) else (val,)
            setattr(cfg, attr, val)
    return cfg
