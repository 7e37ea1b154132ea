"""``BENCHMARK.json`` and the files it names, found by name.

A cell (an entry of ``workloads``) names its configuration and its
traffic mix.  The configuration's file is the ``file`` of its entry under
``configs``; the mix is ``bench/traffic/<traffic>.json``, whose
``window`` names the timed entry ``bench/windows/<window>.py``; the
configuration's ``reference`` names its plain reference and parameter
layout, ``bench/reference/<reference>.py``; the limits of the correctness
check are ``bench/limits/<cell>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding any of them takes new files and new
entries only.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(ValueError):
    pass


def load(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    if not path.is_file():
        raise SpecError(f"missing {path}")
    name = "bench_dyn_" + re.sub(r"\W", "_", str(path.relative_to(path.parents[2])))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_path(c: dict, root: Path = ROOT) -> Path:
    """``bench/reference/<c["reference"]>.py``, the plain reference that
    configuration ``c`` names; an error where it names none or a missing
    file."""
    name = c.get("reference")
    if not isinstance(name, str) or not re.fullmatch(r"\w[\w.-]*", name):
        raise SpecError(f"{c.get('name')}: the configuration names no "
                        f"reference module (key 'reference')")
    path = root / "bench" / "reference" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"{c.get('name')}: missing reference {path}")
    return path


def reference(c: dict, root: Path = ROOT):
    """The reference module of configuration ``c``: its ``shapes(c)`` gives
    the parameter layout, its ``score(weights, c, tokens, precision=...)``
    the plain forward."""
    return load_module(reference_path(c, root))


def cell(bm: dict, name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, resolved from its name."""
    wl = {w["name"]: w for w in bm["workloads"]}
    if name not in wl:
        raise SpecError(f"unknown workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    cfgs = {c["name"]: c for c in bm["configs"]}
    if w["config"] not in cfgs:
        raise SpecError(f"{name}: unknown config {w['config']!r}")
    bench = root / "bench"
    config = _json(root / cfgs[w["config"]]["file"])
    mix = _json(bench / "traffic" / f"{w['traffic']}.json")
    return {
        "name": name,
        "root": root,
        "chips": int(w["chips"]),
        "config": config,
        "reference": reference_path(config, root),
        "traffic": mix,
        "window": bench / "windows" / f"{mix['window']}.py",
        "limits": _json(bench / "limits" / f"{name}.json"),
        "end_to_end": [m for m in bm["end_to_end"] if applies(m, name)],
        "per_layer": [m for m in bm["per_layer"] if applies(m, name)],
        "metric_files": {m["name"]: bench / "metrics" / f"{m['name']}.py"
                         for m in bm["per_layer"] if applies(m, name)},
    }


def read_per_layer(c: dict, record: dict) -> dict:
    """Run each per-layer metric's reader on the traced run's record.  A
    reader that finds nothing to read returns None; the metric is then left
    out."""
    out = {}
    for m in c["per_layer"]:
        value = load_module(c["metric_files"][m["name"]]).read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
