"""``BENCHMARK.json`` and the files it names, found by name.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/mixes/<traffic>.json`` and a per-layer metric reader
``bench/metrics/<metric>.py``.  Adding a cell, a mix or a metric adds
files and entries; nothing here is edited for it.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _need(cond: bool, what: str) -> None:
    if not cond:
        raise ManifestError(what)


def _name(x, what: str) -> str:
    _need(isinstance(x, str) and NAME.fullmatch(x) is not None,
          f"{what} {x!r} is not a name: 1-64 of A-Z a-z 0-9 _ . -, "
          "not starting with . or -")
    return x


def validate(m: Dict) -> Dict:
    """Check the names, units and cross references the harness relies on."""
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        _need(isinstance(m.get(key), list) and m[key], f"{key} is empty")
    configs = {_name(c["name"], "config") for c in m["configs"]}
    _need(len(configs) == len(m["configs"]), "two configs share a name")
    cells = set()
    for w in m["workloads"]:
        _name(w["name"], "workload")
        _name(w["traffic"], "traffic")
        _need(w["config"] in configs, f"{w['name']}: unknown config")
        _need(w["chips"] in (1, 4), f"{w['name']}: chips must be 1 or 4")
        cells.add(w["name"])
    _need(len(cells) == len(m["workloads"]), "two workloads share a name")
    names = set()
    for metric in m["end_to_end"] + m["per_layer"]:
        n = _name(metric["name"], "metric")
        _need(n not in names, f"metric {n} named twice")
        names.add(n)
        _need(isinstance(metric["unit"], str)
              and UNIT.fullmatch(metric["unit"]) is not None,
              f"metric {n}: unit {metric['unit']!r} has characters outside "
              "A-Z a-z 0-9 _ / % . -")
        _need(metric["better"] in ("lower", "higher"), f"metric {n}: better")
        _need(metric["source"] in SOURCES, f"metric {n}: source")
        for cell in metric.get("workloads", ()):
            _need(cell in cells, f"metric {n}: unknown workload {cell}")
    e2e = {x["name"] for x in m["end_to_end"]}
    for metric in m["per_layer"]:
        _need(metric["moves"] in e2e, f"{metric['name']}: moves "
              f"{metric['moves']!r} is not an end-to-end metric")
    return m


def load(path: Path = MANIFEST) -> Dict:
    with open(path) as f:
        return validate(json.load(f))


def data(kind: str, name: str) -> Dict:
    """``bench/<kind>/<name>.json``: a configuration or a traffic mix."""
    _name(name, kind)
    path = BENCH / kind / f"{name}.json"
    _need(path.is_file(), f"no {kind} file {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def cell(m: Dict, workload: str) -> Dict:
    """The workload entry with its configuration and mix loaded."""
    for w in m["workloads"]:
        if w["name"] == workload:
            return {**w, "config_data": data("configs", w["config"]),
                    "mix_data": data("mixes", w["traffic"])}
    raise ManifestError(f"no workload {workload!r} in {MANIFEST.name}")


def rehearsal(cell: Dict) -> Dict:
    """The cell at its configuration's small ``rehearsal`` sizes, for
    checking the path off the chip."""
    config = {**cell["config_data"], **cell["config_data"]["rehearsal"]}
    return {**cell, "config_data": config}


def metrics_for(m: Dict, workload: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [x for x in m[kind]
            if "workloads" not in x or workload in x["workloads"]]


def reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    _name(name, "metric")
    path = BENCH / "metrics" / f"{name}.py"
    _need(path.is_file(), f"no metric reader {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
