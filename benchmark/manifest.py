"""BENCHMARK.json and the files it names. jax-free.

Everything that belongs to one cell, one configuration or one per-layer
metric is a file found by the name in BENCHMARK.json:
``workloads/<cell>.json``, ``configs/<config>.json``,
``metrics/<metric>.json`` and the reader it names,
``readers/<reader>.py``. BENCHMARK.json alone says what a metric is (unit,
source, layer, what it moves, which cells report it); its file says only
how it is read: ``reader`` and, where the reader takes any, ``args``. A
later PR adds entries and files; it edits none.
"""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def workload_file(name: str) -> dict:
    return json.loads((HERE / "workloads" / f"{name}.json").read_text())


def metric_file(name: str) -> dict:
    return json.loads((HERE / "metrics" / f"{name}.json").read_text())


def cell_metrics(bench: dict, c: dict, group: str) -> list:
    """The metrics of ``group`` that cell ``c`` reports."""
    return [m for m in bench[group]
            if "workloads" not in m or c["name"] in m["workloads"]]


def read_layer_metrics(bench: dict, c: dict, artifacts: dict) -> dict:
    """Run each of the cell's per-layer readers over the run's artifacts;
    a reader that finds nothing to read returns None and its metric is
    left out."""
    out = {}
    for m in cell_metrics(bench, c, "per_layer"):
        spec = metric_file(m["name"])
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(artifacts, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def validate(bench: dict) -> list:
    """Every fault found, as text; empty when the manifest and its files
    hold together."""
    bad = []
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {c["name"]: c for c in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        bad += [f"{group}: duplicate name {n}" for n in set(names)
                if names.count(n) > 1]
        bad += [f"{group}: bad name {n!r}" for n in names
                if not NAME.match(n)]
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT.match(m["unit"]):
            bad.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better={m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source={m['source']!r}")
        bad += [f"{m['name']}: unknown workload {w}"
                for w in m.get("workloads", []) if w not in cells]
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: an end-to-end metric is taken by "
                       f"the benchmark itself")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
    for c in bench["configs"]:
        if not (ROOT / c["file"]).is_file():
            bad.append(f"config {c['name']}: no file {c['file']}")
        if not any(w["config"] == c["name"] for w in bench["workloads"]):
            bad.append(f"config {c['name']}: used by no cell")
    for c in bench["workloads"]:
        if c["config"] not in configs:
            bad.append(f"cell {c['name']}: unknown config {c['config']}")
        if c["chips"] not in (1, 4):
            bad.append(f"cell {c['name']}: chips={c['chips']}")
        if not 1 <= len(c["why"]) <= 200:
            bad.append(f"cell {c['name']}: why has {len(c['why'])} characters")
        if not (HERE / "workloads" / f"{c['name']}.json").is_file():
            bad.append(f"cell {c['name']}: no workloads/{c['name']}.json")
        mine = {m["name"] for m in cell_metrics(bench, c, "end_to_end")}
        if len(mine - {"setup_s"}) < 1 or "setup_s" not in mine:
            bad.append(f"cell {c['name']}: reports {sorted(mine)}")
        layer = cell_metrics(bench, c, "per_layer")
        if not layer:
            bad.append(f"cell {c['name']}: no per-layer metric")
        bad += [f"cell {c['name']}: {m['name']} moves {m['moves']}, which "
                f"the cell does not report"
                for m in layer if m["moves"] not in mine]
    four = sum(c["chips"] == 4 for c in bench["workloads"])
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} of {len(cells)} cells ask for four chips")
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            bad.append(f"{m['name']}: moves unknown metric {m['moves']}")
        path = HERE / "metrics" / f"{m['name']}.json"
        if not path.is_file():
            bad.append(f"{m['name']}: no metrics/{m['name']}.json")
            continue
        spec = json.loads(path.read_text())
        if "reader" not in spec or set(spec) - {"reader", "args"}:
            bad.append(f"{m['name']}: its file holds {sorted(spec)}, not "
                       f"reader (and args)")
            continue
        if not (HERE / "readers" / f"{spec['reader']}.py").is_file():
            bad.append(f"{m['name']}: no readers/{spec['reader']}.py")
    return bad
