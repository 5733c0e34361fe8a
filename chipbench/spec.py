"""Finds a cell's files by the names `BENCHMARK.json` gives them.

`BENCHMARK.json` is the index: cells, metrics, units, layers and
bounds. A configuration's sizes are in `configs/<config>.json`, a
traffic mix's parameters in `traffic/<traffic>.json`, the limits of a
cell's `correct` in `limits/<cell>.json`, and a per-layer metric's
reader and its arguments in `metrics/<metric>.json`. A later PR adds
entries and files; it edits none.
"""
from __future__ import annotations

import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")

_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
_UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")


class SpecError(ValueError):
    pass


def check_name(name) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise SpecError(f"bad name {name!r}: a letter, digit or _ first, "
                        "then at most 63 letters, digits, _ . -")
    return name


def check_unit(unit) -> str:
    if not isinstance(unit, str) or not _UNIT.match(unit):
        raise SpecError(f"bad unit {unit!r}: 1 to 16 letters, digits, "
                        "_ / % . -")
    return unit


def _read(kind: str, name: str, root: str) -> dict:
    path = os.path.join(root, kind, check_name(name) + ".json")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: str = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: dict = None, root: str = ROOT) -> dict:
    """Everything one run needs, found by name: the cell's entry, its
    configuration, its traffic mix, its limits, the end-to-end metrics
    it reports and the per-layer metrics that list it, each with its
    reader."""
    bench = benchmark if benchmark is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if check_name(name) not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; it has "
                        + ", ".join(sorted(cells)))
    cell = dict(cells[name])
    if cell["chips"] not in (1, 4):
        raise SpecError(f"{name}: chips must be 1 or 4")
    per_layer = []
    for m in bench["per_layer"]:
        check_name(m["name"])
        check_unit(m["unit"])
        if _applies(m, name):
            per_layer.append({**m, **_read("metrics", m["name"], root)})
    end_to_end = [m for m in bench["end_to_end"] if _applies(m, name)]
    for m in end_to_end:
        check_name(m["name"])
        check_unit(m["unit"])
    return {
        "cell": cell,
        "config": _read("configs", cell["config"], root),
        "traffic": _read("traffic", cell["traffic"], root),
        "limits": _read("limits", name, root)["limits"],
        "end_to_end": end_to_end,
        "per_layer": per_layer,
    }


def load_driver(name: str):
    return importlib.import_module(
        "chipbench.drivers." + check_name(name))


def load_reference(name: str):
    """A configuration's plain reference: `reference/<name>.py`."""
    return importlib.import_module(
        "chipbench.reference." + check_name(name))


def load_program(name: str):
    """A configuration's program under test: `programs/<name>.py` with
    one function `build`, which the configuration's driver calls."""
    return importlib.import_module(
        "chipbench.programs." + check_name(name))


def load_reader(name: str):
    """A reader is `readers/<name>.py` with one function `read(record,
    trace, args)`: the number, or None where it finds nothing to read."""
    return importlib.import_module(
        "chipbench.readers." + check_name(name)).read


def peaks(device_kind: str) -> dict:
    with open(os.path.join(ROOT, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise SpecError(f"no peaks for device_kind {device_kind!r} in "
                        "chipbench/peaks.json: add its row with a source")
    return table[device_kind]
