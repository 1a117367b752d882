"""Loading and checking the benchmark's data files.

``BENCHMARK.json`` at the checkout root lists the cells, configurations
and metrics.  Each cell has a file ``bench/workloads/<cell>.json`` and
each configuration ``bench/configs/<config>.json``; the cell names the job
module ``bench/jobs/<kind>.py`` that runs it, and every per-layer metric
has a reader ``bench/metrics/<metric>.py``.  Adding a cell, a
configuration or a metric therefore adds files and entries, never edits.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """A data file of the benchmark is malformed."""


def check_name(name, what: str = "name") -> str:
    if not isinstance(name, str) or not NAME_RE.fullmatch(name):
        raise SpecError(f"{what} {name!r} is not a name: 1-64 of letters, "
                        "digits, '_', '.', '-', not starting with '.'/'-'")
    return name


def check_unit(unit) -> str:
    if not isinstance(unit, str) or not UNIT_RE.fullmatch(unit):
        raise SpecError(f"unit {unit!r}: 1-16 of letters, digits, "
                        "'_', '/', '%', '.', '-'")
    return unit


def _check_metric(m: dict, cells: set) -> None:
    check_name(m.get("name"), "metric name")
    check_unit(m.get("unit"))
    if m.get("better") not in ("lower", "higher"):
        raise SpecError(f"metric {m['name']}: better must be lower/higher")
    if m.get("source") not in SOURCES:
        raise SpecError(f"metric {m['name']}: source {m.get('source')!r}")
    for w in m.get("workloads", ()):
        if w not in cells:
            raise SpecError(f"metric {m['name']} lists unknown cell {w!r}")


def load_benchmark(root: str = ROOT) -> dict:
    """``BENCHMARK.json``, with every name, unit and cross-reference
    checked."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = {check_name(c["name"], "config name") for c in bench["configs"]}
    cells = set()
    for w in bench["workloads"]:
        cells.add(check_name(w["name"], "cell name"))
        check_name(w["traffic"], "traffic")
        if w["config"] not in configs:
            raise SpecError(f"cell {w['name']} names unknown config "
                            f"{w['config']!r}")
        if w["chips"] not in (1, 4):
            raise SpecError(f"cell {w['name']}: chips must be 1 or 4")
    for m in bench["end_to_end"] + bench["per_layer"]:
        _check_metric(m, cells)
    return bench


def _load_json(kind: str, name: str, root: str) -> dict:
    check_name(name, f"{kind} name")
    path = os.path.join(root, "bench", kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """``bench/workloads/<name>.json``: config, kind, params, chips, why."""
    cell = _load_json("workloads", name, root)
    for key in ("config", "kind", "params", "chips", "why"):
        if key not in cell:
            raise SpecError(f"cell {name} lacks {key!r}")
    check_name(cell["config"], "config name")
    check_name(cell["kind"], "job kind")
    return cell


def load_config(name: str, root: str = ROOT) -> dict:
    """``bench/configs/<name>.json``: source, reduced, assumed, sizes."""
    cfg = _load_json("configs", name, root)
    for key in ("source", "reduced", "assumed"):
        if key not in cfg:
            raise SpecError(f"config {name} lacks {key!r}")
    for key in cfg["reduced"]:
        check_name(key, "reduced key")
    return cfg


def load_module(kind: str, name: str, root: str = ROOT):
    """Import ``bench/<kind>/<name>.py`` (a job module or a metric
    reader) under a private module name."""
    check_name(name, f"{kind} name")
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} file {os.path.relpath(path, root)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, group: str) -> list:
    """The metrics of ``group`` ("end_to_end"/"per_layer") this cell
    reports: those without a ``workloads`` key, and those listing it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]
