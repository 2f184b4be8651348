"""Finds everything a cell names — configuration, traffic mix, driver,
reference, operation counts, per-layer metric readers — as files under
``benchmarks/``, by the names in ``BENCHMARK.json``. ``run.py`` holds no
name of any of them."""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)

#: the contract's rule for every name (and so for every file stem here)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def check_name(name) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(
            f"{name!r} is not a name: 1-64 of letters, digits, '_', '.', "
            "'-', not starting with '.' or '-'")
    return name


def load_benchmark() -> dict:
    with open(os.path.join(REPO_DIR, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, check_name(name) + ".json")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module; names may hold dots."""
    path = os.path.join(BENCH_DIR, kind, check_name(name) + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    modname = "benchmarks_%s_%s" % (kind, re.sub(r"[.\-]", "_", name))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> dict:
    check_name(name)
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"BENCHMARK.json has no workload {name!r}")


def metrics_for(bench: dict, group: str, cell: dict) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports:
    those that list it under ``workloads``, and those with no such key."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is None or cell["name"] in cells:
            out.append(m)
    return out


def resolve(dotted: str):
    """``package.module:attribute`` -> the attribute (a config's builder)."""
    modname, _, attr = dotted.partition(":")
    mod = importlib.import_module(modname)
    return getattr(mod, attr)
