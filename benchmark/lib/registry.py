"""Finding a cell's files by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def module(directory: str, name: str):
    """``benchmark/<directory>/<name>.py``, loaded by its path: metric
    readers, cluster generators, churn generators and warm-up rules are
    each a file that a name in a data file finds."""
    path = os.path.join(BENCH, directory, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"{directory}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(``BENCHMARK.json``, the cell's entry, its configuration file, its
    traffic mix).  ``KeyError`` where the benchmark has no such cell."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    config = load_json(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    mix = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    return bench, cell, config, mix
