"""Where the benchmark's data files live and how they are found by name.

``BENCHMARK.json`` names cells, configurations and metrics; everything
that belongs to one of them sits in a file of its own under this
directory, found by that name. Adding a cell, a configuration, a
traffic mix, a kind of cell or a per-layer metric is adding files and
manifest entries: no file here has a table of names to extend, and no
key of a data file has a default hidden in code.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(ValueError):
    """A name in BENCHMARK.json that no data file answers to."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


class table(dict):
    """A data file's table. A key the file lacks is an error that names
    the file, never a default."""

    def __init__(self, path: str):
        super().__init__(load_json(path))
        self.path = path

    def __missing__(self, key: str):
        raise ManifestError(f"{self.path} has no {key!r}")


def _named(kind: str, name: str, bench_dir: str) -> Dict[str, Any]:
    path = os.path.join(bench_dir, kind, f"{name}.json")
    if not os.path.exists(path):
        raise ManifestError(f"no {kind} file for {name!r}: {path}")
    return table(path)


def workload(man: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(
        f"unknown workload {name!r} (have "
        f"{[w['name'] for w in man['workloads']]})")


def config_file(man: Dict[str, Any], name: str, root: str = ROOT) -> str:
    for c in man["configs"]:
        if c["name"] == name:
            return os.path.join(root, c["file"])
    raise ManifestError(f"unknown configuration {name!r}")


def cell(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    return _named("cells", name, bench_dir)


def traffic(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    return _named("traffic", name, bench_dir)


def layer_metric(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    return _named("layer_metrics", name, bench_dir)


def metrics_for(man: Dict[str, Any], section: str, cell_name: str
                ) -> List[Dict[str, Any]]:
    """The metrics of ``section`` (end_to_end / per_layer) that this
    cell reports: those that list it, and those that list no cell."""
    return [m for m in man[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def cell_runner(kind: str):
    """How a cell of a traffic mix's ``kind`` is run, by that name: the
    module ``benchmark/<kind>_cell.py`` with ``run(man, wl, seed,
    seconds, trace, require_tpu, control, bench_dir)``."""
    try:
        return importlib.import_module(f"benchmark.{kind}_cell").run
    except ModuleNotFoundError as e:
        raise ManifestError(f"no cell module for traffic kind {kind!r}: "
                            f"{e}") from e


def reader(kind: str):
    """A per-layer metric's reader, by the name its file gives: the
    module ``benchmark/readers/<kind>.py`` with ``read(ctx, args)``."""
    try:
        return importlib.import_module(f"benchmark.readers.{kind}").read
    except ModuleNotFoundError as e:
        raise ManifestError(f"no reader {kind!r}: {e}") from e


def read_layer_metrics(man: Dict[str, Any], cell_name: str,
                       ctx: Dict[str, Any],
                       bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """Every per-layer metric of this cell whose reader finds something
    to read, as ``{name: {"value": v, "unit": u}}``."""
    out = {}
    for m in metrics_for(man, "per_layer", cell_name):
        spec = layer_metric(m["name"], bench_dir)
        value = reader(spec["reader"])(ctx, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
