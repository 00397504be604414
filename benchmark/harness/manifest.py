"""Finds a cell's files by the names in BENCHMARK.json.

A cell is one entry of `workloads` there: its configuration is
`configs/<config>.json` (with the plain reference and the program adapter it
names beside it), its traffic `traffic/<traffic>.json` (which names the driver
under `drivers/`), its limits `workloads/<cell>.json`, and its metrics the
readers `metrics/<metric>.py`. Nothing here knows a cell's name.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class ManifestError(ValueError):
    pass


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ManifestError(
            f"{what} {name!r}: a name starts with a letter, a digit or '_' and "
            "holds at most 64 letters, digits, '_', '.' and '-'")
    return name


def _read_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, what: str):
    if not os.path.isfile(path):
        raise ManifestError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, HERE))[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One cell with everything its run needs, loaded and checked."""

    def __init__(self, workload: str, data_dir: str = HERE,
                 manifest_path: str = os.path.join(ROOT, "BENCHMARK.json")):
        """`data_dir` holds the data files (configs/, traffic/, workloads/);
        only the tests point it and `manifest_path` elsewhere, at tiny sizes.
        Code (references, adapters, drivers, readers) is always this
        directory's."""
        check_name(workload, "workload")
        self.data_dir = data_dir
        self.manifest = _read_json(manifest_path, "manifest")
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise ManifestError(f"workload {workload!r} is not in BENCHMARK.json "
                                f"(it has {sorted(cells)})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        config = check_name(self.entry["config"], "config")
        traffic = check_name(self.entry["traffic"], "traffic")
        self.config = _read_json(self._data("configs", config + ".json"),
                                 f"configuration {config!r}")
        self.traffic = _read_json(self._data("traffic", traffic + ".json"),
                                  f"traffic {traffic!r}")
        self.limits = _read_json(self._data("workloads", workload + ".json"),
                                 f"cell {workload!r}")["limits"]
        self.reference = load_module(
            self._path("configs", self.config["reference"]), "plain reference")
        self.adapter = load_module(
            self._path("configs", self.config["program"]), "program adapter")
        driver = check_name(self.traffic["driver"], "driver")
        self.driver = load_module(self._path("drivers", driver + ".py"),
                                  f"driver {driver!r}")

    def _path(self, *parts: str) -> str:
        return os.path.join(HERE, *parts)

    def _data(self, *parts: str) -> str:
        return os.path.join(self.data_dir, *parts)

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self):
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    def per_layer(self):
        return [m for m in self.manifest["per_layer"] if self._reports(m)]

    def reader(self, metric: str):
        check_name(metric, "metric")
        return load_module(self._path("metrics", metric + ".py"),
                           f"reader of {metric!r}")
