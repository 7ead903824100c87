"""BENCHMARK.json and the files its names resolve to.

Nothing here knows a cell, a size or a transport: a name in the manifest
is looked up as a file, and a file that is missing is an error that says
which name wanted it.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

MANIFEST = "BENCHMARK.json"


class ManifestError(Exception):
    """A name in BENCHMARK.json that does not resolve."""


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of `workloads` with everything it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    driver_name: str
    end_to_end: tuple[dict, ...]   # the manifest entries reported here
    per_layer: tuple[dict, ...]


def _load_json(path: pathlib.Path, wanted_by: str) -> dict:
    if not path.is_file():
        raise ManifestError(f"{wanted_by}: no file {path}")
    with path.open() as f:
        return json.load(f)


def _load_module(path: pathlib.Path, wanted_by: str):
    """A driver or a reader, loaded by its path so that a file dropped
    into a copy of the tree is found without being importable by name."""
    if not path.is_file():
        raise ManifestError(f"{wanted_by}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bm_{path.parent.name}_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


def _reported_in(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


class Manifest:
    """`root` is the directory that holds BENCHMARK.json; the benchmark's
    own directory is the first of its `paths`."""

    def __init__(self, root):
        self.root = pathlib.Path(root)
        self.doc = _load_json(self.root / MANIFEST, "the benchmark")
        self.home = self.root / self.doc["paths"][0]

    def cell_names(self) -> list[str]:
        return [w["name"] for w in self.doc["workloads"]]

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.doc["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise ManifestError(
                f"no workload {name!r}; BENCHMARK.json has "
                f"{self.cell_names()}")
        cfg_entry = next((c for c in self.doc["configs"]
                          if c["name"] == entry["config"]), None)
        if cfg_entry is None:
            raise ManifestError(
                f"workload {name!r}: no config {entry['config']!r}")
        config = _load_json(self.root / cfg_entry["file"],
                            f"config {cfg_entry['name']!r}")
        traffic = _load_json(
            self.home / "traffic" / f"{entry['traffic']}.json",
            f"traffic {entry['traffic']!r}")
        end_to_end = tuple(m for m in self.doc["end_to_end"]
                           if _reported_in(m, name))
        seen = {m["name"] for m in end_to_end}
        # A per-layer metric is reported only where the metric it moves is.
        per_layer = tuple(m for m in self.doc["per_layer"]
                          if _reported_in(m, name) and m["moves"] in seen)
        return Cell(name=name, chips=entry["chips"],
                    config_name=cfg_entry["name"], config=config,
                    traffic=traffic,
                    driver_name=config["driver"],
                    end_to_end=end_to_end, per_layer=per_layer)

    def driver(self, name: str):
        return _load_module(self.home / "drivers" / f"{name}.py",
                            f"driver {name!r}")

    def reader(self, metric_name: str):
        return _load_module(
            self.home / "layer_metrics" / f"{metric_name}.py",
            f"per-layer metric {metric_name!r}")
