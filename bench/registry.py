"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root names each cell's
configuration and traffic; everything else is a file of its own under
``bench/``, found from those names:

* ``configs/<config>.json``  -- the configuration's sizes, and
  ``configs/<config>.py`` beside it where the configuration has a plain
  reference of its forward pass;
* ``traffic/<traffic>.json`` -- the traffic mix's parameters, whose
  ``entry`` names ``entries/<entry>.py``, the code that drives the
  system under test for that kind of request;
* ``e2e/<metric>.py`` and ``metrics/<metric>.py`` -- one reader per
  end-to-end and per-layer metric.

A later change adds a cell, a configuration, a traffic mix or a metric
by adding such files and ``BENCHMARK.json`` entries; nothing here
changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Any, Dict, List, Optional

__all__ = ["BENCH_DIR", "Cell", "load_module"]

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)


def load_module(path: str, name: Optional[str] = None) -> ModuleType:
    """Import a file by path (names may hold dots and dashes)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = name or "bench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with its files."""

    def __init__(self, root: str, name: str,
                 bench_dir: str = BENCH_DIR) -> None:
        self.root = root
        self.bench_dir = bench_dir
        self.spec = _read_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"have {sorted(cells)}")
        self.cell = cells[name]
        self.name = name
        self.chips = int(self.cell["chips"])
        self.config = _read_json(self._path("configs",
                                            self.cell["config"] + ".json"))
        self.traffic = _read_json(self._path("traffic",
                                             self.cell["traffic"] + ".json"))

    def _path(self, *parts: str) -> str:
        return os.path.join(self.bench_dir, *parts)

    def entry_module(self) -> ModuleType:
        return load_module(self._path("entries",
                                      self.traffic["entry"] + ".py"))

    def reference_module(self) -> ModuleType:
        """The configuration's plain forward-pass reference."""
        return load_module(self._path("configs",
                                      self.cell["config"] + ".py"))

    def end_to_end(self) -> List[Dict[str, Any]]:
        """This cell's end-to-end metrics: those that list it, or list
        no cells at all."""
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[Dict[str, Any]]:
        """This cell's per-layer metrics: those that list it; one that
        lists no cells goes with the end-to-end metric it moves."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def metric_module(self, kind: str, name: str) -> ModuleType:
        return load_module(self._path(kind, name + ".py"))

