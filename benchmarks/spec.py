"""Finding a cell's files by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix. The configuration's file
is the ``file`` of its entry; the traffic file is
``<paths[0]>/traffic/<cell>.json``; a per-layer metric ``m`` is read by
``<paths[0]>/layer_metrics/<m>.py``. Nothing here knows a cell, a
configuration or a metric by name.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def overlay(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys laid on top, nested groups merged."""
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = overlay(out[k], v)
        else:
            out[k] = v
    return out


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic
    parameters and the metrics it reports."""

    def __init__(self, name: str, root: str = ROOT, rehearsal: bool = False):
        bm = load_benchmark(root)
        cells = {w["name"]: w for w in bm["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.root = root
        self.base = os.path.join(root, bm["paths"][0])
        cfg_entry = {c["name"]: c for c in bm["configs"]}[self.entry["config"]]
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            self.base, "traffic", name + ".json"))
        self.rehearsal = rehearsal
        if rehearsal:
            self.config = overlay(self.config,
                                  self.config.get("rehearsal", {}))
            self.traffic = overlay(self.traffic,
                                   self.traffic.get("rehearsal", {}))

        def mine(m):
            return "workloads" not in m or name in m["workloads"]
        self.end_to_end = [m for m in bm["end_to_end"] if mine(m)]
        e2e_names = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bm["per_layer"]
                          if mine(m) and m["moves"] in e2e_names]

    def load_module(self, kind: str, name: str):
        """``<base>/<kind>/<name>.py`` as a module (drivers, references,
        layer_metrics): found by name, so a later PR adds files only."""
        path = os.path.join(self.base, kind, name + ".py")
        if not os.path.exists(path):
            raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
        spec = importlib.util.spec_from_file_location(
            f"benchmarks.{kind}.{name.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
