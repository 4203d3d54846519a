"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, layer op, traffic mix or
per-layer metric sits in a file of its own, found by its name under
``bench/``:

    configs/<config>.json      sizes, bits, scales, correctness limit
    models/<family>.py         layers(cfg): the family's generic layer list
    ops/<op>.py                one layer op: shape, MACs, graph nodes and
                               reference (``bench/layers.py``)
    traffic/<traffic>.json     one mix; its "driver" names
    drivers/<driver>.py        the general generator that runs it
    metrics/<metric>.py        read(record) -> number, or None
    peaks.json                 peaks by device_kind

A layer list may branch and merge (a layer's ``"in"`` names the earlier
layers it reads), so a new cell, configuration, layer op or per-layer
metric is new files plus new entries in ``BENCHMARK.json``, and no edit of
a file already there.
"""
from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class UnknownDevice(KeyError):
    """The device kind has no row in ``bench/peaks.json``."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a benchmark file by path (its name may hold '-' and '.')."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def module_name(kind: str, name: str) -> str:
    return f"bench_{kind}_" + "".join(c if c.isalnum() else "_" for c in name)


class Bench:
    """``BENCHMARK.json`` of a checkout and the files it names."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.dir = os.path.join(root, "bench")
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def layers(self, cfg: dict) -> list[dict]:
        """The configuration's generic layer list (``bench/layers.py``)."""
        fam = load_module(os.path.join(self.dir, "models",
                                       f"{cfg['family']}.py"),
                          module_name("family", cfg["family"]))
        return fam.layers(cfg)

    def traffic(self, name: str) -> dict:
        return load_json(os.path.join(self.dir, "traffic", f"{name}.json"))

    def driver(self, traffic_doc: dict):
        name = traffic_doc["driver"]
        return load_module(os.path.join(self.dir, "drivers", f"{name}.py"),
                           module_name("driver", name))

    def metric_reader(self, name: str):
        return load_module(os.path.join(self.dir, "metrics", f"{name}.py"),
                           module_name("metric", name))

    def metrics_for(self, cell: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` entries the cell reports."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def peaks(self, device_kind: str) -> dict:
        table = load_json(os.path.join(self.dir, "peaks.json"))["devices"]
        if device_kind not in table:
            raise UnknownDevice(
                f"device_kind {device_kind!r} is not in bench/peaks.json "
                f"(known: {sorted(table)}); add its published peaks first")
        return table[device_kind]
