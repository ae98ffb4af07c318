"""Finds everything a cell is made of by the names in ``BENCHMARK.json``.

One rule, no fallbacks: under ``<root>/<paths[0]>/`` a configuration is the
``file`` its entry names (with ``<config>.reference.py`` beside it), a traffic
mix is ``traffic/<traffic>.json``, a driver ``drivers/<driver>.py``, a model
builder ``models/<model>.py`` and a per-layer metric
``layer_metrics/<metric>.py``.  A later PR adds files and ``BENCHMARK.json``
entries and edits nothing that is here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re


class Registry:
    def __init__(self, root):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        self.home = os.path.join(self.root, self.bench["paths"][0])
        self._modules = {}

    def _entry(self, table, name):
        for e in self.bench[table]:
            if e["name"] == name:
                return e
        raise KeyError("%s: no %r in BENCHMARK.json (have %s)" % (
            table, name, [e["name"] for e in self.bench[table]]))

    def cell(self, name):
        return self._entry("workloads", name)

    def config(self, name):
        """The configuration's file as it is run, plus its entry's ``name``."""
        with open(os.path.join(self.root, self._entry("configs", name)["file"])) as f:
            return dict(json.load(f), name=name)

    def traffic(self, name):
        with open(os.path.join(self.home, "traffic", name + ".json")) as f:
            return dict(json.load(f), name=name)

    def metrics(self, table, cell):
        """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
        return [m for m in self.bench[table]
                if cell in m.get("workloads", [cell])]

    def module(self, kind, name):
        """``<home>/<kind>/<name>.py``, loaded by path (names may hold dots)."""
        return self._load(os.path.join(self.home, kind, name + ".py"))

    def reference(self, config_name):
        """The configuration's plain reference, beside its file."""
        rel = self._entry("configs", config_name)["file"]
        return self._load(os.path.join(
            self.root, os.path.splitext(rel)[0] + ".reference.py"))

    def _load(self, path):
        if path not in self._modules:
            name = "chipbench_" + re.sub(
                r"\W", "_", os.path.relpath(path, self.home))
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]
