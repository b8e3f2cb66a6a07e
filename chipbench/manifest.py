"""Everything a cell is made of, found by name from BENCHMARK.json:

    configs/<config>.json     model family, entry parameters, source,
                              reduced, assumed, departures
    traffic/<traffic>.json    kind of driver and its parameters
    cells/<workload>.json     what `correct` compares and its limits
    layers/<metric>.json      one per-layer metric: its reader and args

A later PR adds files and entries; nothing here names a cell, a
configuration, a traffic mix or a metric."""

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _load(path):
    with open(path) as f:
        return json.load(f)


class Manifest(object):
    def __init__(self, root=ROOT):
        self.root = root
        self.bench = _load(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, self.bench["paths"][0])

    def _by_name(self, key, name):
        for entry in self.bench[key]:
            if entry["name"] == name:
                return entry
        raise KeyError("BENCHMARK.json has no %s named %r" % (key, name))

    def workload(self, name):
        return self._by_name("workloads", name)

    def config(self, name):
        return _load(os.path.join(self.root,
                                  self._by_name("configs", name)["file"]))

    def traffic(self, name):
        return _load(os.path.join(self.dir, "traffic", name + ".json"))

    def cell(self, name):
        return _load(os.path.join(self.dir, "cells", name + ".json"))

    def metric_spec(self, folder, metric):
        """`metrics/<name>.json` (end to end) or `layers/<name>.json`
        (per layer): {"reader": "[module:]function", "args": {...}}."""
        return _load(os.path.join(self.dir, folder, metric + ".json"))

    def metrics_of(self, kind, workload):
        """Entries of `end_to_end` or `per_layer` that this cell
        reports: those without a `workloads` key, or that list it."""
        return [m for m in self.bench[kind]
                if workload in m.get("workloads", [workload])]

    def driver(self, traffic):
        """The driver module of a traffic kind: `drivers/<kind>.py`."""
        return importlib.import_module(
            "%s.drivers.%s" % (os.path.basename(self.dir), traffic["kind"]))

    def reference(self, config):
        return importlib.import_module(
            "%s.refs.%s" % (os.path.basename(self.dir), config["family"]))
