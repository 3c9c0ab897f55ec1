"""Finding a cell's files by name and putting its pieces together.

There is no list to edit: a cell is ``workloads/<cell>.json`` plus its
entry in ``BENCHMARK.json``, a configuration is a directory under
``configs/``, a driver is ``drivers/<driver>.py`` and a per-layer metric is
``layer_metrics/<metric>.py``; each is found by a path built from its name.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def _module(root, *parts):
    """The module at ``<root>/chipbench/<parts>.py``, loaded from that path
    (a later PR's files are found where they lie, under any root)."""
    path = os.path.join(root, "chipbench", *parts) + ".py"
    name = "chipbench_found." + ".".join(parts) + f"_{abs(hash(path)):x}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with its files."""

    def __init__(self, name, root=ROOT):
        self.root = root
        self.bench = _json(root, "BENCHMARK.json")
        found = [w for w in self.bench["workloads"] if w["name"] == name]
        if not found:
            raise KeyError(f"BENCHMARK.json has no workload {name!r}")
        self.entry = found[0]
        self.name = name
        self.chips = self.entry["chips"]
        self.spec = _json(root, "chipbench", "workloads", name + ".json")
        config = self.entry["config"]
        self.cfg = _json(root, "chipbench", "configs", config, "config.json")
        self.build = _module(root, "configs", config, "build")
        self.reference = _module(root, "configs", config, "reference")
        self.driver = _module(root, "drivers", self.spec["driver"])

    def metrics(self, kind):
        """This cell's entries of ``end_to_end`` or ``per_layer``."""
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]

    def read_layer_metric(self, name, ctx):
        """The value ``layer_metrics/<name>.py`` reads from ``ctx``, or
        None where it finds nothing to read."""
        return _module(self.root, "layer_metrics", name).read(ctx)


def find_chips(cell):
    """The cell's TPU chips, or None (with a word on stderr) where JAX finds
    no TPU or fewer chips than the cell asks for: no run without them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: FAIL: {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return None
    return devices[:cell.chips]
