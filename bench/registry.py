"""Finds the benchmark's parts by the names `BENCHMARK.json` gives them.

Nothing here lists a cell, a configuration, a traffic mix or a metric.  A
later change adds one as files and an entry, and edits none:

    BENCHMARK.json                    cells, configurations, metrics
    bench/configs/<config>.json       a configuration's sizes (its `file`)
    bench/traffic/<traffic>.json      a traffic mix: its driver and parameters
    bench/drivers/<driver>.py         one entry path of the program
    bench/metrics/<metric>.py         one per-layer metric's reader
    bench/references/<name>.py        a plain reference a configuration names
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class Registry:
    """`BENCHMARK.json` and the files under the benchmark's directory, read
    from `root` (the checkout's root)."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    # -- entries of BENCHMARK.json --------------------------------------------

    def _entry(self, section: str, name: str) -> dict:
        for e in self.spec[section]:
            if e["name"] == name:
                return e
        known = ", ".join(e["name"] for e in self.spec[section])
        raise KeyError(f"no {section} entry named {name!r} in BENCHMARK.json "
                       f"(known: {known})")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def end_to_end(self, cell: str) -> list:
        """The end-to-end metrics a cell reports."""
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        """The per-layer metrics a cell reports: those that list it, and
        those without a list that move an end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    # -- files found by name ----------------------------------------------------

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        with open(os.path.join(self.root, entry["file"])) as f:
            return json.load(f)

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.bench_dir, "traffic", name + ".json")) as f:
            return json.load(f)

    def _module(self, kind: str, name: str):
        path = os.path.join(self.bench_dir, kind, name + ".py")
        if not os.path.exists(path):
            raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is "
                                    f"missing")
        mod_name = f"bench_{kind}_{name}".replace(".", "_")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        return mod

    def driver(self, name: str):
        return self._module("drivers", name)

    def metric(self, name: str):
        return self._module("metrics", name)

    def reference(self, name: str):
        return self._module("references", name)
