"""Find a cell's parts by name: its entry in BENCHMARK.json, its cell
file `bench/workloads/<cell>.json`, its configuration file, its driver
`bench/drivers/<driver>.py` and each metric's reader
`bench/metrics/<metric>.py`.  Adding a cell or a metric adds files and
entries; nothing here changes."""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import `path` as module `name` once per process."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: dict, cell: str) -> bool:
    return cell in entry.get("workloads", [cell])


class Cell:
    """Everything one run needs to know about its cell."""

    def __init__(self, name: str):
        bm = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in bm["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(by_name)}")
        self.name = name
        self.entry = by_name[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bm["configs"]}[
            self.entry["config"]]
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.spec = load_json(os.path.join(BENCH, "workloads",
                                           name + ".json"))
        for key in ("config", "traffic"):
            if self.spec[key] != self.entry[key]:
                raise ValueError(
                    f"{name}: cell file says {key}={self.spec[key]!r}, "
                    f"BENCHMARK.json says {self.entry[key]!r}")
        self.limits = dict(self.spec["limits"])
        # reported only where the per-layer metric's `moves` is one of
        # this cell's end-to-end metrics (the contract's pairing rule)
        self.end_to_end = [m for m in bm["end_to_end"]
                           if _applies(m, name)]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bm["per_layer"]
                          if _applies(m, name) and m["moves"] in e2e]

    def driver(self):
        return load_module(os.path.join(BENCH, "drivers",
                                        self.spec["driver"] + ".py"),
                           "bench_driver_" + self.spec["driver"])

    @staticmethod
    def reader(metric: str):
        return load_module(os.path.join(BENCH, "metrics", metric + ".py"),
                           "bench_metric_" + metric.replace(".", "_")
                           .replace("-", "_"))
