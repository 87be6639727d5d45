"""Find a cell's pieces by name: BENCHMARK.json, its configuration file,
its traffic file, its loop driver and its per-layer metric readers."""
from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, workload: str) -> dict:
    """Everything one run of `workload` needs, resolved by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     w["traffic"] + ".json"))

    def wanted(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {"workload": w, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if wanted(m)],
            "per_layer": [m for m in bench["per_layer"] if wanted(m)]}


def driver(kind: str):
    """The loop driver named by a traffic file's `kind`."""
    return importlib.import_module(f"bench.drivers.{kind}")


def reader(metric_name: str):
    """The per-layer reader named by a metric's stem (before the '.')."""
    return importlib.import_module(f"bench.metrics.{metric_name.split('.')[0]}")


def reference(name: str):
    return importlib.import_module(f"bench.reference.{name}")


def peaks(device_kind: str) -> dict:
    """Published peaks of `device_kind`; an unknown device is an error."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
