"""BENCHMARK.json and the files it names: every piece found by name."""
import importlib
import os
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert 1 <= bench["run_seconds"] <= 51


def test_bounds_and_setup(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = spec.cell(bench, w["name"])
        assert w["chips"] in (1, 4)
        spec.driver(cell["traffic"]["kind"])
        spec.reference(cell["config"]["reference"])
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            assert m["moves"] in reported
            assert callable(spec.reader(m["name"]).read)


def test_config_files_hold_what_is_run(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
        assert set(cfg["reduced"]) <= set(cfg["changed"])


def test_layers_named_alike(bench):
    layers = {m["layer"] for m in bench["per_layer"]}
    assert layers == {"engine host loop", "model step", "olm kernel",
                      "device", "whole step"}


def test_metric_readers_are_modules_of_their_stem():
    for f in os.listdir(os.path.join(spec.BENCH_DIR, "metrics")):
        if f.endswith(".py") and f != "__init__.py":
            importlib.import_module(f"bench.metrics.{f[:-3]}")
