"""Every cell's files load, its data set and its job among them, and
every configuration names its source and the keys it changed from it."""

import json
import os

import pytest

from bench import datagen, harness

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_names_source_and_reduced(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"]
    assert config["source"].startswith("https://")
    assert config["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert key in config and key in config["assumed"], key
    for key in ("dataset", "data_seed", "n", "d", "metric", "k", "solver",
                "guarantees"):
        assert key in config, key
    assert callable(datagen.generator(config["dataset"]))


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads(name):
    cell = harness.Cell(name)
    assert callable(harness.load("", cell.traffic["job"], "Job"))
    assert cell.end_to_end and "setup_s" in cell.end_to_end
    assert cell.per_layer
    for metric in cell.per_layer:
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           metric + ".py")), metric
    assert all(v > 0 for v in cell.limits.values())


def test_unknown_job_names_those_there_are():
    with pytest.raises(LookupError, match="'harness'.*'fit'"):
        harness.load("", "harness", "Job")


def test_every_metric_has_a_cell_and_a_reader():
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= set(CELLS), m["name"]
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           m["name"] + ".py"))
    for m in BENCH["end_to_end"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]
