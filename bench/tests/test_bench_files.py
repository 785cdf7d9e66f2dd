"""Every data file of the benchmark parses, names what exists, and keeps
to the naming rules of BENCHMARK.json."""
import glob
import json
import os
import re

import pytest

from bench import compare
from bench.tests.conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(path):
    with open(path) as f:
        return json.load(f)


#: readings of one leaf over one step, to list the candidate numbers
_READINGS = {"steps": [{"loss": [1.0, 1.0], "leaves": {"w": [1.0, 1.0]}}],
             "final": {"w": [1.0, 1.0]}}
BENCHMARK = _load(os.path.join(ROOT, "BENCHMARK.json"))
CONFIG_FILES = sorted(glob.glob(os.path.join(BENCH, "configs", "*.json")))
WORKLOAD_FILES = sorted(glob.glob(os.path.join(BENCH, "workloads", "*.json")))


def test_benchmark_keys_and_names():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    names = [c["name"] for c in BENCHMARK["configs"]]
    names += [w["name"] for w in BENCHMARK["workloads"]]
    names += [m["name"] for m in BENCHMARK["end_to_end"]
              + BENCHMARK["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCHMARK["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25


@pytest.mark.parametrize("path", CONFIG_FILES, ids=os.path.basename)
def test_config_file(path):
    cfg = _load(path)
    name = os.path.basename(path)[:-len(".json")]
    assert cfg["name"] == name and NAME.match(name)
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == name)
    assert entry["file"] == f"bench/configs/{name}.json"
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["source"] == entry["source"]
    assert cfg["limits"] and set(cfg["limits"]) <= set(
        compare.numbers(_READINGS))
    assert all(v > 0 for v in cfg["limits"].values())
    assert os.path.exists(os.path.join(
        BENCH, "models", cfg["spec"]["data.model"] + ".py"))
    family = cfg["spec"]["transport.codec"].split(":")[0]
    assert os.path.exists(os.path.join(BENCH, "codecs", family + ".py"))
    from repro.api import ExperimentSpec
    ExperimentSpec().with_overrides(cfg["spec"]).validate()


@pytest.mark.parametrize("path", WORKLOAD_FILES, ids=os.path.basename)
def test_workload_file(path):
    wl = _load(path)
    name = os.path.basename(path)[:-len(".json")]
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == name)
    assert entry["config"] == wl["config"]
    assert entry["chips"] == wl["chips"] in (1, 4)
    assert name == f"{entry['config']}.{entry['traffic']}"
    assert os.path.exists(os.path.join(BENCH, "configs",
                                       wl["config"] + ".json"))
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert NAME.match(entry["traffic"])


def test_every_listed_metric_has_a_reader_and_every_cell_its_metrics():
    for m in BENCHMARK["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
        assert m["moves"] in {e["name"] for e in BENCHMARK["end_to_end"]}
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    for w in cells:
        e2e = [m for m in BENCHMARK["end_to_end"]
               if w in m.get("workloads", [w])]
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert any(w in m.get("workloads", [w])
                   for m in BENCHMARK["per_layer"])
    assert len(WORKLOAD_FILES) == len(cells)
    assert len(CONFIG_FILES) == len(BENCHMARK["configs"])
