"""Throwaway benchmark roots for the CPU tests, made only by adding data
files: the real logreg_sent140 cell at a size the CPU runs in seconds,
held to its real limits, and a resident-data CNN cell of the tests' own
(the paper CNN at 8x8 inputs, limits set from CPU readings: the program
agrees with the reference to ~1e-5 there, the bfloat16 control departs
by 0.5 in change_gap), and a tiny language model cell of the tests' own
(nested parameters, a next-token objective)."""
from __future__ import annotations

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

#: the real cells, cut to sizes the CPU runs in seconds
SMALL = {"logreg_sent140.stream_660k": {
    "data.n_clients": 5000, "tiers.clients_per_round": 4,
    "engine.eval_every": 2}}
#: a resident-data CNN cell that exists only in the tests
CNN = {
    "name": "small_cnn", "source": "test",
    "spec": {"strategy.name": "fedat", "data.model": "cnn",
             "data.image_hw": 8, "data.n_classes": 10, "data.n_clients": 12,
             "data.classes_per_client": 2, "data.samples_per_client": 20,
             "tiers.n_tiers": 3, "tiers.clients_per_round": 4,
             "tiers.n_unstable": 2, "engine.local_epochs": 1,
             "engine.eval_every": 2, "transport.codec": "polyline:4"},
    "reduced": [],
    "limits": {"change_gap": 0.05, "loss_gap": 0.003}}
#: a resident-data tiny language model cell that exists only in the
#: tests: nested parameters, a next-token objective.  Limits from CPU
#: readings over 12 seeds: sound runs read change_gap up to 6.7e-5 and
#: loss_gap up to 8.1e-8; the bfloat16 control reads at least 0.61 and
#: 2.6e-5, half the batch 0.41 and 3.1e-4, an unchanged state 1.0 and
#: 4.7e-3
TINY_LM = {
    "name": "small_tiny_lm", "source": "test",
    "spec": {"strategy.name": "fedat", "data.model": "tiny_lm",
             "data.vocab_size": 64, "data.seq_len": 16,
             "data.n_classes": 10, "data.n_clients": 12,
             "data.classes_per_client": 2, "data.samples_per_client": 20,
             "tiers.n_tiers": 3, "tiers.clients_per_round": 4,
             "tiers.n_unstable": 2, "engine.local_epochs": 1,
             "engine.eval_every": 2, "transport.codec": "polyline:4"},
    "reduced": [],
    "limits": {"change_gap": 0.01, "loss_gap": 2e-6}}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def small_cells():
    return (["small_" + c for c in SMALL]
            + ["small_cnn.resident", "small_tiny_lm.resident"])


@pytest.fixture(scope="session")
def small_root(tmp_path_factory):
    """A root whose cells are new files only: ``small_<cell>`` for each
    real cell of SMALL, and ``small_cnn.resident``."""
    root = tmp_path_factory.mktemp("bench_root")
    os.makedirs(root / "bench" / "configs")
    os.makedirs(root / "bench" / "workloads")
    bench = _load(ROOT, "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    configs, workloads = [], []

    def add(name, config, cfg, wl, entry):
        (root / "bench" / "configs" / f"{config}.json").write_text(
            json.dumps(cfg))
        (root / "bench" / "workloads" / f"{name}.json").write_text(
            json.dumps(wl))
        workloads.append(dict(entry, name=name, config=config))
        configs.append({"name": config, "source": "test",
                        "file": f"bench/configs/{config}.json",
                        "reduced": [], "why": "test"})

    for cell, small in SMALL.items():
        config = "small_" + entries[cell]["config"]
        cfg = _load(BENCH, "configs", entries[cell]["config"] + ".json")
        cfg.update(name=config)
        cfg["spec"].update(small)
        wl = _load(BENCH, "workloads", cell + ".json")
        wl["config"] = config
        if "population.eval_clients" in wl["traffic"]:
            wl["traffic"]["population.eval_clients"] = 100
        add("small_" + cell, config, cfg, wl, entries[cell])
    for cfg in (CNN, TINY_LM):
        add(cfg["name"] + ".resident", cfg["name"], cfg,
            {"config": cfg["name"], "chips": 1,
             "traffic": {"engine.total_updates": 10 ** 9}, "why": "test"},
            {"traffic": "resident", "chips": 1, "why": "test"})
    names = {w["name"] for w in workloads}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [n for n in names
                              if n.split("_", 1)[1] in m["workloads"]]
    bench.update(workloads=workloads, configs=configs)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)
