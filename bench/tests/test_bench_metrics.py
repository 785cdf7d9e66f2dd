"""Each per-layer metric of BENCHMARK.json is found by its name and read
from a reduced trace and the window's spans; a reader with nothing to
read returns None, never 0."""
import types

import pytest

from bench import run
from bench.tests.conftest import ROOT


def _ctx(**over):
    ctx = types.SimpleNamespace(
        trace={"window_s": 2.0, "busy_s": 1.5, "devices": 1,
               "modules": {"jit_step(12)": 1.2, "jit_evaluate(3)": 0.1},
               "ops": {}, "idle": {}},
        updates=4, spans={"eval": 0.2, "dispatch": 0.1}, chips=1,
        peak_flops=197e12, train_flops=3.94e12, wire_bytes=2.4e6)
    vars(ctx).update(over)
    return ctx


def test_every_listed_reader_reads_the_window():
    names = [m["name"] for m in run.load_cell(
        "logreg_sent140.stream_660k", ROOT).per_layer]
    ctx = _ctx(spans={"eval": 0.2, "materialize": 0.04})
    got = {n: run.read_metric(n, ctx) for n in names}
    assert got == pytest.approx({
        "round_step_device_ms": 300.0, "eval_ms_per_update": 50.0,
        "materialize_ms_per_update": 10.0, "device_idle_share": 25.0,
        "wire_MB_per_update": 0.6})


def test_mfu_counts_the_live_rows_against_the_bf16_peak():
    assert run.read_metric("mfu", _ctx()) == pytest.approx(1.0)


def test_readers_with_nothing_to_read_return_none():
    ctx = _ctx(trace={"window_s": 2.0, "busy_s": 1.0, "devices": 1,
                      "modules": {}, "ops": {}, "idle": {}},
               spans={}, peak_flops=None, wire_bytes=0.0)
    for name in ("round_step_device_ms", "mfu", "eval_ms_per_update",
                 "materialize_ms_per_update", "wire_MB_per_update"):
        assert run.read_metric(name, ctx) is None
