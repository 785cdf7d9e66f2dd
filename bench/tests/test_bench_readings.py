"""The readings that decide ``correct`` name a state's leaves by path
and copy to the host only what they compare; on the Sent140 cell they
are what they were when the harness took flat dicts only."""
import json
import os

import numpy as np
import pytest

from bench import run
from bench.pytree import named
from bench.tests.conftest import BENCH

RECORDED = os.path.join(BENCH, "tests", "data", "small_sent140_readings.json")


def test_a_flat_dict_keeps_its_names():
    flat = {"w": np.zeros((3, 2)), "b": np.zeros(2)}
    assert list(named(flat)) == ["b", "w"]
    state = named({"global": flat, "tiers": flat})
    assert list(state) == ["global/b", "global/w", "tiers/b", "tiers/w"]
    assert named(state).keys() == state.keys()


def test_nested_paths_are_unique_and_sorted():
    tree = {"embed": 0, "layers": {"attn": {"wq": 1, "wk": 2},
                                   "ffn": {"w_in": 3}, "ln1": [4, 5]},
            "lm_head": 6}
    got = named(tree)
    assert list(got) == ["embed", "layers/attn/wk", "layers/attn/wq",
                         "layers/ffn/w_in", "layers/ln1/0", "layers/ln1/1",
                         "lm_head"]
    assert sorted(got.values()) == list(range(7))
    with pytest.raises(ValueError):
        named({"a/b": 0, "a": {"b": 1}})


def test_small_sent140_readings_equal_the_recorded_ones(small_root):
    with open(RECORDED) as f:
        recorded = json.load(f)
    m = run.measure(recorded["workload"], recorded["seed"], 0.5, False,
                    root=small_root, require_tpu=False)
    readings = run.readings(m)
    assert json.loads(json.dumps(readings)) == recorded["readings"]
    assert run.compare.numbers(readings) == recorded["numbers"]
    # set-up kept on the host one tier slot a step, and one whole state
    def shapes(tree):
        return {k: np.shape(v) for k, v in named(tree).items()}
    slot = shapes(m.init)
    assert [shapes(s["tier"]) for s in m.rec.steps] == [slot] * len(
        m.rec.steps) and len(m.rec.steps) == run.CHECK_STEPS
    tiers = m.flat["tiers.n_tiers"]
    assert shapes(m.rec.final) == {
        **{f"global/{k}": s for k, s in slot.items()},
        **{f"tiers/{k}": (tiers,) + s for k, s in slot.items()}}
