"""Profiler spans inside the program (DESIGN.md §Tracing).

A small streamed FedAT run under ``jax.profiler.trace``: every committed
update carries one ``repro.event`` holding the strategy (with its
``alive()`` mask) and the round (materialize, H2D copy, key split,
launch of the fused step); eval updates add ``repro.eval`` (with its
wait for the device) and ``repro.on_eval``.  The resident plane copies
no batch, and the spans change nothing the run computes.
"""
import dataclasses
import os
import sys

import jax
import pytest

from repro import api

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace_reduce  # noqa: E402

UPDATES = 12
EVAL_EVERY = 5
ROUND_PARTS = ("repro.materialize", "repro.round.h2d", "repro.round.keys",
               "repro.round.launch")


def _spec(plane: str) -> api.ExperimentSpec:
    return api.ExperimentSpec().with_overrides({
        "strategy.name": "fedat", "data.model": "logreg",
        "data.n_features": 30, "data.n_classes": 2, "data.n_clients": 3000,
        "data.samples_per_client": 2, "tiers.n_tiers": 5,
        "tiers.clients_per_round": 4, "tiers.n_unstable": 10,
        "engine.local_epochs": 1, "engine.total_updates": UPDATES,
        "engine.eval_every": EVAL_EVERY, "transport.codec": "polyline:4",
        "population.plane": plane, "population.eval_clients": 50})


def _traced(spec, log_dir):
    """Run ``spec`` under the profiler; its metrics and its ``repro.*``
    host spans as (name, start_ns, end_ns), per host line."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        metrics = api.run_spec(spec).metrics
    trace = trace_reduce.load_xplane(str(log_dir))
    lines = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            spans = sorted((n, s, s + d) for n, s, d in line["events"]
                           if n.startswith("repro."))
            if spans:
                lines.append(spans)
    return metrics, lines


def _inside(outer, spans, name=None):
    return [sp for sp in spans if sp is not outer and sp[1] >= outer[1]
            and sp[2] <= outer[2] and (name is None or sp[0] == name)]


@pytest.fixture(scope="module")
def streamed(tmp_path_factory):
    api.clear_env_cache()
    yield _traced(_spec("streaming"), tmp_path_factory.mktemp("stream"))
    api.clear_env_cache()


def test_every_committed_update_nests_its_layers(streamed):
    _, lines = streamed
    assert len(lines) == 1, "the engine loop runs on one host thread"
    spans = lines[0]
    events = [sp for sp in spans if sp[0] == "repro.event"]
    committed = [e for e in events if _inside(e, spans, "repro.round")]
    assert len(committed) == UPDATES
    for event in committed:
        strategy = _inside(event, spans, "repro.strategy")
        assert len(strategy) == 1
        assert _inside(strategy[0], spans, "repro.alive")
        rounds = _inside(strategy[0], spans, "repro.round")
        assert len(rounds) == 1
        for part in ROUND_PARTS:
            assert len(_inside(rounds[0], spans, part)) == 1, part


def test_eval_updates_carry_eval_wait_and_on_eval(streamed):
    _, lines = streamed
    spans = lines[0]
    evals = [e for e in spans if e[0] == "repro.event"
             and _inside(e, spans, "repro.eval")]
    # updates 5 and 10, and the last one (12)
    assert len(evals) == len(range(EVAL_EVERY, UPDATES, EVAL_EVERY)) + 1
    for event in evals:
        ev = _inside(event, spans, "repro.eval")
        assert len(ev) == 1
        assert len(_inside(ev[0], spans, "repro.eval.wait")) == 1
        on_eval = _inside(event, spans, "repro.on_eval")
        assert len(on_eval) == 1 and on_eval[0][1] >= ev[0][2]
    # eval spans only ever open inside an event
    all_evals = [sp for sp in spans if sp[0] == "repro.eval"]
    assert len(all_evals) == len(evals)


def test_the_resident_plane_copies_no_batch(tmp_path):
    api.clear_env_cache()
    try:
        _, lines = _traced(_spec("stacked"), tmp_path)
    finally:
        api.clear_env_cache()
    names = {sp[0] for spans in lines for sp in spans}
    assert "repro.round.h2d" not in names
    assert {"repro.round", "repro.round.keys",
            "repro.round.launch"} <= names


def test_the_profiler_changes_no_metric(streamed):
    traced, _ = streamed
    api.clear_env_cache()
    try:
        plain = api.run_spec(_spec("streaming")).metrics
    finally:
        api.clear_env_cache()
    assert dataclasses.asdict(plain) == dataclasses.asdict(traced)
