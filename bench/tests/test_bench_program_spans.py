"""The split of a traced window by the program's own ``repro.*`` spans
(``bench/program_spans.py``) on small hand-made traces, on a recorded
chip trace, and on a traced CPU run of a small cell."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import program_spans as ps
from bench import program_trace, run
from bench import trace_reduce as tr
from bench.tests.conftest import ROOT

MS = 1e6


def _trace(host, ops, other=()):
    """A trace of one host line (``host``: [name, start_ms, end_ms]), an
    optional second host line, and one device whose ops run at ``ops``."""
    def events(spans):
        return [[n, s * MS, (e - s) * MS] for n, s, e in spans]
    lines = [{"name": "python", "events": events(host)}]
    if other:
        lines.append({"name": "worker", "events": events(other)})
    return {"planes": [
        {"name": "/host:CPU", "lines": lines},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", s * MS,
                 (e - s) * MS] for s, e in ops]}]}]}


#: three events: one open when the window starts, one with an eval, one
#: still open when it ends; the device runs 28-32 and 70-80
HOST = [
    ["bench.window", 0, 100],
    ["repro.event", -5, 45], ["repro.strategy", -4, 30],
    ["repro.alive", 2, 6], ["repro.round", 10, 30],
    ["repro.materialize", 10, 18], ["repro.round.keys", 18, 24],
    ["repro.round.launch", 24, 28],
    ["repro.event", 50, 95], ["repro.strategy", 50, 60],
    ["repro.eval", 62, 90], ["repro.eval.wait", 62, 70],
    ["repro.on_eval", 90, 94],
    ["repro.event", 97, 110], ["repro.strategy", 98, 108],
    ["PjitFunction(step)", 24, 27]]
OPS = [(28, 32), (70, 80)]


def _ms(table):
    return {k: v * 1e3 for k, v in table.items()}


def test_self_time_leaves_out_nested_program_spans_and_is_clipped():
    r = ps.reduce(_trace(HOST, OPS))
    # an event's own time leaves out its strategy, eval and on_eval; the
    # first event and strategy are cut at 0, the last ones at 100
    assert _ms(r["self_s"]) == pytest.approx({
        "repro.event": 15 + 3 + 1, "repro.strategy": 6 + 10 + 2,
        "repro.alive": 4, "repro.round": 2, "repro.materialize": 8,
        "repro.round.keys": 6, "repro.round.launch": 4,
        "repro.eval": 20, "repro.eval.wait": 8, "repro.on_eval": 4})
    # every instant inside a span counts once: 100 less the 7 between
    # events (45-50, 95-97)
    assert sum(r["self_s"].values()) == pytest.approx(0.093)


def test_nesting_is_per_host_line():
    other = [["repro.alive", 12, 16]]
    r = ps.reduce(_trace(HOST, OPS, other))
    # a span on another thread is no child of the round on this one
    assert r["self_s"]["repro.round"] == pytest.approx(0.002)
    assert r["self_s"]["repro.alive"] == pytest.approx(0.008)


def test_idle_is_split_by_overlap_with_the_innermost_span():
    r = ps.reduce(_trace(HOST, OPS))
    assert _ms(r["idle_s"]) == pytest.approx({
        "repro.strategy": 2 + 4 + 10 + 2, "repro.alive": 4,
        "repro.materialize": 8, "repro.round.keys": 6,
        "repro.round.launch": 4, "repro.event": 13 + 2 + 1 + 1,
        ps.UNATTRIBUTED: 5 + 2, "repro.eval.wait": 8, "repro.eval": 10,
        "repro.on_eval": 4})
    assert sum(r["idle_s"].values()) == pytest.approx(
        tr.reduce(_trace(HOST, OPS))["window_s"]
        - tr.reduce(_trace(HOST, OPS))["busy_s"])


def test_a_gap_across_two_spans_is_split_where_the_midpoint_is_not():
    """One idle gap (0-40) under two spans: the benchmark's midpoint
    label gives all of it to the span at 20; the overlap split gives each
    span its share."""
    host = [["bench.window", 0, 100],
            ["bench.materialize", 0, 22], ["bench.dispatch", 22, 40],
            ["repro.materialize", 0, 22], ["repro.round.launch", 22, 40]]
    trace = _trace(host, [(40, 100)])
    assert _ms(tr.reduce(trace)["idle"]) == pytest.approx(
        {"materialize": 40})
    assert _ms(ps.reduce(trace)["idle_s"]) == pytest.approx(
        {"repro.materialize": 22, "repro.round.launch": 18})


def test_idle_under_no_span_goes_to_loop():
    host = [["bench.window", 0, 100], ["repro.event", 20, 30]]
    r = ps.reduce(_trace(host, [(50, 60)]))
    assert _ms(r["idle_s"]) == pytest.approx(
        {"repro.event": 10, ps.UNATTRIBUTED: 80})


def test_event_cover_counts_from_the_first_event_inside_the_window():
    # from 50 (the event open at 0 is left out): 45 + 3 of 50
    assert ps.reduce(_trace(HOST, OPS))["event_cover"] == pytest.approx(
        0.96)


def test_per_update_costs_and_their_absence():
    r = ps.reduce(_trace(HOST, OPS))
    got = ps.per_update_ms(r, 2)
    assert got == pytest.approx({
        "strategy_ms_per_update": (18 + 19) / 2,
        "alive_ms_per_update": 2.0, "round_keys_ms_per_update": 3.0,
        "round_h2d_ms_per_update": None,
        "round_launch_ms_per_update": 2.0})
    assert got["round_h2d_ms_per_update"] is None


def test_a_program_without_spans_reads_nothing():
    host = [["bench.window", 0, 100], ["bench.dispatch", 10, 20]]
    r = ps.reduce(_trace(host, OPS))
    assert r == {"self_s": {}, "idle_s": {}, "event_cover": None}
    assert set(ps.per_update_ms(r, 4).values()) == {None}
    with pytest.raises(ValueError):
        ps.reduce(_trace([["repro.event", 0, 10]], OPS))


def _recorded():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_trace_program_spans.json")
    with open(path) as f:
        return json.load(f)


def test_a_recorded_tpu_trace_with_program_spans():
    """A slice of a traced window of the stream_660k cell on a TPU v5e,
    with the benchmark's spans and the program's (op names cut to 160
    characters): the program's events cover the slice, every host cost
    reads a number, and the midpoint labels are kept beside the overlap
    split, which they misattribute."""
    trace = _recorded()
    r = ps.reduce(trace)
    old = tr.reduce(trace)
    idle = old["window_s"] - old["busy_s"]
    assert r["event_cover"] >= 0.95
    events = [s for p in trace["planes"] for line in p["lines"]
              for n, s, _ in line["events"] if n == ps.EVENT and s > 0]
    assert len(events) >= 3
    costs = ps.per_update_ms(r, len(events))
    assert all(v is not None and v > 0 for v in costs.values()), costs
    assert sum(r["idle_s"].values()) == pytest.approx(idle)
    assert sum(old["idle"].values()) == pytest.approx(idle)
    assert r["idle_s"].get(ps.UNATTRIBUTED, 0.0) <= 0.05 * idle
    # the midpoint labels give materialize more idle time than the
    # materialize spans last; the overlap split never does
    assert old["idle"]["materialize"] > r["self_s"]["repro.materialize"]
    assert r["idle_s"]["repro.materialize"] <= (
        r["self_s"]["repro.materialize"] + 1e-12)


def test_program_trace_on_a_small_cell(small_root, tmp_path):
    """The program-span reading of a traced window on the CPU: every host
    cost reads a number (the cell streams its clients), and a slice of
    the trace reduces as the recorded one does."""
    m = run.measure("small_logreg_sent140.stream_660k", 3_000_000_019, 1.0,
                    True, root=small_root, require_tpu=False)
    try:
        trace = tr.load_xplane(m.log_dir)
    finally:
        shutil.rmtree(m.log_dir, ignore_errors=True)
    updates = len(m.rec.update_times)
    line = program_trace.program_line(trace, updates)
    assert all(v is not None and v > 0
               for v in line["per_update_ms"].values()), line
    assert line["event_cover"] >= 0.95
    assert {"repro.materialize", "repro.eval",
            "repro.on_eval"} <= set(line["self_ms_per_update"])
    part = program_trace.cut(trace, 5.0)
    json.dumps(part)
    assert tr.host_spans(part)[0] == (tr.WINDOW, 0.0, 5 * MS)
    assert ps.reduce(part)["self_s"]


def test_program_trace_exits_nonzero_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "bench/program_trace.py", "--workload",
         "logreg_sent140.stream_660k", "--seed", "3000000019"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout.strip() == ""
