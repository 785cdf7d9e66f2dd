"""The reduction from a trace to the window's numbers, on a small
hand-made trace."""
import pytest

from bench import trace_reduce as tr

MS = 1e6


def _trace():
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 0.0, 100 * MS],
            ["bench.pop_strategy", 0.0, 30 * MS],
            ["bench.dispatch", 10 * MS, 15 * MS],
            ["bench.eval", 60 * MS, 30 * MS],
            ["PjitFunction(step)", 12 * MS, 1 * MS]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_step(7)", 20 * MS, 40 * MS],
                ["jit_evaluate(3)", 70 * MS, 10 * MS],
                ["jit_step(7)", 95 * MS, 10 * MS]]},
            {"name": "XLA Ops", "events": [
                ["%while.3 = (s32[]) while((s32[]) %t), body=%b", 20 * MS,
                 40 * MS],
                ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop",
                 20 * MS, 25 * MS],
                ["%convolution.2 = f32[8]{0} convolution(f32[8]{0} %a)",
                 45 * MS, 10 * MS],
                ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop",
                 70 * MS, 10 * MS],
                ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop",
                 95 * MS, 10 * MS]]}]}]}


def test_busy_is_the_union_of_ops_within_the_window():
    r = tr.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.1)
    # 20-60 (nested ops count once), 70-80, 95-100 (clipped)
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["devices"] == 1


def test_module_time_and_op_self_time():
    r = tr.reduce(_trace())
    assert r["modules"]["jit_step(7)"] == pytest.approx(0.050)
    assert r["modules"]["jit_evaluate(3)"] == pytest.approx(0.010)
    # the loop's own time leaves out its body ops
    assert r["ops"]["%while.3 while"] == pytest.approx(0.005)
    assert r["ops"]["%convolution.2 convolution"] == pytest.approx(0.010)
    assert tr.top(r["ops"], 1) == [["%fusion.1 fusion",
                                    pytest.approx(0.040)]]


def test_idle_gaps_go_to_the_innermost_open_span():
    r = tr.reduce(_trace())
    # 0-20: dispatch's gap midpoint 10 lies in pop_strategy and dispatch
    # (innermost: dispatch); 60-70: eval; 80-95: eval until 90, midpoint
    # 87.5 -> eval
    assert r["idle"] == pytest.approx({"dispatch": 0.020, "eval": 0.025})


def test_a_trace_without_the_window_or_a_device_is_refused():
    t = _trace()
    t["planes"][0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError):
        tr.reduce(t)
    t = _trace()
    t["planes"].pop(1)
    with pytest.raises(ValueError):
        tr.reduce(t)


def test_a_recorded_tpu_trace():
    """25 ms of a traced window of the stream_660k cell on a TPU v5e
    (op names cut to 160 characters)."""
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_trace_stream_660k.json")
    with open(path) as f:
        r = tr.reduce(json.load(f))
    assert r["window_s"] == pytest.approx(0.025)
    assert 0 < r["busy_s"] < r["window_s"]
    step = [v for k, v in r["modules"].items() if k.startswith("jit_step(")]
    assert len(step) == 1 and step[0] == pytest.approx(3.64923e-4)
    assert sum(r["idle"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert set(r["idle"]) <= {"materialize", "dispatch", "pop_strategy",
                              "eval", "wait_device", tr.UNATTRIBUTED}
    assert tr.top(r["ops"], 1)[0][0] == "%fusion.61 fusion"
