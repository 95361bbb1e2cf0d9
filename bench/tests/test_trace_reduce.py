"""The reduction from a profiler trace to device metrics, checked on a
hand-made trace whose answers are known and on a small trace recorded on
one v5e (``data/v5e_window.json.gz``)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def _ops():
    # ops overlap in [110, 150), then [170, 180); "early" starts before
    # the window; "while.2" nests "fusion.4"
    return [("%fusion.1 = f32[8] fusion(...)", 110.0, 30.0),
            ("%while.2 = (s32[1]) while(...)", 120.0, 30.0),
            ("%fusion.4 = f32[8] fusion(...)", 125.0, 10.0),
            ("%copy.3 = f32[8] copy(...)", 170.0, 10.0),
            ("%early = f32[8] copy(...)", 90.0, 15.0)]


def _trace():
    busy, self_s = trace_reduce.ops_summary(_ops())
    return {"planes": [
        {"name": "/device:TPU:0",
         "modules": [["jit_graph_search(12)", 110.0, 40.0],
                     ["jit_graph_search(12)", 170.0, 10.0],
                     ["jit__f(3)", 90.0, 15.0]],
         "busy": busy, "op_self_s": self_s},
        {"name": "/host:bench", "spans": [
            ["bench.window", 100.0, 100.0],
            ["bench.wait", 100.0, 10.0],
            ["bench.step", 150.0, 25.0],
            ["bench.submit", 185.0, 15.0]]},
    ]}


def test_union_of_busy_intervals():
    assert trace_reduce.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == \
        [[0, 3], [5, 10]]


def test_ops_summary_self_time_and_busy():
    busy, self_s = trace_reduce.ops_summary(_ops())
    assert busy == [[90.0, 105.0], [110.0, 150.0], [170.0, 180.0]]
    assert self_s["%while.2"] == pytest.approx(20e-9)    # 30 less fusion.4
    assert self_s["%fusion.4"] == pytest.approx(10e-9)
    assert self_s["%fusion.1"] == pytest.approx(30e-9)


def test_busy_idle_and_module_time_on_a_known_trace():
    r = trace_reduce.reduce_trace(_trace())
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: [100,105) from "early" clipped, [110,150), [170,180) = 55 ns
    assert r["busy_s"] == pytest.approx(55e-9)
    assert r["devices"] == 1
    assert r["module_s"]["jit_graph_search"] == pytest.approx(50e-9)
    assert r["module_s"]["jit__f"] == pytest.approx(5e-9)
    ops = dict(r["device_ops"])
    assert ops["%fusion.1"] == pytest.approx(30e-9)
    assert ops["%while.2"] == pytest.approx(20e-9)
    # idle [105,110) under wait, [150,170) under step; [180,200) goes
    # whole to submit, which covers most of it
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"bench.wait": 5e-9, "bench.step": 20e-9,
                                  "bench.submit": 20e-9})
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_a_trace_without_the_window_is_refused():
    t = _trace()
    t["planes"][1]["spans"].pop(0)
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace(t)


def test_recorded_v5e_window():
    """300 ms of a sift-batch-poisson window on one v5e (batch flush at
    150 q/s): the device runs only ``graph_search`` modules, so their time
    is the busy time, and the host was inside ``step()`` for the idle."""
    r = trace_reduce.reduce_trace(
        trace_reduce.load(str(DATA / "v5e_window.json.gz")))
    assert r["window_s"] == pytest.approx(0.3)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.21525832899996136)
    assert r["module_s"] == pytest.approx(
        {"jit_graph_search": 0.21528446999996137})
    assert r["busy_s"] <= r["window_s"]
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.step"] == pytest.approx(0.08472939100003832)
    assert r["busy_s"] + sum(gaps.values()) == pytest.approx(r["window_s"])
    assert r["device_ops"][0][0] == "%fusion.260"
