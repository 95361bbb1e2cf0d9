"""The program's spans and the traversal's scopes in a trace
(``bench/spans.py``), and the ``host_syncs_per_query`` reader, on
hand-made traces and records whose answers are known."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import catalog, spans, trace_reduce

DATA = Path(__file__).resolve().parent / "data"
REPO = Path(__file__).resolve().parents[2]


def _trace():
    """One device busy in [110, 150) and [170, 180) of a window
    [100, 200); the program's spans: a batch [105, 160) holding a dispatch
    [108, 112) and a fetch [150, 158); a tick [165, 195) holding an
    active-sync [185, 195) with a fetch [190, 195)."""
    ops = [("%fusion.1 = f32[8] fusion(...)", 110.0, 30.0),
           ("%while.2 = (s32[1]) while(...)", 120.0, 30.0),
           ("%fusion.4 = f32[8] fusion(...)", 125.0, 10.0),
           ("%copy.3 = f32[8] copy(...)", 170.0, 10.0)]
    busy, self_s = trace_reduce.ops_summary(ops)
    return {"planes": [
        {"name": "/device:TPU:0",
         "modules": [["jit_graph_search(12)", 110.0, 40.0],
                     ["jit__f(3)", 170.0, 10.0]],
         "busy": busy, "op_self_s": self_s,
         "ops": [["jit_graph_search", "%fusion.1", "gather-adjacency",
                  30e-9],
                 ["jit_graph_search", "%while.2", None, 20e-9],
                 ["jit_graph_search", "%fusion.4", "pq-lookup", 10e-9],
                 ["jit__f", "%copy.3", None, 10e-9]]},
        {"name": "/host:bench", "spans": [
            ["bench.window", 100.0, 100.0],
            ["bench.step", 105.0, 55.0],
            ["bench.step", 165.0, 30.0]]},
        {"name": "/host:CPU", "spans": [], "program_spans": [
            ["batch", 105.0, 55.0],
            ["dispatch", 108.0, 4.0],
            ["fetch", 150.0, 8.0],
            ["tick", 165.0, 30.0],
            ["active-sync", 185.0, 10.0],
            ["fetch", 190.0, 5.0]]},
    ]}


def test_scope_of_reads_bare_and_transformed_paths():
    assert spans.scope_of(
        "jit(graph_search)/while/body/vmap(gather-codes)/gather") \
        == "gather-codes"
    assert spans.scope_of("jit(f)/beam-select/argsort") == "beam-select"
    # the outermost scope wins; a name inside a word is no scope
    assert spans.scope_of("a/final-rerank/pq-lookup/x") == "final-rerank"
    assert spans.scope_of("jit(f)/my-gather-codes-x/add") is None
    assert spans.scope_of(None) is None


def test_hlo_op_names_follows_a_fusion_into_its_computation():
    text = "\n".join([
        "%fused_computation.7 (p: s32[4]) -> s32[4] {",
        '  %g = s32[4] gather(...), metadata={op_name="jit(f)/'
        'gather-adjacency/gather"}',
        '  ROOT %a = s32[4] add(...), metadata={op_name="jit(f)/'
        'merge-sort/add"}',
        "}",
        "ENTRY %main (x: s32[4]) -> s32[4] {",
        "  ROOT %fusion.3 = s32[4] fusion(...), kind=kLoop, "
        "calls=%fused_computation.7",
        "}",
    ])
    names = spans.hlo_op_names(text)
    assert spans.scope_of(names["g"]) == "gather-adjacency"
    # the fusion has no metadata of its own: its computation's root names it
    assert spans.scope_of(names["fusion.3"]) == "merge-sort"


def test_segments_label_each_instant_by_the_innermost_span():
    segs = spans.segments([(0, 10, "tick"), (2, 4, "round"),
                           (6, 9, "retire"), (7, 8, "fetch")])
    assert segs == [(0, 2, "tick", "tick"), (2, 4, "round", "tick/round"),
                    (4, 6, "tick", "tick"), (6, 7, "retire", "tick/retire"),
                    (7, 8, "fetch", "tick/retire/fetch"),
                    (8, 9, "retire", "tick/retire"), (9, 10, "tick", "tick")]


def test_idle_by_span_on_a_known_trace():
    t = _trace()
    got = spans.idle_by_span(t)
    # idle: [100,110) = 5 outside + 3 batch + 2 dispatch; [150,170) = 8
    # fetch + 2 batch + 5 outside + 5 tick; [180,200) = 5 tick + 5
    # active-sync + 5 fetch + 5 outside
    assert got == pytest.approx({
        spans.OUTSIDE: 15e-9, "fetch": 13e-9, "tick": 10e-9,
        "batch": 5e-9, "active-sync": 5e-9, "dispatch": 2e-9})
    by_path = spans.idle_by_span(t, by_path=True)
    assert by_path["tick/active-sync/fetch"] == pytest.approx(5e-9)
    assert by_path["batch/fetch"] == pytest.approx(8e-9)
    # every idle nanosecond is labelled once, as trace_reduce counts idle
    r = trace_reduce.reduce_trace(t)
    assert sum(got.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_device_scopes_keep_to_the_traversal_modules():
    got = spans.device_scopes(_trace())
    assert got == pytest.approx({"gather-adjacency": 30e-9,
                                 spans.UNSCOPED: 20e-9, "pq-lookup": 10e-9})
    assert spans.gather_device_ms_per_query(got, 3) == pytest.approx(1e-5)
    assert spans.gather_device_ms_per_query({}, 3) is None
    assert spans.gather_device_ms_per_query(got, 0) is None


_HLO = "\n".join([
    "HloModule jit_graph_search, is_scheduled=true",
    "",
    "ENTRY %main (x: s32[4]) -> f32[8] {",
    '  %fusion.2 = f32[8]{0:T(1024)} fusion(%x), kind=kLoop, '
    'calls=%fused_computation.2, metadata={op_name="jit(g)/while/body/'
    'gather-base/gather"}',
    '  ROOT %fusion.3 = (f32[8]{0}, s32[2]{0}) fusion(%x), kind=kLoop, '
    'calls=%fused_computation.3, metadata={op_name="jit(g)/final-topk/'
    'top_k"}',
    "}",
])


def test_result_shape_reads_array_and_tuple_results():
    assert spans.result_shape(
        "%fusion.5 = f32[512]{0:T(1024)S(1)} fusion(f32[64,25]{1,0} %c), "
        "kind=kCustom") == "f32[512]{0:T(1024)S(1)}"
    assert spans.result_shape(
        "  ROOT %while.1 = (s32[8,128]{1,0}, pred[]) while(%tuple.3), "
        "condition=%c") == "(s32[8,128]{1,0}, pred[])"


def test_hlo_scope_index_keys_module_instruction_and_shape():
    index = spans.hlo_scope_index([_HLO])
    assert index == {
        ("jit_graph_search", "fusion.2", "f32[8]{0:T(1024)}"): "gather-base",
        ("jit_graph_search", "fusion.3", "(f32[8]{0}, s32[2]{0})"):
            "final-topk"}


def test_scoped_ops_read_the_scope_by_instruction_and_shape():
    modules = [["jit_graph_search(5)", 0.0, 100.0]]
    # a v5e's op events: the instruction and its shapes, no op_name
    events = [
        ("%fusion.2 = f32[8]{0:T(1024)} fusion(s32[4]{0} %x), kind=kLoop",
         10.0, 20.0),
        # the same instruction name at another batch shape: not that op
        ("%fusion.2 = f32[16]{0:T(1024)} fusion(s32[4]{0} %x)", 60.0, 10.0),
        # a module the index does not hold
        ("%fusion.2 = f32[8]{0:T(1024)} fusion(s32[4]{0} %x)", 150.0, 5.0),
    ]
    got = spans.scoped_ops(events, modules, spans.hlo_scope_index([_HLO]))
    got = sorted((sec, m, op, scope) for m, op, scope, sec in got)
    assert [(m, op, scope) for _, m, op, scope in got] == [
        (None, "fusion.2", None),
        ("jit_graph_search", "fusion.2", None),
        ("jit_graph_search", "fusion.2", "gather-base")]
    assert [sec for sec, *_ in got] == pytest.approx([5e-9, 10e-9, 20e-9])


def _x(name, ts, dur, cat="serve"):
    return {"name": name, "cat": cat, "ph": "X", "ts": ts, "dur": dur,
            "pid": 0, "tid": 0, "args": {}}


def _events():
    """Two batches (100 us and 60 us) and an idle tick, in the tracer's
    Chrome form (``ts``/``dur`` in us)."""
    return [
        {"name": "process_name", "ph": "M", "pid": 0, "tid": 0, "args": {}},
        _x("batch", 0.0, 100.0),
        _x("batch-assembly", 1.0, 9.0),
        _x("kernel-execute", 10.0, 80.0, "plan"),
        _x("dispatch", 12.0, 20.0, "plan"),
        _x("device-wait", 32.0, 50.0, "plan"),
        _x("fetch", 82.0, 6.0, "plan"),
        _x("post-process", 90.0, 8.0),
        _x("tick", 150.0, 5.0),
        _x("batch", 200.0, 60.0),
        _x("kernel-execute", 205.0, 50.0, "plan"),
        _x("dispatch", 206.0, 10.0, "plan"),
        _x("device-wait", 216.0, 30.0, "plan"),
        _x("fetch", 246.0, 8.0, "plan"),
    ]


def test_steps_skip_ticks_that_did_no_work():
    got = spans.steps(_events())
    assert [(e["name"], e["ts"]) for e, _ in got] == [("batch", 0.0),
                                                      ("batch", 200.0)]


def test_sched_host_ms_per_step():
    # self time of the serve spans: batch 100-9-80-8 = 3, assembly 9,
    # post-process 8; batch 60-50 = 10 -> (20 + 10) / 2 steps = 15 us
    assert spans.sched_host_ms_per_step(_events()) == pytest.approx(0.015)
    assert spans.sched_host_ms_per_step([_x("tick", 0.0, 5.0)]) is None


def test_plan_host_ms_per_dispatch():
    # plan self time less device-wait: kernel-execute 80-76 = 4, dispatch
    # 20, fetch 6; kernel-execute 50-48 = 2, dispatch 10, fetch 8 -> 50 us
    # over 2 launches
    assert spans.plan_host_ms_per_dispatch(_events()) == \
        pytest.approx(0.025)
    assert spans.plan_host_ms_per_dispatch([_x("batch", 0.0, 1.0)]) is None


def test_longest_steps_name_their_spans():
    top = spans.longest_steps(_events(), n=1)
    name, ts, dur, below = top[0]
    assert (name, ts, dur) == ("batch", 0.0, 100.0)
    assert dict(below)["batch/kernel-execute/device-wait"] == 50.0
    assert [p for p, _ in below][:2] == ["batch/batch-assembly",
                                         "batch/kernel-execute"]


def test_host_syncs_per_query_reader():
    read = catalog.reader("host_syncs_per_query", REPO)
    assert read({"engine": {"device_syncs": 30, "queries": 12}}) == 2.5
    # a program without the count (an older engine), or no queries
    assert read({"engine": {"queries": 12}}) is None
    assert read({"engine": {"device_syncs": 3, "queries": 0}}) is None


def test_the_plain_form_with_spans_reduces_as_before():
    """The extra keys of the spans form leave ``reduce_trace`` unchanged,
    on the recorded v5e window as on the hand-made one."""
    old = trace_reduce.load(str(DATA / "v5e_window.json.gz"))
    want = trace_reduce.reduce_trace(old)
    for plane in old["planes"]:
        if trace_reduce._is_device(plane):
            plane["ops"] = [["jit_graph_search", "%fusion.260", None, 1.0]]
        else:
            plane["program_spans"] = [["batch", 0.0, 1e12]]
    assert trace_reduce.reduce_trace(old) == want
    assert want["busy_s"] == pytest.approx(0.21525832899996136)
    assert want["module_s"] == pytest.approx(
        {"jit_graph_search": 0.21528446999996137})
    assert dict(want["idle_gaps"])["bench.step"] == \
        pytest.approx(0.08472939100003832)
    assert want["device_ops"][0][0] == "%fusion.260"


def test_recorded_v5e_window_with_program_spans_and_scopes():
    """300 ms of a glove-cont-burst traced window on one v5e with the
    program's tracer on (``data/v5e_spans_window.json.gz``, written by
    ``bench/profile_cell.py --fixture``): every instant of device idle is
    labelled once, nearly all of it by the continuous scheduler's leaf
    spans, and the traversal's device time by its named scopes."""
    t = trace_reduce.load(str(DATA / "v5e_spans_window.json.gz"))
    r = trace_reduce.reduce_trace(t)
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(0.3)
    assert r["busy_s"] == pytest.approx(0.074012992)
    idle = r["window_s"] - r["busy_s"]
    assert dict(r["idle_gaps"]) == pytest.approx({"bench.step": idle})

    by_span = spans.idle_by_span(t)
    assert sum(by_span.values()) == pytest.approx(idle)
    assert by_span["fetch"] == pytest.approx(0.0745857350000267)
    assert by_span["round"] == pytest.approx(0.037400731)
    assert list(by_span)[:3] == ["fetch", "round", "device-wait"]
    parents = {"tick", "refill", "admit", "active-sync", "retire",
               spans.OUTSIDE}
    leaves = sum(v for k, v in by_span.items() if k not in parents)
    assert leaves > 0.9 * idle
    by_path = spans.idle_by_span(t, by_path=True)
    assert sum(v for k, v in by_path.items()
               if k.startswith("tick/retire/")) > 0.3 * idle

    scopes = spans.device_scopes(t)
    assert list(scopes)[:3] == ["gather-adjacency", "pq-lookup",
                                "merge-sort"]
    assert scopes["gather-adjacency"] == pytest.approx(0.03401396399999999)
    traversal = sum(v for k, v in r["module_s"].items()
                    if k in spans.TRAVERSAL_MODULES)
    assert sum(scopes.values()) == pytest.approx(traversal, rel=0.02)
    assert scopes[spans.UNSCOPED] < 0.1 * sum(scopes.values())
