"""The reduction from a profiler trace to busy time, per-program busy time
and the breakdown, on small traces whose answers are known."""
import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def _trace():
    """Device 0: ops at [0,10) [5,20) [30,40) ns inside an apply_segment
    program [0,25) and a search program [28,45); device 1: one op [0,20).
    Host: a query span over [21,29)."""
    ops0 = [["fusion.1", 0, 10, "jit_apply_segment"],
            ["fusion.2", 5, 15, "jit_apply_segment"],
            ["gather.3", 30, 10, "jit_search_batch"]]
    mods0 = [["jit_apply_segment(1)", 0, 25, ""],
             ["jit_search_batch(2)", 28, 17, ""]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops0},
            {"name": "XLA Modules", "events": mods0}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["fusion.9", 0, 20, "m"]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["bench.query.call", 21, 8, ""],
                ["bench.traffic", 0, 100, ""]]}]},
    ]}


def test_merge_and_intersect():
    assert tr.merge([(5, 20), (0, 10), (30, 40)]) == [[0, 20], [30, 40]]
    assert tr.intersect([[0, 20], [30, 40]], [[10, 35]]) == [[10, 20],
                                                             [30, 35]]
    assert tr.length([[0, 20], [30, 40]]) == 30


def test_busy_is_the_union_of_ops_averaged_over_devices():
    # device 0: [0,20) + [30,40) = 30 ns; device 1: 20 ns; mean 25 ns
    assert tr.busy_s(_trace()) == pytest.approx(25e-9)


def test_busy_in_programs_counts_ops_inside_matching_programs():
    t = _trace()
    # update: ops [0,20) inside [0,25) on device 0 = 20 ns; device 1 has no
    # module line and no op of that module: 0 -> mean 10 ns
    assert tr.busy_in_programs(t, "apply_segment") == pytest.approx(10e-9)
    assert tr.busy_in_programs(t, "search") == pytest.approx(5e-9)
    assert tr.busy_in_programs(t, "nothing") == 0.0


def test_breakdown_names_gaps_by_innermost_host_span():
    b = tr.breakdown(_trace())
    assert b["device_ops"][0] == ["fusion.9", pytest.approx(10e-9)]
    # the one gap on device 0 is [20,30), midpoint 25: inside both host
    # spans, the query call is the innermost
    assert b["idle_gaps"] == [["bench.query.call", pytest.approx(10e-9)]]


def test_no_device_plane_reads_nothing():
    t = {"planes": [p for p in _trace()["planes"]
                    if not p["name"].startswith("/device:")]}
    assert tr.busy_s(t) == 0.0
    assert tr.breakdown(t) == {"device_ops": [], "idle_gaps": []}


RECORDED = {
    "gmm128-hr.churn": "apply_segment",
    "gmm128-lr.search": "search",
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_chip_trace(cell):
    """Traces recorded on a v5e by the cells' ``--trace 1`` runs (cut to
    the first 1,500 events of each line): the reduction finds the device,
    the program that does the cell's work, and the host spans."""
    with open(os.path.join(HERE, "data", f"trace_{cell}.json")) as f:
        t = json.load(f)
    busy = tr.busy_s(t)
    assert busy > 0
    assert 0 < tr.busy_in_programs(t, RECORDED[cell]) <= busy
    assert any(n.startswith("bench.") for n, _, _ in tr.host_spans(t))
    b = tr.breakdown(t)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(s >= 0 for _, s in b["device_ops"] + b["idle_gaps"])
