"""The readers of the program's own spans and scopes (``metrics/_program.py``
and the seven metrics built on it), on small traces whose answers are
worked out by hand, and the loader on the events a profiler gives."""
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import spec

sys.path.insert(0, os.path.join(spec.BENCH_DIR, "metrics"))
import _program as pg  # noqa: E402

US = 1000  # ns


def _op(path, start_us, end_us):
    return [path, start_us * US, (end_us - start_us) * US]


# one traced update call, then two traced query batches; times in us
OPS = [
    _op("ann.map", 0, 10),
    _op("ann.insert.search", 10, 40),
    _op("ann.insert.search/ann.search.hops", 15, 35),
    _op("ann.insert.link", 40, 100),
    _op("ann.insert.link/ann.prune", 42, 48),
    _op("ann.insert.link/ann.edges.append", 50, 90),
    _op("ann.insert.link/ann.edges.append/ann.prune", 55, 75),
    _op("ann.delete.search", 100, 130),
    _op("ann.delete.repair", 130, 200),
    _op("ann.delete.repair/ann.edges.append/ann.prune", 150, 170),
    _op("ann.map", 200, 205),
    _op("ann.consolidate", 205, 207),
    _op("ann.search.hops", 300, 380),
    _op("ann.search.select", 380, 390),
    _op("ann.search.hops", 400, 440),
]
SPANS = [[n, s * US, e * US] for n, s, e in [
    ("bench.traffic", 0, 3000),
    ("bench.update.wait", 205, 280),
    ("bench.query.call", 290, 398),
    ("ann.search", 292, 397),
    ("ann.search.pad", 292, 294),
    ("ann.search.dispatch", 294, 392),
    ("ann.search.map_ids", 392, 397),
    ("ann.search", 1000, 1100),
    ("ann.search.dispatch", 1005, 1090),
    ("ann.search", 2000, 2040),
    ("ann.search.dispatch", 2010, 2030),
]]


def _device_trace():
    """The same operations as ``trace_reduce`` sees them, plus a layout
    copy [207, 210) that carries no scope, inside an apply_segment
    program [0, 210) and a search program [300, 440)."""
    ops = [["op", s, d, ""] for _, s, d in OPS] + [["copy", 207 * US,
                                                    3 * US, ""]]
    mods = [["jit_apply_segment(1)", 0, 210 * US, ""],
            ["jit_batched_greedy_search(2)", 300 * US, 140 * US, ""]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": mods}]}]}


def _grouped(ops):
    out = {}
    for path, s, d in ops:
        out.setdefault(path, []).append([s, d])
    return out


def _run(program=None, updates=(True,), searches=(True, True)):
    run = harness.Run(cell=None, cfg=None, peaks={})
    run.trace = _device_trace()
    run.program_trace = {"devices": [_grouped(OPS)], "spans": SPANS} \
        if program is None else program
    run.updates = [{"traced": t} for t in updates]
    run.searches = [{"traced": t} for t in searches]
    return run


# by hand: insert search [10,40) holds its hop loop [15,35): 30 us; insert
# link [40,100) holds its prunes and appends: 60 us; delete search 30 us;
# delete repair [130,200): 70 us; prunes beneath appends [55,75) and
# [150,170): 40 us; all per the one traced update call.  The search
# program's hop loop [300,380) and [400,440): 120 us over two traced
# batches.  Front door self time: 105 - 98 = 7, 100 - 85 = 15 and
# 40 - 20 = 20 us, median 15 us.
EXPECTED = {
    "update.insert_search_ms": 0.030,
    "update.insert_link_ms": 0.060,
    "update.delete_search_ms": 0.030,
    "update.delete_repair_ms": 0.070,
    "update.append_prune_ms": 0.040,
    "search.hop_loop_ms": 0.060,
    "search.front_door_self_ms": 0.015,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_on_a_known_trace(metric):
    read = spec.load_reader(metric)
    assert read(_run()) == pytest.approx(EXPECTED[metric])
    # a run whose program has no scopes and no front door spans (an older
    # checkout), or a run without a trace, reads nothing rather than 0
    assert read(_run(program={"devices": [], "spans": []})) is None
    run = _run()
    run.trace = None
    assert read(run) is None


@pytest.mark.parametrize("metric", sorted(set(EXPECTED)
                                          - {"search.front_door_self_ms"}))
def test_reader_without_traced_calls_reads_nothing(metric):
    run = _run(updates=(False,), searches=(False, False))
    assert spec.load_reader(metric)(run) is None


def test_phase_coverage_and_idle_gaps():
    run = _run()
    # phases cover [0, 207) of the 210 us busy inside apply_segment
    assert pg.phase_coverage(run) == pytest.approx(100 * 207 / 210)
    # gaps [210, 300) (middle 255: in bench.update.wait) and [390, 400)
    # (middle 395: in the map_ids child of the front door)
    assert pg.idle_gaps(run) == [
        ["bench.update.wait", pytest.approx(90e-6)],
        ["ann.search.map_ids", pytest.approx(10e-6)]]


def test_program_is_read_once_and_not_without_a_trace(tmp_path,
                                                      monkeypatch):
    run = _run()
    del run.program_trace
    run.cell = SimpleNamespace(name="none")
    monkeypatch.setattr(spec, "BENCH_DIR", str(tmp_path))
    assert pg.program(run) is None            # nothing was written there
    calls = []
    monkeypatch.setattr(pg, "load", lambda d: calls.append(d) or
                        {"devices": [_grouped(OPS)], "spans": SPANS})
    del run.program_trace
    assert pg.program(run) is pg.program(run)
    assert calls == [os.path.join(str(tmp_path), ".cache", "trace", "none")]
    run.trace = None
    del run.program_trace
    assert pg.program(run) is None


def _event(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           end_ns=start + dur)


def test_scoped_ops_joins_events_to_their_programs_hlo():
    upd = "jit(apply_segment)/while/body/closed_call/"
    mods = SimpleNamespace(events=[_event("jit_apply_segment(1)", 0, 100),
                                   _event("jit_other(2)", 200, 100)])
    op_names = {
        "jit_apply_segment(1)": {
            "fusion.1": upd + "ann.map/add",
            "while.2": upd + "jit(f)/ann.insert.link/while/body/"
                             "ann.edges.append/vmap(jit(p))/ann.prune/while",
        },
        # the same instruction name in another program
        "jit_other(2)": {"fusion.1": "jit(other)/add",
                         "fusion.4": "jit(other)/ann.map/x"},
    }
    ops = SimpleNamespace(events=[
        _event("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop",
               0, 10),
        _event("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop",
               20, 10),
        _event("%while.2 = (s32[]) while((s32[]) %t), body=%b", 30, 60),
        _event("%copy.3 = s32[8]{0} copy(s32[8]{0} %p)", 95, 5),
        _event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %q)", 200, 10),
        _event("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %q)", 210, 10),
        _event("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %q)", 400, 10),
    ])
    assert pg.scoped_ops(ops, mods, op_names) == {
        "ann.map": [[0, 10], [20, 10], [210, 10]],
        "ann.insert.link/ann.edges.append/ann.prune": [[30, 60]]}


def test_load_keeps_the_program_spans_and_hlo_of_a_cpu_trace(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped(x):
        with jax.named_scope("ann.map"):
            return jnp.sin(x) * 2

    jax.block_until_ready(scoped(jnp.ones(4)))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.query.call"):
            with jax.profiler.TraceAnnotation("ann.search"):
                jax.block_until_ready(scoped(jnp.ones(4)))
        with jax.profiler.TraceAnnotation("other"):
            pass
    finally:
        jax.profiler.stop_trace()
    trace = pg.load(str(tmp_path))
    assert [s[0] for s in trace["spans"]] == ["bench.query.call",
                                              "ann.search"]
    (_, s0, e0), (_, s1, e1) = trace["spans"]
    assert s0 <= s1 <= e1 <= e0
    assert trace["devices"] == []            # the CPU has no device plane
    # the program's HLO, decoded from the trace's metadata plane
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    with open(path, "rb") as f:
        programs = pg.hlo_op_names(memoryview(f.read()))
    ops = next(v for k, v in programs.items() if k.startswith("jit_scoped"))
    assert {pg.scopes_of(n) for n in ops.values()} >= {"ann.map"}
    assert any(n.endswith("ann.map/sin") for n in ops.values())
    with pytest.raises(FileNotFoundError):
        pg.load(str(tmp_path / "empty"))


def test_scopes_and_nesting():
    assert pg.scopes_of("jit(a)/ann.insert.link/while/body/ann.prune/x") \
        == "ann.insert.link/ann.prune"
    keep = pg.nested(pg.EDGES_APPEND, pg.PRUNE)
    assert keep(["ann.delete.repair", "ann.edges.append", "ann.prune"])
    assert not keep(["ann.insert.link", "ann.prune"])
    assert not keep(["ann.prune", "ann.edges.append"])
    assert pg.outermost_phase(["ann.insert.search", "ann.search.hops"]) \
        == "ann.insert.search"
    assert np.isclose(pg.union_ms({"devices": [_grouped(OPS),
                                               _grouped(OPS[:1])]},
                                  lambda sc: sc == ["ann.map"]), 0.0125)


# The recorded v5e trace (data/program_v5e_tiny.json), worked by hand in ns
# over its 2 traced update calls and 4 traced query batches:
#   insert search: [337,787) [1135,1138) [1142,1689) [3859,3870)
#     = 450 + 3 + 547 + 11 = 1,011 -> 505.5 per call
#   insert link: 3 + 246 + 134 + 6 + 433 + 3 + 2 + 102 = 929 -> 464.5
#   delete search: 255 + 86 + 7 + (666 + 7 adjacent) + 12 = 1,033 -> 516.5
#   delete repair: one loop [12,979,816, +158,747,826) holds the rest
#     -> 79,373,913
#   prunes beneath appends: link 2 + 102, repair 3 + 101 = 208 -> 104
#     (the link phase's own prunes, [2006,2009) and [8104,8238), are not
#     beneath an append)
#   search hop loop: [174,978,252, +331) [174,978,585, +10) = 341 -> 85.25
#   front door: ann.search less dispatch, 5,163,410 4,879,860 5,010,830
#     4,577,890 -> median 4,945,345
RECORDED = {
    "update.insert_search_ms": 505.5e-6,
    "update.insert_link_ms": 464.5e-6,
    "update.delete_search_ms": 516.5e-6,
    "update.delete_repair_ms": 79.373913,
    "update.append_prune_ms": 104e-6,
    "search.hop_loop_ms": 85.25e-6,
    "search.front_door_self_ms": 4.945345,
}


@pytest.mark.parametrize("metric", sorted(RECORDED))
def test_reader_on_a_recorded_chip_trace(metric):
    import json

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "data", "program_v5e_tiny.json")) as f:
        rec = json.load(f)
    run = _run(program={"devices": rec["devices"], "spans": rec["spans"]},
               updates=(True,) * rec["traced_updates"],
               searches=(True,) * rec["traced_searches"])
    assert spec.load_reader(metric)(run) == pytest.approx(RECORDED[metric],
                                                          rel=1e-9)
