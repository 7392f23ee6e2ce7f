"""The p95, roofline and idle-share arithmetic, each on a known base."""
import numpy as np
import pytest

import harness
import spec
from repro.core.types import ANNConfig, KIND_DELETE, KIND_INSERT


def test_p95_interpolates_over_every_sample():
    assert harness.p95(range(1, 101)) == pytest.approx(95.05)
    assert harness.p95([7.0]) == 7.0


def _run(trace, searches=(), updates=(), window_s=0.0):
    cfg = ANNConfig(dim=128, n_cap=1024, r=32)
    run = harness.Run(cell=None, cfg=cfg,
                      peaks={"hbm_bytes_per_s": 1e9})
    run.trace = trace
    run.traced_window_s = window_s
    run.searches = list(searches)
    run.updates = list(updates)
    return run


def _device(pattern, busy_ns):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["op", 0, busy_ns, pattern]]}]}]}


def test_search_roofline_counts_rows_and_adjacency():
    # 10 comps x 128 x 4 B + 2 hops x 32 x 4 B = 5,376 B at 1 GB/s is
    # 5.376 us; busy 10.752 us -> 50%
    s = {"n_comps": np.array([4, 6]), "n_hops": np.array([1, 1]),
         "traced": True}
    run = _run(_device("jit_search_batch", 10752), searches=[s])
    read = spec.load_reader("search.gather_roofline")
    assert read(run) == pytest.approx(50.0)
    # untraced calls and a run without a trace read nothing
    run.searches[0]["traced"] = False
    assert read(run) is None
    assert read(_run(None, searches=[s])) is None


def test_update_roofline_counts_applied_lanes_only():
    u = {"n_comps": np.array([10, 10]), "ok": np.array([True, False]),
         "kind": np.array([KIND_INSERT, KIND_DELETE]), "traced": True}
    # 10 x 128 x 4 B = 5,120 B -> 5.12 us over 20.48 us busy = 25%
    run = _run(_device("jit_apply_segment", 20480), updates=[u])
    assert spec.load_reader("update.gather_roofline")(run) == \
        pytest.approx(25.0)


def test_idle_share_is_one_minus_busy_over_window():
    run = _run(_device("m", 250_000_000), window_s=1.0)
    for name in ("device.idle_share.query", "device.idle_share.update"):
        assert spec.load_reader(name)(run) == pytest.approx(75.0)
    assert spec.load_reader("device.idle_share.query")(
        _run(_device("m", 0), window_s=1.0)) is None


def test_comps_per_op_by_kind_over_applied_lanes():
    u = {"n_comps": np.array([10, 30, 99, 50]),
         "ok": np.array([True, True, False, True]),
         "kind": np.array([KIND_INSERT, KIND_INSERT, KIND_DELETE,
                           KIND_DELETE])}
    run = _run(None, updates=[u])
    assert spec.load_reader("update.comps_per_insert")(run) == 20.0
    assert spec.load_reader("update.comps_per_delete")(run) == 50.0
