"""The readers of the vector-row reads and of the gather kernel
(``metrics/update.vector_rows_ms.py`` and
``metrics/update.gather_kernel_roofline.py``) on a small trace whose
answers are worked out by hand."""
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import spec

US = 1000  # ns
PEAKS = {"hbm_bytes_per_s": 819e9}


def _op(path, start_us, end_us):
    return [path, start_us * US, (end_us - start_us) * US]


# one traced update call (an apply_segment program), then a query batch;
# times in us
OPS = [
    _op("ann.insert.search", 10, 40),
    _op("ann.insert.search/ann.search.hops", 12, 30),
    _op("ann.insert.search/ann.search.hops/ann.gather", 14, 24),
    _op("ann.insert.link/ann.vectors.rows", 42, 45),
    _op("ann.insert.link/ann.edges.append/ann.prune/ann.vectors.rows",
        50, 54),
    _op("ann.delete.search/ann.vectors.rows", 60, 61),
    _op("ann.delete.search/ann.search.hops/ann.gather", 62, 72),
    _op("ann.delete.repair/ann.vectors.rows", 80, 82),
    _op("ann.delete.repair/ann.edges.append/ann.prune/ann.vectors.rows",
        81, 83),
    # the query program: its kernel calls and row reads are not the
    # update path's
    _op("ann.search.hops/ann.gather", 300, 340),
    _op("ann.search.select/ann.vectors.rows", 340, 345),
]


def _grouped(ops):
    out = {}
    for path, s, d in ops:
        out.setdefault(path, []).append([s, d])
    return out


def _run(ops=OPS, updates=((True, [100, 200, 50], [True, True, False]),),
         dim=768):
    run = harness.Run(cell=None, cfg=SimpleNamespace(dim=dim), peaks=PEAKS)
    run.trace = {"planes": []}
    run.program_trace = {"devices": [_grouped(ops)], "spans": []}
    run.updates = [{"traced": t, "n_comps": np.array(c), "ok": np.array(o)}
                   for t, c, o in updates]
    run.searches = [{"traced": True}]
    return run


def read(metric, run):
    return spec.load_reader(metric)(run)


def test_vector_rows_ms_by_hand():
    # under an update phase: 3 + 4 + 1 + [80, 83) 3 = 11 us in one call;
    # the query program's [340, 345) is not counted
    assert read("update.vector_rows_ms", _run()) == pytest.approx(0.011)
    # two traced calls share the time
    two = _run(updates=((True, [1], [True]), (True, [1], [True]),
                        (False, [1], [True])))
    assert read("update.vector_rows_ms", two) == pytest.approx(0.0055)


def test_gather_kernel_roofline_by_hand():
    # applied lanes' comparisons 100 + 200 = 300, of 768 x 4 B rows:
    # 921,600 B at 819 GB/s = 1.1253 us, over the kernel's 10 + 10 us under
    # the update phases (the query program's 40 us not counted): 5.6264%
    got = read("update.gather_kernel_roofline", _run())
    assert got == pytest.approx(100 * 300 * 768 * 4 / 819e9 / 20e-6)
    assert got == pytest.approx(5.626374, rel=1e-6)
    # the same comparisons at 128 lanes read a sixth of that
    assert read("update.gather_kernel_roofline", _run(dim=128)) == \
        pytest.approx(got / 6)


@pytest.mark.parametrize("metric", ["update.vector_rows_ms",
                                    "update.gather_kernel_roofline"])
def test_readers_read_nothing_rather_than_zero(metric):
    # a program without the scopes (an older checkout): None, never 0
    old = [op for op in OPS if "ann.gather" not in op[0]
           and "ann.vectors.rows" not in op[0]]
    assert read(metric, _run(ops=old)) is None
    # scopes only in the query program: nothing on the update path
    query_only = [op for op in OPS if op[0].startswith("ann.search.")]
    assert read(metric, _run(ops=query_only)) is None
    # no traced update call, or no trace at all
    assert read(metric, _run(updates=((False, [1], [True]),))) is None
    run = _run()
    run.trace = None
    assert read(metric, run) is None


def test_gather_kernel_roofline_needs_the_peaks():
    run = _run()
    run.peaks = {}
    assert read("update.gather_kernel_roofline", run) is None
