"""Mean distance computations per applied insert lane
(``SegmentResult.n_comps``), over the window (comps/op)."""
from repro.core.types import KIND_INSERT

import _common


def read(run):
    return _common.comps_per_op(run, KIND_INSERT)
