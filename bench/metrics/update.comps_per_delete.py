"""Mean distance computations per applied delete lane
(``SegmentResult.n_comps``), over the window (comps/op)."""
from repro.core.types import KIND_DELETE

import _common


def read(run):
    return _common.comps_per_op(run, KIND_DELETE)
