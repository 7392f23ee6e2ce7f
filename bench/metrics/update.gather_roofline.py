"""Share of the HBM roofline that the update path's gathers reach (%): the
rows its distance computations read (``SegmentResult.n_comps`` x dim x 4 B),
over peak HBM bandwidth, over the device-busy time inside the
``apply_segment`` programs, in the traced steps."""
import numpy as np

import trace_reduce


def read(run):
    traced = [u for u in run.updates if u["traced"]]
    if run.trace is None or not traced or not run.peaks:
        return None
    busy = trace_reduce.busy_in_programs(run.trace, "apply_segment")
    if busy <= 0:
        return None
    comps = sum(int(np.sum(u["n_comps"][u["ok"]])) for u in traced)
    need = comps * run.cfg.dim * 4 / run.peaks["hbm_bytes_per_s"]
    return 100.0 * need / busy
