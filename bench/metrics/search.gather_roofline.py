"""Share of the HBM roofline that the search's gathers reach (%): the rows
its distance computations read (``n_comps`` x dim x 4 B) and the adjacency
rows of its hops (``n_hops`` x R x 4 B), over peak HBM bandwidth, over the
device-busy time inside the search programs, in the traced steps.  It counts
the work the algorithm needs, not a kernel's own events, so it reads the
same work whatever implements the gather."""
import numpy as np

import trace_reduce


def read(run):
    traced = [s for s in run.searches if s["traced"]]
    if run.trace is None or not traced or not run.peaks:
        return None
    busy = trace_reduce.busy_in_programs(run.trace, "search")
    if busy <= 0:
        return None
    dim, r = run.cfg.dim, run.cfg.r
    comps = sum(int(np.sum(s["n_comps"])) for s in traced)
    hops = sum(int(np.sum(s["n_hops"])) for s in traced)
    need = (comps * dim * 4 + hops * r * 4) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * need / busy
