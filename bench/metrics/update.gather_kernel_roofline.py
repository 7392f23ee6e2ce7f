"""Share of the HBM roofline that the hop loops' gather reaches in the
update path (%): the vector rows the hop loops' comparisons require (the
program's ``SegmentResult.n_comps`` of applied lanes x dim x 4 B: the
insert and delete searches' comparisons, counted by the program, never
from the gather's own events), over peak HBM bandwidth, over the device
time under ``ann.gather`` (the pallas backend's gather and distances: the
Pallas kernel at one table row a vector, XLA's row gather at more)
beneath an update phase (the query program's gathers are not counted), in
the traced steps.
None, never 0, when nothing ran under ``ann.gather``: a program without
the scope, or an engine other than the pallas backend."""
import numpy as np

import _program

GATHER = "ann.gather"


def read(run):
    traced = [u for u in run.updates if u["traced"]]
    trace = _program.program(run)
    if trace is None or not traced or not run.peaks:
        return None
    ms = _program.union_ms(trace, lambda scopes: GATHER in scopes
                           and _program.outermost_phase(scopes) != "")
    if ms <= 0:
        return None
    comps = sum(int(np.sum(u["n_comps"][u["ok"]])) for u in traced)
    need_s = comps * run.cfg.dim * 4 / run.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / (ms / 1e3)
