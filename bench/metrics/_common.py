"""Arithmetic that several metric readers share."""
import numpy as np

import trace_reduce


def comps_per_op(run, kind):
    """Mean ``n_comps`` over applied update lanes of ``kind``."""
    sel = [u["n_comps"][u["ok"] & (u["kind"] == kind)] for u in run.updates]
    sel = np.concatenate(sel) if sel else np.zeros(0)
    return float(np.mean(sel)) if len(sel) else None


def idle_share(run):
    """100 x (1 - device busy / traced window)."""
    if run.trace is None or run.traced_window_s <= 0:
        return None
    busy = trace_reduce.busy_s(run.trace)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / run.traced_window_s)
