"""Median host time of a call into ``apply_segment``, from the call to its
return, before blocking on the result (ms)."""
import numpy as np


def read(run):
    calls = [u["call_s"] for u in run.updates]
    return float(np.median(calls)) * 1e3 if calls else None
