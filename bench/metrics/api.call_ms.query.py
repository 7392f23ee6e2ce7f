"""Median host time of a call into ``search_index``, from the call to its
return, before blocking on the result (ms)."""
import numpy as np


def read(run):
    calls = [s["call_s"] for s in run.searches]
    return float(np.median(calls)) * 1e3 if calls else None
