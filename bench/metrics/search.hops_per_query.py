"""Mean hops (expansions) per query (``SearchResult.n_hops``), over the
window (hops/query)."""
import numpy as np


def read(run):
    if not run.searches:
        return None
    return float(np.mean(np.concatenate([s["n_hops"]
                                         for s in run.searches])))
