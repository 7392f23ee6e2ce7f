"""Raw jnp distance math — internal to the backend layer.

Engine code must NOT import this module directly: go through
``core.backend.resolve_backend(cfg)`` so the pluggable kernel engine
(jnp / pallas / ref) stays the single dispatch seam.  Only
``core/backend.py`` (and its tests) import these functions.

Both metrics are expressed in "matmul + broadcast add" form so the same math
is served by the pure-jnp path (CPU tests) and the Pallas ``gather_distance``
kernel (TPU target): for squared L2,

    d(q, x) = ||q||^2 + ||x||^2 - 2 <q, x>

with ``||x||^2`` precomputed per slot (``GraphState.norms``).  Inner product
uses d = -<q, x> (smaller = closer everywhere in this codebase).
"""
from __future__ import annotations

import jax.numpy as jnp

from .types import ANNConfig, GraphState, clip_ids, read_vectors

BIG = jnp.inf


def dists_from_rows(metric: str, q, q_norm, rows, row_norms):
    """Distance from query ``q`` to ``rows`` (M, D).  No validity masking."""
    prod = rows @ q
    if metric == "l2":
        return q_norm + row_norms - 2.0 * prod
    return -prod


def dists_to_ids(state: GraphState, cfg: ANNConfig, q, ids):
    """f32[M] distances from q to slots ``ids``; inf where id is INVALID."""
    safe = clip_ids(ids, cfg.n_cap)
    rows = read_vectors(state.vectors, safe, cfg.dim)
    q_norm = jnp.dot(q, q) if cfg.metric == "l2" else 0.0
    d = dists_from_rows(cfg.metric, q, q_norm, rows, state.norms[safe])
    return jnp.where(ids >= 0, d, BIG)


def pair_dists(metric: str, a_vecs, a_norms, b_vecs, b_norms,
               precision=None):
    """(A, B) distance matrix between two point sets (no masking)."""
    prod = jnp.matmul(a_vecs, b_vecs.T, precision=precision)
    if metric == "l2":
        return a_norms[:, None] + b_norms[None, :] - 2.0 * prod
    return -prod
