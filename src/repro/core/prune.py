"""RobustPrune (Algorithm 3) with fixed-shape masked iteration.

The paper's loop removes the closest remaining candidate and occludes
candidates that are much closer to it than to ``p``.  Here the candidate set
is a fixed-width id vector (INVALID padded); at most ``r`` selection steps
run as a ``while_loop``; each step issues one (C, D) @ (D,) matvec for the
occlusion distances — O(r * C * D) total, the same asymptotics as the
paper.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .backend import BIG, resolve_backend
from .types import INVALID, ANNConfig, GraphState, clip_ids, mask_duplicates


@functools.partial(jax.jit, static_argnames=("cfg",))
def robust_prune(
    state: GraphState,
    cfg: ANNConfig,
    p_vec: jax.Array,
    cand_ids: jax.Array,
    cand_dists: Optional[jax.Array] = None,
    p_id: Optional[jax.Array] = None,
) -> jax.Array:
    """Select <= r out-neighbours for a point with vector ``p_vec``.

    ``cand_ids``: i32[C] candidate slots (INVALID padded, duplicates ok).
    ``cand_dists``: optional f32[C] distances to p (recomputed when None).
    ``p_id``: optional slot id of p itself, excluded from candidates.
    Returns a front-compacted i32[r] row sorted by distance-to-p order of
    selection (exactly Algorithm 3's emission order).
    """
    ids = mask_duplicates(cand_ids)
    if p_id is not None:
        ids = jnp.where(ids == p_id, INVALID, ids)
    # Never link to dead slots (dangling candidates from stale rows).
    safe = clip_ids(ids, cfg.n_cap)
    ids = jnp.where((ids >= 0) & (state.active[safe] | state.tombstone[safe]),
                    ids, INVALID)
    safe = clip_ids(ids, cfg.n_cap)

    be = resolve_backend(cfg)
    cand_vecs = state.vectors[safe]          # (C, D)
    cand_norms = state.norms[safe]           # (C,)  cached per-slot norms
    p_norm = be.query_norm(cfg, p_vec)
    d_p = be.dists_from_rows(cfg, p_vec, p_norm, cand_vecs, cand_norms)
    if cand_dists is not None:
        d_p = jnp.where(jnp.isfinite(cand_dists), cand_dists, d_p)
    d_p = jnp.where(ids >= 0, d_p, BIG)

    alive = ids >= 0
    out = jnp.full((cfg.r,), INVALID, jnp.int32)

    def body(_, carry):
        alive, out, n_out = carry
        dm = jnp.where(alive, d_p, BIG)
        j = jnp.argmin(dm)
        ok = alive[j] & jnp.isfinite(dm[j])
        out = out.at[n_out].set(jnp.where(ok, ids[j], INVALID))
        n_out = n_out + ok.astype(jnp.int32)
        # occlusion: drop u with alpha * d(u, v) <= d(u, p)
        v_vec = cand_vecs[j]
        v_norm = cand_norms[j]
        d_v = be.dists_from_rows(cfg, v_vec, v_norm, cand_vecs, cand_norms)
        keep = cfg.alpha * d_v > d_p
        alive = alive & jnp.where(ok, keep, True)
        alive = alive.at[j].set(False)
        return alive, out, n_out

    # stop once no candidate is left: the remaining steps of the r-step
    # loop would select nothing
    _, out, _ = lax.while_loop(
        lambda c: jnp.any(c[0]) & (c[2] < cfg.r),
        lambda c: body(None, c),
        (alive, out, jnp.int32(0)),
    )
    return out
