"""RobustPrune (Algorithm 3) over a fixed-width candidate set.

The paper's loop removes the closest remaining candidate and occludes
candidates that are much closer to it than to ``p``.  Here the candidate set
is a fixed-width id vector (INVALID padded), and the greedy selection runs
one of two ways, chosen by that static width C:

* **block** (C <= ``BLOCK_MAX``): all (C, C) candidate-to-candidate
  distances come from one ``pair_dists`` matmul (at HIGHEST precision, so
  float32 on TPU as the loop's matvec is there), the candidates are put in
  distance-to-``p`` order (ties to the lower index, as the loop's
  ``argmin`` takes them), and a forward scan of C unrolled steps keeps the
  i-th iff its distance is finite, no earlier kept candidate occludes it
  and fewer than r are kept.  Every step reads static slices: no loop, no
  gather or scatter per step.  Every edge append prunes this way (C = r + 1).
* **loop** (C > ``BLOCK_MAX``): a ``while_loop`` of at most r selection
  steps, each one (C, D) @ (D,) matvec for the occlusion distances, so
  memory stays O(C * D) where a block would be O(C^2).  The insert's prune
  over its visited list at L 128 (l_build + 64 = 192 wide) and
  consolidation's spliced r + r^2 candidates take it.

Both select exactly Algorithm 3's rows in its emission order; they differ
only where a float32 distance computed by matmul rather than matvec falls
on the other side of an occlusion tie.

The occlusion test ``alpha * d(u, v) <= d(u, p)`` needs a non-negative
distance for alpha > 1 to mean "keep more".  Under ``ip`` the distance is
the negated inner product, on which alpha > 1 prunes *harder*: one kept
neighbour would drop every candidate whose product with it is above
``<u, p> / alpha``, i.e. a whole cluster of near-equal products.  So for
``ip`` the test compares squared euclidean distances,
``|u|^2 + |v|^2 + 2 d_ip(u, v)`` from the cached norms (for unit vectors
``2 + 2 d_ip``, a monotone form of the ip distance), and for ``l2`` the
distances themselves.  Candidates are still visited in ip order.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .backend import BIG, resolve_backend
from .spans import PRUNE, device_scope
from .types import (
    INVALID,
    ANNConfig,
    GraphState,
    clip_ids,
    mask_duplicates,
    read_vectors,
)

BLOCK_MAX = 128  # widest candidate set pruned as one (C, C) distance block


@functools.partial(jax.jit, static_argnames=("cfg",))
@device_scope(PRUNE)
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
    cand_vecs = read_vectors(state.vectors, safe, cfg.dim)   # (C, D)
    cand_norms = state.norms[safe]           # (C,)  cached per-slot norms
    p_norm = be.query_norm(cfg, p_vec)
    d_p = be.dists_from_rows(cfg, p_vec, p_norm, cand_vecs, cand_norms)
    if cand_dists is not None:
        d_p = jnp.where(jnp.isfinite(cand_dists), cand_dists, d_p)
    d_p = jnp.where(ids >= 0, d_p, BIG)

    d_occ = d_p                  # d(u, p) as the occlusion test takes it
    if cfg.metric == "ip":
        d_occ = _euclidean(d_p, cand_norms, jnp.dot(p_vec, p_vec))
    select = _select_block if ids.shape[0] <= BLOCK_MAX else _select_loop
    return select(cfg, ids, cand_vecs, cand_norms, d_p, d_occ)


def _euclidean(d_ip, a_norms, b_norms):
    """ip distances as squared euclidean ones, from the squared norms."""
    return a_norms + b_norms + 2.0 * d_ip


def _select_block(cfg: ANNConfig, ids, cand_vecs, cand_norms, d_p, d_occ):
    """Greedy selection as one distance block and a forward scan."""
    be = resolve_backend(cfg)
    c = ids.shape[0]
    pos = jnp.arange(c)
    # occ[v, u]: v, once kept, drops u (alpha * d(u, v) <= d(u, p)).  At
    # full float32: on TPU the loop's matvec runs as a float32 multiply and
    # sum, where a (C, C) product at default precision would round its
    # operands to bfloat16 and flip about half the rows' decisions.
    occ = be.pair_dists(cfg, cand_vecs, cand_norms, cand_vecs, cand_norms,
                        precision=lax.Precision.HIGHEST)
    if cfg.metric == "ip":
        occ = _euclidean(occ, cand_norms[:, None], cand_norms[None, :])
    occ = cfg.alpha * occ <= d_occ[None, :]
    # The loop's visiting order: by distance to p, ties to the lower index.
    # perm[i, j]: candidate j is visited i-th.  Ranks by comparison and
    # permutations by one-hot products, not a sort and gathers, which cost
    # the TPU far more at this width.
    live = jnp.isfinite(d_p)
    d = jnp.where(live, d_p, BIG)
    first = (d[:, None] < d[None, :]) | (
        (d[:, None] == d[None, :]) & (pos[:, None] < pos[None, :]))
    perm = jnp.sum(first, axis=0)[None, :] == pos[:, None]
    onehot = perm.astype(jnp.float32)
    occ = (onehot @ occ.astype(jnp.float32) @ onehot.T > 0.5) & (
        pos[:, None] < pos[None, :])        # only earlier ones drop u
    live = jnp.any(perm & live[None, :], axis=1)
    ids = jnp.max(jnp.where(perm, ids[None, :], INVALID), axis=1)
    # dropped[i]: the i-th visited is not kept.  Step i finds dropped[i]
    # final and, if i is kept, drops the later ones it occludes.  The degree
    # cap waits for the compaction: the first r kept are the same with or
    # without it.
    dropped = ~live
    for i in range(c):
        dropped = dropped | (~dropped[i] & occ[i])
    kept = ~dropped
    # front-compact: the kept candidate at i goes to slot (kept before i)
    slot = jnp.cumsum(kept.astype(jnp.int32)) - 1
    hit = kept[None, :] & (slot[None, :] == jnp.arange(cfg.r)[:, None])
    return jnp.max(jnp.where(hit, ids[None, :], INVALID), axis=1)


def _select_loop(cfg: ANNConfig, ids, cand_vecs, cand_norms, d_p, d_occ):
    """Greedy selection as a loop of argmin steps (wide candidate sets)."""
    be = resolve_backend(cfg)
    alive = ids >= 0
    out = jnp.full((cfg.r,), INVALID, jnp.int32)

    def body(carry):
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
        if cfg.metric == "ip":
            d_v = _euclidean(d_v, v_norm, cand_norms)
        keep = cfg.alpha * d_v > d_occ
        alive = alive & jnp.where(ok, keep, True)
        alive = alive.at[j].set(False)
        return alive, out, n_out

    # stop once no candidate is left: the remaining steps of the r-step
    # loop would select nothing
    _, out, _ = lax.while_loop(
        lambda c: jnp.any(c[0]) & (c[2] < cfg.r),
        body,
        (alive, out, jnp.int32(0)),
    )
    return out
