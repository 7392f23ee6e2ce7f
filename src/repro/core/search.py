"""GreedySearch (Algorithm 1) as a fixed-shape ``lax.while_loop`` beam search.

TPU adaptation of the paper's priority-queue search:

  * the beam is a fixed-width ``(l,)`` sorted triple (ids, dists, expanded);
    the per-hop "pop min + push R neighbours" becomes one sort-merge of
    ``l + R`` keys (sorts vectorize across the query batch; heaps do not);
  * the visited hash-set becomes a ``bool[n_cap]`` bitmap ("seen");
  * termination (all top-l entries expanded) is the while_loop predicate,
    with a ``max_visits`` safety bound.

Tombstoned slots are navigated but excluded from the visited list and from
the returned top-k, exactly as FreshDiskANN's lazy-delete search does.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .backend import BIG, resolve_backend
from .spans import SEARCH_DISPATCH, SEARCH_PAD, host_span
from .types import (
    INVALID,
    ANNConfig,
    GraphState,
    clip_ids,
    navigable,
    recorded,
)


class SearchResult(NamedTuple):
    topk_ids: jax.Array       # i32[k]
    topk_dists: jax.Array     # f32[k]
    visited_ids: jax.Array    # i32[max_visits]  expansion order, INVALID padded
    visited_dists: jax.Array  # f32[max_visits]
    n_visited: jax.Array      # i32[]
    n_comps: jax.Array        # i32[]  distance computations issued
    n_hops: jax.Array         # i32[]  expansions


class _Loop(NamedTuple):
    beam_ids: jax.Array
    beam_dists: jax.Array
    beam_exp: jax.Array
    seen: jax.Array
    vis_ids: jax.Array
    vis_dists: jax.Array
    n_vis: jax.Array
    n_comps: jax.Array
    n_hops: jax.Array


DistanceFn = Callable[[GraphState, ANNConfig, jax.Array, jax.Array], jax.Array]


@functools.partial(
    jax.jit, static_argnames=("cfg", "k", "l", "max_visits", "distance_fn")
)
def greedy_search(
    state: GraphState,
    cfg: ANNConfig,
    q: jax.Array,
    *,
    k: int,
    l: int,
    max_visits: Optional[int] = None,
    distance_fn: Optional[DistanceFn] = None,
) -> SearchResult:
    """Beam search for the nearest neighbours of ``q`` (Algorithm 1).

    Distance evaluation rides the kernel engine selected by
    ``cfg.backend``; ``distance_fn`` overrides it for experiments.
    """
    if max_visits is None:
        max_visits = cfg.max_visits(l)
    dist_fn = distance_fn or resolve_backend(cfg).dists_to_ids
    nav = navigable(state)
    returnable = state.active
    visit = recorded(state, cfg)

    start = state.start
    d0 = dist_fn(state, cfg, q, start[None])[0]

    beam_ids = jnp.full((l,), INVALID, jnp.int32).at[0].set(start)
    beam_dists = jnp.full((l,), BIG, jnp.float32).at[0].set(
        jnp.where(start >= 0, d0, BIG)
    )
    beam_exp = jnp.zeros((l,), bool)
    seen = jnp.zeros((cfg.n_cap,), bool).at[clip_ids(start[None], cfg.n_cap)].set(
        start >= 0
    )

    init = _Loop(
        beam_ids=beam_ids,
        beam_dists=beam_dists,
        beam_exp=beam_exp,
        seen=seen,
        vis_ids=jnp.full((max_visits,), INVALID, jnp.int32),
        vis_dists=jnp.full((max_visits,), BIG, jnp.float32),
        n_vis=jnp.int32(0),
        n_comps=jnp.where(start >= 0, jnp.int32(1), jnp.int32(0)),
        n_hops=jnp.int32(0),
    )

    def cond(s: _Loop):
        frontier = (s.beam_ids >= 0) & ~s.beam_exp & jnp.isfinite(s.beam_dists)
        return jnp.any(frontier) & (s.n_hops < max_visits)

    def body(s: _Loop):
        # --- pop the closest unexpanded vertex -------------------------------
        frontier_d = jnp.where(
            (s.beam_ids >= 0) & ~s.beam_exp, s.beam_dists, BIG
        )
        i = jnp.argmin(frontier_d)
        v = s.beam_ids[i]
        dv = s.beam_dists[i]
        beam_exp = s.beam_exp.at[i].set(True)

        # --- record in visited list (only recorded vertices) ----------------
        # The write is conditional on ``recorded``: a tombstoned pop must not
        # transiently occupy the slot a later live pop will claim (an
        # out-of-bounds index drops the write entirely).
        v_ret = visit[clip_ids(v, cfg.n_cap)]
        slot = jnp.where(v_ret, s.n_vis, jnp.int32(max_visits))
        vis_ids = s.vis_ids.at[slot].set(v, mode="drop")
        vis_dists = s.vis_dists.at[slot].set(dv, mode="drop")
        n_vis = s.n_vis + v_ret.astype(jnp.int32)

        # --- expand ----------------------------------------------------------
        nbrs = state.adj[clip_ids(v, cfg.n_cap)]
        safe_nbrs = clip_ids(nbrs, cfg.n_cap)
        fresh = (nbrs >= 0) & nav[safe_nbrs] & ~s.seen[safe_nbrs]
        masked = jnp.where(fresh, nbrs, INVALID)
        nd = dist_fn(state, cfg, q, masked)
        n_comps = s.n_comps + jnp.sum(fresh).astype(jnp.int32)
        seen = s.seen.at[jnp.where(fresh, nbrs, cfg.n_cap)].set(
            True, mode="drop"
        )

        # --- sort-merge beam + neighbours, keep top-l ------------------------
        all_d = jnp.concatenate([s.beam_dists, nd])
        all_i = jnp.concatenate([s.beam_ids, masked])
        all_e = jnp.concatenate([beam_exp, jnp.zeros_like(fresh)])
        sd, si, se = lax.sort((all_d, all_i, se_key(all_e)), num_keys=1)
        return _Loop(
            beam_ids=si[:l],
            beam_dists=sd[:l],
            beam_exp=se[:l].astype(bool),
            seen=seen,
            vis_ids=vis_ids,
            vis_dists=vis_dists,
            n_vis=n_vis,
            n_comps=n_comps,
            n_hops=s.n_hops + 1,
        )

    out = lax.while_loop(cond, body, init)

    # --- final top-k over the beam, filtered to live vertices ----------------
    ret = returnable[clip_ids(out.beam_ids, cfg.n_cap)] & (out.beam_ids >= 0)
    final_d = jnp.where(ret, out.beam_dists, BIG)
    kk = min(k, l)  # the beam holds l entries; pad the tail with INVALID
    top_d, top_i = lax.top_k(-final_d, kk)
    topk_ids = jnp.where(jnp.isfinite(-top_d), out.beam_ids[top_i], INVALID)
    if kk < k:
        topk_ids = jnp.pad(topk_ids, (0, k - kk), constant_values=INVALID)
        top_d = jnp.pad(top_d, (0, k - kk), constant_values=-BIG)
    return SearchResult(
        topk_ids=topk_ids,
        topk_dists=-top_d,
        visited_ids=out.vis_ids,
        visited_dists=out.vis_dists,
        n_visited=out.n_vis,
        n_comps=out.n_comps,
        n_hops=out.n_hops,
    )


def se_key(e: jax.Array) -> jax.Array:
    """Bool flags ride through lax.sort as int32 payload."""
    return e.astype(jnp.int32)


def search_batch_vmap(
    state: GraphState,
    cfg: ANNConfig,
    queries: jax.Array,
    *,
    k: int,
    l: int,
    distance_fn: Optional[DistanceFn] = None,
) -> SearchResult:
    """vmapped greedy search over a (B, dim) query batch.

    The pre-batched-engine formulation, kept as the benchmark baseline
    (``benchmarks/search_bench.py``): XLA batches the per-query while_loop
    by select-masking the whole carry every hop, which the native engine
    (``core/search_batched.py``) avoids.
    """
    fn = functools.partial(
        greedy_search, state, cfg, k=k, l=l, distance_fn=distance_fn
    )
    return jax.vmap(fn)(queries)


@functools.lru_cache(maxsize=32)
def _lift_distance_fn(distance_fn: DistanceFn):
    """Lift a per-query distance_fn to the batched signature, cached so the
    wrapper stays a stable (hashable) static jit argument across calls.
    Callers must pass a stable function object (as with ``greedy_search``'s
    static ``distance_fn``) — a fresh closure per call defeats both this
    cache and the jit cache behind it; the bounded size caps the damage."""

    def batched_fn(state, cfg, queries, ids):
        return jax.vmap(
            lambda q, row: distance_fn(state, cfg, q, row)
        )(queries, ids)

    return batched_fn


def search_batch(
    state: GraphState,
    cfg: ANNConfig,
    queries: jax.Array,
    *,
    k: int,
    l: int,
    distance_fn: Optional[DistanceFn] = None,
    bucket: bool = True,
) -> SearchResult:
    """Batched greedy search over a (B, dim) query batch.

    Runs the natively batched beam engine (one shared hop loop, fused
    (B, R) gather-distance tiles); per lane the traversal (neighbour ids
    and counters) is identical to ``greedy_search``, distances to f32
    tolerance.  ``bucket`` pads ragged batch sizes up to the next
    power of two so streaming callers stop paying a jit recompile per
    distinct B (padded lanes run a zero query and are sliced off).
    ``distance_fn`` keeps the legacy per-query signature and is lifted with
    ``jax.vmap``; pass it to ``batched_greedy_search`` directly for a
    natively batched override.
    """
    from .search_batched import batched_greedy_search, pad_batch

    b = queries.shape[0]
    batched_fn = _lift_distance_fn(distance_fn) if distance_fn else None
    with host_span(SEARCH_PAD):
        qs = pad_batch(queries, b) if bucket else queries
        # padding lanes are masked dead (empty beam, zero comps, zero hops)
        # instead of running a throwaway zero-query search to convergence.
        # The mask is passed even when b fills the bucket exactly, so every
        # batch size of a bucket shares ONE trace (valid=None is a different
        # jit key than a bool[B] mask).
        valid = jnp.arange(qs.shape[0]) < b if bucket else None
    with host_span(SEARCH_DISPATCH):
        res = batched_greedy_search(
            state, cfg, qs, k=k, l=l, distance_fn=batched_fn, valid=valid
        )
    if qs.shape[0] != b:
        res = jax.tree.map(lambda x: x[:b], res)
    return res
