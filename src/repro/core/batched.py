"""Beyond-paper optimization: batched update processing.

The paper's implementation overlaps updates across 16 CPU threads; in-flight
updates don't observe each other's graph writes.  The TPU-native equivalent
splits each update into a *search phase* and a *write phase*:

  phase 1 — all B updates' greedy searches run through the natively batched
            beam engine (core/search_batched.py: one shared hop loop, one
            fused (B, R) gather-distance tile per hop) against the
            pre-batch graph (exactly the paper's relaxed visibility);
  phase 2 — graph writes apply serially via scan, reusing the precomputed
            candidate lists.  Each op's edges run as one vmapped batch
            (``edges.append_edges``); inserts' own RobustPrunes run for the
            whole batch at once before the scan.

The searches dominate update cost (the paper's Table 3 shows deletion time
is search-bound), so batching them converts the serial update stream into
one wide SPMD program.  Recall impact is bounded by the batch size (same
argument as the paper's multi-threaded execution) and measured in
benchmarks/perf_ann.py.

All distance math here (batched searches, top-c candidate matrices, prune)
goes through the backend selected by ``cfg.backend`` (core/backend.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .delete import DeleteStats, _next_start, _repair_edges, _topc_candidates
from .edges import append_edges, remove_target_rows
from .insert import InsertStats
from .prune import robust_prune
from .quant import quant_write_rows
from .search_batched import batched_greedy_search
from .types import INVALID, ANNConfig, GraphState, clip_ids


@functools.partial(jax.jit, static_argnames=("cfg",))
def insert_many_batched(state: GraphState, cfg: ANNConfig, xs: jax.Array,
                        valid: Optional[jax.Array] = None):
    """Batched inserts: batched-engine searches, serial writes.  xs: (B, dim).

    ``valid``: optional bool[B] lane mask — False lanes are no-ops (no slot
    allocated, no write), letting ragged streaming batches ride a padded
    power-of-two bucket (see ``StreamingIndex``) without recompiling.
    """
    b = xs.shape[0]
    if valid is None:
        valid = jnp.ones((b,), bool)

    # phase 0: allocate slots and write vectors (so searches can't find them:
    # slots stay inactive until phase 2 links them).  Valid lanes take
    # consecutive stack entries; when capacity runs short the earliest lanes
    # lose out, matching the unmasked formulation.
    n_valid = jnp.sum(valid.astype(jnp.int32))
    rank = jnp.cumsum(valid.astype(jnp.int32)) - valid.astype(jnp.int32)
    idxs = state.free_top - n_valid + rank
    ok = valid & (idxs >= 0)
    slots = jnp.where(ok, state.free_stack[jnp.maximum(idxs, 0)], INVALID)
    sslots = clip_ids(slots, cfg.n_cap)
    xs_f = xs.astype(state.vectors.dtype)
    # failed/masked lanes must DROP their writes, not rewrite a stale copy:
    # their clipped slot is 0, and if a valid lane was just allocated slot 0
    # the duplicate-index scatter order would decide which write wins
    write_idx = jnp.where(ok, sslots, cfg.n_cap)
    state = state._replace(
        vectors=state.vectors.at[write_idx].set(xs_f, mode="drop"),
        norms=state.norms.at[write_idx].set(
            jnp.sum(xs_f * xs_f, axis=1), mode="drop"
        ),
    )
    if state.quant is not None:
        # int8 tier written in phase 0 too, so the phase-1 searches (which
        # traverse on quantized distances when cfg.quantized) see a
        # consistent code table
        state = state._replace(
            quant=quant_write_rows(state.quant, write_idx, xs_f)
        )

    # phase 1: one shared-hop-loop batched search against the pre-batch graph
    # (masked lanes are dead from hop 0 and contribute no comps or hops)
    res = batched_greedy_search(state, cfg, xs_f, k=1, l=cfg.l_build,
                                valid=valid)
    vis_ids, vis_dists, comps = res.visited_ids, res.visited_dists, res.n_comps

    # phase 2a: every lane's out-neighbours at once.  A lane's candidates
    # are pre-batch vertices (new slots are inactive during phase 1), whose
    # vectors and liveness the link phase never changes, so RobustPrune
    # gives each lane the row it would get inside the serial scan.
    nouts = jax.vmap(
        lambda x, vids, vdists, slot: robust_prune(
            state, cfg, x, vids, vdists, p_id=slot)
    )(xs_f, vis_ids, vis_dists, slots)
    nouts = jnp.where(ok[:, None], nouts, INVALID)

    # phase 2b: serial link application — lane b's reverse edges see the
    # rows earlier lanes wrote.  Masked lanes rewrite what is there (no
    # lax.cond returning the state)
    def link(st: GraphState, args):
        slot, nout, ok = args
        at = clip_ids(slot, cfg.n_cap)
        st = st._replace(
            # a dynamic row update, not a scatter (see ``edges.put_rows``)
            adj=lax.dynamic_update_index_in_dim(
                st.adj, jnp.where(ok, nout, st.adj[at]), at, 0),
            active=st.active.at[at].set(st.active[at] | ok),
            n_active=st.n_active + ok.astype(jnp.int32),
            free_top=st.free_top - ok.astype(jnp.int32),
            start=jnp.where(ok & (st.start < 0), slot, st.start),
        )
        return append_edges(st, cfg, nout, slot), None

    state, _ = lax.scan(link, state, (slots, nouts, ok))
    stats = InsertStats(
        slot=jnp.where(ok, slots, INVALID),
        n_comps=comps,
        n_hops=jnp.zeros_like(comps),
    )
    return state, stats


@functools.partial(jax.jit, static_argnames=("cfg",))
def ip_delete_many_batched(state: GraphState, cfg: ANNConfig, ps: jax.Array):
    """Batched in-place deletes: batched-engine searches, serial edge repair."""
    b = ps.shape[0]
    sps = clip_ids(ps, cfg.n_cap)
    valid = (ps >= 0) & state.active[sps]

    # phase 1: one shared-hop-loop batched search from every deleted point
    # (invalid lanes — INVALID or non-active slots — are dead from hop 0)
    x_ps = state.vectors[sps]
    res = batched_greedy_search(state, cfg, x_ps, k=cfg.k_delete,
                                l=cfg.l_delete, valid=valid)
    vis_b = jnp.where(res.visited_ids == ps[:, None], INVALID,
                      res.visited_ids)
    cands_b = jnp.where(res.topk_ids == ps[:, None], INVALID, res.topk_ids)
    comps_b = res.n_comps

    def repair(st: GraphState, args):
        # branch-free: a masked lane's edges are all INVALID and its slot
        # writes rewrite what is there
        p, vis, cands, ok = args
        sp = clip_ids(p, cfg.n_cap)
        nout_p = jnp.where(ok, st.adj[sp], INVALID)
        vis_rows = st.adj[clip_ids(vis, cfg.n_cap)]
        in_mask = jnp.any(vis_rows == p, axis=1) & (vis >= 0) & ok
        # read p's row before the writes: XLA would otherwise re-read it
        # from the pre-write adjacency after them, keeping a 256 MiB copy
        nout_p, adj = lax.optimization_barrier((nout_p, st.adj))
        st = st._replace(adj=adj)
        cz = _topc_candidates(st, cfg, vis, cands, cfg.n_copies)
        st = st._replace(adj=remove_target_rows(
            st, cfg, jnp.where(in_mask, vis, INVALID), p))
        cw = _topc_candidates(st, cfg, nout_p, cands, cfg.n_copies)
        st = append_edges(st, cfg,
                          *_repair_edges(in_mask, vis, cz, nout_p, cw))
        new_start = _next_start(st, cfg, p, nout_p)
        return st._replace(
            adj=lax.dynamic_update_index_in_dim(
                st.adj, jnp.where(ok, INVALID, st.adj[sp]), sp, 0),
            active=st.active.at[sp].set(st.active[sp] & ~ok),
            quarantine=st.quarantine.at[sp].set(st.quarantine[sp] | ok),
            n_active=st.n_active - ok.astype(jnp.int32),
            n_pending=st.n_pending + ok.astype(jnp.int32),
            start=jnp.where(ok, new_start, st.start),
        ), None

    state, _ = lax.scan(repair, state, (ps, vis_b, cands_b, valid))
    stats = DeleteStats(ok=valid, n_comps=comps_b,
                        n_in=jnp.zeros_like(comps_b))
    return state, stats
