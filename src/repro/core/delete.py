"""In-place deletion (Algorithm 5) — the paper's core contribution — plus the
lazy tombstone delete used by the FreshDiskANN baseline.

Algorithm 5, TPU form:
  1. GreedySearch(x_p, k, l_d) -> Visited (expansion list), Candidates (top-k).
  2. Approximate in-neighbours: N'_in = {z in Visited : p in N_out(z)} — one
     (V, r) gather + compare, no in-neighbour lists maintained.
  3. For each z in N'_in: remove edge z->p, add edges z -> closest-c
     candidates to x_z.  The closest-c selection for *all* visited rows is one
     (V, k) distance matrix + top-c.
  4. For each w in N_out(p): add edges y -> w for the closest-c candidates y
     to x_w ((r, k) matrix + top-c).
  5. Remove p immediately: slot goes to *quarantine* (not the free stack) so
     dangling in-edges cannot alias a reused slot; Algorithm 6 releases it.

Degree overflow is resolved per-append via RobustPrune (as in Algorithm 2),
which matches the reference implementation's behaviour for fixed-degree rows.

These entry points are owned by the registered ``UpdatePolicy`` objects in
``core/api.py`` ("ip" -> in-place, "fresh" -> lazy): callers stream deletes
through the unified ``apply(state, cfg, UpdateBatch)`` front door rather
than invoking ``ip_delete_many`` / ``lazy_delete_many`` directly.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .backend import BIG, resolve_backend
from .edges import append_edges, remove_target_everywhere, remove_target_rows
from .search import greedy_search
from .spans import DELETE_REPAIR, DELETE_SEARCH, device_scope
from .types import INVALID, ANNConfig, GraphState, clip_ids, read_vectors


class DeleteStats(NamedTuple):
    ok: jax.Array       # bool[] point existed and was removed
    n_comps: jax.Array  # i32[]


def _topc_candidates(state, cfg, src_ids, cand_ids, c):
    """For each source row, the c closest candidate ids (excluding itself)."""
    d = resolve_backend(cfg).pair_dists_ids(state, cfg, src_ids, cand_ids)
    d = jnp.where(cand_ids[None, :] == src_ids[:, None], BIG, d)  # (S, K)
    _, idx = lax.top_k(-d, c)                      # (S, c)
    chosen = cand_ids[idx]
    finite = jnp.take_along_axis(d, idx, axis=1) < BIG
    return jnp.where(finite, chosen, INVALID)      # (S, c)


@functools.partial(jax.jit, static_argnames=("cfg",))
def ip_delete(state: GraphState, cfg: ANNConfig, p: jax.Array):
    """Delete slot ``p`` in place (Algorithm 5)."""
    sp = clip_ids(p, cfg.n_cap)
    valid = (p >= 0) & state.active[sp]

    def no_op(st: GraphState):
        return st, DeleteStats(jnp.bool_(False), jnp.int32(0))

    def do_delete(st: GraphState):
        with device_scope(DELETE_SEARCH):
            res = greedy_search(st, cfg, read_vectors(st.vectors, sp, cfg.dim),
                                k=cfg.k_delete, l=cfg.l_delete)
        with device_scope(DELETE_REPAIR):
            return repair(st, res)

    def repair(st: GraphState, res):
        vis = jnp.where(res.visited_ids == p, INVALID, res.visited_ids)
        cands = jnp.where(res.topk_ids == p, INVALID, res.topk_ids)
        nout_p = st.adj[sp]

        # --- approximate in-neighbours & their replacement edges -----------
        vis_rows = st.adj[clip_ids(vis, cfg.n_cap)]          # (V, r)
        in_mask = jnp.any(vis_rows == p, axis=1) & (vis >= 0)
        cz = _topc_candidates(st, cfg, vis, cands, cfg.n_copies)   # (V, c)

        # remove z -> p for every approximated in-neighbour
        st = st._replace(
            adj=remove_target_rows(
                st, cfg, jnp.where(in_mask, vis, INVALID), p
            )
        )

        # --- replacement edges: z -> cz, then cw -> p's out-neighbours -----
        cw = _topc_candidates(st, cfg, nout_p, cands, cfg.n_copies)  # (r, c)
        st = append_edges(st, cfg,
                          *_repair_edges(in_mask, vis, cz, nout_p, cw))

        # --- remove p (quarantine the slot until Algorithm 6) --------------
        new_start = _next_start(st, cfg, p, nout_p)
        st = st._replace(
            adj=st.adj.at[sp].set(jnp.full((cfg.r,), INVALID, jnp.int32)),
            active=st.active.at[sp].set(False),
            quarantine=st.quarantine.at[sp].set(True),
            n_active=st.n_active - 1,
            n_pending=st.n_pending + 1,
            start=new_start,
        )
        # distance comps: search + (V + r) * k selection matrices
        extra = (res.n_visited + jnp.sum(nout_p >= 0)) * cfg.k_delete
        return st, DeleteStats(
            jnp.bool_(True), res.n_comps + extra.astype(jnp.int32)
        )

    return lax.cond(valid, do_delete, no_op, state)


def _repair_edges(in_mask, vis, cz, nout_p, cw):
    """Algorithm 5's replacement edges as ``(sources, targets)`` in the
    order the algorithm adds them: each in-neighbour ``vis[i]`` (where
    ``in_mask``) to its candidates ``cz[i, :]``, then each candidate
    ``cw[i, j]`` to the out-neighbour ``nout_p[i]``."""
    z_src = jnp.where(in_mask[:, None], vis[:, None], INVALID)
    z_src = jnp.broadcast_to(z_src, cz.shape)
    w_dst = jnp.broadcast_to(nout_p[:, None], cw.shape)
    return (jnp.concatenate([z_src.reshape(-1), cw.reshape(-1)]),
            jnp.concatenate([cz.reshape(-1), w_dst.reshape(-1)]))


def _next_start(st: GraphState, cfg: ANNConfig, p, nout_p):
    """Reassign the entry point if it is being deleted."""
    nav = (st.active | st.tombstone).at[clip_ids(p, cfg.n_cap)].set(False)
    nbr_ok = (nout_p >= 0) & nav[clip_ids(nout_p, cfg.n_cap)]
    first_nbr = nout_p[jnp.argmax(nbr_ok)]
    any_nbr = jnp.any(nbr_ok)
    fallback = jnp.argmax(nav).astype(jnp.int32)
    has_any = jnp.any(nav)
    replacement = jnp.where(
        any_nbr, first_nbr, jnp.where(has_any, fallback, INVALID)
    )
    return jnp.where(st.start == p, replacement, st.start)


@functools.partial(jax.jit, static_argnames=("cfg",))
def ip_delete_many(state: GraphState, cfg: ANNConfig, ps: jax.Array):
    def step(st, p):
        st, stats = ip_delete(st, cfg, p)
        return st, stats

    return lax.scan(step, state, ps)


# ---------------------------------------------------------------------------
# Topology-aware localized repair (the "local" policy, arXiv 2503.00402)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def local_delete(state: GraphState, cfg: ANNConfig, p: jax.Array):
    """Delete slot ``p`` with topology-aware localized repair.

    Where Algorithm 5 approximates the in-neighbourhood by greedy search
    and quarantines the slot for a later Algorithm-6 sweep, this policy
    reads the in-neighbourhood straight off the topology and repairs it on
    the spot:

      1. Exact in-neighbours: one (n_cap, r) compare over the adjacency
         matrix — no search, no distance computations.
      2. Remove EVERY edge ``z -> p`` (``remove_target_everywhere``).  The
         removal is unbounded, so no dangling in-edge can ever survive a
         delete — which is what lets step 4 skip quarantine entirely.
      3. Reconnect the first ``resolved_local_in_cap()`` in-neighbours (a
         static bound, ascending slot order) through the bounded local
         candidate set around the deleted vertex: each repaired ``z`` gains
         edges to the ``c`` candidates of ``N_out(p)`` closest to ``x_z``.
         In-neighbours past the bound just lose one edge — a graph-quality
         trade, never a correctness one.
      4. Release the slot DIRECTLY onto the free stack.  There is no
         quarantine, no pending debt and nothing for a consolidation sweep
         to do; the slot is reusable by the very next insert lane.

    Distance cost is bounded by ``min(in_degree, local_in_cap) * r`` pairs
    per delete — independent of ``l_delete`` and of graph size.
    """
    sp = clip_ids(p, cfg.n_cap)
    valid = (p >= 0) & state.active[sp]

    def no_op(st: GraphState):
        return st, DeleteStats(jnp.bool_(False), jnp.int32(0))

    def do_delete(st: GraphState):
        b_in = min(cfg.resolved_local_in_cap(), cfg.n_cap)
        nout_p = st.adj[sp]                      # local candidate set

        # --- exact in-neighbourhood off the topology -----------------------
        in_rows = jnp.any(st.adj == p, axis=1)
        in_rows = in_rows.at[sp].set(False)      # no self loops, but be safe
        z_idx = jnp.where(
            in_rows, jnp.arange(cfg.n_cap, dtype=jnp.int32), cfg.n_cap
        )
        z_ids = jnp.sort(z_idx)[:b_in]
        z_ids = jnp.where(z_ids < cfg.n_cap, z_ids, INVALID).astype(jnp.int32)

        # --- remove every z -> p (unbounded, exact) ------------------------
        st = st._replace(adj=remove_target_everywhere(st, cfg, p))

        # --- reconnect the bounded in-neighbourhood through N_out(p) -------
        cz = _topc_candidates(st, cfg, z_ids, nout_p, cfg.n_copies)

        st = append_edges(st, cfg, z_ids[:, None], cz)

        # --- release the slot directly (no quarantine, no pending debt) ----
        new_start = _next_start(st, cfg, p, nout_p)
        st = st._replace(
            adj=st.adj.at[sp].set(jnp.full((cfg.r,), INVALID, jnp.int32)),
            active=st.active.at[sp].set(False),
            free_stack=st.free_stack.at[st.free_top].set(
                sp.astype(jnp.int32)
            ),
            free_top=st.free_top + 1,
            n_active=st.n_active - 1,
            start=new_start,
        )
        comps = jnp.sum(z_ids >= 0) * jnp.sum(nout_p >= 0)
        return st, DeleteStats(jnp.bool_(True), comps.astype(jnp.int32))

    return lax.cond(valid, do_delete, no_op, state)


@functools.partial(jax.jit, static_argnames=("cfg",))
def local_delete_many(state: GraphState, cfg: ANNConfig, ps: jax.Array):
    """Serial scan of ``local_delete`` — like the lazy baseline, the serial
    scan IS the batched formulation: each lane's in-neighbour compare must
    see the previous lane's repairs to stay exact, so relaxed visibility
    would reintroduce the dangling edges the policy exists to prevent."""

    def step(st, p):
        st, stats = local_delete(st, cfg, p)
        return st, stats

    with device_scope(DELETE_REPAIR):
        return lax.scan(step, state, ps)


# ---------------------------------------------------------------------------
# FreshDiskANN lazy delete (baseline)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def lazy_delete(state: GraphState, cfg: ANNConfig, p: jax.Array):
    """Tombstone ``p``: still navigable, no longer returnable (FreshDiskANN)."""
    sp = clip_ids(p, cfg.n_cap)
    valid = (p >= 0) & state.active[sp]

    def do(st: GraphState):
        # keep the entry point navigable; tombstones remain navigable so no
        # start reassignment is needed here (Alg 4 handles it on consolidate).
        return st._replace(
            active=st.active.at[sp].set(False),
            tombstone=st.tombstone.at[sp].set(True),
            n_active=st.n_active - 1,
            n_pending=st.n_pending + 1,
        ), DeleteStats(jnp.bool_(True), jnp.int32(0))

    def no_op(st: GraphState):
        return st, DeleteStats(jnp.bool_(False), jnp.int32(0))

    return lax.cond(valid, do, no_op, state)


@functools.partial(jax.jit, static_argnames=("cfg",))
def lazy_delete_many(state: GraphState, cfg: ANNConfig, ps: jax.Array):
    def step(st, p):
        st, stats = lazy_delete(st, cfg, p)
        return st, stats

    with device_scope(DELETE_REPAIR):
        return lax.scan(step, state, ps)
