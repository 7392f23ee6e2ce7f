"""Edge mutation helpers shared by insert / delete (Algorithms 2 and 5)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .prune import robust_prune
from .types import (
    INVALID,
    ANNConfig,
    GraphState,
    clip_ids,
    compact_row,
    row_contains,
    row_count,
)


def _appended_row(state: GraphState, cfg: ANNConfig, v, u, row):
    """v's row after adding edge v -> u (Algorithm 2 lines 5-8): ``row``
    with u appended, RobustPruned when it would exceed degree r, or ``row``
    as it was when v/u is INVALID, u == v (self loop), u is already present
    or either end is dead.  Reads ``row`` and the vectors and liveness flags
    of ``state`` — never ``state.adj``."""
    sv = clip_ids(v, cfg.n_cap)
    su = clip_ids(u, cfg.n_cap)
    u_live = state.active[su] | state.tombstone[su]
    # v must itself be live: under batched updates a stale candidate may
    # refer to a vertex deleted earlier in the same batch
    v_live = state.active[sv] | state.tombstone[sv]
    skip = (
        (v < 0) | (u < 0) | (v == u) | row_contains(row, u)
        | ~u_live | ~v_live
    )
    cnt = row_count(row)
    # selects, not lax.cond: under vmap a cond with a per-lane predicate
    # broadcasts every operand it closes over (the vector table) per lane
    appended = row.at[cnt].set(u, mode="drop")
    cand = jnp.concatenate([row, jnp.asarray(u, jnp.int32)[None]])
    pruned = robust_prune(state, cfg, state.vectors[sv], cand, p_id=v)
    return jnp.where(skip, row, jnp.where(cnt < cfg.r, appended, pruned))


LANES = 64  # rows an append round prunes per vmapped batch


def append_edges(state: GraphState, cfg: ANNConfig, vs, us) -> GraphState:
    """Add the edges ``vs[k] -> us[k]`` in order k (broadcast together,
    then flattened row-major), each as Algorithm 2 lines 5-8 (see
    ``_appended_row``).

    The result is the graph of appending the edges one at a time.  An
    append reads only its own row besides vectors and liveness flags, so
    appends to different rows commute: round t applies the t-th pending
    append of every row, and the rounds number the most edges any one row
    receives, not the edges.  A round prunes its rows ``LANES`` at a time
    as one vmapped batch, so it costs what its own edges cost, and writes
    them back row by row (distinct rows, so no write races; see
    ``put_rows``).  No ``lax.cond`` returns the state, which on TPU would
    copy the (n_cap, r) adjacency per call.
    """
    vs, us = jnp.broadcast_arrays(jnp.asarray(vs, jnp.int32),
                                  jnp.asarray(us, jnp.int32))
    vs, us = vs.reshape(-1), us.reshape(-1)
    n = vs.shape[0]
    w = min(n, LANES)
    # rank of each edge among the earlier edges into the same row
    same = (vs[:, None] == vs[None, :]) & jnp.tri(n, n, -1, dtype=bool)
    rank = jnp.where(vs >= 0, jnp.sum(same, axis=1), -1)
    # edges in the order their rows are written: by round, then by k; the
    # tail points at an extra INVALID edge (index n) that fills a batch
    order = jnp.argsort(jnp.where(rank >= 0, rank, n), stable=True)
    order = jnp.concatenate([order, jnp.full((w,), n, order.dtype)])
    vs = jnp.concatenate([vs, jnp.full((1,), INVALID, jnp.int32)])
    us = jnp.concatenate([us, jnp.full((1,), INVALID, jnp.int32)])
    rows_after = jax.vmap(
        lambda v, u, row: _appended_row(state, cfg, v, u, row)
    )

    def batch(carry):
        # edges order[j : min(j + w, end)], all in one round
        j, end, adj = carry
        k = lax.dynamic_slice(order, (j,), (w,))
        k = jnp.where(j + jnp.arange(w) < end, k, n)
        sv = clip_ids(vs[k], cfg.n_cap)
        new = rows_after(vs[k], us[k], adj[sv])
        return j + w, end, put_rows(adj, sv, new, jnp.minimum(end - j, w))

    def round_(carry):
        t, done, adj = carry
        end = done + jnp.sum(rank == t)
        _, _, adj = lax.while_loop(lambda c: c[0] < c[1], batch,
                                   (done, end, adj))
        return t + 1, end, adj

    _, _, adj = lax.while_loop(
        lambda c: c[0] <= jnp.max(rank), round_,
        (jnp.int32(0), jnp.int32(0), state.adj),
    )
    return state._replace(adj=adj)


def put_rows(adj, idx, rows, n):
    """``adj`` with ``rows[j]`` written at row ``idx[j]`` for ``j < n``,
    one dynamic row update each (distinct rows).

    One scatter would write them at once, but on TPU a scatter into the
    (n, r) adjacency makes XLA keep it column-major (r = 64 fills half a
    128-lane tile row-major) and then transpose all of it to gather rows:
    256 MiB per gather at n_cap 2^20."""
    return lax.fori_loop(
        0, n,
        lambda j, a: lax.dynamic_update_index_in_dim(a, rows[j], idx[j], 0),
        adj,
    )


def remove_target_everywhere(state: GraphState, cfg: ANNConfig, target):
    """Remove every edge ``* -> target`` from the whole adjacency matrix.

    One (n_cap, r) compare over the topology — the exact in-neighbourhood,
    where Algorithm 5 settles for the in-neighbours its greedy search
    happens to visit.  Rows that lose an entry are re-compacted (the
    front-compaction contract ``append_edges`` writes against); untouched
    rows come back bit-identical.  Returns new adj.
    """
    hit = (state.adj == target) & (target >= 0)
    cleaned = jnp.where(hit, INVALID, state.adj)
    compacted = jax.vmap(compact_row)(cleaned)
    return jnp.where(jnp.any(hit, axis=1)[:, None], compacted, cleaned)


def remove_target_rows(state: GraphState, cfg: ANNConfig, row_ids, target):
    """Vectorised removal of ``target`` from the rows listed in ``row_ids``.

    ``row_ids`` i32[M], INVALID padded, assumed unique among valid entries.
    Returns new adj.
    """
    safe = clip_ids(row_ids, cfg.n_cap)
    rows = state.adj[safe]                      # (M, r)
    hit = (rows == target) & (row_ids >= 0)[:, None]
    cleaned = jnp.where(hit, INVALID, rows)
    cleaned = jnp.vectorize(compact_row, signature="(r)->(r)")(cleaned)
    # write only rows that actually changed (changed rows first); the rest,
    # INVALID-padded row ids included, are skipped so duplicate clip
    # targets can't race.
    write = jnp.any(hit, axis=1)
    order = jnp.argsort(~write, stable=True)
    return put_rows(state.adj, safe[order], cleaned[order], jnp.sum(write))
