"""Consolidation passes.

``light_consolidate`` — Algorithm 6 (ours): strip dangling edges to
quarantined slots and release those slots to the free stack.  **No distance
computations** — one gather + compare + compact over the adjacency matrix,
exactly the paper's "extremely lightweight" sweep.

``fresh_consolidate`` — Algorithm 4 (FreshDiskANN baseline): for every live
vertex with tombstoned out-neighbours, splice in the tombstones'
out-neighbourhoods and RobustPrune.  Host-orchestrated (it is the *offline
background* pass in the paper): affected rows are selected on host, then
pruned in vmapped device chunks.  The prune's distance math rides the
kernel engine selected by ``cfg.backend`` (core/backend.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .prune import robust_prune
from .types import (
    INVALID,
    ANNConfig,
    GraphState,
    clip_ids,
    compact_row,
)


def consolidation_due(state: GraphState, cfg: ANNConfig) -> jax.Array:
    """Device-side consolidation trigger: a traced bool scalar over the
    pending/active counters carried in ``GraphState``.  This is the same
    predicate the old host-side ``UpdatePolicy.should_consolidate`` computed
    from synced ints — expressed on device so compiled update streams
    (``core/api.py::apply_segment``) can branch on it under ``lax.cond``
    without a per-op host round-trip."""
    n_active = jnp.maximum(state.n_active, 1).astype(jnp.float32)
    return (state.n_pending > 0) & (
        state.n_pending.astype(jnp.float32)
        > cfg.consolidation_threshold * n_active
    )


# The exact GraphState fields Algorithm 6 reads and writes.  Streams that
# run the sweep under ``lax.cond`` (``core/api.py::device_sweep``) narrow
# the cond's operands to this tuple, so the untouched multi-MB leaves
# (vectors, norms, active, ...) never ride the branch — on CPU a cond
# copies every carried operand each step even when the branch never fires.
# The "local" policy also declares these fields: its deletes release slots
# directly (n_pending stays 0, the trigger never fires on a pure-local
# stream), so the sweep is purely defensive for states inherited from
# another policy.
LIGHT_CONSOLIDATE_FIELDS = (
    "adj", "quarantine", "free_stack", "free_top", "n_pending"
)


def light_consolidate_fields(cfg: ANNConfig, adj, quarantine, free_stack,
                             free_top, n_pending):
    """Algorithm 6 on exactly the fields it touches; returns the updated
    ``LIGHT_CONSOLIDATE_FIELDS`` tuple.  Un-jitted on purpose: callers
    embed it in larger programs (the narrowed ``lax.cond`` branch) where a
    nested jit would re-widen the operand set."""
    dead = quarantine[clip_ids(adj, cfg.n_cap)] & (adj >= 0)
    adj = jnp.where(dead, INVALID, adj)
    adj = jax.vmap(compact_row)(adj)

    # release quarantined slots onto the free stack
    n = cfg.n_cap
    q_idx = jnp.where(quarantine, jnp.arange(n, dtype=jnp.int32), n)
    q_sorted = jnp.sort(q_idx)                      # quarantined ids first
    n_q = jnp.sum(quarantine).astype(jnp.int32)
    pos = free_top + jnp.arange(n, dtype=jnp.int32)
    pos = jnp.where(jnp.arange(n) < n_q, pos, n)    # only first n_q written
    free_stack = free_stack.at[pos].set(
        q_sorted.astype(jnp.int32), mode="drop"
    )
    return (
        adj,
        jnp.zeros_like(quarantine),
        free_stack,
        free_top + n_q,
        jnp.int32(0),
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def light_consolidate(state: GraphState, cfg: ANNConfig) -> GraphState:
    """Algorithm 6: remove dangling edges, free quarantined slots."""
    out = light_consolidate_fields(
        cfg, *(getattr(state, f) for f in LIGHT_CONSOLIDATE_FIELDS)
    )
    return state._replace(**dict(zip(LIGHT_CONSOLIDATE_FIELDS, out)))


# ---------------------------------------------------------------------------
# FreshDiskANN batch consolidation (Algorithm 4)
# ---------------------------------------------------------------------------


def _splice_candidates(state: GraphState, cfg: ANNConfig, node):
    """Candidate set for one affected node: (own row \\ D) U (rows of deleted
    out-neighbours \\ D).  Fixed width r + r*r."""
    row = state.adj[node]                                       # (r,)
    srow = clip_ids(row, cfg.n_cap)
    nbr_dead = state.tombstone[srow] & (row >= 0)
    # rows of deleted out-neighbours
    two_hop = state.adj[srow]                                   # (r, r)
    two_hop = jnp.where(nbr_dead[:, None], two_hop, INVALID)
    keep_own = jnp.where((row >= 0) & ~nbr_dead, row, INVALID)
    cand = jnp.concatenate([keep_own, two_hop.reshape(-1)])
    scand = clip_ids(cand, cfg.n_cap)
    cand = jnp.where(
        (cand >= 0) & ~state.tombstone[scand] & (cand != node), cand, INVALID
    )
    return cand, jnp.any(nbr_dead)


def _consolidate_rows(state: GraphState, cfg: ANNConfig, nodes):
    """New rows for a chunk of affected nodes (vmapped Alg 4 lines 4-7)."""

    def one(node):
        cand, _ = _splice_candidates(state, cfg, node)
        # Alg 4 prunes the spliced candidate set back to <= r.
        return robust_prune(
            state, cfg, state.vectors[node], cand, p_id=node
        )

    return jax.vmap(one)(nodes)


_consolidate_rows_j = jax.jit(_consolidate_rows, static_argnames=("cfg",))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _affected_mask(state: GraphState, cfg: ANNConfig):
    dead = state.tombstone[clip_ids(state.adj, cfg.n_cap)] & (state.adj >= 0)
    return jnp.any(dead, axis=1) & state.active


@functools.partial(jax.jit, static_argnames=("cfg",))
def _release_tombstones(state: GraphState, cfg: ANNConfig) -> GraphState:
    """Clear tombstoned slots and return them to the free stack."""
    n = cfg.n_cap
    t = state.tombstone
    t_idx = jnp.where(t, jnp.arange(n, dtype=jnp.int32), n)
    t_sorted = jnp.sort(t_idx)
    n_t = jnp.sum(t).astype(jnp.int32)
    pos = state.free_top + jnp.arange(n, dtype=jnp.int32)
    pos = jnp.where(jnp.arange(n) < n_t, pos, n)
    free_stack = state.free_stack.at[pos].set(t_sorted, mode="drop")
    adj = jnp.where(t[:, None], INVALID, state.adj)
    # entry point must stay live
    nav = state.active
    start_dead = (state.start >= 0) & t[clip_ids(state.start, n)]
    new_start = jnp.where(
        start_dead,
        jnp.where(jnp.any(nav), jnp.argmax(nav).astype(jnp.int32), INVALID),
        state.start,
    )
    return state._replace(
        adj=adj,
        tombstone=jnp.zeros_like(t),
        free_stack=free_stack,
        free_top=state.free_top + n_t,
        n_pending=jnp.int32(0),
        start=new_start,
    )


@functools.partial(jax.jit, donate_argnums=0)
def _write_row(block: GraphState, row: GraphState, i) -> GraphState:
    """Write one shard's graph into row ``i`` of a DONATED (G, ...) stack
    block: XLA updates the row in the existing buffers, so the write is
    O(one shard) in copies.  ``i`` is a traced scalar — one compiled
    program serves every row."""
    return jax.tree.map(
        lambda full, new: jax.lax.dynamic_update_index_in_dim(
            full, new.astype(full.dtype), i, 0
        ),
        block, row,
    )


def _blocks(x: jax.Array):
    """The device blocks of ``x``'s leading axis, in axis order."""
    shards = sorted(x.addressable_shards,
                    key=lambda sh: sh.index[0].start or 0)
    if sum(sh.data.shape[0] for sh in shards) != x.shape[0]:
        raise ValueError("consolidate_stacked needs the stack's leading "
                         "axis split over its devices, each row held once")
    return [sh.data for sh in shards]


def consolidate_stacked(graphs: GraphState, cfg: ANNConfig, consolidate_fn,
                        shard_ids) -> GraphState:
    """Run a per-shard consolidation pass over a STACKED ``GraphState``
    (leading shard axis, as ``ShardedIndex`` carries it: on one device, or
    split in (G, ...) blocks over a mesh axis).

    For each shard in ``shard_ids``: take that shard's graph out of the
    device block that holds it, run ``consolidate_fn(graph, cfg)`` (e.g.
    the fresh policy's host-orchestrated Algorithm 4, or
    ``light_consolidate`` under ``force``) on that device, and write the
    result back with the jitted DONATED ``_write_row``.  Nothing crosses
    devices and no device holds another's graph; the blocks are then
    reassembled under the stack's sharding.  The caller's ``graphs`` handle
    is consumed: use the RETURNED stack, exactly as with the donated update
    front doors.
    """
    leaves, tree = jax.tree.flatten(graphs)
    layouts = [(x.shape, x.sharding) for x in leaves]
    blocks = [_blocks(x) for x in leaves]
    starts = np.cumsum([0] + [b.shape[0] for b in blocks[0]])
    for s in shard_ids:
        k = int(np.searchsorted(starts, s, side="right")) - 1
        block = tree.unflatten([b[k] for b in blocks])
        i = int(s - starts[k])
        g = consolidate_fn(jax.tree.map(lambda x: x[i], block), cfg)
        block = _write_row(block, g, jnp.int32(i))
        for b, x in zip(blocks, jax.tree.leaves(block)):
            b[k] = x
    return tree.unflatten([
        jax.make_array_from_single_device_arrays(shape, sharding, b)
        for (shape, sharding), b in zip(layouts, blocks)
    ])


def fresh_consolidate(
    state: GraphState, cfg: ANNConfig, chunk: int = 256
) -> GraphState:
    """Algorithm 4 (baseline).  Host-orchestrated offline pass."""
    mask = np.asarray(_affected_mask(state, cfg))
    affected = np.nonzero(mask)[0].astype(np.int32)
    # fixed-size device chunks (pad the tail so one compilation serves all)
    if affected.size:
        pad = (-affected.size) % chunk
        padded = np.concatenate(
            [affected, np.full((pad,), affected[0], np.int32)]
        )
        adj = state.adj
        for i in range(0, padded.size, chunk):
            nodes = jnp.asarray(padded[i : i + chunk])
            rows = _consolidate_rows_j(state, cfg, nodes)
            take = min(chunk, affected.size - i)
            adj = adj.at[nodes[:take]].set(rows[:take])
        state = state._replace(adj=adj)
    return _release_tombstones(state, cfg)
