"""The unified op-stream API: one pure ``apply(state, cfg, batch)`` front door.

The paper's streaming workload is ONE interleaved stream of inserts, deletes
and queries against ONE index handle.  This module is that handle's
functional surface:

  * ``IndexState`` (``core/types.py``) carries the graph, the external-id
    <-> slot map and the per-op counters entirely on device;
  * ``UpdateBatch`` is the unified op type — a padded lane-batch of mixed
    inserts and deletes (kind / ext_id / vector / valid-lane mask);
  * ``apply(state, cfg, batch, policy=..., sequential=...)`` is the single
    jitted update entry point.  One call compiles to ONE device program per
    power-of-two bucket: id-map resolution, the batched search phases
    (through ``core/search_batched.py``'s shared hop loop, delete lanes
    masked during the insert search and vice versa), the serial write scans
    and the id-map scatter all fuse — where the old front doors paid two
    dispatches and a host numpy round-trip per runbook step;
  * ``search(state, cfg, queries)`` is the query front door, mapping slot
    ids back to external ids on device;
  * ``UpdatePolicy`` replaces the old ``mode="ip"/"fresh"`` strings with a
    registered object (mirroring the ``DistanceBackend`` registry) that owns
    the delete strategy and the consolidation trigger — the trigger is a
    device-side predicate over the counters carried in ``IndexState``, so
    compiled streams never sync to host to decide;
  * ``apply_segment(state, cfg, ops)`` is the whole-segment compiled
    stream: ``lax.scan`` of the ``apply`` body over a (T, B) op tensor —
    one dispatch for T ops, the ip policy's consolidation sweep running
    under ``lax.cond`` mid-segment.  ``plan_segments``/``run_segments``
    chop an arbitrary op stream into bucket-padded segments;
  * ``compact_owner_batch``/``compact_owner_segment`` are the sharding
    constructors: they pack each shard's owned lanes of a batch (or a
    whole (T, B) segment) into static power-of-two per-shard sub-tensors,
    so ``ShardedIndex`` ships every shard only its ~B/S owned lanes
    instead of replicating the batch and masking S-1 of every lane.

Semantics (pinned lane-for-lane by ``tests/test_api.py``): a mixed batch
applies all insert lanes first (in lane order), then all delete lanes (in
lane order), with delete lanes resolving external ids against the
post-insert map — exactly the old two-call ``insert(...)`` then
``delete(...)`` sequence, collapsed into one program.  ``sequential=True``
runs the paper-faithful serial scan (each lane's search sees every earlier
lane's writes — the bootstrap regime); ``sequential=False`` runs the
relaxed-visibility batched phases (searches of a kind see the graph as of
that phase's start — the paper's multi-threaded regime).

Both update front doors DONATE their state argument
(``donate_argnums=0``): every caller that drops its old handle
(``state, res = apply(state, cfg, batch)``) lets XLA update the multi-MB
graph buffers in place instead of reallocating them per step.  The old
handle is dead after the call — ``clone_state`` first if it must survive.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .batched import insert_many_batched, ip_delete_many_batched
from .consolidate import (
    LIGHT_CONSOLIDATE_FIELDS,
    consolidation_due,
    fresh_consolidate,
    light_consolidate,
    light_consolidate_fields,
)
from .delete import ip_delete_many, lazy_delete_many, local_delete_many
from .insert import insert_many
from .search import search_batch
from .search_batched import next_bucket
from .spans import (
    CONSOLIDATE,
    MAP,
    SEARCH,
    SEARCH_MAP_IDS,
    TRACE_COUNTER,
    TRACE_UNROLL,
    device_scope,
    host_span,
)
from .types import (
    INVALID,
    KIND_DELETE,
    KIND_INSERT,
    ANNConfig,
    ApplyResult,
    GraphState,
    IndexState,
    SegmentResult,
    UpdateBatch,
    clip_ids,
    init_index_state,
    noop_update_batch,
    stack_update_batches,
    take_update_lanes,
)


def auto_unroll(t: int, b: int) -> int:
    """Size-aware default ``lax.scan`` unroll for a (T, B) update segment.

    Cross-op fusion is worth most exactly where each op is small: the
    per-op work of a narrow-lane segment underfills the machine, so
    unrolling a few ops per loop iteration lets XLA fuse across op
    boundaries (measured ~5-9% on the update bench).  Wide-lane segments
    already saturate per op, and unrolling only multiplies compile time —
    so the factor steps down as B grows and is 1 past B=256.  Callers pin
    ``unroll`` explicitly to override."""
    if t <= 1:
        return 1
    if b <= 16:
        return min(8, t)
    if b <= 64:
        return min(4, t)
    if b <= 256:
        return min(2, t)
    return 1


def clone_state(state):
    """A deep on-device copy of a state pytree.

    The jitted front doors (``apply``, ``apply_segment``,
    ``consolidate_if_needed``) DONATE their state argument: XLA reuses the
    multi-MB graph buffers in place and the caller's input handle is dead
    after the call.  Callers that must keep the pre-update handle (parity
    tests, benchmarks replaying one start state) clone it first."""
    return jax.tree.map(jnp.copy, state)


class SnapshotHandle(NamedTuple):
    """A sequence-numbered read-only view of an index state.

    ``state`` is a DEEP COPY of the writer's pytree at publication time
    (``take_snapshot`` clones), so subsequent donated updates to the
    writer's handle can never touch its buffers: searches against a
    snapshot observe exactly the updates applied before it was taken and
    none after — the snapshot-isolation contract the serving layer
    (``repro/serving``) builds its double-buffered swap protocol on.
    ``seq`` is the host-side publication sequence number."""

    seq: int
    state: IndexState


def take_snapshot(state, seq: int = 0) -> SnapshotHandle:
    """Clone ``state`` into an immutable ``SnapshotHandle`` tagged ``seq``.

    The clone is the isolation boundary: the returned handle's buffers are
    fresh, so the caller may keep donating its writer handle to
    ``apply``/``apply_segment`` while readers search the snapshot."""
    return SnapshotHandle(seq=int(seq), state=clone_state(state))


# ---------------------------------------------------------------------------
# Update policies (the old ``mode`` strings, as registered objects)
# ---------------------------------------------------------------------------


class UpdatePolicy:
    """Pluggable delete strategy + consolidation trigger.

    Mirrors the ``DistanceBackend`` registry: selection is by name, the
    registered singleton is resolved at trace time (``apply``'s ``policy``
    argument is static), and custom policies plug in with
    ``@register_policy("name")``.
    """

    name = "abstract"
    # True when ``consolidate`` is a pure jittable GraphState -> GraphState
    # pass: compiled update streams then run it under ``lax.cond`` right at
    # the trigger point.  False (fresh): the pass is host-orchestrated, so
    # streams only surface a ``needs_consolidation`` flag and the host runs
    # it between segments.
    device_consolidation = False
    # Device policies whose pass touches only a few GraphState fields name
    # them here (with a matching ``consolidate_narrow``): ``device_sweep``
    # then conds over just those operands instead of the whole state —
    # on CPU a lax.cond copies every carried operand per step, so keeping
    # the multi-MB vector table out of the branch is the whole win.
    # None = the pass may touch anything; the cond carries the full state.
    consolidation_fields: Optional[tuple] = None

    def consolidate_narrow(self, cfg: ANNConfig, sub: tuple) -> tuple:
        """``consolidate`` restricted to the ``consolidation_fields`` tuple
        (same order in and out).  Must be un-jitted traced code so the
        narrowed ``lax.cond`` branch stays narrow."""
        raise NotImplementedError

    def delete_many(self, graph: GraphState, cfg: ANNConfig, ps,
                    *, sequential: bool):
        """Delete the slots ``ps`` (i32[B], INVALID lanes are no-ops).
        Returns ``(graph, DeleteStats)`` with per-lane ``ok``/``n_comps``."""
        raise NotImplementedError

    def should_consolidate(self, cfg: ANNConfig, n_active: int,
                           n_pending: int) -> bool:
        """Host-side trigger (legacy shells): consolidate once pending
        removals exceed the configured fraction of the live set."""
        if n_pending == 0:
            return False
        return n_pending > cfg.consolidation_threshold * max(n_active, 1)

    def should_consolidate_device(self, cfg: ANNConfig,
                                  graph: GraphState) -> jax.Array:
        """The same trigger as a traced bool scalar over the device-resident
        counters — no host sync, so ``lax.scan`` streams can branch on it."""
        return consolidation_due(graph, cfg)

    def consolidate(self, graph: GraphState, cfg: ANNConfig) -> GraphState:
        """The policy's consolidation pass.  Jittable when
        ``device_consolidation`` (ip: Algorithm 6); host-orchestrated
        otherwise (fresh: Algorithm 4 is the paper's offline pass)."""
        raise NotImplementedError


_POLICIES: dict[str, UpdatePolicy] = {}


def register_policy(name: str):
    """Class decorator: instantiate and register a policy under ``name``."""

    def deco(cls):
        cls.name = name
        _POLICIES[name] = cls()
        return cls

    return deco


def available_policies() -> tuple:
    return tuple(sorted(_POLICIES))


def get_policy(name: str) -> UpdatePolicy:
    try:
        return _POLICIES[name]
    except KeyError:
        raise KeyError(
            f"unknown update policy {name!r}; "
            f"available: {available_policies()}"
        ) from None


@register_policy("ip")
class IPDiskANNPolicy(UpdatePolicy):
    """The paper's contribution: in-place deletes (Alg 5), quarantined slots
    released by the lightweight Alg 6 sweep (no distance computations).
    The sweep is pure device code, so compiled streams run it inline."""

    device_consolidation = True
    consolidation_fields = LIGHT_CONSOLIDATE_FIELDS

    def delete_many(self, graph, cfg, ps, *, sequential):
        fn = ip_delete_many if sequential else ip_delete_many_batched
        return fn(graph, cfg, ps)

    def consolidate(self, graph, cfg):
        return light_consolidate(graph, cfg)

    def consolidate_narrow(self, cfg, sub):
        return light_consolidate_fields(cfg, *sub)


@register_policy("fresh")
class FreshDiskANNPolicy(UpdatePolicy):
    """FreshDiskANN baseline: tombstone deletes + batch consolidation
    (Alg 4) past the threshold."""

    def delete_many(self, graph, cfg, ps, *, sequential):
        if cfg.origin_start:
            # its consolidation releases every tombstone, the frozen start's
            # slot among them
            raise ValueError("the fresh policy does not take "
                             "ANNConfig.origin_start")
        # lazy delete is a trivially cheap mask flip; the serial scan IS the
        # batched formulation
        return lazy_delete_many(graph, cfg, ps)

    def consolidate(self, graph, cfg):
        return fresh_consolidate(graph, cfg)


@register_policy("local")
class LocalRepairPolicy(UpdatePolicy):
    """Topology-aware localized repair (arXiv 2503.00402): the delete reads
    the EXACT in-neighbourhood off the adjacency matrix, removes every
    in-edge, reconnects a bounded in-neighbour set through the deleted
    vertex's own out-neighbourhood (``cfg.local_in_cap``; see
    ``core/delete.py::local_delete``) and releases the slot straight onto
    the free stack — no search, no quarantine, no consolidation debt.

    The pass is pure device code, so it composes with ``apply_segment``'s
    scan and donation exactly like ip.  ``device_consolidation`` stays True
    with the same narrowed Algorithm-6 fields: on a pure-local stream the
    trigger can never fire (``n_pending`` stays 0 — every delete settles
    its own repairs), so the cond compiles but costs nothing; the sweep
    remains as a defensive pass for states inherited from another policy
    (e.g. a checkpoint written under ip with quarantined slots in flight).
    """

    device_consolidation = True
    consolidation_fields = LIGHT_CONSOLIDATE_FIELDS

    def delete_many(self, graph, cfg, ps, *, sequential):
        # one formulation for both visibility modes: each lane's exact
        # in-neighbour compare must see the previous lane's repairs
        return local_delete_many(graph, cfg, ps)

    def consolidate(self, graph, cfg):
        return light_consolidate(graph, cfg)

    def consolidate_narrow(self, cfg, sub):
        return light_consolidate_fields(cfg, *sub)


# ---------------------------------------------------------------------------
# UpdateBatch constructors (host helpers)
# ---------------------------------------------------------------------------


def make_update_batch(kind, ext_ids, vectors, valid=None) -> UpdateBatch:
    """Assemble an ``UpdateBatch`` from per-lane arrays (no padding)."""
    kind = jnp.asarray(kind, jnp.int32)
    ext_ids = jnp.asarray(ext_ids, jnp.int32)
    vectors = jnp.asarray(vectors, jnp.float32)
    if valid is None:
        valid = jnp.ones((kind.shape[0],), bool)
    else:
        valid = jnp.asarray(valid, bool)
    return UpdateBatch(kind=kind, ext_id=ext_ids, vector=vectors, valid=valid)


def pad_update_batch(batch: UpdateBatch, bucket: Optional[int] = None
                     ) -> UpdateBatch:
    """Pad a batch up to ``bucket`` lanes (default: the next power of two)
    with masked no-op lanes, so streaming callers compile one program per
    bucket instead of one per distinct batch size."""
    b = batch.kind.shape[0]
    bucket = bucket if bucket is not None else next_bucket(b)
    if b == bucket:
        return batch

    def pad(arr, fill):
        widths = [(0, bucket - b)] + [(0, 0)] * (arr.ndim - 1)
        return jnp.pad(arr, widths, constant_values=fill)

    return UpdateBatch(
        kind=pad(batch.kind, KIND_INSERT),
        ext_id=pad(batch.ext_id, INVALID),
        vector=pad(batch.vector, 0.0),
        valid=pad(batch.valid, False),
    )


def insert_batch(ext_ids, vectors, *, bucket: bool = True) -> UpdateBatch:
    """An insert-only ``UpdateBatch`` (bucket-padded by default).

    External ids must be unique within the batch: duplicate insert lanes
    would race in the device id-map scatter (undefined winner, stale
    reverse entries), so they are rejected here on host."""
    ext_ids = np.asarray(ext_ids)
    if len(np.unique(ext_ids)) != len(ext_ids):
        raise ValueError("duplicate external ids in one insert batch")
    b = make_update_batch(
        np.full((len(ext_ids),), KIND_INSERT), ext_ids, vectors
    )
    return pad_update_batch(b) if bucket else b


def delete_batch(ext_ids, dim: int, *, bucket: bool = True) -> UpdateBatch:
    """A delete-only ``UpdateBatch``; delete lanes carry zero vectors."""
    ext_ids = np.asarray(ext_ids)
    b = make_update_batch(
        np.full((len(ext_ids),), KIND_DELETE), ext_ids,
        np.zeros((len(ext_ids), dim), np.float32),
    )
    return pad_update_batch(b) if bucket else b


def mixed_update_batch(ins_ext, ins_vectors, del_ext, dim: int):
    """A kind-major mixed batch: insert lanes bucket-padded first, delete
    lanes bucket-padded after.  Returns ``(UpdateBatch, split)`` where
    ``split`` is the static insert/delete boundary — pass it to ``apply``
    so each internal phase runs only over its own lane range (the layout
    costs exactly the two single-kind programs, fused).  Semantics are
    identical to any interleaved layout of the same ops."""
    ins = insert_batch(ins_ext, ins_vectors)
    dele = delete_batch(del_ext, dim)
    batch = UpdateBatch(*[
        jnp.concatenate([a, b]) for a, b in zip(ins, dele)
    ])
    return batch, ins.kind.shape[0]


# ---------------------------------------------------------------------------
# Owner-compacted sharding constructors (ShardedIndex host helpers)
# ---------------------------------------------------------------------------


def _np_update_batch(batch: UpdateBatch) -> UpdateBatch:
    return UpdateBatch(*[np.asarray(f) for f in batch])


def _compact_owner_batch_np(batch: UpdateBatch, owners, n_shards: int,
                            *, bucket: Optional[int] = None):
    """``compact_owner_batch`` body on numpy payloads (the segment packer
    loops this per step and converts to device arrays exactly once)."""
    b = _np_update_batch(batch)
    owners = np.where(b.valid, np.asarray(owners), -1)
    if owners.size and int(owners.max()) >= n_shards:
        raise ValueError(
            f"owner id(s) >= n_shards={n_shards}: "
            f"{np.unique(owners[owners >= n_shards]).tolist()}"
        )
    counts = np.bincount(owners[owners >= 0], minlength=n_shards)
    need = int(counts.max())
    if bucket is None:
        bucket = next_bucket(max(need, 1))
    if need > bucket:
        raise ValueError(
            f"per-shard bucket {bucket} < max owned lanes {need}"
        )
    dim = b.vector.shape[1]
    pos = np.full(owners.shape, -1, np.int32)
    out = UpdateBatch(
        kind=np.full((n_shards, bucket), KIND_INSERT, np.int32),
        ext_id=np.full((n_shards, bucket), INVALID, np.int32),
        vector=np.zeros((n_shards, bucket, dim), np.float32),
        valid=np.zeros((n_shards, bucket), bool),
    )
    for s in range(n_shards):
        idx = np.nonzero(owners == s)[0]
        pos[idx] = np.arange(len(idx), dtype=np.int32)
        sub = take_update_lanes(b, idx)
        out.kind[s, : len(idx)] = sub.kind
        out.ext_id[s, : len(idx)] = sub.ext_id
        out.vector[s, : len(idx)] = sub.vector
        out.valid[s, : len(idx)] = sub.valid
    return out, pos, bucket


def compact_owner_batch(batch: UpdateBatch, owners, n_shards: int,
                        *, bucket: Optional[int] = None):
    """Pack each shard's owned lanes of one ``UpdateBatch`` into a compact
    per-shard sub-batch.

    ``owners``: i32[B] owning shard per lane (negative = unowned; values
    at or beyond ``n_shards`` are a loud ``ValueError``; invalid lanes are
    ignored regardless).  Returns ``(stacked, pos, bucket)``:

      * ``stacked`` — an (S, bucket) ``UpdateBatch``; row ``s`` holds shard
        ``s``'s owned lanes in their original relative order, padded to the
        static power-of-two ``bucket`` with masked no-op lanes.  Feed it to
        an update program whose ``shard_map`` in-spec shards the leading
        axis: each shard then applies ONLY ~B/S lanes instead of masking
        S-1 of every replicated lane;
      * ``pos`` — i32[B], lane i's position inside its owner's sub-batch
        (-1 for unowned/invalid lanes), for scattering per-lane results
        back to the caller's lane order;
      * ``bucket`` — the per-shard lane width actually used
        (``next_bucket`` of the max owned-lane count unless pinned).

    Per-shard relative lane order is preserved, so per-shard serial
    semantics are bit-identical to the replicate-and-mask layout.
    """
    out, pos, bucket = _compact_owner_batch_np(
        batch, owners, n_shards, bucket=bucket
    )
    return UpdateBatch(*[jnp.asarray(f) for f in out]), pos, bucket


def compact_owner_segment(ops: UpdateBatch, owners, n_shards: int,
                          *, bucket: Optional[int] = None):
    """Per-shard segment planning: owner-compact every op of a (T, B)
    segment tensor into one (S, T, bucket) op tensor.

    ``owners``: i32[T, B].  One common power-of-two ``bucket`` (the max
    owned-lane count over every (shard, op) cell unless pinned) keeps the
    stacked tensor static — the whole-segment scan then compiles once per
    (T_bucket, bucket) shape while each shard scans T ops of ~B/S lanes.
    Returns ``(stacked, pos, bucket)`` with ``pos`` i32[T, B] as in
    ``compact_owner_batch``.
    """
    ops_np = _np_update_batch(ops)
    owners = np.where(ops_np.valid, np.asarray(owners), -1)
    t_steps = ops_np.kind.shape[0]
    need = 1
    for t in range(t_steps):
        row = owners[t]
        counts = np.bincount(row[row >= 0], minlength=n_shards)
        need = max(need, int(counts.max()))
    if bucket is None:
        bucket = next_bucket(need)
    # pack every step in numpy; one stack + one host->device conversion
    # per field at the end (not T x 4 small transfers)
    steps, pos = [], []
    for t in range(t_steps):
        sub, p, _ = _compact_owner_batch_np(
            take_update_lanes(ops_np, t), owners[t], n_shards, bucket=bucket
        )
        steps.append(sub)
        pos.append(p)
    stacked = UpdateBatch(*[
        jnp.asarray(np.stack(arrs, axis=1)) for arrs in zip(*steps)
    ])
    return stacked, np.stack(pos), bucket


# ---------------------------------------------------------------------------
# The unified update front door
# ---------------------------------------------------------------------------


def _apply_impl(
    state: IndexState,
    cfg: ANNConfig,
    batch: UpdateBatch,
    pol: UpdatePolicy,
    sequential: bool,
    split: Optional[int],
):
    """The traced ``apply`` body, shared verbatim by the per-op front door
    and the ``lax.scan`` step of ``apply_segment`` (segment-vs-loop parity
    is bit parity because this IS the same program)."""
    # every operation here but the two phases' own runs under ``ann.map``
    b = batch.kind.shape[0]
    e_cap = state.ext2slot.shape[0]
    with device_scope(MAP):
        ext_ok = (batch.ext_id >= 0) & (batch.ext_id < e_cap)
        sext = jnp.clip(batch.ext_id, 0, e_cap - 1)
        is_ins = batch.valid & ext_ok & (batch.kind == KIND_INSERT)
        is_del = batch.valid & ext_ok & (batch.kind == KIND_DELETE)
        if split is not None:
            lane = jnp.arange(b)
            is_ins = is_ins & (lane < split)
            is_del = is_del & (lane >= split)
        ins_lanes = slice(None) if split is None else slice(None, split)
        ins_vectors, ins_valid = batch.vector[ins_lanes], is_ins[ins_lanes]

    # ---- insert phase ------------------------------------------------------
    ins_fn = insert_many if sequential else insert_many_batched
    graph, ins_stats = ins_fn(state.graph, cfg, ins_vectors, ins_valid)

    with device_scope(MAP):
        if split is None:
            ins_slots = ins_stats.slot              # INVALID on masked/full
            ins_comps_lane = ins_stats.n_comps
        else:
            tail = jnp.full((b - split,), INVALID, jnp.int32)
            ins_slots = jnp.concatenate([ins_stats.slot, tail])
            ins_comps_lane = jnp.concatenate(
                [ins_stats.n_comps.astype(jnp.int32), jnp.zeros_like(tail)]
            )
        ok_ins = is_ins & (ins_slots >= 0)

        # rebind: clear the stale reverse entry of a re-inserted external id
        prev = jnp.where(ok_ins, state.ext2slot[sext], INVALID)
        slot2ext = state.slot2ext.at[
            jnp.where(prev >= 0, clip_ids(prev, cfg.n_cap), cfg.n_cap)
        ].set(INVALID, mode="drop")
        ext2slot = state.ext2slot.at[
            jnp.where(ok_ins, sext, e_cap)
        ].set(ins_slots, mode="drop")
        slot2ext = slot2ext.at[
            jnp.where(ok_ins, clip_ids(ins_slots, cfg.n_cap), cfg.n_cap)
        ].set(batch.ext_id, mode="drop")

        # resolve deletes against the POST-insert map: a batch may delete an
        # id that an earlier lane of the same batch inserted
        del_slots = jnp.where(is_del, ext2slot[sext], INVALID)
        del_lanes = del_slots if split is None else del_slots[split:]

    # ---- delete phase (policy-owned strategy) ------------------------------
    graph, del_stats = pol.delete_many(graph, cfg, del_lanes,
                                       sequential=sequential)

    with device_scope(MAP):
        if split is None:
            del_ok_lane = del_stats.ok
            del_comps_lane = del_stats.n_comps
        else:
            head_f = jnp.zeros((split,), bool)
            del_ok_lane = jnp.concatenate([head_f, del_stats.ok])
            del_comps_lane = jnp.concatenate(
                [jnp.zeros((split,), jnp.int32),
                 del_stats.n_comps.astype(jnp.int32)]
            )
        ok_del = is_del & del_ok_lane
        ext2slot = ext2slot.at[
            jnp.where(ok_del, sext, e_cap)
        ].set(INVALID, mode="drop")
        slot2ext = slot2ext.at[
            jnp.where(ok_del, clip_ids(del_slots, cfg.n_cap), cfg.n_cap)
        ].set(INVALID, mode="drop")

        # ---- counters + per-lane result -----------------------------------
        ins_comps = jnp.where(is_ins, ins_comps_lane, 0).astype(jnp.int32)
        del_comps = jnp.where(is_del, del_comps_lane, 0).astype(jnp.int32)
        new_state = IndexState(
            graph=graph,
            ext2slot=ext2slot,
            slot2ext=slot2ext,
            n_inserts=state.n_inserts + jnp.sum(ok_ins).astype(jnp.int32),
            n_deletes=state.n_deletes + jnp.sum(ok_del).astype(jnp.int32),
            insert_comps=state.insert_comps + jnp.sum(ins_comps),
            delete_comps=state.delete_comps + jnp.sum(del_comps),
        )
        result = ApplyResult(
            slot=jnp.where(
                ok_ins, ins_slots, jnp.where(is_del, del_slots, INVALID)
            ),
            ok=ok_ins | ok_del,
            n_comps=ins_comps + del_comps,
        )
    return new_state, result


@functools.partial(
    jax.jit, static_argnames=("cfg", "policy", "sequential", "split"),
    donate_argnums=0,
)
def apply(
    state: IndexState,
    cfg: ANNConfig,
    batch: UpdateBatch,
    *,
    policy: str = "ip",
    sequential: bool = False,
    split: Optional[int] = None,
):
    """Apply one mixed insert+delete ``UpdateBatch``; returns
    ``(IndexState, ApplyResult)``.

    All insert lanes apply first (lane order), then all delete lanes (lane
    order), deletes resolving against the post-insert id map — the exact
    semantics of the old two-call sequence, in one compiled program.  Lanes
    whose ``valid`` is False, whose external id is out of range, or (for
    deletes) unmapped, are no-ops with ``ok=False``.  Re-inserting a mapped
    external id rebinds it and clears the stale ``slot2ext`` entry of the
    previous slot (which stays occupied until deleted).  External ids must
    be unique per kind within one batch: duplicate insert lanes race in the
    id-map scatter (undefined winner; ``insert_batch`` rejects them on
    host), and of duplicate delete lanes only the first applies (the rest
    report ``ok=False``).

    ``split`` is a static layout hint for kind-major batches (see
    ``mixed_update_batch``): insert lanes live in ``[0, split)`` and delete
    lanes in ``[split, B)``, so each internal phase runs only over its own
    lane range.  It never changes semantics — insert-kind lanes at or past
    ``split`` (and delete-kind lanes before it) are rejected with
    ``ok=False`` rather than silently applied out of order.

    The ``state`` argument is DONATED: XLA writes the new graph into the
    input's buffers, so the caller's old handle is dead after the call.
    Rebind it (``state, res = apply(state, ...)``) or ``clone_state`` first.
    """
    TRACE_COUNTER["apply"] += 1
    return _apply_impl(state, cfg, batch, get_policy(policy), sequential,
                       split)


# ---------------------------------------------------------------------------
# Device-side consolidation trigger
# ---------------------------------------------------------------------------


def device_sweep(graph: GraphState, cfg: ANNConfig, pol: UpdatePolicy,
                 trig: jax.Array) -> GraphState:
    """Run ``pol``'s device consolidation pass under ``lax.cond`` when the
    traced ``trig`` scalar is set.  THE one cond site every device-trigger
    path shares (per-op ``consolidate_if_needed``, the segment scan, the
    sharded per-op update) — so trigger semantics cannot diverge.

    Policies that declare ``consolidation_fields`` get a NARROW cond: only
    those fields are operands/results of the branches, the untouched
    leaves (the (n_cap, dim) vector table above all) bypass it entirely —
    the full-state reassembly happens out here, past the cond.  The
    branches must not close over the full state, or tracing would hoist
    the closed-over leaves right back into the cond's operands."""
    fields = pol.consolidation_fields
    if fields is None:
        return jax.lax.cond(
            trig, lambda g: pol.consolidate(g, cfg), lambda g: g, graph
        )
    sub = tuple(getattr(graph, f) for f in fields)
    out = jax.lax.cond(
        trig, lambda s: pol.consolidate_narrow(cfg, s), lambda s: s, sub
    )
    return graph._replace(**dict(zip(fields, out)))


@functools.partial(
    jax.jit, static_argnames=("cfg", "policy", "force"), donate_argnums=0
)
def consolidate_if_needed(
    state: IndexState, cfg: ANNConfig, *, policy: str = "ip",
    force: bool = False,
):
    """One fused device step: evaluate the policy's consolidation trigger
    over the counters carried in ``state`` and, if it fires, run the
    device-side pass under ``lax.cond`` — no host round-trip anywhere.

    Returns ``(IndexState, did: bool[])`` with ``did`` still on device.
    Only policies with ``device_consolidation`` (ip) qualify; the
    host-orchestrated fresh baseline goes through ``maybe_consolidate``.
    ``state`` is donated.
    """
    pol = get_policy(policy)
    if not pol.device_consolidation:
        raise ValueError(
            f"policy {policy!r} consolidates on host; use maybe_consolidate"
        )
    if force:
        trig = state.graph.n_pending > 0
    else:
        trig = pol.should_consolidate_device(cfg, state.graph)
    return state._replace(
        graph=device_sweep(state.graph, cfg, pol, trig)
    ), trig


# ---------------------------------------------------------------------------
# Whole-segment compiled update streams
# ---------------------------------------------------------------------------


def segment_scan(
    state: IndexState,
    cfg: ANNConfig,
    ops: UpdateBatch,
    pol: UpdatePolicy,
    sequential: bool,
    split: Optional[int],
    consolidate: bool = True,
    unroll: int = 1,
):
    """The traced body of ``apply_segment``: ``lax.scan`` of the per-op
    ``apply`` body over a (T, B) op tensor, with the consolidation trigger
    evaluated on device after every op.  Shared with the sharded index's
    segment path (which runs it under ``shard_map``).

    ``consolidate=False`` drops the trigger from the compiled stream
    entirely (flags stay False): on CPU the ``lax.cond`` makes XLA copy the
    graph carry every step even when the sweep never fires, so callers that
    own consolidation elsewhere — or deliberately exclude it, like the
    update benchmark's parity paths — opt out statically.

    ``unroll``: ``lax.scan`` unroll factor.  A compiled stream can fuse
    ACROSS op boundaries — something per-op dispatch can never do — and
    unrolling a few ops per loop iteration is what unlocks it (measured
    ~5% at unroll=4, ~9% at unroll=16 on the update bench's B=256 stream).
    The trade is compile time, which grows with the unrolled body; 1 keeps
    compiles identical to the per-op program."""

    def body(st: IndexState, op: UpdateBatch):
        st, res = _apply_impl(st, cfg, op, pol, sequential, split)
        consolidated = needs = jnp.bool_(False)
        if consolidate:
            with device_scope(CONSOLIDATE):
                trig = pol.should_consolidate_device(cfg, st.graph)
                if pol.device_consolidation:
                    st = st._replace(
                        graph=device_sweep(st.graph, cfg, pol, trig)
                    )
                    consolidated = trig
                else:
                    needs = trig
        return st, SegmentResult(
            slot=res.slot, ok=res.ok, n_comps=res.n_comps,
            consolidated=consolidated, needs_consolidation=needs,
        )

    return jax.lax.scan(body, state, ops, unroll=unroll)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "policy", "sequential", "split", "consolidate", "unroll"
    ),
    donate_argnums=0,
)
def apply_segment(
    state: IndexState,
    cfg: ANNConfig,
    ops: UpdateBatch,
    *,
    policy: str = "ip",
    sequential: bool = False,
    split: Optional[int] = None,
    consolidate: bool = True,
    unroll: Optional[int] = None,
):
    """Run a whole update-stream segment — an ``UpdateBatch`` with a leading
    (T,) op axis — as ONE compiled program: ``lax.scan`` of the ``apply``
    body, one dispatch for T ops instead of T dispatches.

    Returns ``(IndexState, SegmentResult)`` with per-op stacked lanes.  Op
    ``t``'s semantics are exactly ``apply(state_t, cfg, ops[t], ...)``
    followed by the policy's consolidation trigger:

      * device policies (ip) run ``light_consolidate`` under ``lax.cond``
        the moment the trigger fires — mid-segment, no host involvement;
      * host policies (fresh) surface ``needs_consolidation[t]`` and the
        host consolidates between segments (``run_segments`` does this),
        which is where the scan cleanly splits at trigger points.

    ``split`` is the same static kind-major layout hint as ``apply``,
    applied to every op in the segment (``plan_segments`` builds segments
    with one common split).  One program compiles per (T, B[, split])
    bucket — pad the op axis with ``noop_update_batch`` steps (masked lanes
    are no-ops) so ragged segment lengths share buckets.

    ``consolidate=False`` statically drops the per-op trigger from the
    stream, and ``unroll`` trades compile time for fusion across op
    boundaries (see ``segment_scan``).  The default ``unroll=None``
    resolves per (T, B) bucket via ``auto_unroll`` — deeper unrolls for
    narrow-lane segments, none for wide ones — recorded in
    ``TRACE_UNROLL`` at trace time; pass an int to pin it.

    ``state`` is donated, as with ``apply``.
    """
    TRACE_COUNTER["apply_segment"] += 1
    t, b = ops.kind.shape
    if unroll is None:
        unroll = auto_unroll(t, b)
        TRACE_UNROLL[(t, b)] = unroll
    return segment_scan(state, cfg, ops, get_policy(policy), sequential,
                        split, consolidate, unroll)


class Segment(NamedTuple):
    """One bucket-padded op tensor of a ``SegmentPlan``."""

    ops: UpdateBatch        # (T_bucket, B) stacked lanes
    split: Optional[int]    # common kind-major split of every op (or None)
    n_ops: int              # real ops; ops[n_ops:] are all-masked padding


@dataclasses.dataclass(frozen=True)
class SegmentPlan:
    """A runbook chopped into compiled-stream segments.

    ``plan_segments`` groups consecutive same-shape ops, pads each group's
    op axis to a power-of-two bucket (masked no-op steps) and caps groups at
    ``max_t`` — so an arbitrary stream of mixed batch shapes executes with
    one dispatch per segment and one compilation per (T_bucket, B, split)
    bucket."""

    segments: tuple  # tuple[Segment, ...]

    @property
    def n_ops(self) -> int:
        return sum(s.n_ops for s in self.segments)


def plan_segments(
    steps,
    *,
    splits=None,
    max_t: int = 64,
    keys=None,
) -> SegmentPlan:
    """Chop a list of same-or-mixed-width ``UpdateBatch``es into
    ``Segment``s.  ``splits``: optional per-step static split (one per
    step; consecutive steps only share a segment when their (B, split)
    agree).  ``max_t``: segment length cap (a power of two keeps T buckets
    trivially aligned).  ``keys``: optional per-step hashable grouping key
    folded into the segment boundary condition — consecutive steps share a
    segment only when their keys agree.  The sharded compact router uses
    this to fold each step's per-shard compact bucket into the plan
    (``ShardedIndex.update_stream``): segments then carry one static
    (T, Bc) shape decided at plan time, so consecutive segments with the
    same owner distribution share one compiled program instead of
    re-deriving (and re-packing) a bucket per segment."""
    steps = list(steps)
    if splits is None:
        splits = [None] * len(steps)
    if len(splits) != len(steps):
        raise ValueError("one split per step required")
    if keys is None:
        keys = [None] * len(steps)
    if len(keys) != len(steps):
        raise ValueError("one key per step required")
    max_t = max(1, max_t)

    segments = []
    i = 0
    while i < len(steps):
        b = steps[i].kind.shape[0]
        dim = steps[i].vector.shape[1]
        split = splits[i]
        key = keys[i]
        j = i
        while (
            j < len(steps)
            and j - i < max_t
            and steps[j].kind.shape[0] == b
            and steps[j].vector.shape[1] == dim
            and splits[j] == split
            and keys[j] == key
        ):
            j += 1
        group = steps[i:j]
        t_bucket = min(next_bucket(len(group)), next_bucket(max_t))
        group = group + [
            noop_update_batch(b, dim) for _ in range(t_bucket - len(group))
        ]
        segments.append(
            Segment(stack_update_batches(group), split, j - i)
        )
        i = j
    return SegmentPlan(segments=tuple(segments))


def segment_step(
    state: IndexState,
    cfg: ANNConfig,
    seg: Segment,
    *,
    policy: str = "ip",
    sequential: bool = False,
    unroll: Optional[int] = None,
):
    """Apply ONE planned ``Segment`` — the compiled ``apply_segment``
    dispatch plus the host-policy consolidation boundary (fresh: run the
    policy's host pass whenever any op of the segment raised its
    ``needs_consolidation`` flag).  This is the unit of determinism the
    durability layer builds on: ``run_segments`` is a plain loop of it, and
    ``core/persist.py``'s supervised runner replays exactly this function
    after a restore, so recovered streams cannot diverge from uninterrupted
    ones.  ``state`` is donated (via ``apply_segment``)."""
    pol = get_policy(policy)
    state, res = apply_segment(
        state, cfg, seg.ops, policy=policy, sequential=sequential,
        split=seg.split, unroll=unroll,
    )
    if not pol.device_consolidation and bool(
        np.asarray(res.needs_consolidation).any()
    ):
        state = state._replace(graph=pol.consolidate(state.graph, cfg))
    return state, res


def run_segments(
    state: IndexState,
    cfg: ANNConfig,
    plan: SegmentPlan,
    *,
    policy: str = "ip",
    sequential: bool = False,
    unroll: Optional[int] = None,
    start: int = 0,
):
    """Execute a ``SegmentPlan``, threading the carry state across segments.

    Device policies (ip) never touch the host inside the loop; for host
    policies (fresh) each segment's ``needs_consolidation`` flags are
    checked at the segment boundary and the policy's host pass runs there.
    ``start`` skips the first segments (restore paths replay a plan tail
    from a checkpointed state).  Returns ``(state, [SegmentResult, ...])``
    (one result per executed segment; the caller slices ``[:n_ops]`` rows
    via the plan)."""
    results = []
    for seg in plan.segments[start:]:
        state, res = segment_step(
            state, cfg, seg, policy=policy, sequential=sequential,
            unroll=unroll,
        )
        results.append(res)
    return state, results


# ---------------------------------------------------------------------------
# The query front door
# ---------------------------------------------------------------------------


def search(
    state: IndexState,
    cfg: ANNConfig,
    queries: jax.Array,
    *,
    k: int = 10,
    l: Optional[int] = None,
):
    """Query the handle; returns ``(ext_ids, dists, SearchResult)`` with the
    slot -> external-id mapping applied on device (the ``SearchResult``
    keeps slot ids for state-level consumers).

    Host spans (``core/spans.py``): ``ann.search`` over the whole call, and
    in it ``ann.search.pad`` and ``ann.search.dispatch`` (in
    ``search_batch``), then ``ann.search.map_ids``."""
    with host_span(SEARCH):
        res = search_batch(state.graph, cfg, queries, k=k,
                           l=l or cfg.l_search)
        with host_span(SEARCH_MAP_IDS):
            sids = res.topk_ids
            ext = jnp.where(
                sids >= 0, state.slot2ext[clip_ids(sids, cfg.n_cap)], INVALID
            )
    return ext, res.topk_dists, res


def maybe_consolidate(
    state: IndexState, cfg: ANNConfig, *, policy: str = "ip",
    force: bool = False,
) -> tuple[IndexState, bool]:
    """Run the policy's consolidation pass if its trigger fires.

    Device policies (ip) route through ``consolidate_if_needed`` — the
    trigger AND the pass execute in one fused program, and the only host
    sync left is the returned ``did`` bool (this legacy shell contract;
    compiled streams via ``apply_segment`` avoid even that).  Host policies
    (fresh) keep the host-side decision: consolidation is the paper's
    offline/background activity there."""
    pol = get_policy(policy)
    if pol.device_consolidation:
        state, did = consolidate_if_needed(
            state, cfg, policy=policy, force=force
        )
        return state, bool(did)
    n_active = int(state.graph.n_active)
    n_pending = int(state.graph.n_pending)
    if not (force and n_pending > 0) and not pol.should_consolidate(
        cfg, n_active, n_pending
    ):
        return state, False
    return state._replace(graph=pol.consolidate(state.graph, cfg)), True


__all__ = [
    "TRACE_COUNTER",
    "TRACE_UNROLL",
    "Segment",
    "SegmentPlan",
    "SnapshotHandle",
    "UpdatePolicy",
    "apply",
    "apply_segment",
    "auto_unroll",
    "available_policies",
    "clone_state",
    "compact_owner_batch",
    "compact_owner_segment",
    "consolidate_if_needed",
    "device_sweep",
    "delete_batch",
    "get_policy",
    "init_index_state",
    "insert_batch",
    "make_update_batch",
    "maybe_consolidate",
    "mixed_update_batch",
    "pad_update_batch",
    "plan_segments",
    "register_policy",
    "run_segments",
    "search",
    "segment_scan",
    "segment_step",
    "take_snapshot",
]
