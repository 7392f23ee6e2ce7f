"""Sharded streaming index: the paper's single-node system scaled out.

Each device along the flattened mesh owns an independent sub-index — a full
device-resident ``IndexState`` handle (graph + external-id map + op
counters) stacked on a leading shard axis — and every operation goes
through the SAME pure front doors as ``StreamingIndex`` (``core/api.py``),
just under ``shard_map``.  Callers address points by external id only;
slots and owner bookkeeping are internal.

Since the shard-native rework, per-shard work SHRINKS as shards are added
instead of being masked away:

  * **updates** (default ``routing="compact"``): the host packs each
    shard's owned lanes (stable hash routing) into a compact power-of-two
    per-shard sub-batch (``core/api.py::compact_owner_batch`` /
    ``compact_owner_segment``, padded with masked no-op lanes), so each
    shard's ``apply`` scan runs over ~B/S lanes.  The pre-rework
    replicate-and-mask layout — every shard receives all B lanes and masks
    the S-1/S it does not own — is kept as ``routing="replicate"`` and is
    bit-identical per shard (compaction preserves per-shard lane order).
    What a masked lane COSTS depends on the visibility mode: the batched
    phases (``sequential=False``) carry every lane through the shared
    (B, R) beam tiles, so compaction shrinks real per-shard compute S-fold
    (benchmarks/shard_bench.py measures ~1.4x at S=2); the serial scan
    (``sequential=True``, default) early-exits masked lanes per
    ``lax.cond``, so there the win is structural — S-fold shorter scans
    and op tensors — rather than CPU wall clock.
  * **search** has two modes.  Replicate-and-merge (default): the query
    batch fans out to every shard, each runs ONE natively batched beam
    (core/search_batched.py) over its local graph, and a global top-k
    merge over the all-gathered (S, Q, k) candidates yields the answer.
    ``partition="queries"``: disjoint query sub-batches start one per
    shard and rotate around the ring (``lax.ppermute``), each carrying a
    running global top-k that is merged incrementally
    (``search_batched.merge_topk``) after every hop — per shard, the beam
    is Q/S wide instead of Q, and each sub-batch's merge overlaps the next
    sub-batch's beams inside one compiled step.
  * **consolidation**: device policies (ip) sweep mid-stream under
    ``lax.cond`` exactly as the local front doors; host-orchestrated
    policies (fresh, the paper's offline Algorithm 4) go through
    ``consolidate_sharded`` — gather one shard's graph off the stacked
    state, run the policy's pass, scatter it back — driven automatically
    by the ``needs_consolidation`` flags that ``update_stream`` segments
    surface.

Straggler mitigation for serving: replicate-mode ``search`` queries all
shards anyway (fan-out IS the redundancy); at 1000-node scale the merge
tolerates missing shards by masking their results (see ft/supervisor).

**Logical shards & elastic reshard-on-restore.**  The unit of data
ownership is a LOGICAL shard: routing hashes external ids into
``n_logical`` = L buckets (fixed at creation and persisted in the
checkpoint manifest), and the stacked state's leading axis is L, laid out
over the S physical mesh devices (L % S == 0, G = L/S rows per device).
Every SPMD program runs its per-row body in a Python loop over the G local
rows — NOT vmap, so each row executes exactly the single-shard compiled
program (beam while-loops and pallas kernels unchanged, results bit-exact
regardless of S).  Because per-logical-row programs are independent of the
physical layout, a checkpoint written under one mesh restores under ANY
mesh whose size divides L with bit-identical search answers and update
behaviour — ``save``/``restore`` below thread this through
``core/persist.py``.  G == 1 (the default L = S) reproduces the
pre-logical-shard programs exactly.  This also answers the uneven-mesh
question: meshes whose sizes share L (e.g. L=12 over S in {1,2,3,4,6,12})
interoperate through checkpoints without re-hashing a single point.

Distance math inside every per-shard beam rides the kernel engine selected
by ``cfg.backend`` (the unified front doors resolve it from the static
config under ``shard_map``); lane payloads are int32 end-to-end (external
ids and slots are never laundered through floats).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .api import (
    _compact_owner_batch_np,
    apply,
    compact_owner_batch,
    delete_batch,
    device_sweep,
    get_policy,
    insert_batch,
    plan_segments,
    segment_scan,
)
from ..checkpoint.manager import CheckpointMismatchError
from .backend import BIG
from .consolidate import consolidate_stacked
from .grow import ensure_capacity
from .persist import restore_index, save_index
from .search_batched import batched_greedy_search, merge_topk, next_bucket
from .types import (
    INVALID, KIND_INSERT, ANNConfig, IndexState, UpdateBatch, clip_ids,
    init_index_state, noop_update_batch,
)

# Incremented once per trace (not per call) of each SPMD program, with the
# traced op-tensor shape recorded in TRACE_SHAPES: the sharding tests pin
# both the power-of-two bucketing discipline (ragged batches share
# compiles) and the compact-routing contract (per-shard lane width <=
# next_bucket(ceil(B / S)), S-fold smaller than the replicated width).
# ``segment_pack`` is the one host-side entry: it counts owner-compaction
# packs of individual stream steps (``update_stream`` packs every step
# EXACTLY once, at plan time — the owner-aware planning test pins that no
# step is ever re-packed per segment).
TRACE_COUNTER = {
    "update_compact": 0,
    "segment_compact": 0,
    "segment_pack": 0,
    "update_replicate": 0,
    "segment_replicate": 0,
    "search_replicate": 0,
    "search_partition": 0,
}
TRACE_SHAPES: dict = {k: [] for k in TRACE_COUNTER}


def _row(tree, g: int):
    """Logical row ``g`` of a device-local (G, ...) stacked block."""
    return jax.tree.map(lambda x: x[g], tree)


def _restack(rows):
    """Stack per-row pytrees back into the device-local (G, ...) block."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)


def as_int_payload(ids) -> jax.Array:
    """Lossless int32 device payload for slot/external ids.

    The pre-``apply`` update path routed delete payloads through a shared
    ``jnp.float32`` buffer, which silently rounds integers above 2**24; the
    unified op stream is int-clean end-to-end.  Guarded here so a regression
    cannot reintroduce the rounding."""
    arr = np.asarray(ids, np.int64)
    if arr.size and (arr.max() >= 2**31 or arr.min() < -(2**31)):
        raise OverflowError("id payload exceeds int32 range")
    return jnp.asarray(arr.astype(np.int32))


class ShardedIndex:
    """S sub-indexes run in SPMD over a 1-d ("shard",) mesh, all fronted by
    the unified ``apply`` op stream (external-id semantics per shard).

    ``routing`` selects the update fan-out: ``"compact"`` (default) ships
    each shard only its owned lanes, ``"replicate"`` ships every shard the
    whole batch with non-owned lanes masked (the pre-rework layout, kept
    for parity checks and benchmarking the difference).

    ``n_logical`` fixes the routing-hash modulus L independently of the
    mesh size S (default L = S).  L must be a multiple of S; each device
    owns G = L/S logical rows.  Checkpoints record L, so ``restore`` can
    lay the same L rows over a different mesh (elastic reshard) without
    moving any point between shards.
    """

    def __init__(self, cfg: ANNConfig, mesh: Mesh, axis: str = "shard",
                 policy: str = "ip", max_external_id: Optional[int] = None,
                 routing: str = "compact", sequential: bool = True,
                 n_logical: Optional[int] = None, auto_grow: bool = True):
        if routing not in ("compact", "replicate"):
            raise ValueError(f"unknown routing {routing!r}")
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.policy = policy
        self.routing = routing
        # True: per-shard serial lane scan (the paper's concurrency model,
        # each lane's search sees every earlier lane's writes).  False: the
        # relaxed-visibility batched phases — the regime where owner
        # compaction also shrinks the per-shard (B, R) beam tiles S-fold
        # (masked lanes of a replicated batch still pay tile width there).
        self.sequential = sequential
        self.auto_grow = auto_grow
        self.n_shards = mesh.shape[axis]
        self.n_logical = int(n_logical) if n_logical else self.n_shards
        if self.n_logical % self.n_shards:
            raise ValueError(
                f"n_logical={self.n_logical} must be a multiple of the "
                f"mesh size {self.n_shards} (each device holds "
                f"G = n_logical/n_shards whole logical rows)"
            )
        self.rows_per_shard = self.n_logical // self.n_shards
        if max_external_id is None:
            max_external_id = cfg.n_cap * 4
        self.max_external_id = max_external_id
        # stacked per-LOGICAL-shard handles, the leading L axis laid out
        # over the S mesh devices (G whole rows per device)
        self.states: IndexState = jax.device_put(
            jax.vmap(lambda _: init_index_state(cfg, max_external_id))(
                jnp.arange(self.n_logical)
            ),
            NamedSharding(mesh, P(axis)),
        )
        self._shard_spec = NamedSharding(mesh, P(axis))
        self._build_programs()

    # -- SPMD programs -------------------------------------------------------

    def _build_programs(self):
        """(Re)build every SPMD program against the current ``self.cfg``.
        Capacity growth walks ``n_cap`` into a new power-of-two bucket,
        which changes the static shapes every program closed over — one
        rebuild (and recompile on next dispatch) per bucket."""
        self._search = self._build_search()
        self._search_part = self._build_search_partitioned()
        self._update = self._build_update()
        self._update_compact = self._build_update_compact()
        self._update_segment = self._build_update_segment()
        self._update_segment_compact = self._build_update_segment_compact()

    def _build_search(self):
        cfg, axis, G = self.cfg, self.axis, self.rows_per_shard

        @functools.partial(jax.jit, static_argnames=("k", "l"))
        def search(states, queries, *, k: int, l: int):
            TRACE_COUNTER["search_replicate"] += 1
            TRACE_SHAPES["search_replicate"].append(tuple(queries.shape))

            def shard_fn(state, q):
                me = lax.axis_index(axis)
                # one beam per local logical row (Python loop, NOT vmap:
                # each row runs exactly the single-shard program, so
                # answers are bit-identical under any G = L/S layout)
                exts, dists, heres = [], [], []
                comps = jnp.zeros((), jnp.int32)
                for g in range(G):
                    row = _row(state, g)
                    res = batched_greedy_search(row.graph, cfg, q, k=k, l=l)
                    ids = res.topk_ids                       # (Q, k) local
                    # device-resident id map: local slots -> external ids
                    exts.append(jnp.where(
                        ids >= 0,
                        row.slot2ext[clip_ids(ids, cfg.n_cap)],
                        INVALID,
                    ))
                    dists.append(res.topk_dists)
                    heres.append(jnp.broadcast_to(
                        me * G + g, ids.shape
                    ).astype(jnp.int32))                     # logical id
                    comps = comps + jnp.sum(res.n_comps).astype(jnp.int32)
                # concat local rows k-major: after the gather the flat
                # candidate order is (logical shard, k) exactly as in the
                # G == 1 layout, so lax.top_k tie-breaking is identical
                # for every S that divides L
                ext = jnp.concatenate(exts, axis=1)          # (Q, G*k)
                d = jnp.concatenate(dists, axis=1)
                here = jnp.concatenate(heres, axis=1)
                # global merge: gather every device's candidates, re-select
                all_ids = lax.all_gather(ext, axis)          # (S, Q, G*k)
                all_d = lax.all_gather(d, axis)
                all_s = lax.all_gather(here, axis)
                flat_d = all_d.transpose(1, 0, 2).reshape(q.shape[0], -1)
                flat_i = all_ids.transpose(1, 0, 2).reshape(q.shape[0], -1)
                flat_s = all_s.transpose(1, 0, 2).reshape(q.shape[0], -1)
                top_d, idx = lax.top_k(-flat_d, k)
                gids = jnp.take_along_axis(flat_i, idx, axis=1)
                gshard = jnp.take_along_axis(flat_s, idx, axis=1)
                return (
                    gids[None], gshard[None], (-top_d)[None],
                    comps[None],
                )

            return jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(axis), P()),       # queries replicated
                out_specs=(P(axis), P(axis), P(axis), P(axis)),
                check_vma=False,  # while-loop carries mix varying/invariant axes
            )(states, queries)

        return search

    def _build_search_partitioned(self):
        cfg, axis, n_shards = self.cfg, self.axis, self.n_shards
        G = self.rows_per_shard

        @functools.partial(jax.jit, static_argnames=("k", "l"))
        def search_p(states, queries, valid, *, k: int, l: int):
            """queries: (S * Qs, dim) padded batch sharded on the lane
            axis; valid: bool[S * Qs] lane mask.  Each shard starts with
            the disjoint sub-batch it owns; sub-batches rotate around the
            ring (``lax.ppermute``) carrying their running global top-k,
            so after S hops every query has beamed over every shard's
            graph.  Per shard the beam is Qs = Q/S lanes wide instead of
            Q, and the incremental ``merge_topk`` of one sub-batch is
            data-independent of the NEXT sub-batch's beam, so XLA overlaps
            the merge with the incoming hop inside the compiled step."""
            TRACE_COUNTER["search_partition"] += 1
            TRACE_SHAPES["search_partition"].append(tuple(queries.shape))

            def shard_fn(state, q, v):
                me = lax.axis_index(axis)
                perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
                qs = q.shape[0]
                best_d = jnp.full((qs, k), BIG, jnp.float32)
                best_i = jnp.full((qs, k), INVALID, jnp.int32)
                best_s = jnp.full((qs, k), INVALID, jnp.int32)
                comps = jnp.zeros((), jnp.int32)
                for _ in range(n_shards):
                    # beam over every LOCAL logical row before rotating —
                    # after S hops a sub-batch has merged all L rows
                    for g in range(G):
                        row = _row(state, g)
                        res = batched_greedy_search(
                            row.graph, cfg, q, k=k, l=l, valid=v
                        )
                        ids = res.topk_ids
                        ext = jnp.where(
                            ids >= 0,
                            row.slot2ext[clip_ids(ids, cfg.n_cap)],
                            INVALID,
                        )
                        here = jnp.where(
                            ids >= 0,
                            jnp.broadcast_to(me * G + g, ids.shape),
                            INVALID,
                        ).astype(jnp.int32)
                        d = jnp.where(ids >= 0, res.topk_dists, BIG)
                        best_d, (best_i, best_s) = merge_topk(
                            best_d, d, k, (best_i, ext), (best_s, here)
                        )
                        comps = (comps
                                 + jnp.sum(res.n_comps).astype(jnp.int32))
                    # rotate the sub-batch (and its running merge) onward
                    q, v, best_d, best_i, best_s, comps = [
                        lax.ppermute(x, axis, perm)
                        for x in (q, v, best_d, best_i, best_s, comps)
                    ]
                # S rotations: every sub-batch is back on its home shard
                return best_i, best_s, best_d, comps[None]

            return jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(axis), P(axis), P(axis)),
                out_specs=(P(axis), P(axis), P(axis), P(axis)),
                check_vma=False,
            )(states, queries, valid)

        return search_p

    def _build_update(self):
        cfg, axis, policy = self.cfg, self.axis, self.policy
        sequential, G = self.sequential, self.rows_per_shard

        @functools.partial(jax.jit, donate_argnums=0)
        def update(states, batch, owners):
            """Replicate-and-mask layout: ``batch`` is a replicated
            ``UpdateBatch``; ``owners`` i32[B] is the owning LOGICAL shard
            of each lane.  Every logical row runs the same unified
            ``apply`` over all B lanes with non-owned lanes masked
            invalid."""
            TRACE_COUNTER["update_replicate"] += 1
            TRACE_SHAPES["update_replicate"].append(tuple(batch.kind.shape))

            def shard_fn(state, batch, owners):
                me = lax.axis_index(axis)
                rows, ress = [], []
                for g in range(G):
                    row = _row(state, g)
                    mine = batch._replace(
                        valid=batch.valid & (owners == me * G + g)
                    )
                    # per-shard update semantics (sequential: the paper's
                    # serial concurrency model; else relaxed-visibility)
                    row, res = apply(
                        row, cfg, mine, policy=policy, sequential=sequential
                    )
                    # device-side consolidation trigger per op, exactly as
                    # the segment path and StreamingIndex: each logical row
                    # sweeps when ITS counters cross the threshold
                    pol = get_policy(policy)
                    if pol.device_consolidation:
                        trig = pol.should_consolidate_device(cfg, row.graph)
                        row = row._replace(
                            graph=device_sweep(row.graph, cfg, pol, trig)
                        )
                    rows.append(row)
                    ress.append(res)
                return _restack(rows), _restack(ress)

            return jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(axis), P(), P()),
                out_specs=(P(axis), P(axis)),
                check_vma=False,
            )(states, batch, owners)

        return update

    def _build_update_compact(self):
        cfg, axis, policy = self.cfg, self.axis, self.policy
        sequential, G = self.sequential, self.rows_per_shard

        @functools.partial(jax.jit, donate_argnums=0)
        def update(states, batch):
            """Owner-compacted layout: ``batch`` is an (L, Bc)
            ``UpdateBatch`` sharded on the leading axis — row ``l`` holds
            exactly logical shard ``l``'s owned lanes (original relative
            order, bucket-padded).  No owner masking: each row's ``apply``
            scan is Bc ~= B/L lanes wide instead of B."""
            TRACE_COUNTER["update_compact"] += 1
            TRACE_SHAPES["update_compact"].append(tuple(batch.kind.shape))

            def shard_fn(state, batch):
                rows, ress = [], []
                for g in range(G):
                    row = _row(state, g)
                    mine = _row(batch, g)
                    row, res = apply(
                        row, cfg, mine, policy=policy, sequential=sequential
                    )
                    pol = get_policy(policy)
                    if pol.device_consolidation:
                        trig = pol.should_consolidate_device(cfg, row.graph)
                        row = row._replace(
                            graph=device_sweep(row.graph, cfg, pol, trig)
                        )
                    rows.append(row)
                    ress.append(res)
                return _restack(rows), _restack(ress)

            return jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(axis), P(axis)),
                out_specs=(P(axis), P(axis)),
                check_vma=False,
            )(states, batch)

        return update

    def _build_update_segment(self):
        cfg, axis, policy = self.cfg, self.axis, self.policy
        sequential, G = self.sequential, self.rows_per_shard

        @functools.partial(jax.jit, donate_argnums=0)
        def update_segment(states, ops, owners):
            """Replicate-and-mask segment: ``ops`` is a replicated (T, B)
            op tensor; ``owners`` i32[T, B] of LOGICAL shard ids.  Every
            logical row runs the same compiled ``lax.scan`` of the
            ``apply`` body (core/api.py::segment_scan) with non-owned
            lanes masked invalid — T ops, ONE dispatch, per-shard serial
            semantics, device-side consolidation trigger per op (the ip
            policy's light sweep fires mid-segment on whichever row's
            counters cross the threshold)."""
            TRACE_COUNTER["segment_replicate"] += 1
            TRACE_SHAPES["segment_replicate"].append(tuple(ops.kind.shape))

            def shard_fn(state, ops, owners):
                me = lax.axis_index(axis)
                rows, ress = [], []
                for g in range(G):
                    row = _row(state, g)
                    mine = ops._replace(
                        valid=ops.valid & (owners == me * G + g)
                    )
                    row, res = segment_scan(
                        row, cfg, mine, get_policy(policy),
                        sequential=sequential, split=None,
                    )
                    rows.append(row)
                    ress.append(res)
                return _restack(rows), _restack(ress)

            return jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(axis), P(), P()),
                out_specs=(P(axis), P(axis)),
                check_vma=False,
            )(states, ops, owners)

        return update_segment

    def _build_update_segment_compact(self):
        cfg, axis, policy = self.cfg, self.axis, self.policy
        sequential, G = self.sequential, self.rows_per_shard

        @functools.partial(jax.jit, donate_argnums=0)
        def update_segment(states, ops):
            """Owner-compacted segment: ``ops`` is an (L, T, Bc) op tensor
            sharded on the leading axis (``compact_owner_segment``) — the
            same compiled ``lax.scan`` of the ``apply`` body, but each
            logical row scans T ops of Bc ~= B/L lanes instead of B."""
            TRACE_COUNTER["segment_compact"] += 1
            TRACE_SHAPES["segment_compact"].append(tuple(ops.kind.shape))

            def shard_fn(state, ops):
                rows, ress = [], []
                for g in range(G):
                    row = _row(state, g)
                    mine = _row(ops, g)
                    row, res = segment_scan(
                        row, cfg, mine, get_policy(policy),
                        sequential=sequential, split=None,
                    )
                    rows.append(row)
                    ress.append(res)
                return _restack(rows), _restack(ress)

            return jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(axis), P(axis)),
                out_specs=(P(axis), P(axis)),
                check_vma=False,
            )(states, ops)

        return update_segment

    # -- host API -------------------------------------------------------------

    def route(self, ext_ids: np.ndarray) -> np.ndarray:
        """Owner LOGICAL shard of each external id (stable hash routing).
        The modulus is ``n_logical``, fixed at creation and persisted in
        checkpoints — resharding onto a different mesh never re-routes a
        point."""
        n = getattr(self, "n_logical", None) or self.n_shards
        return (np.asarray(ext_ids, np.int64) * 2654435761 % 2**31
                % n).astype(np.int32)

    def _ensure_capacity(self, max_owned: int) -> bool:
        """Grow every logical row into the next capacity bucket when the
        fullest row plus ``max_owned`` incoming inserts would cross the
        high-water mark (``core/grow.py``).  All ``n_logical`` rows grow
        in LOCKSTEP — the stacked state keeps one static shape, so one
        grow costs one program rebuild regardless of L."""
        if not self.auto_grow:
            return False
        states, cfg, grew = ensure_capacity(self.states, self.cfg, max_owned)
        if not grew:
            return False
        self.states = jax.device_put(states, self._shard_spec)
        self.cfg = cfg
        self._build_programs()
        return True

    def _owned_insert_demand(self, batches) -> int:
        """Worst-case per-logical-row insert count of an update stream:
        the growth trigger's ``incoming`` (deletes never consume slots)."""
        counts = np.zeros((self.n_logical,), np.int64)
        for batch in batches:
            ins = np.asarray(batch.valid) & (
                np.asarray(batch.kind) == KIND_INSERT
            )
            if ins.any():
                owners = self.route(np.asarray(batch.ext_id, np.int64))
                counts += np.bincount(
                    owners[ins], minlength=self.n_logical
                )
        return int(counts.max()) if counts.size else 0

    def _apply_update(self, batch, owners):
        """Route one bucket-padded ``UpdateBatch`` through the selected
        update program (``self.routing``).  ``owners``: i32[B] per-lane
        owner (-1 for padding lanes).  Returns per-original-lane
        ``(ok, slot)`` numpy arrays, independent of the routing layout."""
        if self.routing == "compact":
            cbatch, pos, _ = compact_owner_batch(
                batch, owners, self.n_logical
            )
            cbatch = jax.device_put(cbatch, self._shard_spec)
            self.states, res = self._update_compact(self.states, cbatch)
            ok_c = np.asarray(res.ok)                       # (S, Bc)
            slot_c = np.asarray(res.slot)
            ok = np.zeros(owners.shape, bool)
            slot = np.full(owners.shape, INVALID, np.int32)
            m = pos >= 0
            ok[m] = ok_c[owners[m], pos[m]]
            slot[m] = slot_c[owners[m], pos[m]]
            return ok, slot
        self.states, res = self._update(
            self.states, batch, as_int_payload(owners)
        )
        # off-owner lanes are masked no-ops: ok False, slot INVALID
        return (np.asarray(res.ok).any(axis=0),
                np.asarray(res.slot).max(axis=0))

    def insert(self, ext_ids, vectors):
        """Insert by external id; returns (slots, owners) bookkeeping (the
        slot within the owner shard — informational, callers address points
        by external id)."""
        ext_ids = np.asarray(ext_ids)
        oob = (ext_ids < 0) | (ext_ids >= self.max_external_id)
        if oob.any():
            raise ValueError(
                f"external id(s) outside [0, {self.max_external_id}): "
                f"{ext_ids[oob][:8].tolist()}"
            )
        owners = self.route(ext_ids)
        if len(ext_ids):
            self._ensure_capacity(int(np.bincount(
                owners, minlength=self.n_logical
            ).max()))
        batch = insert_batch(ext_ids, vectors)
        pad = batch.kind.shape[0] - len(ext_ids)
        ok, slot = self._apply_update(
            batch,
            np.concatenate([owners, np.full(pad, -1)]).astype(np.int32),
        )
        ok = ok[: len(ext_ids)]
        if not ok.all():
            raise RuntimeError(
                f"insert failed on owning shard (capacity exhausted) for "
                f"external id(s) {ext_ids[~ok][:8].tolist()}"
            )
        return slot[: len(ext_ids)], owners

    def delete(self, ext_ids) -> None:
        """Delete by external id, routed to the owning shard.  Duplicates
        within one call delete once; unknown ids raise ``KeyError`` after
        the known ids of the batch have been applied (the id map lives on
        device — pre-validation would cost a host sync per call)."""
        ext_ids = np.asarray(ext_ids)
        _, keep = np.unique(ext_ids, return_index=True)
        ext_ids = ext_ids[np.sort(keep)]
        owners = self.route(ext_ids)
        batch = delete_batch(ext_ids, self.cfg.dim)
        pad = batch.kind.shape[0] - len(ext_ids)
        ok, _ = self._apply_update(
            batch,
            np.concatenate([owners, np.full(pad, -1)]).astype(np.int32),
        )
        ok = ok[: len(ext_ids)]
        if not ok.all():
            raise KeyError(
                f"delete of unknown external id(s): "
                f"{ext_ids[~ok][:8].tolist()}"
            )

    def delete_slots(self, slots, owners) -> None:
        """Deprecated shim (pre-external-id API): delete by (slot, owner)
        pairs.  Recovers the external ids from the device-resident
        ``slot2ext`` maps and routes an int32 payload through the unified
        ``apply`` stream — ids above 2**24 survive exactly (the oldest
        path carried slots in a float32 buffer)."""
        slots = np.asarray(as_int_payload(slots))
        owners = np.asarray(owners, np.int64)
        ext = np.asarray(self.states.slot2ext)[owners, slots]
        if (ext < 0).any():
            raise KeyError("delete_slots of unoccupied slot(s)")
        batch = delete_batch(ext, self.cfg.dim)
        pad = batch.kind.shape[0] - len(ext)
        self._apply_update(
            batch,
            np.concatenate([owners, np.full(pad, -1)]).astype(np.int32),
        )

    def update_stream(self, batches, *, max_t: int = 64):
        """Run a stream of ``UpdateBatch``es as whole-segment compiled
        scans under ``shard_map`` — one dispatch per (T, B) bucket instead
        of one per batch.  Bucketing rides the same ``plan_segments``
        discipline as the local front doors (consecutive same-width
        batches share a segment; width changes start a new one); with the
        default compact routing each segment is additionally owner-packed
        (``compact_owner_segment``) so every shard scans T ops of
        ~B/S lanes.

        Lanes route to their owning shard by external id (same stable hash
        as ``insert``/``delete``); invalid lanes are no-ops everywhere.
        Unlike the per-op paths this surface raises no per-id exceptions —
        a failed lane is visible as ``ok=False`` in the returned
        per-segment ``SegmentResult`` list.  Under compact routing the
        per-lane fields (``slot``/``ok``/``n_comps``) are scattered back
        to CALLER lane order, (T, B) — so stream lane (t, b) is
        addressable directly; under replicate they stay shard-stacked
        (S, T, B) with off-owner lanes masked.  The consolidation flags
        (``consolidated``/``needs_consolidation``) are per-shard (S, T)
        in both layouts.

        Host-orchestrated policies (fresh) consolidate at segment
        boundaries through ``consolidate_sharded``: any shard whose
        ``needs_consolidation`` flag fired gets its graph gathered, passed
        through the policy's host pass and scattered back (consolidation
        is the paper's offline activity — the transfer is off the serving
        path).

        **Owner-aware planning** (compact routing): every stream step is
        owner-packed exactly ONCE up front, and its per-shard compact
        bucket ``bc`` is folded into the ``plan_segments`` key.  Segments
        therefore carry a static (L, T, Bc) shape decided at plan time —
        consecutive segments whose steps share an owner distribution share
        ONE compiled program, and no step is ever re-packed per segment
        (the pre-rework path re-derived a bucket and re-packed every step
        of every segment inside the segment loop)."""
        pol = get_policy(self.policy)
        # grow BEFORE planning/packing: the whole stream's per-row insert
        # demand is provisioned up front so every segment compiles against
        # one n_cap bucket end to end
        batches = list(batches)
        self._ensure_capacity(self._owned_insert_demand(batches))
        results = []

        def _post(res):
            if not pol.device_consolidation:
                flags = np.asarray(res.needs_consolidation)   # (S, T)
                self.consolidate_sharded(np.nonzero(flags.any(axis=1))[0])
            results.append(res)

        if self.routing != "compact":
            plan = plan_segments(batches, max_t=max_t)
            for seg in plan.segments:
                owners = np.where(
                    np.asarray(seg.ops.valid),
                    self.route(np.asarray(seg.ops.ext_id, np.int64)), -1,
                ).astype(np.int32)                          # (T, B)
                self.states, res = self._update_segment(
                    self.states, seg.ops, as_int_payload(owners)
                )
                _post(res)
            return results

        # pack each step once (host, numpy); bc joins the plan key
        packed, positions, owner_rows, bcs = [], [], [], []
        for batch in batches:
            own = np.where(
                np.asarray(batch.valid),
                self.route(np.asarray(batch.ext_id, np.int64)), -1,
            ).astype(np.int32)                              # (B,)
            sub, p, bc = _compact_owner_batch_np(
                batch, own, self.n_logical
            )
            TRACE_COUNTER["segment_pack"] += 1
            TRACE_SHAPES["segment_pack"].append(tuple(sub.kind.shape))
            packed.append(sub)
            positions.append(p)
            owner_rows.append(own)
            bcs.append(bc)
        plan = plan_segments(batches, max_t=max_t, keys=bcs)
        i = 0
        for seg in plan.segments:
            t_bucket, b = seg.ops.kind.shape
            n = seg.n_ops
            bc = bcs[i]
            dim = packed[i].vector.shape[2]
            # T padding: packed all-masked no-op steps of the segment's bc
            pad_step, _, _ = _compact_owner_batch_np(
                noop_update_batch(b, dim),
                np.full((b,), -1, np.int32),
                self.n_logical, bucket=bc,
            ) if t_bucket > n else (None, None, None)
            steps = packed[i:i + n] + [pad_step] * (t_bucket - n)
            cops = UpdateBatch(*[
                jnp.asarray(np.stack(arrs, axis=1)) for arrs in zip(*steps)
            ])
            cops = jax.device_put(cops, self._shard_spec)
            self.states, res = self._update_segment_compact(
                self.states, cops
            )
            # per-lane results back to caller lane order: without this
            # an ok=False cell of the owner-packed (S, T, Bc) tensor
            # is not attributable to a stream lane
            pos = np.full((t_bucket, b), -1, np.int32)
            pos[:n] = np.stack(positions[i:i + n])
            owners = np.full((t_bucket, b), -1, np.int32)
            owners[:n] = np.stack(owner_rows[i:i + n])
            ok_c = np.asarray(res.ok)
            slot_c = np.asarray(res.slot)
            comps_c = np.asarray(res.n_comps)
            m = pos >= 0
            t_of = np.broadcast_to(
                np.arange(pos.shape[0])[:, None], pos.shape
            )
            ok = np.zeros(pos.shape, bool)
            slot = np.full(pos.shape, INVALID, np.int32)
            comps = np.zeros(pos.shape, comps_c.dtype)
            ok[m] = ok_c[owners[m], t_of[m], pos[m]]
            slot[m] = slot_c[owners[m], t_of[m], pos[m]]
            comps[m] = comps_c[owners[m], t_of[m], pos[m]]
            _post(res._replace(slot=slot, ok=ok, n_comps=comps))
            i += n
        return results

    def consolidate_sharded(self, shard_ids=None, *, force: bool = False):
        """Host-orchestrated per-shard consolidation over the stacked
        state: for each shard in ``shard_ids``, gather its graph, run the
        policy's consolidation pass (fresh: Algorithm 4, the paper's
        offline batch pass; ip: the Algorithm-6 sweep) and scatter the
        result back (``core/consolidate.py::consolidate_stacked``).

        ``shard_ids=None`` selects every shard whose consolidation
        trigger currently fires — or, with ``force=True``, every shard
        with pending removals.  Returns the list of shard ids
        consolidated.  ``update_stream`` calls this automatically for
        host-orchestrated policies whenever a segment surfaces
        ``needs_consolidation``."""
        pol = get_policy(self.policy)
        if shard_ids is None:
            n_pending = np.asarray(self.states.graph.n_pending)
            n_active = np.asarray(self.states.graph.n_active)
            if force:
                fire = n_pending > 0
            else:
                fire = np.array([
                    pol.should_consolidate(self.cfg, int(a), int(p))
                    for a, p in zip(n_active, n_pending)
                ], dtype=bool)
            shard_ids = np.nonzero(fire)[0]
        shard_ids = [int(s) for s in np.asarray(shard_ids).ravel()]
        if shard_ids:
            self.states = self.states._replace(
                graph=consolidate_stacked(
                    self.states.graph, self.cfg, pol.consolidate, shard_ids
                )
            )
        return shard_ids

    # -- durability -----------------------------------------------------------

    def save(self, manager, step: int, *, extra: Optional[dict] = None,
             on_event=None):
        """Checkpoint the stacked per-logical-shard state through
        ``core/persist.py::save_index``.  The manifest records
        ``n_logical`` (the stacked leading axis), so ``restore`` can lay
        the same L rows over a different mesh.  Serving knobs (routing /
        sequential) ride the user extra as defaults for the restored
        instance.  Must be called BEFORE the next update invalidates the
        donated ``states`` handle."""
        user = {"routing": self.routing, "sequential": self.sequential}
        user.update(extra or {})
        return save_index(
            manager, step, self.states, self.cfg,
            policy=self.policy, extra=user, on_event=on_event,
        )

    @classmethod
    def restore(cls, manager, cfg: ANNConfig, mesh: Mesh, *,
                step: Optional[int] = None, axis: str = "shard",
                policy: Optional[str] = None,
                routing: Optional[str] = None,
                sequential: Optional[bool] = None):
        """Restore a ``ShardedIndex`` checkpoint onto ``mesh`` — which may
        have a DIFFERENT size than the mesh that wrote it (elastic
        reshard), as long as it divides the checkpoint's ``n_logical``.
        Because routing and every per-row program are functions of the
        logical shard only, the restored index answers searches and
        absorbs updates bit-identically to the original layout.

        Returns ``(index, step)``.  ``policy``/``routing``/``sequential``
        default to what the checkpoint recorded; passing ``policy``
        explicitly validates it against the checkpoint (typed
        ``CheckpointMismatchError`` on disagreement)."""
        step, state, extra = restore_index(
            manager, cfg, step=step, policy=policy, device=False
        )
        meta = extra["index"]
        n_logical = meta["n_logical"]
        if not n_logical:
            raise CheckpointMismatchError(
                "checkpoint holds a single IndexState, not a sharded "
                "stack (restore it with core.persist.restore_index)"
            )
        n_shards = mesh.shape[axis]
        if n_logical % n_shards:
            raise CheckpointMismatchError(
                f"cannot reshard: checkpoint has {n_logical} logical "
                f"shards, not divisible by the restore mesh size "
                f"{n_shards}"
            )
        user = extra.get("user", {})
        idx = cls(
            cfg, mesh, axis=axis, policy=meta["policy"],
            max_external_id=meta["max_external_id"],
            routing=routing if routing is not None
            else user.get("routing", "compact"),
            sequential=sequential if sequential is not None
            else user.get("sequential", True),
            n_logical=n_logical,
        )
        idx.states = jax.device_put(state, idx._shard_spec)
        return idx, step

    def search(self, queries, k=10, l=64, *, partition: Optional[str] = None):
        """Returns (ext_ids (Q, k), owner LOGICAL shards (Q, k), dists
        (Q, k), total comps) — ids are EXTERNAL ids off the
        device-resident ``slot2ext`` maps.

        ``partition=None``/``"replicate"`` (default) fans the whole query
        batch out to every shard and merges the all-gathered candidates —
        lowest latency for small Q, and inherently straggler-redundant.
        ``partition="queries"`` routes disjoint Q/S sub-batches to
        different shards and rotates them around the ring, overlapping
        each sub-batch's global merge with the next one's beams — per-hop
        work per shard shrinks S-fold, the right trade once Q is large
        enough to fill every shard (queries are padded to S equal
        power-of-two sub-batches; both modes return identical top-k)."""
        q = np.asarray(queries, np.float32)
        if partition in (None, "replicate"):
            return self.search_state(self.states, q, k=k, l=l)
        if partition != "queries":
            raise ValueError(f"unknown search partition {partition!r}")
        n_q = q.shape[0]
        per_shard = next_bucket(max(-(-n_q // self.n_shards), 1))
        total = per_shard * self.n_shards
        qpad = np.zeros((total, q.shape[1]), np.float32)
        qpad[:n_q] = q
        valid = np.zeros((total,), bool)
        valid[:n_q] = True
        ids, shards, dists, comps = self._search_part(
            self.states,
            jax.device_put(jnp.asarray(qpad), self._shard_spec),
            jax.device_put(jnp.asarray(valid), self._shard_spec),
            k=k, l=l,
        )
        return (np.asarray(ids)[:n_q], np.asarray(shards)[:n_q],
                np.asarray(dists)[:n_q], int(np.asarray(comps).sum()))

    # -- serving (snapshot-isolated reads) ------------------------------------

    def search_state(self, states: IndexState, queries, k=10, l=64):
        """Replicate-and-merge search against an EXPLICIT stacked state —
        the snapshot-isolated read path (``repro.serving.ShardedEngine``).
        ``states`` is any (L, ...) stacked ``IndexState`` pytree laid out
        like ``self.states`` (e.g. a ``snapshot_states`` clone); the live
        ``search`` is just this over ``self.states``.  Same compiled
        program, same return contract as ``search``."""
        ids, shards, dists, comps = self._search(
            states, jnp.asarray(np.asarray(queries, np.float32)), k=k, l=l
        )
        # every shard computed the same global merge; take shard 0's copy
        return (np.asarray(ids)[0], np.asarray(shards)[0],
                np.asarray(dists)[0], int(np.asarray(comps).sum()))

    def snapshot_states(self, states: Optional[IndexState] = None
                        ) -> IndexState:
        """A deep, layout-preserving clone of the stacked state (defaults
        to the live one): fresh buffers on the same shard sharding, safe to
        search while subsequent updates DONATE the live handle.  This is
        ``core.api.clone_state`` lifted to the stacked layout — the sharded
        analogue of ``take_snapshot``."""
        states = self.states if states is None else states
        return jax.device_put(
            jax.tree.map(jnp.copy, states), self._shard_spec
        )
