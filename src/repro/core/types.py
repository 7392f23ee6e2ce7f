"""Core data structures for the streaming ANNS graph index.

The paper's CPU implementation stores the graph as per-node ``Vec<u32>``
adjacency lists guarded by locks.  The TPU-native representation used here is
a dense slot matrix:

  * ``vectors[n_cap, dim]``  — vector payload per slot
  * ``adj[n_cap, r]``        — out-neighbour ids, ``INVALID`` (-1) padded and
                               kept front-compacted
  * per-slot status masks    — active / tombstone / quarantine
  * a free stack             — slot allocator (paper: free-list)

All updates are pure functions ``GraphState -> GraphState`` so the update
stream can be expressed as ``lax.scan`` (serial, paper-faithful) or batched.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .quant import QuantStore, init_quant_store

INVALID = -1

# ---------------------------------------------------------------------------
# Static configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ANNConfig:
    """Static (hashable) configuration of a streaming graph index.

    Mirrors the paper's parameters: R (degree), l_b / l_s / l_d (beam widths
    for build / search / delete), alpha (prune slack), k (delete candidate
    list size), c (edge copies per delete).
    """

    dim: int
    n_cap: int
    r: int = 64
    l_build: int = 128
    l_search: int = 128
    l_delete: int = 128
    k_delete: int = 50
    n_copies: int = 3  # the paper's ``c``
    alpha: float = 1.2
    metric: str = "l2"  # "l2" (squared euclidean) | "ip" (negative dot)
    # Hard bound on beam-search expansions (while_loop safety net).  The
    # search converges when the top-l beam is fully expanded, typically after
    # ~l + a few dozen expansions.
    max_visit_slack: int = 64
    consolidation_threshold: float = 0.2
    # Distance-backend selection (see core/backend.py): "auto" resolves to
    # the Pallas kernels on TPU and pure jnp elsewhere.
    backend: str = "auto"
    # Fused multi-hop beam engine (core/search_batched.py): hops per
    # super-step of the batched hop loop.  -1 = auto (off on every
    # backend: the fused Pallas kernel does not compile with Mosaic yet);
    # 0 = off (one while_loop cond per hop); H >= 1 = fused, H hops per
    # outer-loop iteration.  Traversal is lane-exact against the unfused
    # engine for every H.
    hop_fused: int = -1
    # Quantized memory tier (core/quant.py): maintain per-row symmetric
    # int8 codes next to the f32 table, traverse the beam on quantized
    # distances and exactly rescore the final top-k against f32 before
    # ids are returned.  Changes the GraphState pytree structure (a
    # ``quant`` leaf appears), so it is checkpoint-critical.
    quantized: bool = False
    # "local" update policy (topology-aware localized repair, arXiv
    # 2503.00402): static bound on the number of exact in-neighbours that
    # receive replacement edges per delete.  Every in-edge is still
    # removed (the removal is a full-topology compare, not bounded), so
    # the bound trades graph quality, never correctness.  0 = auto (2r —
    # the mean in-degree of a degree-R graph is <= R, so 2r covers the
    # bulk of the in-degree distribution).
    local_in_cap: int = 0

    def max_visits(self, l: int) -> int:
        return l + self.max_visit_slack

    def resolved_local_in_cap(self) -> int:
        """The static in-neighbour repair bound of the "local" policy
        (``core/delete.py::local_delete``): ``local_in_cap``, or 2r when 0
        (auto)."""
        return self.local_in_cap if self.local_in_cap > 0 else 2 * self.r

    def __post_init__(self):
        assert self.metric in ("l2", "ip"), self.metric
        assert self.r >= 1 and self.n_cap >= 1 and self.dim >= 1
        assert self.hop_fused >= -1, self.hop_fused
        assert self.local_in_cap >= 0, self.local_in_cap
        if self.backend != "auto":
            # validate against the live registry so custom engines added via
            # register_backend are selectable (import deferred: backend.py
            # imports this module at load time)
            from .backend import available_backends

            if self.backend not in available_backends():
                raise ValueError(
                    f"unknown backend {self.backend!r}; "
                    f"known: {('auto',) + available_backends()}"
                )


# ---------------------------------------------------------------------------
# Graph state (pytree)
# ---------------------------------------------------------------------------


class GraphState(NamedTuple):
    """The full mutable state of one index shard, as a JAX pytree."""

    vectors: jax.Array     # f32[n_cap, dim]
    norms: jax.Array       # f32[n_cap]  squared L2 norms (l2 metric fast path)
    adj: jax.Array         # i32[n_cap, r]  out-neighbours, INVALID padded
    active: jax.Array      # bool[n_cap]  live and returnable
    tombstone: jax.Array   # bool[n_cap]  lazily deleted (fresh mode): still navigable
    quarantine: jax.Array  # bool[n_cap]  freed in-place (ip mode): awaiting Alg-6 sweep
    free_stack: jax.Array  # i32[n_cap]  slot allocator stack
    free_top: jax.Array    # i32[]  number of free slots
    start: jax.Array       # i32[]  entry point (INVALID when empty)
    n_active: jax.Array    # i32[]
    n_pending: jax.Array   # i32[]  tombstoned (fresh) or quarantined (ip) count
    # Quantized memory tier (core/quant.py), present iff ``cfg.quantized``.
    # ``None`` is an empty pytree node, so unquantized states keep their
    # pre-existing leaf structure (and checkpoint layout) exactly.
    quant: Optional[QuantStore] = None


def init_state(cfg: ANNConfig, dtype=jnp.float32) -> GraphState:
    n = cfg.n_cap
    return GraphState(
        vectors=jnp.zeros((n, cfg.dim), dtype),
        norms=jnp.zeros((n,), jnp.float32),
        adj=jnp.full((n, cfg.r), INVALID, jnp.int32),
        active=jnp.zeros((n,), bool),
        tombstone=jnp.zeros((n,), bool),
        quarantine=jnp.zeros((n,), bool),
        free_stack=jnp.arange(n - 1, -1, -1, dtype=jnp.int32),
        free_top=jnp.int32(n),
        start=jnp.int32(INVALID),
        n_active=jnp.int32(0),
        n_pending=jnp.int32(0),
        quant=init_quant_store(n, cfg.dim) if cfg.quantized else None,
    )


# ---------------------------------------------------------------------------
# Device-resident index handle (graph + external-id map + op counters)
# ---------------------------------------------------------------------------

# ``UpdateBatch.kind`` codes.  An update stream is one sequence of these.
KIND_INSERT = 0
KIND_DELETE = 1


class IndexState(NamedTuple):
    """The device-resident index handle: one pytree holding everything a
    front door needs — the graph, the external-id <-> slot map, and per-op
    counters.  ``core/api.py::apply`` is the single update entry point over
    this state; no host-side id bookkeeping exists anywhere.
    """

    graph: GraphState
    ext2slot: jax.Array      # i32[max_ext]  external id -> slot (INVALID free)
    slot2ext: jax.Array      # i32[n_cap]    slot -> external id (INVALID free)
    n_inserts: jax.Array     # i32[]  applied inserts
    n_deletes: jax.Array     # i32[]  applied deletes
    insert_comps: jax.Array  # i32[]  distance comps spent in insert lanes
    delete_comps: jax.Array  # i32[]  distance comps spent in delete lanes


class UpdateBatch(NamedTuple):
    """One padded lane-batch of the unified update stream.

    ``kind[b]`` in {KIND_INSERT, KIND_DELETE}; ``vector`` rows are ignored
    (zeros by convention) for delete lanes; ``valid`` masks no-op padding
    lanes so ragged streaming batches ride power-of-two buckets without
    recompiling (see ``core/api.py::pad_update_batch``).
    """

    kind: jax.Array    # i32[B]
    ext_id: jax.Array  # i32[B]
    vector: jax.Array  # f32[B, dim]
    valid: jax.Array   # bool[B]


class ApplyResult(NamedTuple):
    """Per-lane outcome of one ``apply`` call."""

    slot: jax.Array     # i32[B]  slot assigned (insert) / freed (delete)
    ok: jax.Array       # bool[B] lane applied (False: masked, unknown ext id,
                        #         or capacity exhausted)
    n_comps: jax.Array  # i32[B]  distance computations spent by the lane


class SegmentResult(NamedTuple):
    """Per-step stacked outcome of one ``apply_segment`` call (leading axis
    ``T`` = ops in the segment; lane axes as in ``ApplyResult``)."""

    slot: jax.Array                # i32[T, B]
    ok: jax.Array                  # bool[T, B]
    n_comps: jax.Array             # i32[T, B]
    consolidated: jax.Array        # bool[T]  device-side pass ran after the op
    needs_consolidation: jax.Array # bool[T]  trigger fired but the policy is
                                   #          host-orchestrated (fresh): the
                                   #          caller consolidates between
                                   #          segments


def stack_update_batches(steps) -> UpdateBatch:
    """Stack ``T`` same-width ``UpdateBatch``es into one (T, B) op tensor
    (the payload of ``apply_segment``)."""
    widths = {s.kind.shape[0] for s in steps}
    if len(widths) != 1:
        raise ValueError(f"segment steps must share one lane width: {widths}")
    return UpdateBatch(*[jnp.stack(arrs) for arrs in zip(*steps)])


def noop_update_batch(b: int, dim: int) -> UpdateBatch:
    """An all-masked ``UpdateBatch`` (T-axis padding for segment buckets)."""
    return UpdateBatch(
        kind=jnp.full((b,), KIND_INSERT, jnp.int32),
        ext_id=jnp.full((b,), INVALID, jnp.int32),
        vector=jnp.zeros((b, dim), jnp.float32),
        valid=jnp.zeros((b,), bool),
    )


def take_update_lanes(batch: UpdateBatch, idx) -> UpdateBatch:
    """Gather the lanes ``idx`` (any integer index array) out of ``batch``.

    Field-generic, so it works for numpy payloads (the host-side compact
    routing in ``core/api.py``) and jax payloads alike; lane order follows
    ``idx``."""
    return UpdateBatch(
        kind=batch.kind[idx],
        ext_id=batch.ext_id[idx],
        vector=batch.vector[idx],
        valid=batch.valid[idx],
    )


def init_index_state(
    cfg: ANNConfig, max_external_id: int, dtype=jnp.float32
) -> IndexState:
    """A fresh device-resident handle admitting external ids in
    ``[0, max_external_id)``."""
    if max_external_id <= 0:
        raise ValueError(
            f"max_external_id must be positive, got {max_external_id}"
        )
    return IndexState(
        graph=init_state(cfg, dtype),
        ext2slot=jnp.full((max_external_id,), INVALID, jnp.int32),
        slot2ext=jnp.full((cfg.n_cap,), INVALID, jnp.int32),
        n_inserts=jnp.int32(0),
        n_deletes=jnp.int32(0),
        insert_comps=jnp.int32(0),
        delete_comps=jnp.int32(0),
    )


# ---------------------------------------------------------------------------
# Small row utilities
# ---------------------------------------------------------------------------


def navigable(state: GraphState) -> jax.Array:
    """Slots the greedy search may traverse (live or tombstoned)."""
    return state.active | state.tombstone


def row_count(row: jax.Array) -> jax.Array:
    return jnp.sum(row >= 0).astype(jnp.int32)


def row_contains(row: jax.Array, u: jax.Array) -> jax.Array:
    return jnp.any(row == u)


def compact_row(row: jax.Array) -> jax.Array:
    """Move valid entries to the front, preserving order (stable argsort)."""
    order = jnp.argsort(row < 0, stable=True)
    return row[order]


def mask_duplicates(ids: jax.Array) -> jax.Array:
    """Replace duplicate ids (keep first occurrence) with INVALID.  O(C^2)."""
    eq = ids[:, None] == ids[None, :]
    earlier = jnp.tril(jnp.ones_like(eq), k=-1)
    dup = jnp.any(eq & earlier, axis=1)
    return jnp.where(dup | (ids < 0), INVALID, ids)


def clip_ids(ids: jax.Array, n_cap: int) -> jax.Array:
    return jnp.clip(ids, 0, n_cap - 1)


def as_numpy_state(state: GraphState) -> dict:
    return {
        k: (
            v
            if v is None
            else type(v)(*map(np.asarray, v))
            if isinstance(v, tuple)
            else np.asarray(v)
        )
        for k, v in state._asdict().items()
    }
