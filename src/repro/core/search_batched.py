"""Natively batched beam-search engine: one shared hop loop for B queries.

``search_batch`` used to be ``jax.vmap(greedy_search)`` over a per-query
``lax.while_loop``.  XLA batches a vmapped while_loop by running the body for
*every* lane until the slowest lane terminates and then ``select``-ing the
old carry back in for lanes whose predicate went false — so each hop pays a
full-carry masked copy (the seen bitmaps and ``(B, max_visits)`` visited
lists dominate), and the per-lane neighbour gather stays B separate ``(R,)``
random HBM reads that the Pallas kernel cannot coalesce.

This module carries the batch natively instead:

  * one ``(B, l)`` beam (ids / dists / expanded), one BITPACKED
    ``uint32[B, ceil(n_cap/32)]`` seen bitmap (``core/bitset.py`` — 8x less
    carry traffic than the old bool[B, n_cap]), one ``(B, max_visits)``
    visited list;
  * a single shared ``lax.while_loop`` whose predicate is "any lane still has
    an unexpanded frontier"; converged lanes are masked per-op (their pops
    become no-ops and their counters freeze) rather than per-carry, so no
    whole-carry select is ever issued;
  * each hop gathers all lanes' frontier neighbourhoods at once — one
    ``(B, R)`` id tile through ``DistanceBackend.dists_to_ids_batched`` (the
    2-D-grid Pallas gather kernel on TPU: one launch per hop, not B).

Hop fusion (``ANNConfig.hop_fused``): the while_loop can drive H hops per
iteration ("super-steps") instead of one.  The hop body is an exact no-op
for a lane whose frontier is exhausted (its pop is masked, its counters
freeze, the sort-merge re-sorts an unchanged beam against all-inf
neighbours), so grouping hops never changes any lane's traversal — it only
amortizes the loop's termination check and lets the engine fuse across hop
boundaries.  The super-step itself is a ``DistanceBackend`` surface
(``beam_superstep``): the default runs H compositions of the shared jnp hop
body; the pallas engine overrides it with the fused multi-hop kernel
(``kernels/beam_hop.py``) that keeps the (B, l) beam resident in VMEM
across all H hops with per-lane early exit.  ``hop_fused = -1`` (default)
resolves to unfused on every backend: the fused kernel does not compile
with Mosaic yet (see ``resolved_hop_fused``).

Per lane, the traversal is identical to per-query ``greedy_search``: the
pop order, tie-breaks (first-minimum argmin, stable sort-merge), visited
accounting, comparison counts and hop counts all follow the same ops, just
with a leading batch axis — so ``topk_ids``/``visited_ids``/``n_comps``/
``n_hops`` match exactly (distances agree to f32 tolerance: XLA reduces a
batched matmul in a different order than a single matvec, exactly as the
old vmap formulation already did).  ``tests/test_search_batched.py`` and
``tests/test_beam_fused.py`` pin this lane-by-lane.

Batch-size bucketing: streaming callers present ragged batch sizes; every
distinct B is a distinct jit specialization of the whole loop.  ``pad_batch``
rounds B up to the next power of two so the number of compiled programs
stays logarithmic; padded lanes run a zero query and are sliced off.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import bitset
from .backend import BIG, resolve_backend
from .search import SearchResult
from .spans import (SEARCH_HOPS, SEARCH_SELECT, TRACE_COUNTER,
                    device_scope)
from .types import (
    INVALID,
    ANNConfig,
    GraphState,
    clip_ids,
    navigable,
    recorded,
)


# Hops per super-step the fused-engine benchmarks and parity tests pin
# (``cfg.hop_fused`` auto resolves to unfused; see ``resolved_hop_fused``).
DEFAULT_FUSED_HOPS = 4


class _BLoop(NamedTuple):
    beam_ids: jax.Array    # i32[B, l]
    beam_dists: jax.Array  # f32[B, l]
    beam_exp: jax.Array    # bool[B, l]
    seen: jax.Array        # u32[B, ceil(n_cap/32)]  bitpacked (core/bitset.py)
    vis_ids: jax.Array     # i32[B, max_visits]
    vis_dists: jax.Array   # f32[B, max_visits]
    n_vis: jax.Array       # i32[B]
    n_comps: jax.Array     # i32[B]
    n_hops: jax.Array      # i32[B]


BatchedDistanceFn = Callable[
    [GraphState, ANNConfig, jax.Array, jax.Array], jax.Array
]


def next_bucket(b: int) -> int:
    """The batch-size bucket for ``b``: the next power of two (>= 1)."""
    p = 1
    while p < b:
        p *= 2
    return p


def pad_batch(arr, b: int, fill=None):
    """Pad the leading axis of ``arr`` up to the bucket for ``b`` lanes.

    ``fill`` defaults by dtype: ``INVALID`` for integer payloads (id
    arrays — a float 0.0 fill would silently truncate to slot id 0, a
    VALID slot), ``False`` for bools, ``0.0`` for floats.  Pass ``fill``
    explicitly to override.
    """
    bucket = next_bucket(b)
    if arr.shape[0] == bucket:
        return arr
    if fill is None:
        if jnp.issubdtype(arr.dtype, jnp.integer):
            fill = INVALID
        elif jnp.issubdtype(arr.dtype, jnp.bool_):
            fill = False
        else:
            fill = 0.0
    pad = [(0, bucket - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, pad, constant_values=fill)


def resolved_hop_fused(cfg: ANNConfig) -> int:
    """The engine's hops-per-super-step: ``cfg.hop_fused`` when pinned
    (0 = unfused), else auto — 0 on every backend.  The fused multi-hop
    kernel (``kernels/beam_hop.py``) runs in interpret mode only: Mosaic
    refuses its vector gathers and in-kernel sort, so the pallas engine's
    chip path is the per-hop loop over ``gather_distance_batched``, and an
    explicit ``hop_fused > 0`` there raises (``PallasBackend``)."""
    return max(cfg.hop_fused, 0)


def make_hop_body(state: GraphState, cfg: ANNConfig, queries: jax.Array,
                  dist_fn: BatchedDistanceFn, *, l: int, max_visits: int):
    """The shared per-hop transition ``_BLoop -> _BLoop`` of the batched
    beam engine.  Both engines compose it: the unfused loop runs it once
    per while_loop iteration, ``superstep_reference`` runs H back-to-back
    compositions per iteration.  A lane with no unexpanded frontier (or at
    its hop bound) is an EXACT no-op — pops mask out, counters freeze, and
    the stable sort-merge against all-inf neighbours returns the beam
    unchanged — which is what makes hop grouping traversal-neutral."""
    nav = navigable(state)
    visit = recorded(state, cfg)
    b = queries.shape[0]
    bidx = jnp.arange(b)

    def hop(s: _BLoop) -> _BLoop:
        active = (
            jnp.any(
                (s.beam_ids >= 0) & ~s.beam_exp & jnp.isfinite(s.beam_dists),
                axis=1,
            )
            & (s.n_hops < max_visits)
        )

        # --- pop each lane's closest unexpanded vertex -----------------------
        frontier_d = jnp.where(
            (s.beam_ids >= 0) & ~s.beam_exp, s.beam_dists, BIG
        )
        i = jnp.argmin(frontier_d, axis=1)                         # i32[B]
        v = s.beam_ids[bidx, i]
        dv = s.beam_dists[bidx, i]
        beam_exp = s.beam_exp.at[bidx, i].set(s.beam_exp[bidx, i] | active)

        # --- record in visited list (recorded pops of active lanes) --------
        sv = clip_ids(v, cfg.n_cap)
        write = active & visit[sv]
        slot = jnp.where(write, s.n_vis, max_visits)   # OOB => dropped write
        vis_ids = s.vis_ids.at[bidx, slot].set(v, mode="drop")
        vis_dists = s.vis_dists.at[bidx, slot].set(dv, mode="drop")
        n_vis = s.n_vis + write.astype(jnp.int32)

        # --- expand: one (B, R) frontier-neighbourhood tile ------------------
        nbrs = state.adj[sv]                                       # (B, R)
        safe_nbrs = clip_ids(nbrs, cfg.n_cap)
        fresh = (
            (nbrs >= 0)
            & nav[safe_nbrs]
            & ~bitset.getbit_rows(s.seen, safe_nbrs)
            & active[:, None]
        )
        masked = jnp.where(fresh, nbrs, INVALID)
        nd = dist_fn(state, cfg, queries, masked)                  # (B, R)
        n_comps = s.n_comps + jnp.sum(fresh, axis=1).astype(jnp.int32)
        seen = bitset.setbits_rows(s.seen, safe_nbrs, fresh)

        # --- sort-merge beams + neighbours, keep top-l per lane --------------
        # (id, expanded) ride the stable key sort as ONE packed int32 payload
        # (id << 1 | exp; exact for INVALID = -1) — a 2-operand variadic sort
        # is ~1.4x cheaper than the per-query loop's 3-operand one, and the
        # packing never affects order: the distance is the only sort key and
        # stability resolves ties positionally, exactly as the reference.
        all_d = jnp.concatenate([s.beam_dists, nd], axis=1)
        all_p = jnp.concatenate(
            [
                (s.beam_ids << 1) | beam_exp.astype(jnp.int32),
                masked << 1,  # fresh neighbours enter unexpanded
            ],
            axis=1,
        )
        sd, sp = lax.sort((all_d, all_p), num_keys=1)
        return _BLoop(
            beam_ids=sp[:, :l] >> 1,
            beam_dists=sd[:, :l],
            beam_exp=(sp[:, :l] & 1).astype(bool),
            seen=seen,
            vis_ids=vis_ids,
            vis_dists=vis_dists,
            n_vis=n_vis,
            n_comps=n_comps,
            n_hops=s.n_hops + active.astype(jnp.int32),
        )

    return hop


def superstep_reference(dist_fn: BatchedDistanceFn, state: GraphState,
                        cfg: ANNConfig, queries: jax.Array,
                        carry: _BLoop, *, h: int, l: int,
                        max_visits: int) -> _BLoop:
    """The pure-jnp H-hop super-step: exactly ``h`` compositions of the
    shared hop body, unrolled so XLA can fuse across hop boundaries.  This
    is both ``DistanceBackend.beam_superstep``'s default implementation and
    the oracle the fused Pallas kernel is verified against — per lane it IS
    the unfused engine, re-grouped."""
    hop = make_hop_body(state, cfg, queries, dist_fn, l=l,
                        max_visits=max_visits)
    for _ in range(h):
        carry = hop(carry)
    return carry


@functools.partial(
    jax.jit, static_argnames=("cfg", "k", "l", "max_visits", "distance_fn")
)
def batched_greedy_search(
    state: GraphState,
    cfg: ANNConfig,
    queries: jax.Array,          # f32[B, dim]
    *,
    k: int,
    l: int,
    max_visits: Optional[int] = None,
    distance_fn: Optional[BatchedDistanceFn] = None,
    valid: Optional[jax.Array] = None,
) -> SearchResult:
    """GreedySearch (Algorithm 1) for B queries in one shared hop loop.

    Returns a ``SearchResult`` whose leaves carry a leading batch axis;
    per lane the traversal (ids and counters) is identical to
    ``greedy_search`` on that lane's query.
    ``distance_fn`` (batched signature: ``(state, cfg, (B, D) queries,
    (B, M) ids) -> (B, M)``) overrides the engine's
    ``dists_to_ids_batched`` for experiments (and routes hop fusion
    through the generic super-step instead of a backend kernel).
    ``valid`` (bool[B]) masks whole lanes out of the traversal: a masked
    lane starts with an empty beam, performs no distance computations, adds
    no hops to the shared loop and returns all-INVALID results — the
    mechanism bucket-padded callers (``search_batch``, ``core/api.py``) use
    to make padding lanes free.

    When ``cfg.quantized`` is set (and the state carries a quant store),
    the hop loop traverses on int8 traversal-tier distances
    (``dists_to_ids_batched_q``) and the final top-k is *exactly rescored*
    against the f32 vector table before selection — returned ``topk_dists``
    are bit-identical to recomputing ``dists_to_ids_batched`` on the
    returned ids.  Quantization error can therefore perturb which
    candidates reach the beam, never the reported distances.
    """
    TRACE_COUNTER["batched_greedy_search"] += 1
    if max_visits is None:
        max_visits = cfg.max_visits(l)
    backend = resolve_backend(cfg)
    # ``state.quant is not None`` is a pytree-structure check, decided at
    # trace time like cfg itself; an explicit distance_fn override wins
    use_q = (
        cfg.quantized and state.quant is not None and distance_fn is None
    )
    dist_fn = distance_fn or (
        backend.dists_to_ids_batched_q if use_q
        else backend.dists_to_ids_batched
    )
    returnable = state.active

    with device_scope(SEARCH_HOPS):
        b = queries.shape[0]
        starts = jnp.broadcast_to(state.start, (b,))
        if valid is not None:
            starts = jnp.where(valid, starts, INVALID)
        d0 = dist_fn(state, cfg, queries, starts[:, None])[:, 0]

        beam_ids = jnp.full((b, l), INVALID, jnp.int32).at[:, 0].set(starts)
        beam_dists = jnp.full((b, l), BIG, jnp.float32).at[:, 0].set(
            jnp.where(starts >= 0, d0, BIG)
        )
        seen = bitset.setbits_rows(
            bitset.empty_rows(b, cfg.n_cap),
            clip_ids(starts, cfg.n_cap)[:, None],
            (starts >= 0)[:, None],
        )

        init = _BLoop(
            beam_ids=beam_ids,
            beam_dists=beam_dists,
            beam_exp=jnp.zeros((b, l), bool),
            seen=seen,
            vis_ids=jnp.full((b, max_visits), INVALID, jnp.int32),
            vis_dists=jnp.full((b, max_visits), BIG, jnp.float32),
            n_vis=jnp.zeros((b,), jnp.int32),
            n_comps=jnp.where(starts >= 0, 1, 0).astype(jnp.int32),
            n_hops=jnp.zeros((b,), jnp.int32),
        )

        def lane_active(s: _BLoop):
            frontier = (
                (s.beam_ids >= 0) & ~s.beam_exp & jnp.isfinite(s.beam_dists)
            )
            return jnp.any(frontier, axis=1) & (s.n_hops < max_visits)

        def cond(s: _BLoop):
            return jnp.any(lane_active(s))

        h = resolved_hop_fused(cfg)
        if h <= 0:
            body = make_hop_body(state, cfg, queries, dist_fn, l=l,
                                 max_visits=max_visits)
        elif distance_fn is not None:
            # a custom distance_fn has no kernel; fuse through the generic
            # super-step so the override still sees every hop's distances
            def body(s):
                return superstep_reference(dist_fn, state, cfg, queries, s,
                                           h=h, l=l, max_visits=max_visits)
        elif use_q:
            def body(s):
                return backend.beam_superstep_q(state, cfg, queries, s, h=h,
                                                l=l, max_visits=max_visits)
        else:
            def body(s):
                return backend.beam_superstep(state, cfg, queries, s, h=h,
                                              l=l, max_visits=max_visits)

        out = lax.while_loop(cond, body, init)

    with device_scope(SEARCH_SELECT):
        # --- final top-k over each lane's beam, filtered to live vertices
        ret = (returnable[clip_ids(out.beam_ids, cfg.n_cap)]
               & (out.beam_ids >= 0))
        if use_q:
            # exact rescore (FreshDiskANN): re-rank the surviving beam
            # against the full-precision table so the selection (and the
            # reported distances) never carry quantization error; one
            # (B, l) exact tile per query batch vs. the hops' many (B, R)
            # quantized tiles
            beam_d = backend.dists_to_ids_batched(
                state, cfg, queries, jnp.where(ret, out.beam_ids, INVALID)
            )
            out = out._replace(
                beam_dists=beam_d,
                n_comps=(out.n_comps
                         + jnp.sum(ret, axis=1).astype(jnp.int32)),
            )
        final_d = jnp.where(ret, out.beam_dists, BIG)
        kk = min(k, l)  # the beam holds l entries; pad the tail with INVALID
        top_d, top_i = lax.top_k(-final_d, kk)
        topk_ids = jnp.where(
            jnp.isfinite(-top_d),
            jnp.take_along_axis(out.beam_ids, top_i, axis=1),
            INVALID,
        )
        if kk < k:
            topk_ids = jnp.pad(
                topk_ids, ((0, 0), (0, k - kk)), constant_values=INVALID
            )
            top_d = jnp.pad(top_d, ((0, 0), (0, k - kk)),
                            constant_values=-BIG)
        topk_dists = -top_d
        if use_q:
            # recompute on exactly the returned (B, k) ids so topk_dists are
            # BIT-equal to the caller-side f32 rescore oracle (same jitted
            # call, same operand shapes => same reduction order)
            topk_dists = backend.dists_to_ids_batched(
                state, cfg, queries, topk_ids
            )
    return SearchResult(
        topk_ids=topk_ids,
        topk_dists=topk_dists,
        visited_ids=out.vis_ids,
        visited_dists=out.vis_dists,
        n_visited=out.n_vis,
        n_comps=out.n_comps,
        n_hops=out.n_hops,
    )


def merge_topk(dists_a, dists_b, k: int, *payload_pairs):
    """Merge two per-lane candidate sets into the k best by distance.

    ``dists_a``/``dists_b``: f32[..., Ka] / f32[..., Kb] (pad dead slots
    with ``BIG`` so they lose every merge).  Each extra argument is an
    ``(payload_a, payload_b)`` pair of integer arrays aligned with the
    distances (ids, owner shards, ...); every payload rides the same merge
    permutation.  Returns ``(dists[..., k], (payload[..., k], ...))``.

    This is the sub-batch merge of the sharded query path: incremental —
    ``merge_topk(running, incoming)`` after every shard hop keeps the carry
    at width k instead of accumulating an (S*k) concat — and order-stable
    for distinct distances (``lax.top_k`` on the concatenated axis), so an
    incremental merge chain selects the same ids as one flat merge whenever
    distances are tie-free.
    """
    d = jnp.concatenate([dists_a, dists_b], axis=-1)
    top_d, idx = lax.top_k(-d, k)
    outs = tuple(
        jnp.take_along_axis(jnp.concatenate([pa, pb], axis=-1), idx, axis=-1)
        for pa, pb in payload_pairs
    )
    return -top_d, outs


__all__ = [
    "DEFAULT_FUSED_HOPS",
    "TRACE_COUNTER",
    "batched_greedy_search",
    "make_hop_body",
    "merge_topk",
    "next_bucket",
    "pad_batch",
    "resolved_hop_fused",
    "superstep_reference",
]
