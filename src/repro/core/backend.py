"""The unified distance-backend layer.

Every hot path of the engine — GreedySearch (Alg 1), insert (Alg 2),
in-place delete (Alg 5), RobustPrune (Alg 3), consolidation and the
brute-force recall oracle — bottoms out in one primitive: "distances from a
query to a gathered set of slots".  This module is the single seam those
call sites go through.  A ``DistanceBackend`` bundles the four shapes of
that primitive:

  * ``dists_to_ids``      — q vs. a gathered id set (the beam-search loop);
  * ``dists_from_rows``   — q vs. already-gathered rows (prune occlusion);
  * ``pair_dists``        — (A, D) vs. (B, D) matrices (delete top-c);
  * ``brute_force_topk``  — exact top-k over the live slot table (recall).

Three implementations are registered:

  * ``jnp``    — pure ``jax.numpy`` math (``core/distance.py``), the CPU/
                 debug path and the reference the engine was built on;
  * ``pallas`` — the fused Pallas TPU kernels (``kernels/gather_distance``
                 for the beam loop, ``kernels/topk_score`` for brute-force
                 scoring), auto-falling back to interpret mode off-TPU.
                 Tile-local math (rows already in registers/VMEM) reuses the
                 jnp expressions — the kernels' win is the HBM gather/scan;
  * ``ref``    — the pure-jnp kernel oracles (``kernels/ref.py``) used by
                 parity tests.

Selection is by name via ``ANNConfig.backend`` (default ``"auto"``: pallas
on a TPU backend, jnp elsewhere).  ``ANNConfig`` is a static (hashable)
jit argument everywhere, so backend dispatch happens at trace time and
costs nothing at run time.  Per-slot squared norms are precomputed once in
``GraphState.norms`` at insert time; every backend consumes that cache
instead of re-reducing rows per call.

Each engine also serves the quantized memory tier (``core/quant.py``)
through ``dists_to_ids_batched_q`` / ``beam_superstep_q`` — int8 traversal
distances the batched beam engine hops on when ``ANNConfig.quantized`` is
set.  Future backends (GPU, multi-host) plug in with
``@register_backend("name")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import distance as _math
from .spans import GATHER, device_scope
from .types import (
    ROW_LANES,
    ANNConfig,
    GraphState,
    clip_ids,
    read_vectors,
    row_blocks,
)

BIG = _math.BIG


# ---------------------------------------------------------------------------
# Interface
# ---------------------------------------------------------------------------


class DistanceBackend:
    """Pluggable kernel engine for all distance math.

    Distances are "smaller = closer" for both metrics: squared L2, or the
    negated inner product.  Methods must be pure and jit-traceable; ``cfg``
    is static wherever these are called.
    """

    name = "abstract"

    # -- scalars ------------------------------------------------------------

    def query_norm(self, cfg: ANNConfig, q: jax.Array) -> jax.Array:
        """||q||^2 for l2 (the metric's precomputable term), 0 for ip."""
        if cfg.metric == "l2":
            return jnp.dot(q, q).astype(jnp.float32)
        return jnp.float32(0.0)

    # -- the beam-search hot loop -------------------------------------------

    def dists_to_ids(self, state: GraphState, cfg: ANNConfig, q, ids):
        """f32[M] distances from ``q`` to slots ``ids``; inf where INVALID."""
        raise NotImplementedError

    def dists_to_ids_batched(self, state: GraphState, cfg: ANNConfig,
                             queries, ids):
        """f32[B, M] distances from ``queries[b]`` to slots ``ids[b]``; inf
        where INVALID.  One fused (B, M) gather-distance tile per call — the
        per-hop primitive of the batched beam engine
        (``core/search_batched.py``).  Default: vmap of the per-query
        primitive, so every backend is batched-correct by construction;
        engines with a natively batched kernel override it."""
        return jax.vmap(
            lambda q, row: self.dists_to_ids(state, cfg, q, row)
        )(queries, ids)

    def beam_superstep(self, state: GraphState, cfg: ANNConfig, queries,
                       carry, *, h: int, l: int, max_visits: int):
        """Advance the batched beam engine's carry by ``h`` hops in one
        step (``core/search_batched.py``; carry is its ``_BLoop``).  A lane
        whose frontier is exhausted must be an exact no-op for the extra
        hops — that invariant is what lets ``batched_greedy_search`` run a
        while_loop of super-steps with unchanged traversal.  Default: h
        compositions of the shared jnp hop body over this backend's
        ``dists_to_ids_batched``; engines with a fused multi-hop kernel
        override it."""
        from .search_batched import superstep_reference

        return superstep_reference(
            self.dists_to_ids_batched, state, cfg, queries, carry,
            h=h, l=l, max_visits=max_visits,
        )

    # -- the quantized memory tier (core/quant.py) --------------------------

    def dists_to_ids_batched_q(self, state: GraphState, cfg: ANNConfig,
                               queries, ids):
        """f32[B, M] *traversal-tier* distances from ``queries[b]`` to the
        int8 codes of slots ``ids[b]`` (``state.quant`` must be present);
        inf where INVALID.  The batched beam engine hops on these when
        ``cfg.quantized`` and rescores the final top-k with the exact
        ``dists_to_ids_batched``.  Default: the shared jnp math from
        ``core/quant.py``; kernel engines override with the int8 gather
        kernel."""
        from .quant import quant_dists_to_ids_batched

        return quant_dists_to_ids_batched(state, cfg, queries, ids)

    def beam_superstep_q(self, state: GraphState, cfg: ANNConfig, queries,
                         carry, *, h: int, l: int, max_visits: int):
        """``beam_superstep`` over the quantized tier: same carry contract,
        distances from ``dists_to_ids_batched_q``.  Engines with a fused
        int8 multi-hop kernel override it."""
        from .search_batched import superstep_reference

        return superstep_reference(
            self.dists_to_ids_batched_q, state, cfg, queries, carry,
            h=h, l=l, max_visits=max_visits,
        )

    # -- gathered-tile math (prune / delete) --------------------------------

    def dists_from_rows(self, cfg: ANNConfig, q, q_norm, rows, row_norms):
        """f32[M] distances from ``q`` to rows (M, D).  No masking."""
        raise NotImplementedError

    def pair_dists(self, cfg: ANNConfig, a_vecs, a_norms, b_vecs, b_norms,
                   precision=None):
        """(A, B) distance matrix between two point sets.  No masking.
        ``precision`` is the inner products' ``jax.lax.Precision``."""
        raise NotImplementedError

    def pair_dists_ids(self, state: GraphState, cfg: ANNConfig, a_ids, b_ids):
        """(A, B) distances between two id sets; inf where either INVALID."""
        sa = clip_ids(a_ids, cfg.n_cap)
        sb = clip_ids(b_ids, cfg.n_cap)
        d = self.pair_dists(
            cfg,
            read_vectors(state.vectors, sa, cfg.dim), state.norms[sa],
            read_vectors(state.vectors, sb, cfg.dim), state.norms[sb],
        )
        invalid = (a_ids[:, None] < 0) | (b_ids[None, :] < 0)
        return jnp.where(invalid, BIG, d)

    # -- exact scan (recall oracle / exhaustive baseline) --------------------

    def brute_force_topk(self, state: GraphState, cfg: ANNConfig, queries,
                         *, k: int):
        """Exact top-k over live slots.  Returns (ids i32[Q,k], dists f32[Q,k]),
        ascending by distance, ids == -1 past the live count."""
        raise NotImplementedError

    def _biased_topk(self, state: GraphState, score_fn):
        """Shared dead-slot masking contract for kernel-style scorers:
        +inf bias excludes non-live slots, non-finite results map to id -1.
        ``score_fn(bias) -> (dists, ids)``."""
        bias = jnp.where(state.active, 0.0, BIG).astype(jnp.float32)
        d, ids = score_fn(bias)
        return jnp.where(jnp.isfinite(d), ids, -1), d


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, DistanceBackend] = {}


def register_backend(name: str):
    """Class decorator: instantiate and register a backend under ``name``."""

    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls()
        return cls

    return deco


def available_backends() -> tuple:
    return tuple(sorted(_REGISTRY))


def get_backend(name: str) -> DistanceBackend:
    """Resolve a backend by name.  ``"auto"`` picks pallas on TPU, jnp off."""
    if name == "auto":
        name = "pallas" if jax.default_backend() == "tpu" else "jnp"
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown distance backend {name!r}; "
            f"available: {available_backends()}"
        ) from None


def resolve_backend(cfg: ANNConfig) -> DistanceBackend:
    """The backend selected by ``cfg.backend``."""
    return get_backend(cfg.backend)


# ---------------------------------------------------------------------------
# jnp — pure jax.numpy math (CPU / debug / autodiff path)
# ---------------------------------------------------------------------------


@register_backend("jnp")
class JnpBackend(DistanceBackend):
    """The matmul+broadcast-add formulation from ``core/distance.py``."""

    def dists_to_ids(self, state, cfg, q, ids):
        return _math.dists_to_ids(state, cfg, q, ids)

    def dists_from_rows(self, cfg, q, q_norm, rows, row_norms):
        return _math.dists_from_rows(cfg.metric, q, q_norm, rows, row_norms)

    def pair_dists(self, cfg, a_vecs, a_norms, b_vecs, b_norms,
                   precision=None):
        return _math.pair_dists(cfg.metric, a_vecs, a_norms, b_vecs, b_norms,
                                precision)

    def brute_force_topk(self, state, cfg, queries, *, k):
        q_norms = (
            jnp.sum(queries * queries, axis=1)
            if cfg.metric == "l2"
            else jnp.zeros((queries.shape[0],), jnp.float32)
        )
        if row_blocks(cfg.dim) > 1:
            return _chunked_topk(self, state, cfg, queries, q_norms, k)
        d = self.pair_dists(cfg, _in_lanes(cfg, queries), q_norms,
                            state.vectors, state.norms)
        d = jnp.where(state.active[None, :], d, BIG)
        neg, idx = jax.lax.top_k(-d, k)
        return jnp.where(jnp.isfinite(neg), idx, -1), -neg


def _in_lanes(cfg: ANNConfig, queries):
    """``queries`` (..., dim) zero-padded to a table row's ``ROW_LANES``
    lanes, so they score a one-row-per-vector table as it is (zero lanes
    add 0)."""
    if cfg.dim == ROW_LANES:
        return queries
    pad = [(0, 0)] * (queries.ndim - 1) + [(0, ROW_LANES - cfg.dim)]
    return jnp.pad(queries, pad)


SCAN_CHUNK = 4096  # slots per step of the exact scan over multi-row tables


def _chunked_topk(be, state, cfg, queries, q_norms, k):
    """The exact scan over a table of several rows per vector: chunks of
    ``SCAN_CHUNK`` slots read through ``read_vectors``, each merged into a
    running top-k, so no step holds more than one chunk of vectors (the
    table as ``(n_cap, dim)`` would be a relayout copy of all of it)."""
    chunk = min(SCAN_CHUNK, cfg.n_cap)
    n_chunks = -(-cfg.n_cap // chunk)
    q = queries.shape[0]

    def step(carry, c):
        top_d, top_i = carry
        ids = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
        safe = clip_ids(ids, cfg.n_cap)
        d = be.pair_dists(cfg, queries, q_norms,
                          read_vectors(state.vectors, safe, cfg.dim),
                          state.norms[safe])
        live = (ids < cfg.n_cap) & state.active[safe]
        d = jnp.where(live[None, :], d, BIG)
        all_d = jnp.concatenate([top_d, d], axis=1)
        all_i = jnp.concatenate(
            [top_i, jnp.broadcast_to(ids, (q, chunk))], axis=1)
        neg, pos = jax.lax.top_k(-all_d, k)
        return (-neg, jnp.take_along_axis(all_i, pos, axis=1)), None

    init = (jnp.full((q, k), BIG, jnp.float32),
            jnp.full((q, k), -1, jnp.int32))
    (top_d, top_i), _ = jax.lax.scan(step, init,
                                     jnp.arange(n_chunks, dtype=jnp.int32))
    return jnp.where(jnp.isfinite(top_d), top_i, -1), top_d


# ---------------------------------------------------------------------------
# pallas — fused TPU kernels (interpret mode off-TPU)
# ---------------------------------------------------------------------------


@register_backend("pallas")
class PallasBackend(JnpBackend):
    """Routes the HBM-bound primitives through the Pallas kernels.

    ``dists_to_ids`` is the fused gather+distance kernel (the random HBM
    gather is the hot cost of the beam loop); ``brute_force_topk`` is the
    streaming top-k scorer (candidate rows read exactly once).  The
    tile-local helpers (``dists_from_rows`` / ``pair_dists``) operate on
    rows the caller already gathered, so they inherit the jnp math — there
    is no HBM traffic left for a kernel to save.

    The kernels take one table row a vector (``dim <= 128``, the query
    zero-padded to the row's lanes).  Wider vectors, several rows each,
    take the inherited XLA row gather, chosen from the static ``cfg.dim``
    (``kernels/gather_distance.py`` says why).  Either way the gather and
    its distances run under the ``ann.gather`` scope.
    """

    interpret = None  # None => auto: interpret off-TPU, Mosaic on TPU

    def _refuse_on_mosaic(self, what: str, cfg_field: str):
        """Kernels that only run in interpret mode fail loudly when the
        kernels compile with Mosaic, rather than silently running some
        other engine."""
        from ..kernels import ops

        interpret = self.interpret
        if interpret is None:
            interpret = ops._default_interpret()
        if not interpret:
            raise NotImplementedError(
                f"{what} does not compile with Mosaic yet (it runs in "
                f"interpret mode only); unset ANNConfig.{cfg_field} or pick "
                f"another backend on TPU"
            )

    def dists_to_ids(self, state, cfg, q, ids):
        from ..kernels import ops

        with device_scope(GATHER):
            if row_blocks(cfg.dim) > 1:
                return super().dists_to_ids(state, cfg, q, ids)
            return ops.gather_distances(
                ids, _in_lanes(cfg, q), state.vectors, norms=state.norms,
                metric=cfg.metric, interpret=self.interpret,
            )

    def dists_to_ids_batched(self, state, cfg, queries, ids):
        from ..kernels import ops

        with device_scope(GATHER):
            if row_blocks(cfg.dim) > 1:
                return super().dists_to_ids_batched(state, cfg, queries, ids)
            return ops.gather_distances_batched(
                ids, _in_lanes(cfg, queries), state.vectors,
                norms=state.norms, metric=cfg.metric,
                interpret=self.interpret,
            )

    def beam_superstep(self, state, cfg, queries, carry, *, h, l,
                       max_visits):
        from . import bitset
        from .types import navigable, recorded
        from ..kernels import ops

        self._refuse_on_mosaic(
            "the fused multi-hop kernel (kernels/beam_hop.py: vector "
            "gathers, in-kernel sort, an (1, n_cap) VMEM norms block)",
            "hop_fused",
        )
        if row_blocks(cfg.dim) > 1:
            # one table row a vector, as the gather kernel
            return super().beam_superstep(state, cfg, queries, carry, h=h,
                                          l=l, max_visits=max_visits)
        # cheap O(n_cap) elementwise packs of the loop-invariant masks;
        # dwarfed by the O(B * R * D) distance math of the h hops
        nav_words = bitset.pack_bits(navigable(state))
        ret_words = bitset.pack_bits(recorded(state, cfg))
        out = ops.beam_hop(
            _in_lanes(cfg, queries), carry.beam_ids, carry.beam_dists,
            carry.beam_exp.astype(jnp.int32), carry.seen, carry.vis_ids,
            carry.vis_dists, carry.n_vis, carry.n_comps, carry.n_hops,
            state.adj, state.vectors, state.norms, nav_words, ret_words,
            metric=cfg.metric, h=h, interpret=self.interpret,
        )
        bi, bd, be, seen, vi, vd, n_vis, n_comps, n_hops = out
        return type(carry)(bi, bd, be != 0, seen, vi, vd, n_vis, n_comps,
                           n_hops)

    def dists_to_ids_batched_q(self, state, cfg, queries, ids):
        from ..kernels import ops

        self._refuse_on_mosaic(
            "the int8 gather kernel (kernels/quant_gather.py: single-row "
            "int8 DMAs are not aligned to the (32, 128) int8 tiling)",
            "quantized",
        )
        return ops.gather_distances_batched_q(
            ids, queries, state.quant.codes, state.quant.scale,
            state.quant.qnorms, metric=cfg.metric, interpret=self.interpret,
        )

    def beam_superstep_q(self, state, cfg, queries, carry, *, h, l,
                         max_visits):
        from . import bitset
        from .types import navigable, recorded
        from ..kernels import ops

        self._refuse_on_mosaic(
            "the fused int8 multi-hop kernel (kernels/beam_hop.py)",
            "hop_fused",
        )
        nav_words = bitset.pack_bits(navigable(state))
        ret_words = bitset.pack_bits(recorded(state, cfg))
        out = ops.beam_hop_q(
            queries, carry.beam_ids, carry.beam_dists,
            carry.beam_exp.astype(jnp.int32), carry.seen, carry.vis_ids,
            carry.vis_dists, carry.n_vis, carry.n_comps, carry.n_hops,
            state.adj, state.quant.codes, state.quant.scale,
            state.quant.qnorms, nav_words, ret_words,
            metric=cfg.metric, h=h, interpret=self.interpret,
        )
        bi, bd, be, seen, vi, vd, n_vis, n_comps, n_hops = out
        return type(carry)(bi, bd, be != 0, seen, vi, vd, n_vis, n_comps,
                           n_hops)

    def brute_force_topk(self, state, cfg, queries, *, k):
        from ..kernels import ops

        if row_blocks(cfg.dim) > 1:
            # the scorer streams (tile, D) blocks of a one-row-per-vector
            # table; wider vectors take the XLA scan
            return super().brute_force_topk(state, cfg, queries, k=k)
        return self._biased_topk(state, lambda bias: ops.topk_search(
            _in_lanes(cfg, queries), state.vectors, state.norms, k=k,
            metric=cfg.metric, bias=bias, interpret=self.interpret,
        ))


# ---------------------------------------------------------------------------
# ref — the kernel oracles (parity testing)
# ---------------------------------------------------------------------------


@register_backend("ref")
class RefBackend(JnpBackend):
    """Mirrors ``kernels/ref.py`` so backend-parity tests exercise the same
    oracle the per-kernel tests trust."""

    def dists_to_ids(self, state, cfg, q, ids):
        from ..kernels import ref

        return ref.gather_distance_ref(
            ids, q, _plain_table(state, cfg), metric=cfg.metric
        )

    # dists_to_ids_batched: the inherited vmap default IS the batched ref
    # oracle (kernels/ref.gather_distance_batched_ref is the same vmap)

    def dists_to_ids_batched_q(self, state, cfg, queries, ids):
        from ..kernels import ref

        return ref.quant_gather_distance_batched_ref(
            ids, queries, state.quant.codes, state.quant.scale,
            state.quant.qnorms, metric=cfg.metric,
        )

    def brute_force_topk(self, state, cfg, queries, *, k):
        from ..kernels import ref

        return self._biased_topk(state, lambda bias: ref.topk_score_ref(
            queries, _plain_table(state, cfg), state.norms, bias, k=k,
            metric=cfg.metric,
        ))


def _plain_table(state: GraphState, cfg: ANNConfig):
    """The whole table as ``(n_cap, dim)``, for the oracles (a copy of it:
    never on the engines' paths)."""
    return read_vectors(state.vectors, jnp.arange(cfg.n_cap), cfg.dim)


__all__ = [
    "BIG",
    "DistanceBackend",
    "available_backends",
    "get_backend",
    "register_backend",
    "resolve_backend",
]
