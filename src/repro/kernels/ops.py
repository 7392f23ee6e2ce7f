"""Public jit'd wrappers around the Pallas kernels.

``interpret`` defaults to True off-TPU (CPU CI executes the kernel bodies in
Python); on a TPU backend the Mosaic path compiles.  The engine integration
point is ``make_kernel_distance_fn`` which plugs into
``repro.core.search.greedy_search(distance_fn=...)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .beam_hop import beam_hop_fused, beam_hop_fused_q
from .gather_distance import gather_distance, gather_distance_batched
from .quant_gather import gather_distance_batched_q
from .topk_score import topk_score
from . import ref


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def gather_distances(ids, query, vectors, norms=None, *, metric="l2",
                     interpret=None):
    """Fused gather+distance.  ``norms``: optional cached squared row norms
    (``GraphState.norms``) so the l2 path skips the in-kernel reduction."""
    if interpret is None:
        interpret = _default_interpret()
    return gather_distance(
        ids, query, vectors, norms, metric=metric, interpret=interpret
    )


def gather_distances_batched(ids, queries, vectors, norms=None, *,
                             metric="l2", interpret=None):
    """Fused gather+distance over a (B, K) id tile — one 2-D-grid kernel
    launch per beam hop (the batched engine's ``dists_to_ids_batched``)."""
    if interpret is None:
        interpret = _default_interpret()
    return gather_distance_batched(
        ids, queries, vectors, norms, metric=metric, interpret=interpret
    )


def gather_distances_batched_q(ids, queries, codes, scales, qnorms, *,
                               metric="l2", interpret=None):
    """Quantized-tier gather+distance over a (B, K) id tile: int8 rows
    gathered from the code table, dequantized in-register (the batched
    engine's ``dists_to_ids_batched_q`` on the pallas backend)."""
    if interpret is None:
        interpret = _default_interpret()
    return gather_distance_batched_q(
        ids, queries, codes, scales, qnorms, metric=metric,
        interpret=interpret,
    )


def beam_hop(queries, beam_ids, beam_dists, beam_exp, seen, vis_ids,
             vis_dists, n_vis, n_comps, n_hops, adj, vectors, norms,
             nav_words, ret_words, *, metric="l2", h=4, interpret=None):
    """Fused multi-hop beam super-step: advance every lane's traversal by
    (up to) ``h`` masked hops in one kernel launch, beam + bitpacked seen
    resident in VMEM throughout (the pallas engine's ``beam_superstep``).
    Returns the updated ``(beam_ids, beam_dists, beam_exp, seen, vis_ids,
    vis_dists, n_vis, n_comps, n_hops)`` carry."""
    if interpret is None:
        interpret = _default_interpret()
    return beam_hop_fused(
        queries, beam_ids, beam_dists, beam_exp, seen, vis_ids, vis_dists,
        n_vis, n_comps, n_hops, adj, vectors, norms, nav_words, ret_words,
        metric=metric, h=h, interpret=interpret,
    )


def beam_hop_q(queries, beam_ids, beam_dists, beam_exp, seen, vis_ids,
               vis_dists, n_vis, n_comps, n_hops, adj, codes, scales,
               qnorms, nav_words, ret_words, *, metric="l2", h=4,
               interpret=None):
    """Fused multi-hop beam super-step over the quantized memory tier:
    neighbour rows gather from the int8 code table and dequantize
    in-register (the pallas engine's ``beam_superstep_q``)."""
    if interpret is None:
        interpret = _default_interpret()
    return beam_hop_fused_q(
        queries, beam_ids, beam_dists, beam_exp, seen, vis_ids, vis_dists,
        n_vis, n_comps, n_hops, adj, codes, scales, qnorms, nav_words,
        ret_words, metric=metric, h=h, interpret=interpret,
    )


def topk_search(queries, vectors, norms=None, *, k, metric="l2", bias=None,
                tile_n=1024, interpret=None):
    """Exact top-k scoring.  Pads the candidate table to the tile size with
    +inf-distance rows when needed (production tables should be pre-aligned
    so the pad copy never happens on the hot path).  ``bias``: optional
    f32[N] additive row bias; +inf excludes a row (dead-slot masking)."""
    if interpret is None:
        interpret = _default_interpret()
    n, d = vectors.shape
    if norms is None:
        norms = jnp.sum(vectors * vectors, axis=1)
    if bias is None:
        bias = jnp.zeros((n,), jnp.float32)
    tile_n = min(tile_n, max(n, 1))
    pad = (-n) % tile_n
    if pad:
        vectors = jnp.concatenate(
            [vectors, jnp.zeros((pad, d), vectors.dtype)], axis=0
        )
        norms = jnp.concatenate(
            [norms, jnp.full((pad,), jnp.inf, norms.dtype)], axis=0
        )
        bias = jnp.concatenate(
            [bias, jnp.full((pad,), jnp.inf, jnp.float32)], axis=0
        )
    dists, ids = topk_score(
        queries, vectors, norms, bias, k=k, metric=metric, tile_n=tile_n,
        interpret=interpret,
    )
    # biased/padded rows score +inf; mask anything out of range or non-finite
    valid = (ids < n) & jnp.isfinite(dists)
    return (
        jnp.where(valid, dists, jnp.inf),
        jnp.where(valid, ids, -1),
    )


def make_kernel_distance_fn(*, interpret=None):
    """A drop-in ``distance_fn`` for ``repro.core.search.greedy_search``.

    Legacy injection point — prefer ``ANNConfig(backend="pallas")``, which
    routes every hot path (not just search) through the kernels.
    """

    def distance_fn(state, cfg, q, ids):
        # the kernel takes one table row a vector (gather_distance.py): the
        # query is zero-padded to the row's lanes
        q = jnp.pad(q, (0, state.vectors.shape[1] - q.shape[0]))
        return gather_distances(
            ids, q, state.vectors, state.norms, metric=cfg.metric,
            interpret=interpret,
        )

    return distance_fn


__all__ = [
    "beam_hop",
    "beam_hop_q",
    "gather_distances",
    "gather_distances_batched",
    "gather_distances_batched_q",
    "topk_search",
    "make_kernel_distance_fn",
    "ref",
]
