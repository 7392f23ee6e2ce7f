"""Pure-jnp oracles for the Pallas kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gather_distance_ref(ids, query, vectors, *, metric: str = "l2"):
    """f32[K] distances from query to vectors[ids]; +inf where ids < 0.
    ``vectors`` is a plain (N, D) table (the kernels' oracle: the kernels
    take a table of rows, ``kernels/gather_distance.py``)."""
    safe = jnp.clip(ids, 0, vectors.shape[0] - 1)
    rows = vectors[safe]
    prod = rows @ query
    if metric == "l2":
        d = jnp.dot(query, query) + jnp.sum(rows * rows, axis=1) - 2.0 * prod
    else:
        d = -prod
    return jnp.where(ids >= 0, d, jnp.inf)


def gather_distance_batched_ref(ids, queries, vectors, *, metric: str = "l2"):
    """f32[B, K] distances from queries[b] to vectors[ids[b]]; +inf where
    ids < 0.  vmap of the per-query oracle so per-lane math is identical."""
    return jax.vmap(
        lambda q, row: gather_distance_ref(row, q, vectors, metric=metric)
    )(queries, ids)


def quant_gather_distance_batched_ref(ids, queries, codes, scales, qnorms,
                                      *, metric: str = "l2"):
    """f32[B, K] quantized-tier distances (the ``quant_gather`` oracle):
    raw int8 dot accumulated in f32, per-row scale applied to the product,
    cached dequantized-row qnorms as the l2 norm term — the op-order
    contract of ``core/quant.py::quant_dists_to_ids_batched``."""
    n = codes.shape[0]

    def one(q, row):
        safe = jnp.clip(row, 0, n - 1)
        raw = codes[safe].astype(jnp.float32) @ q
        prod = raw * scales[safe]
        if metric == "l2":
            d = jnp.dot(q, q) + qnorms[safe] - 2.0 * prod
        else:
            d = -prod
        return jnp.where(row >= 0, d, jnp.inf)

    return jax.vmap(one)(queries.astype(jnp.float32), ids)


def topk_score_ref(queries, vectors, norms, bias=None, *, k: int,
                   metric: str = "l2"):
    """(dists f32[B, k], ids i32[B, k]) ascending by distance.  ``bias``:
    optional f32[N] additive row bias (+inf excludes the row)."""
    prod = queries @ vectors.T                       # (B, N)
    if metric == "l2":
        q2 = jnp.sum(queries * queries, axis=1)
        d = q2[:, None] + norms[None, :] - 2.0 * prod
    else:
        d = -prod
    if bias is not None:
        d = d + bias[None, :]
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx.astype(jnp.int32)
