"""Pallas TPU kernel: fused gather + distance for the beam-search hot loop.

The greedy search expands a vertex and must compute d(q, x_u) for its <= R
out-neighbours — a random gather of R rows from the HBM-resident vector table
followed by a tiny matvec.  On CPU (the paper's target) this is pointer
chasing; on TPU we express it as:

  * neighbour ids are scalar-prefetched into SMEM (they drive address
    generation, so they must be available before the DMA program runs);
  * the vector table stays in HBM (``MemorySpace.ANY``) — it is far too large
    for VMEM (the whole point of DiskANN-style indices);
  * each grid step issues TILE_K row DMAs HBM->VMEM into a (TILE_K, D)
    scratch tile, then one MXU matvec ``X @ q`` plus a VPU row-square for the
    L2 norm term:      d = ||q||^2 + ||x||^2 - 2 <x, q>
    so the distance math rides the matmul unit, not elementwise subtract.

The DMA rule.  The table is ``f32[N, W]`` with one row per vector, and the
query is ``W`` wide: the index's table of 128-lane rows at ``dim <= 128``
(``core/types.py``; lanes past ``dim`` are zero, and the backend pads the
query with zeros, which add exactly 0 to either metric).  Each id is one
``(1, W)`` DMA, and only ids ``>= 0`` are fetched: the hop loop marks every
neighbour it does not compare (seen, not navigable, or in a finished lane)
``INVALID``, so most of a hop's tile needs no row.  ``_fetch_rows`` starts
a tile's copies back to back, all before the first wait, then waits once
per started copy on the one DMA semaphore with a descriptor of the same
``(1, W)`` size, so each wait takes one row's bytes and the tile pays about
one HBM round trip, not one a row.  The scratch rows of skipped ids keep
stale data (an earlier tile's rows, or whatever VMEM held); every output
row depends on its own scratch row only, and the wrappers mask the
positions of ``INVALID`` ids to ``+inf``, so nothing stale, NaN included,
reaches a result.  The rows read are then exactly the comparisons the hop
loop counts in ``n_comps``, so a roofline built on ``n_comps`` is this
kernel's achieved share of HBM bandwidth.

On TPU, where XLA tiles an f32 table (8, 128), W must be a multiple of 128
lanes (Mosaic refuses a 100-lane row), and a vector of several rows has no
single-row DMA: Mosaic refuses a one-row slice of a ``f32[N, 768]`` table
(not aligned to the tiling of 8).  Wider vectors take XLA's row gather in
``core/backend.py`` instead: a version of this kernel with one
``(nb, 128)`` DMA a vector, started and waited in turn, ran three times
slower than XLA's gather on a v5e at 768-d.  Interpret-mode tests accept
any W.

VMEM budget: TILE_K * W * 4B  (64 x 128 x 4 = 32 KiB) plus the (1, W) query —
far below the ~16 MiB/core VMEM of v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ANY = pltpu.MemorySpace.ANY


def _fetch_rows(ids_ref, base, tile_k: int, vec_ref, x_scratch, sem):
    """DMA the table rows of ``ids_ref[base : base + tile_k]`` that are
    ``>= 0`` into the same rows of ``x_scratch``: every copy started before
    any is waited on, then one wait per started copy.  Rows of ``INVALID``
    ids are neither fetched nor cleared (the wrappers mask them)."""

    def row_copy(idx, j):
        return pltpu.make_async_copy(
            vec_ref.at[pl.ds(idx, 1), :], x_scratch.at[pl.ds(j, 1), :], sem
        )

    def start(j, n_started):
        idx = ids_ref[base + j]

        @pl.when(idx >= 0)
        def _():
            row_copy(idx, j).start()

        return n_started + (idx >= 0).astype(jnp.int32)

    n_started = lax.fori_loop(0, tile_k, start, jnp.int32(0))

    def wait(_, carry):
        # any descriptor of one row's size: each wait takes one row's bytes
        row_copy(0, 0).wait()
        return carry

    lax.fori_loop(0, n_started, wait, 0)


def _kernel(metric: str, has_norms: bool, tile_k: int, d: int,
            ids_ref, q_ref, n_ref, vec_ref, out_ref, x_scratch, sem):
    i = pl.program_id(0)
    _fetch_rows(ids_ref, i * tile_k, tile_k, vec_ref, x_scratch, sem)
    x = x_scratch[...]                                    # (TILE_K, D)
    q = q_ref[0, :]                                       # (D,)
    prod = jnp.dot(x, q, preferred_element_type=jnp.float32)
    if metric == "l2":
        q2 = jnp.sum(q * q)
        # per-slot norms come precomputed from GraphState when available
        # (one fewer VPU reduction per tile); recomputed in-kernel otherwise
        x2 = n_ref[...] if has_norms else jnp.sum(x * x, axis=1)
        out_ref[...] = q2 + x2 - 2.0 * prod
    else:
        out_ref[...] = -prod


@functools.partial(
    jax.jit, static_argnames=("metric", "tile_k", "interpret")
)
def gather_distance(
    ids: jax.Array,       # i32[K]  (INVALID = -1 entries allowed)
    query: jax.Array,     # f32[D]
    vectors: jax.Array,   # f32[N, D]  (HBM resident)
    norms=None,           # optional f32[N] cached squared row norms (l2)
    *,
    metric: str = "l2",
    tile_k: int = 64,
    interpret: bool = True,
) -> jax.Array:           # f32[K]  (+inf where ids < 0)
    k = ids.shape[0]
    n, d = vectors.shape
    tile_k = min(tile_k, max(k, 1))
    pad = (-k) % tile_k
    ids_p = jnp.pad(ids, (0, pad), constant_values=-1)
    has_norms = norms is not None and metric == "l2"
    # the per-id norm gather is a [K] scalar gather (cheap; the kernel only
    # avoids the *row* gather) — done here so the kernel reads a VMEM tile
    row_norms = (
        jnp.where(ids_p >= 0, norms[jnp.clip(ids_p, 0, n - 1)], 0.0)
        if has_norms
        else jnp.zeros((k + pad,), jnp.float32)
    ).astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((k + pad) // tile_k,),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, ids: (0, 0)),
            pl.BlockSpec((tile_k,), lambda i, ids: (i,)),
            pl.BlockSpec(memory_space=_ANY),
        ],
        out_specs=pl.BlockSpec((tile_k,), lambda i, ids: (i,)),
        scratch_shapes=[
            pltpu.VMEM((tile_k, d), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, metric, has_norms, tile_k, d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k + pad,), jnp.float32),
        interpret=interpret,
    )(ids_p, query[None].astype(jnp.float32), row_norms, vectors)
    out = out[:k]
    return jnp.where(ids >= 0, out, jnp.inf)


def _kernel_batched(metric: str, has_norms: bool, tile_k: int, kp: int,
                    d: int, ids_ref, q_ref, n_ref, vec_ref, out_ref,
                    x_scratch, sem):
    b = pl.program_id(0)
    i = pl.program_id(1)
    _fetch_rows(ids_ref, b * kp + i * tile_k, tile_k, vec_ref, x_scratch,
                sem)
    x = x_scratch[...]                                    # (TILE_K, D)
    q = q_ref[0, :]                                       # (D,)
    prod = jnp.dot(x, q, preferred_element_type=jnp.float32)
    if metric == "l2":
        q2 = jnp.sum(q * q)
        x2 = n_ref[0, :] if has_norms else jnp.sum(x * x, axis=1)
        out_ref[0, :] = q2 + x2 - 2.0 * prod
    else:
        out_ref[0, :] = -prod


@functools.partial(
    jax.jit, static_argnames=("metric", "tile_k", "interpret")
)
def gather_distance_batched(
    ids: jax.Array,       # i32[B, K]  (INVALID = -1 entries allowed)
    queries: jax.Array,   # f32[B, D]
    vectors: jax.Array,   # f32[N, D]  (HBM resident)
    norms=None,           # optional f32[N] cached squared row norms (l2)
    *,
    metric: str = "l2",
    tile_k: int = 64,
    interpret: bool = True,
) -> jax.Array:           # f32[B, K]  (+inf where ids < 0)
    """The 2-D-grid form of ``gather_distance`` for the batched beam engine:
    grid axis 0 walks the query batch, axis 1 the id tiles, so one kernel
    launch covers the whole (B, K) frontier-neighbourhood tile per hop
    instead of B vmapped launches.  Lane and tile axes are LEADING dims of
    the (B, 1, D) query and (B, K/TILE_K, 1, TILE_K) norm/output operands,
    so every block's last two dims equal the array's — the Mosaic block
    rule — at any B.  Per-(lane, tile) math is identical to the 1-D
    kernel, so per-lane results match it bitwise."""
    bsz, k = ids.shape
    n, d = vectors.shape
    tile_k = min(tile_k, max(k, 1))
    pad = (-k) % tile_k
    ids_p = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
    kp = k + pad
    n_tiles = kp // tile_k
    has_norms = norms is not None and metric == "l2"
    row_norms = (
        jnp.where(ids_p >= 0, norms[jnp.clip(ids_p, 0, n - 1)], 0.0)
        if has_norms
        else jnp.zeros((bsz, kp), jnp.float32)
    ).astype(jnp.float32)
    tiled = (bsz, n_tiles, 1, tile_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, n_tiles),
        in_specs=[
            pl.BlockSpec((None, 1, d), lambda b, i, ids: (b, 0, 0)),
            pl.BlockSpec((None, None, 1, tile_k),
                         lambda b, i, ids: (b, i, 0, 0)),
            pl.BlockSpec(memory_space=_ANY),
        ],
        out_specs=pl.BlockSpec((None, None, 1, tile_k),
                               lambda b, i, ids: (b, i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((tile_k, d), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_batched, metric, has_norms, tile_k, kp, d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(tiled, jnp.float32),
        interpret=interpret,
    )(ids_p.reshape(-1), queries.astype(jnp.float32)[:, None, :],
      row_norms.reshape(tiled), vectors)
    out = out.reshape(bsz, kp)[:, :k]
    return jnp.where(ids >= 0, out, jnp.inf)
