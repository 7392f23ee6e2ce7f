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

VMEM budget: TILE_K * D * 4B  (64 x 128 x 4 = 32 KiB) plus the (1, D) query —
far below the ~16 MiB/core VMEM of v5e.  D should be padded to a multiple of
128 lanes for production tables (interpret-mode tests accept any D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ANY = pltpu.MemorySpace.ANY


def _kernel(metric: str, has_norms: bool, tile_k: int, d: int,
            ids_ref, q_ref, n_ref, vec_ref, out_ref, x_scratch, sem):
    i = pl.program_id(0)

    def load_row(j, _):
        idx = jnp.maximum(ids_ref[i * tile_k + j], 0)
        cp = pltpu.make_async_copy(
            vec_ref.at[pl.ds(idx, 1), :], x_scratch.at[pl.ds(j, 1), :], sem
        )
        cp.start()
        cp.wait()
        return 0

    lax.fori_loop(0, tile_k, load_row, 0)
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

    def load_row(j, _):
        idx = jnp.maximum(ids_ref[b * kp + i * tile_k + j], 0)
        cp = pltpu.make_async_copy(
            vec_ref.at[pl.ds(idx, 1), :], x_scratch.at[pl.ds(j, 1), :], sem
        )
        cp.start()
        cp.wait()
        return 0

    lax.fori_loop(0, tile_k, load_row, 0)
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
