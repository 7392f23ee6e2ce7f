"""Pallas TPU kernel: fused gather + distance over the int8 code table.

The quantized twin of ``gather_distance_batched`` (see that module for the
DMA/grid anatomy): the beam loop's per-hop primitive when the quantized
memory tier is active (``ANNConfig.quantized``).  Differences from the f32
kernel, and nothing else:

  * the HBM-resident table is the ``QuantStore.codes`` int8 matrix — each
    row DMA carries D bytes instead of 4D, which is the whole point: the
    hop loop is bandwidth-bound on exactly these gathers;
  * rows dequantize in-register: the dot product accumulates the raw int8
    codes in f32 on the MXU, THEN the per-row scale multiplies the product
    (``prod = (codes . q) * scale``) — one fused multiply per output
    element instead of D per row, and the exact op order of
    ``core/quant.py::quant_dists_to_ids_batched``, so the engines agree
    bitwise in interpret mode;
  * the l2 norm term is the cached ``QuantStore.qnorms`` (squared norms of
    the *dequantized* rows), gathered outside the kernel like the f32
    path's ``GraphState.norms``.

VMEM budget: TILE_K * D bytes of int8 scratch (64 x 128 = 8 KiB) — a
quarter of the f32 kernel's tile.  Interpret mode only: Mosaic refuses the
single-row int8 DMA (int8 tiles are (32, 128), so a one-row slice is not
aligned to the tiling), and the pallas engine refuses ``quantized`` under
Mosaic (``core/backend.py``).  Viewing the code table as 32-bit words is
one route to an aligned gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ANY = pltpu.MemorySpace.ANY


def _kernel_batched_q(metric: str, tile_k: int, kp: int, d: int,
                      ids_ref, q_ref, s_ref, n_ref, codes_ref, out_ref,
                      x_scratch, sem):
    b = pl.program_id(0)
    i = pl.program_id(1)

    def load_row(j, _):
        idx = jnp.maximum(ids_ref[b * kp + i * tile_k + j], 0)
        cp = pltpu.make_async_copy(
            codes_ref.at[pl.ds(idx, 1), :], x_scratch.at[pl.ds(j, 1), :], sem
        )
        cp.start()
        cp.wait()
        return 0

    lax.fori_loop(0, tile_k, load_row, 0)
    x = x_scratch[...].astype(jnp.float32)                # (TILE_K, D)
    q = q_ref[0, :]                                       # (D,)
    raw = jnp.dot(x, q, preferred_element_type=jnp.float32)
    prod = raw * s_ref[0, :]                              # dequantize the dot
    if metric == "l2":
        q2 = jnp.sum(q * q)
        out_ref[0, :] = q2 + n_ref[0, :] - 2.0 * prod
    else:
        out_ref[0, :] = -prod


@functools.partial(
    jax.jit, static_argnames=("metric", "tile_k", "interpret")
)
def gather_distance_batched_q(
    ids: jax.Array,       # i32[B, K]  (INVALID = -1 entries allowed)
    queries: jax.Array,   # f32[B, D]
    codes: jax.Array,     # i8[N, D]   (HBM resident)
    scales: jax.Array,    # f32[N]     per-row dequantization scales
    qnorms: jax.Array,    # f32[N]     cached squared dequantized-row norms
    *,
    metric: str = "l2",
    tile_k: int = 64,
    interpret: bool = True,
) -> jax.Array:           # f32[B, K]  (+inf where ids < 0)
    bsz, k = ids.shape
    n, d = codes.shape
    tile_k = min(tile_k, max(k, 1))
    pad = (-k) % tile_k
    ids_p = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
    kp = k + pad
    # per-id scale/norm gathers are [B, K] scalar gathers (cheap; the kernel
    # only avoids the *row* gathers) — done here so the kernel reads VMEM tiles
    safe = jnp.clip(ids_p, 0, n - 1)
    row_scales = jnp.where(ids_p >= 0, scales[safe], 0.0).astype(jnp.float32)
    row_qnorms = (
        jnp.where(ids_p >= 0, qnorms[safe], 0.0).astype(jnp.float32)
        if metric == "l2"
        else jnp.zeros((bsz, kp), jnp.float32)
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, kp // tile_k),
        in_specs=[
            pl.BlockSpec((1, d), lambda b, i, ids: (b, 0)),
            pl.BlockSpec((1, tile_k), lambda b, i, ids: (b, i)),
            pl.BlockSpec((1, tile_k), lambda b, i, ids: (b, i)),
            pl.BlockSpec(memory_space=_ANY),
        ],
        out_specs=pl.BlockSpec((1, tile_k), lambda b, i, ids: (b, i)),
        scratch_shapes=[
            pltpu.VMEM((tile_k, d), jnp.int8),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_batched_q, metric, tile_k, kp, d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, kp), jnp.float32),
        interpret=interpret,
    )(ids_p.reshape(-1), queries.astype(jnp.float32), row_scales,
      row_qnorms, codes)
    out = out[:, :k]
    return jnp.where(ids >= 0, out, jnp.inf)
