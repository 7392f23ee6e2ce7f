"""Pallas TPU kernel: fused multi-hop beam-search super-step.

The batched beam engine (``core/search_batched.py``) advances B greedy
searches one hop per ``while_loop`` iteration; with the gather-distance
kernel each hop is its own launch, so the (B, l) beam round-trips
HBM <-> VMEM between every expansion.  This kernel fuses H hops into ONE
invocation: grid axis 0 walks the lanes, and each lane's program keeps its
entire traversal state — beam ids/dists/expanded bits, the bitpacked seen
bitmap (``core/bitset.py`` layout), the visited list and the counters — in
VMEM/registers across all H pops, re-reading HBM only for what a hop truly
needs: the popped vertex's adjacency row and its <= R neighbour vectors
(DMA'd in ``gather_distance``-shaped tiles).  That is the in-memory
analogue of DiskANN beam pipelining: traversal becomes bandwidth-bound on
the neighbour gathers instead of launch/carry-bound.

Per-lane early exit: the hop body is masked by the lane's ``active``
predicate exactly like the engine's shared hop body — a finished lane's
pops, counter bumps, seen updates and visited writes all become no-ops and
its sort-merge re-sorts an unchanged beam — and ``pl.when(active)`` skips
the adjacency/vector DMAs entirely, so a lane that converges after hop
t < H spends no memory bandwidth on its remaining hops.  This masking is
what makes the kernel's H-hop step bit-identical to running the engine's
hop body H times (``tests/test_beam_fused.py`` pins it lane by lane).

Math mirrors ``gather_distance_batched`` bit for bit: neighbour ids are
padded to TILE_K tiles, each tile is DMA-gathered to a (TILE_K, D) scratch
and reduced with one ``jnp.dot(x, q)`` MXU matvec; the l2 path adds the
cached row norms (gathered in-kernel from the VMEM-resident norms row);
invalid ids gather row 0 and mask to +inf afterwards.

Interpret mode only: Mosaic (the TPU kernel compiler) refuses this kernel.
Compiled for a v5e it fails on the (1, .) blocks of its per-lane operands,
then on the value-level gathers of ``_lane_hop`` (the bitmap word gather
in ``_getbit``, ``bi[i]`` / ``.at[i].set`` after the argmin, the norms and
scales gathers), and behind those sit the in-kernel ``lax.sort`` over the
(l + R,) candidate row and the (1, n_cap) norms block held in VMEM (4 MiB
at n_cap = 2^20 before sublane padding, double-buffered).  The pallas
engine therefore refuses ``hop_fused > 0`` under Mosaic
(``core/backend.py``); a Mosaic version needs a sort network, no vector
gathers and a VMEM budget at real n_cap.  ``beam_hop_ref`` below is the
self-contained pure-jnp oracle (same per-lane math, plain gathers instead
of DMA) that the kernel parity tests run against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ANY = pltpu.MemorySpace.ANY

BIG = jnp.inf
INVALID = -1


def _getbit(words, ids):
    """Bit test against a packed u32 little-endian bitmap (scalar or vector
    ``ids``; must be pre-clipped) — the ``core/bitset.py`` layout."""
    w = words[ids >> 5]
    return ((w >> (ids & 31).astype(jnp.uint32)) & jnp.uint32(1)) != 0


def _setbits(seen, ids, mask):
    """OR masked-in id bits into one packed (W,) row, sequentially: OR is
    idempotent, so duplicate ids need no dedup here (unlike the engine's
    scatter-add formulation)."""

    def step(j, s):
        bit = jnp.where(
            mask[j],
            jnp.uint32(1) << (ids[j] & 31).astype(jnp.uint32),
            jnp.uint32(0),
        )
        w = ids[j] >> 5
        return s.at[w].set(s[w] | bit)

    return lax.fori_loop(0, ids.shape[0], step, seen)


def _lane_hop(metric, l, r, mv, n_cap, tile_k, fetch_adj, fetch_tile,
              norms, nav_words, ret_words, q, c, *, scales=None):
    """ONE masked hop of one lane — the per-lane transcription of the
    engine's shared hop body (``core/search_batched.make_hop_body``), with
    the adjacency/vector reads abstracted behind ``fetch_adj(sv, active)``
    / ``fetch_tile(t, tile_ids, active)`` so the kernel (DMA) and the ref
    oracle (plain gather) share every other op.  An inactive lane is an
    exact no-op.

    ``scales`` activates the quantized memory tier: ``fetch_tile`` then
    returns raw int8 codes cast to f32 and the per-row scale multiplies the
    dot *product* — the exact op order of
    ``core/quant.py::quant_dists_to_ids_batched`` (``norms`` must be the
    cached dequantized-row ``qnorms``)."""
    bi, bd, be, seen, vi, vd, n_vis, n_comps, n_hops = c
    active = (
        jnp.any((bi >= 0) & (be == 0) & jnp.isfinite(bd))
        & (n_hops < mv)
    )

    # --- pop the closest unexpanded vertex -----------------------------------
    frontier_d = jnp.where((bi >= 0) & (be == 0), bd, BIG)
    i = jnp.argmin(frontier_d)
    v = bi[i]
    dv = bd[i]
    be = be.at[i].set(be[i] | active.astype(jnp.int32))
    sv = jnp.clip(v, 0, n_cap - 1)

    # --- visited list (returnable pops only) ---------------------------------
    write = active & _getbit(ret_words, sv)
    slot = jnp.where(write, n_vis, mv)  # mv => dropped write
    vi = vi.at[slot].set(v, mode="drop")
    vd = vd.at[slot].set(dv, mode="drop")
    n_vis = n_vis + write.astype(jnp.int32)

    # --- expand --------------------------------------------------------------
    nbrs = fetch_adj(sv, active)                              # (r,) i32
    safe = jnp.clip(nbrs, 0, n_cap - 1)
    fresh = (
        (nbrs >= 0)
        & _getbit(nav_words, safe)
        & ~_getbit(seen, safe)
        & active
    )
    masked = jnp.where(fresh, nbrs, INVALID)

    # distances, in gather_distance_batched's exact tile decomposition
    n_tiles = -(-r // tile_k)
    kp = n_tiles * tile_k
    ids_p = (
        jnp.concatenate([masked, jnp.full((kp - r,), INVALID, jnp.int32)])
        if kp > r
        else masked
    )
    if metric == "l2":
        q2 = jnp.sum(q * q)
    tiles = []
    for t in range(n_tiles):
        tile_ids = ids_p[t * tile_k:(t + 1) * tile_k]
        x = fetch_tile(t, tile_ids, active)                   # (tile_k, d)
        prod = jnp.dot(x, q, preferred_element_type=jnp.float32)
        if scales is not None:
            s_t = jnp.where(
                tile_ids >= 0,
                scales[jnp.clip(tile_ids, 0, n_cap - 1)],
                0.0,
            ).astype(jnp.float32)
            prod = prod * s_t
        if metric == "l2":
            x2 = jnp.where(
                tile_ids >= 0,
                norms[jnp.clip(tile_ids, 0, n_cap - 1)],
                0.0,
            ).astype(jnp.float32)
            tiles.append(q2 + x2 - 2.0 * prod)
        else:
            tiles.append(-prod)
    nd = jnp.concatenate(tiles)[:r]
    nd = jnp.where(masked >= 0, nd, BIG)
    n_comps = n_comps + jnp.sum(fresh).astype(jnp.int32)
    seen = _setbits(seen, safe, fresh)

    # --- sort-merge, keep top-l ----------------------------------------------
    # packed (id << 1 | expanded) payload, exactly as the engine's merge
    all_d = jnp.concatenate([bd, nd])
    all_p = jnp.concatenate([(bi << 1) | be, masked << 1])
    sd, sp = lax.sort((all_d, all_p), num_keys=1)
    return (
        sp[:l] >> 1,
        sd[:l],
        sp[:l] & 1,
        seen,
        vi,
        vd,
        n_vis,
        n_comps,
        n_hops + active.astype(jnp.int32),
    )


def _kernel(metric, h, l, r, mv, n_cap, w, tile_k, d,
            q_ref, bi_ref, bd_ref, be_ref, seen_ref, vi_ref, vd_ref, c_ref,
            nav_ref, ret_ref, n_ref, adj_ref, vec_ref,
            bi_out, bd_out, be_out, seen_out, vi_out, vd_out, c_out,
            adj_scratch, x_scratch, sem_a, sem_v):
    q = q_ref[0, :]
    norms = n_ref[0, :]
    nav_words = nav_ref[0, :]
    ret_words = ret_ref[0, :]

    def fetch_adj(sv, active):
        @pl.when(active)
        def _():
            cp = pltpu.make_async_copy(
                adj_ref.at[pl.ds(sv, 1), :], adj_scratch, sem_a
            )
            cp.start()
            cp.wait()

        # inactive lanes read stale scratch: every consumer is masked by
        # ``active`` (fresh mask / inf distances), so the values never land
        return adj_scratch[0, :]

    def fetch_tile(t, tile_ids, active):
        @pl.when(active)
        def _():
            def load_row(j, _):
                idx = jnp.maximum(tile_ids[j], 0)
                cp = pltpu.make_async_copy(
                    vec_ref.at[pl.ds(idx, 1), :],
                    x_scratch.at[pl.ds(j, 1), :],
                    sem_v,
                )
                cp.start()
                cp.wait()
                return 0

            lax.fori_loop(0, tile_k, load_row, 0)

        return x_scratch[...]

    c = (
        bi_ref[0, :], bd_ref[0, :], be_ref[0, :], seen_ref[0, :],
        vi_ref[0, :], vd_ref[0, :], c_ref[0, 0], c_ref[0, 1], c_ref[0, 2],
    )
    # Python-unrolled: H is a compile-time constant, and unrolling lets the
    # compiler fuse across hop boundaries (the point of the super-step)
    for _ in range(h):
        c = _lane_hop(metric, l, r, mv, n_cap, tile_k, fetch_adj,
                      fetch_tile, norms, nav_words, ret_words, q, c)

    bi, bd, be, seen, vi, vd, n_vis, n_comps, n_hops = c
    bi_out[0, :] = bi
    bd_out[0, :] = bd
    be_out[0, :] = be
    seen_out[0, :] = seen
    vi_out[0, :] = vi
    vd_out[0, :] = vd
    c_out[0, :] = jnp.stack([n_vis, n_comps, n_hops])


@functools.partial(
    jax.jit, static_argnames=("metric", "h", "tile_k", "interpret")
)
def beam_hop_fused(
    queries,     # f32[B, D]
    beam_ids,    # i32[B, l]
    beam_dists,  # f32[B, l]
    beam_exp,    # i32[B, l]  (0/1 expanded flags)
    seen,        # u32[B, W]  bitpacked seen (core/bitset.py layout)
    vis_ids,     # i32[B, mv]
    vis_dists,   # f32[B, mv]
    n_vis,       # i32[B]
    n_comps,     # i32[B]
    n_hops,      # i32[B]
    adj,         # i32[n_cap, R]  (HBM resident)
    vectors,     # f32[n_cap, D]  (HBM resident)
    norms,       # f32[n_cap]  cached squared row norms
    nav_words,   # u32[W]  packed navigable mask
    ret_words,   # u32[W]  packed returnable (active) mask
    *,
    metric: str = "l2",
    h: int = 4,
    tile_k: int = 64,
    interpret: bool = True,
):
    """Advance every lane's beam traversal by (up to) ``h`` masked hops in
    one kernel launch.  Returns the updated carry
    ``(beam_ids, beam_dists, beam_exp, seen, vis_ids, vis_dists, n_vis,
    n_comps, n_hops)``."""
    b, l = beam_ids.shape
    n_cap, r = adj.shape
    d = vectors.shape[1]
    w = seen.shape[1]
    mv = vis_ids.shape[1]
    tile_k = min(tile_k, max(r, 1))
    counters = jnp.stack([n_vis, n_comps, n_hops], axis=1).astype(jnp.int32)

    lane = lambda i: (i, 0)
    bcast = lambda i: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, d), lane),       # queries
            pl.BlockSpec((1, l), lane),       # beam_ids
            pl.BlockSpec((1, l), lane),       # beam_dists
            pl.BlockSpec((1, l), lane),       # beam_exp
            pl.BlockSpec((1, w), lane),       # seen
            pl.BlockSpec((1, mv), lane),      # vis_ids
            pl.BlockSpec((1, mv), lane),      # vis_dists
            pl.BlockSpec((1, 3), lane),       # counters
            pl.BlockSpec((1, w), bcast),      # nav_words
            pl.BlockSpec((1, w), bcast),      # ret_words
            pl.BlockSpec((1, n_cap), bcast),  # norms
            pl.BlockSpec(memory_space=_ANY),  # adj
            pl.BlockSpec(memory_space=_ANY),  # vectors
        ],
        out_specs=[
            pl.BlockSpec((1, l), lane),
            pl.BlockSpec((1, l), lane),
            pl.BlockSpec((1, l), lane),
            pl.BlockSpec((1, w), lane),
            pl.BlockSpec((1, mv), lane),
            pl.BlockSpec((1, mv), lane),
            pl.BlockSpec((1, 3), lane),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, r), jnp.int32),
            pltpu.VMEM((tile_k, d), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    outs = pl.pallas_call(
        functools.partial(
            _kernel, metric, h, l, r, mv, n_cap, w, tile_k, d
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, l), jnp.int32),
            jax.ShapeDtypeStruct((b, l), jnp.float32),
            jax.ShapeDtypeStruct((b, l), jnp.int32),
            jax.ShapeDtypeStruct((b, w), jnp.uint32),
            jax.ShapeDtypeStruct((b, mv), jnp.int32),
            jax.ShapeDtypeStruct((b, mv), jnp.float32),
            jax.ShapeDtypeStruct((b, 3), jnp.int32),
        ],
        interpret=interpret,
    )(
        queries.astype(jnp.float32), beam_ids, beam_dists,
        beam_exp.astype(jnp.int32), seen, vis_ids, vis_dists, counters,
        nav_words[None, :], ret_words[None, :],
        norms[None, :].astype(jnp.float32), adj, vectors,
    )
    bi, bd, be, seen_o, vi, vd, c = outs
    return bi, bd, be, seen_o, vi, vd, c[:, 0], c[:, 1], c[:, 2]


def _kernel_q(metric, h, l, r, mv, n_cap, w, tile_k, d,
              q_ref, bi_ref, bd_ref, be_ref, seen_ref, vi_ref, vd_ref, c_ref,
              nav_ref, ret_ref, n_ref, s_ref, adj_ref, codes_ref,
              bi_out, bd_out, be_out, seen_out, vi_out, vd_out, c_out,
              adj_scratch, x_scratch, sem_a, sem_v):
    """The quantized twin of ``_kernel``: the HBM table is the int8 code
    matrix (row DMAs carry D bytes, not 4D), ``n_ref`` carries the cached
    dequantized-row qnorms and ``s_ref`` the per-row scales; dequantization
    happens in-register via the ``scales`` path of ``_lane_hop``."""
    q = q_ref[0, :]
    norms = n_ref[0, :]
    scales = s_ref[0, :]
    nav_words = nav_ref[0, :]
    ret_words = ret_ref[0, :]

    def fetch_adj(sv, active):
        @pl.when(active)
        def _():
            cp = pltpu.make_async_copy(
                adj_ref.at[pl.ds(sv, 1), :], adj_scratch, sem_a
            )
            cp.start()
            cp.wait()

        return adj_scratch[0, :]

    def fetch_tile(t, tile_ids, active):
        @pl.when(active)
        def _():
            def load_row(j, _):
                idx = jnp.maximum(tile_ids[j], 0)
                cp = pltpu.make_async_copy(
                    codes_ref.at[pl.ds(idx, 1), :],
                    x_scratch.at[pl.ds(j, 1), :],
                    sem_v,
                )
                cp.start()
                cp.wait()
                return 0

            lax.fori_loop(0, tile_k, load_row, 0)

        return x_scratch[...].astype(jnp.float32)

    c = (
        bi_ref[0, :], bd_ref[0, :], be_ref[0, :], seen_ref[0, :],
        vi_ref[0, :], vd_ref[0, :], c_ref[0, 0], c_ref[0, 1], c_ref[0, 2],
    )
    for _ in range(h):
        c = _lane_hop(metric, l, r, mv, n_cap, tile_k, fetch_adj,
                      fetch_tile, norms, nav_words, ret_words, q, c,
                      scales=scales)

    bi, bd, be, seen, vi, vd, n_vis, n_comps, n_hops = c
    bi_out[0, :] = bi
    bd_out[0, :] = bd
    be_out[0, :] = be
    seen_out[0, :] = seen
    vi_out[0, :] = vi
    vd_out[0, :] = vd
    c_out[0, :] = jnp.stack([n_vis, n_comps, n_hops])


@functools.partial(
    jax.jit, static_argnames=("metric", "h", "tile_k", "interpret")
)
def beam_hop_fused_q(
    queries, beam_ids, beam_dists, beam_exp, seen, vis_ids, vis_dists,
    n_vis, n_comps, n_hops, adj,
    codes,       # i8[n_cap, D]  (HBM resident) int8 code table
    scales,      # f32[n_cap]  per-row dequantization scales
    qnorms,      # f32[n_cap]  cached squared dequantized-row norms
    nav_words, ret_words,
    *,
    metric: str = "l2",
    h: int = 4,
    tile_k: int = 64,
    interpret: bool = True,
):
    """``beam_hop_fused`` over the quantized memory tier: neighbour rows
    gather from the int8 code table (~4x less DMA traffic per hop) and
    dequantize in-register.  Same carry in, same carry out."""
    b, l = beam_ids.shape
    n_cap, r = adj.shape
    d = codes.shape[1]
    w = seen.shape[1]
    mv = vis_ids.shape[1]
    tile_k = min(tile_k, max(r, 1))
    counters = jnp.stack([n_vis, n_comps, n_hops], axis=1).astype(jnp.int32)

    lane = lambda i: (i, 0)
    bcast = lambda i: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, d), lane),       # queries
            pl.BlockSpec((1, l), lane),       # beam_ids
            pl.BlockSpec((1, l), lane),       # beam_dists
            pl.BlockSpec((1, l), lane),       # beam_exp
            pl.BlockSpec((1, w), lane),       # seen
            pl.BlockSpec((1, mv), lane),      # vis_ids
            pl.BlockSpec((1, mv), lane),      # vis_dists
            pl.BlockSpec((1, 3), lane),       # counters
            pl.BlockSpec((1, w), bcast),      # nav_words
            pl.BlockSpec((1, w), bcast),      # ret_words
            pl.BlockSpec((1, n_cap), bcast),  # qnorms
            pl.BlockSpec((1, n_cap), bcast),  # scales
            pl.BlockSpec(memory_space=_ANY),  # adj
            pl.BlockSpec(memory_space=_ANY),  # codes
        ],
        out_specs=[
            pl.BlockSpec((1, l), lane),
            pl.BlockSpec((1, l), lane),
            pl.BlockSpec((1, l), lane),
            pl.BlockSpec((1, w), lane),
            pl.BlockSpec((1, mv), lane),
            pl.BlockSpec((1, mv), lane),
            pl.BlockSpec((1, 3), lane),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, r), jnp.int32),
            pltpu.VMEM((tile_k, d), jnp.int8),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    outs = pl.pallas_call(
        functools.partial(
            _kernel_q, metric, h, l, r, mv, n_cap, w, tile_k, d
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, l), jnp.int32),
            jax.ShapeDtypeStruct((b, l), jnp.float32),
            jax.ShapeDtypeStruct((b, l), jnp.int32),
            jax.ShapeDtypeStruct((b, w), jnp.uint32),
            jax.ShapeDtypeStruct((b, mv), jnp.int32),
            jax.ShapeDtypeStruct((b, mv), jnp.float32),
            jax.ShapeDtypeStruct((b, 3), jnp.int32),
        ],
        interpret=interpret,
    )(
        queries.astype(jnp.float32), beam_ids, beam_dists,
        beam_exp.astype(jnp.int32), seen, vis_ids, vis_dists, counters,
        nav_words[None, :], ret_words[None, :],
        qnorms[None, :].astype(jnp.float32),
        scales[None, :].astype(jnp.float32), adj, codes,
    )
    bi, bd, be, seen_o, vi, vd, c = outs
    return bi, bd, be, seen_o, vi, vd, c[:, 0], c[:, 1], c[:, 2]


@functools.partial(jax.jit, static_argnames=("metric", "h", "tile_k"))
def beam_hop_ref_q(
    queries, beam_ids, beam_dists, beam_exp, seen, vis_ids, vis_dists,
    n_vis, n_comps, n_hops, adj, codes, scales, qnorms, nav_words, ret_words,
    *, metric: str = "l2", h: int = 4, tile_k: int = 64,
):
    """Pure-jnp oracle for ``beam_hop_fused_q``: shared ``_lane_hop`` with
    plain int8 gathers, scales applied to the dot product."""
    n_cap, r = adj.shape
    l = beam_ids.shape[1]
    mv = vis_ids.shape[1]
    tile_k = min(tile_k, max(r, 1))

    def lane(q, bi, bd, be, sn, vi, vd, nv, nc, nh):
        fetch_adj = lambda sv, active: adj[sv]
        fetch_tile = lambda t, tile_ids, active: (
            codes[jnp.maximum(tile_ids, 0)].astype(jnp.float32)
        )
        c = (bi, bd, be, sn, vi, vd, nv, nc, nh)
        for _ in range(h):
            c = _lane_hop(metric, l, r, mv, n_cap, tile_k, fetch_adj,
                          fetch_tile, qnorms.astype(jnp.float32),
                          nav_words, ret_words, q, c,
                          scales=scales.astype(jnp.float32))
        return c

    return jax.vmap(lane)(
        queries.astype(jnp.float32), beam_ids, beam_dists,
        beam_exp.astype(jnp.int32), seen, vis_ids, vis_dists,
        n_vis.astype(jnp.int32), n_comps.astype(jnp.int32),
        n_hops.astype(jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("metric", "h", "tile_k"))
def beam_hop_ref(
    queries, beam_ids, beam_dists, beam_exp, seen, vis_ids, vis_dists,
    n_vis, n_comps, n_hops, adj, vectors, norms, nav_words, ret_words,
    *, metric: str = "l2", h: int = 4, tile_k: int = 64,
):
    """Pure-jnp oracle for ``beam_hop_fused``: identical per-lane math
    (shared ``_lane_hop``), plain gathers instead of DMA, vmapped over
    lanes.  Same signature minus ``interpret``; same return tuple."""
    n_cap, r = adj.shape
    l = beam_ids.shape[1]
    mv = vis_ids.shape[1]
    tile_k = min(tile_k, max(r, 1))

    def lane(q, bi, bd, be, sn, vi, vd, nv, nc, nh):
        fetch_adj = lambda sv, active: adj[sv]
        fetch_tile = lambda t, tile_ids, active: (
            vectors[jnp.maximum(tile_ids, 0)].astype(jnp.float32)
        )
        c = (bi, bd, be, sn, vi, vd, nv, nc, nh)
        for _ in range(h):
            c = _lane_hop(metric, l, r, mv, n_cap, tile_k, fetch_adj,
                          fetch_tile, norms.astype(jnp.float32),
                          nav_words, ret_words, q, c)
        return c

    return jax.vmap(lane)(
        queries.astype(jnp.float32), beam_ids, beam_dists,
        beam_exp.astype(jnp.int32), seen, vis_ids, vis_dists,
        n_vis.astype(jnp.int32), n_comps.astype(jnp.int32),
        n_hops.astype(jnp.int32),
    )
