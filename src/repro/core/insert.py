"""Insert (Algorithm 2): greedy search -> RobustPrune -> reverse edges."""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .edges import append_edges
from .prune import robust_prune
from .quant import quant_write_rows
from .search import greedy_search
from .types import INVALID, ANNConfig, GraphState, clip_ids


class InsertStats(NamedTuple):
    slot: jax.Array     # i32[] slot assigned (INVALID if capacity exhausted)
    n_comps: jax.Array  # i32[] distance computations
    n_hops: jax.Array   # i32[]


@functools.partial(jax.jit, static_argnames=("cfg",))
def insert(state: GraphState, cfg: ANNConfig, x: jax.Array):
    """Insert one vector; returns (new_state, InsertStats)."""
    has_slot = state.free_top > 0
    slot = jnp.where(
        has_slot, state.free_stack[jnp.maximum(state.free_top - 1, 0)], INVALID
    )
    sslot = clip_ids(slot, cfg.n_cap)
    x = x.astype(state.vectors.dtype)

    def no_capacity(st: GraphState):
        return st, InsertStats(jnp.int32(INVALID), jnp.int32(0), jnp.int32(0))

    def do_insert(st: GraphState):
        st = st._replace(
            vectors=st.vectors.at[sslot].set(x),
            norms=st.norms.at[sslot].set(
                jnp.dot(x, x).astype(jnp.float32)
            ),
            free_top=st.free_top - 1,
            n_active=st.n_active + 1,
        )
        if st.quant is not None:
            # keep the int8 tier in lockstep with the f32 write
            st = st._replace(
                quant=quant_write_rows(st.quant, sslot[None], x[None])
            )
        empty = st.start < 0

        def first_point(s: GraphState):
            s = s._replace(
                adj=s.adj.at[sslot].set(jnp.full((cfg.r,), INVALID, jnp.int32)),
                start=slot,
                active=s.active.at[sslot].set(True),
            )
            return s, InsertStats(slot, jnp.int32(0), jnp.int32(0))

        def grow(s: GraphState):
            res = greedy_search(s, cfg, x, k=1, l=cfg.l_build)
            nout = robust_prune(
                s, cfg, x, res.visited_ids, res.visited_dists, p_id=slot
            )
            s = s._replace(
                adj=s.adj.at[sslot].set(nout),
                active=s.active.at[sslot].set(True),
            )
            s = append_edges(s, cfg, nout, slot)
            return s, InsertStats(slot, res.n_comps, res.n_hops)

        return lax.cond(empty, first_point, grow, st)

    return lax.cond(has_slot, do_insert, no_capacity, state)


@functools.partial(jax.jit, static_argnames=("cfg",))
def insert_many(state: GraphState, cfg: ANNConfig, xs: jax.Array,
                valid: Optional[jax.Array] = None):
    """Serial (paper-faithful) scan of inserts.  xs: (B, dim).

    ``valid``: optional bool[B] lane mask — False lanes are no-ops (no slot
    allocated, no search, no write), so ragged bootstrap batches can ride a
    padded power-of-two bucket and every bucket size compiles exactly once
    (the batched path's ``pad_batch`` discipline, applied to the serial scan).
    """
    if valid is None:
        valid = jnp.ones((xs.shape[0],), bool)

    def step(st, args):
        x, ok = args

        def skip(s):
            return s, InsertStats(
                jnp.int32(INVALID), jnp.int32(0), jnp.int32(0)
            )

        st, stats = lax.cond(ok, lambda s: insert(s, cfg, x), skip, st)
        return st, stats

    return lax.scan(step, state, (xs, valid))
