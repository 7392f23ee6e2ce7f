"""Online capacity growth: rebuild the index into a larger slot bucket.

``IndexState`` fixes ``n_cap`` at construction; a streaming index that keeps
absorbing inserts eventually exhausts its slots.  Rather than failing (the
pre-growth behaviour) the front doors grow the state into the next
power-of-two capacity bucket when the live count crosses a high-water mark —
the hnswlib ``resizeIndex`` move, under this repo's bucketing discipline
(docs/ARCHITECTURE.md "Contract 1"): capacities walk powers of two, so a
stream from 64k to 10M slots costs ~8 recompiles total, amortized to zero.

``grow_index`` is a pure function: every graph leaf (vectors, norms, adj,
masks, the quant store), the slot->ext map and the free stack are padded
into the new bucket; ``ext2slot``, counters, the entry point and all live
rows are untouched, so searches and replays see the identical graph.

Free-stack determinism (the replay contract): the fresh slots
``[n_cap, new_cap)`` are pushed ABOVE the surviving free entries in
ascending-pop order — after a grow, allocation pops ``n_cap, n_cap+1, ...``
first, then whatever was free before, exactly as a function of the input
state.  A segment replay that crosses a growth boundary (crash recovery,
``core/persist.py``) therefore re-allocates bit-identical slots.

``ensure_capacity`` is the host-side trigger shared by ``StreamingIndex``
and ``ShardedIndex`` (which grows all ``n_logical`` rows in lockstep —
``grow_index`` vmaps itself over a stacked state).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .quant import QuantStore
from .types import INVALID, ANNConfig, GraphState, IndexState, init_vectors

# Grow when (live + incoming) would exceed this fraction of capacity: the
# graph needs free slots for in-flight quarantined/tombstoned rows, and
# growing *before* exhaustion keeps the failure path ("capacity exhausted")
# strictly for callers that disable growth.
HIGH_WATER = 0.9


def next_capacity(needed: int, n_cap: int,
                  high_water: float = HIGH_WATER) -> int:
    """The smallest power-of-two bucket >= ``n_cap`` whose high-water mark
    admits ``needed`` slots.  A non-power-of-two starting capacity snaps
    onto the bucket grid at its first growth."""
    cap = 1 << max(n_cap - 1, 1).bit_length()
    while needed > high_water * cap:
        cap *= 2
    return cap


def _grow_graph(g: GraphState, cfg: ANNConfig, new_cap: int) -> GraphState:
    extra = new_cap - cfg.n_cap

    def pad_rows(a, fill):
        return jnp.concatenate(
            [a, jnp.full((extra,) + a.shape[1:], fill, a.dtype)]
        )

    # fresh slots land ABOVE the surviving free entries, popping in
    # ascending slot order (n_cap first) — deterministic in the input state,
    # which is what keeps segment replays bit-identical across a grow
    stack = jnp.concatenate(
        [g.free_stack, jnp.zeros((extra,), jnp.int32)]
    )
    pos = g.free_top + jnp.arange(extra, dtype=jnp.int32)
    stack = stack.at[pos].set(
        (new_cap - 1 - jnp.arange(extra)).astype(jnp.int32)
    )

    quant = g.quant
    if quant is not None:
        quant = QuantStore(
            codes=pad_rows(quant.codes, 0),
            scale=pad_rows(quant.scale, 1.0),
            qnorms=pad_rows(quant.qnorms, 0.0),
        )
    return g._replace(
        vectors=jnp.concatenate(
            [g.vectors, init_vectors(extra, cfg.dim, g.vectors.dtype)]),
        norms=pad_rows(g.norms, 0.0),
        adj=pad_rows(g.adj, INVALID),
        active=pad_rows(g.active, False),
        tombstone=pad_rows(g.tombstone, False),
        quarantine=pad_rows(g.quarantine, False),
        free_stack=stack,
        free_top=g.free_top + extra,
        quant=quant,
    )


def _grow_one(state: IndexState, cfg: ANNConfig, new_cap: int) -> IndexState:
    extra = new_cap - cfg.n_cap
    return state._replace(
        graph=_grow_graph(state.graph, cfg, new_cap),
        slot2ext=jnp.concatenate(
            [state.slot2ext, jnp.full((extra,), INVALID, jnp.int32)]
        ),
    )


def grow_index(state: IndexState, cfg: ANNConfig,
               new_cap: int) -> Tuple[IndexState, ANNConfig]:
    """Rebuild ``state`` into capacity ``new_cap`` >= ``cfg.n_cap``.
    Returns ``(new_state, new_cfg)``; the input handle stays valid (pure
    function).  Stacked states (``ShardedIndex``'s leading ``n_logical``
    axis) grow every row in lockstep.  The automatic triggers only ever
    pass power-of-two buckets (``next_capacity``); arbitrary larger
    capacities are allowed here so restores can target any bucket."""
    if new_cap < cfg.n_cap:
        raise ValueError(
            f"grow_index cannot shrink: {cfg.n_cap} -> {new_cap}"
        )
    new_cfg = dataclasses.replace(cfg, n_cap=new_cap)
    if new_cap == cfg.n_cap:
        return state, new_cfg
    if state.graph.vectors.ndim == 3:
        state = jax.vmap(lambda s: _grow_one(s, cfg, new_cap))(state)
    else:
        state = _grow_one(state, cfg, new_cap)
    return state, new_cfg


def needs_growth(state: IndexState, cfg: ANNConfig, incoming: int,
                 high_water: float = HIGH_WATER) -> bool:
    """Host-side trigger: would ``incoming`` more inserts push the fullest
    row past the high-water mark?  (Stacked states use the minimum free
    count, so every logical row grows in lockstep.)"""
    free = int(np.asarray(state.graph.free_top).min())
    return (cfg.n_cap - free) + incoming > high_water * cfg.n_cap


def ensure_capacity(
    state: IndexState, cfg: ANNConfig, incoming: int,
    high_water: float = HIGH_WATER,
) -> Tuple[IndexState, ANNConfig, bool]:
    """Grow ``state`` (if needed) so ``incoming`` more inserts stay below
    the high-water mark.  Returns ``(state, cfg, grew)``."""
    if not needs_growth(state, cfg, incoming, high_water):
        return state, cfg, False
    free = int(np.asarray(state.graph.free_top).min())
    needed = (cfg.n_cap - free) + incoming
    state, cfg = grow_index(
        state, cfg, next_capacity(needed, cfg.n_cap, high_water)
    )
    return state, cfg, True


__all__ = [
    "HIGH_WATER",
    "ensure_capacity",
    "grow_index",
    "needs_growth",
    "next_capacity",
]
