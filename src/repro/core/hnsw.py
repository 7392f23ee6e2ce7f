"""HNSW baseline (§4, hnswlib-style) with mark-delete + replacement inserts.

A faithful-but-compact JAX port of the comparison system the paper uses:
hierarchical layers, ef_construction/ef_search beams, the select-neighbours
heuristic (== RobustPrune with alpha = 1), deletion as tombstoning, and the
"replace a deleted node on insert" repair path described in §4:

    "it updates all of the deleted point p's one-hop neighbors by adding all
     of p's two-hop neighbors to each of them, and then trimming them back
     down to respect the degree limit ... then it proceeds like a standard
     insert [into the reused slot]."

The per-level graphs reuse the DiskANN machinery by viewing each level's
adjacency as a ``GraphState`` (same vectors / masks, different ``adj``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .index import EvalCounters, OpCounters
from .prune import robust_prune
from .search import greedy_search, search_batch
from .types import (
    INVALID,
    ANNConfig,
    GraphState,
    clip_ids,
    init_vectors,
    read_vectors,
    write_vectors,
)


@dataclasses.dataclass(frozen=True)
class HNSWConfig:
    dim: int
    n_cap: int
    m: int = 48                      # paper: M = 48
    ef_construction: int = 128
    ef_search: int = 128
    max_level: int = 4               # levels 1..max_level live in adj_up
    metric: str = "l2"
    consolidation_threshold: float = 0.2

    @property
    def m0(self) -> int:
        return 2 * self.m

    def level_cfg(self, level: int) -> ANNConfig:
        r = self.m0 if level == 0 else self.m
        return ANNConfig(
            dim=self.dim, n_cap=self.n_cap, r=r,
            l_build=self.ef_construction, l_search=self.ef_search,
            alpha=1.0, metric=self.metric,
        )


class HNSWState(NamedTuple):
    vectors: jax.Array    # the vector table (core/types.py: 128-lane rows)
    norms: jax.Array      # f32[n_cap]
    adj0: jax.Array       # i32[n_cap, m0]
    adj_up: jax.Array     # i32[max_level, n_cap, m]
    level: jax.Array      # i32[n_cap]  top level of each node (-1 = unused)
    active: jax.Array     # bool[n_cap]
    tombstone: jax.Array  # bool[n_cap]
    free_stack: jax.Array
    free_top: jax.Array
    entry: jax.Array      # i32[]
    entry_level: jax.Array
    n_active: jax.Array
    n_pending: jax.Array


def init_hnsw(cfg: HNSWConfig) -> HNSWState:
    n = cfg.n_cap
    return HNSWState(
        vectors=init_vectors(n, cfg.dim),
        norms=jnp.zeros((n,), jnp.float32),
        adj0=jnp.full((n, cfg.m0), INVALID, jnp.int32),
        adj_up=jnp.full((cfg.max_level, n, cfg.m), INVALID, jnp.int32),
        level=jnp.full((n,), INVALID, jnp.int32),
        active=jnp.zeros((n,), bool),
        tombstone=jnp.zeros((n,), bool),
        free_stack=jnp.arange(n - 1, -1, -1, dtype=jnp.int32),
        free_top=jnp.int32(n),
        entry=jnp.int32(INVALID),
        entry_level=jnp.int32(INVALID),
        n_active=jnp.int32(0),
        n_pending=jnp.int32(0),
    )


def _level_view(st: HNSWState, cfg: HNSWConfig, level: int) -> GraphState:
    adj = st.adj0 if level == 0 else st.adj_up[level - 1]
    return GraphState(
        vectors=st.vectors, norms=st.norms, adj=adj,
        active=st.active, tombstone=st.tombstone,
        quarantine=jnp.zeros_like(st.active),
        free_stack=st.free_stack, free_top=st.free_top,
        start=st.entry, n_active=st.n_active, n_pending=st.n_pending,
    )


def _put_adj(st: HNSWState, level: int, adj: jax.Array) -> HNSWState:
    if level == 0:
        return st._replace(adj0=adj)
    return st._replace(adj_up=st.adj_up.at[level - 1].set(adj))


def _descend(st: HNSWState, cfg: HNSWConfig, x, from_level: int,
             to_level: int, start):
    """Greedy ef=1 descent from ``from_level`` down to ``to_level`` (excl)."""
    cur = start
    for lvl in range(from_level, to_level, -1):
        if lvl > cfg.max_level:
            continue
        view = _level_view(st, cfg, lvl)._replace(start=cur)
        res = greedy_search(view, cfg.level_cfg(lvl), x, k=1, l=1,
                            max_visits=64)
        cur = jnp.where(res.topk_ids[0] >= 0, res.topk_ids[0], cur)
    return cur


def _link(st: HNSWState, cfg: HNSWConfig, level: int, slot, x,
          cand_ids, cand_dists) -> HNSWState:
    """Select neighbours for ``slot`` on ``level`` and add reverse edges."""
    lcfg = cfg.level_cfg(level)
    view = _level_view(st, cfg, level)
    nout = robust_prune(view, lcfg, x, cand_ids, cand_dists, p_id=slot)
    adj = view.adj.at[clip_ids(slot, cfg.n_cap)].set(nout)

    def rev(i, adj):
        v = nout[i]
        sv = clip_ids(v, cfg.n_cap)
        row = adj[sv]
        cnt = jnp.sum(row >= 0)
        dup = jnp.any(row == slot)
        skip = (v < 0) | dup

        def append(a):
            return a.at[sv, cnt].set(slot)

        def shrink(a):
            cand = jnp.concatenate([row, jnp.asarray(slot, jnp.int32)[None]])
            new_row = robust_prune(
                view._replace(adj=a), lcfg,
                read_vectors(st.vectors, sv, cfg.dim), cand, p_id=v
            )
            return a.at[sv].set(new_row)

        return lax.cond(
            skip, lambda a: a,
            lambda a: lax.cond(cnt < lcfg.r, append, shrink, a), adj)

    adj = lax.fori_loop(0, lcfg.r, rev, adj)
    return _put_adj(st, level, adj)


@functools.partial(jax.jit, static_argnames=("cfg", "node_level"))
def _insert_at_levels(st: HNSWState, cfg: HNSWConfig, x, slot,
                      node_level: int) -> HNSWState:
    """Jitted per-(node_level) insert body (slot already allocated)."""
    x = x.astype(jnp.float32)
    sslot = clip_ids(slot, cfg.n_cap)
    st = st._replace(
        vectors=write_vectors(st.vectors, sslot, x, cfg.dim),
        norms=st.norms.at[sslot].set(jnp.dot(x, x)),
        level=st.level.at[sslot].set(node_level),
        active=st.active.at[sslot].set(True),
        n_active=st.n_active + 1,
    )
    entry_level = st.entry_level
    cur = _descend(st, cfg, x, cfg.max_level, node_level, st.entry)
    for lvl in range(min(cfg.max_level, node_level), -1, -1):
        lcfg = cfg.level_cfg(lvl)
        view = _level_view(st, cfg, lvl)._replace(start=cur)
        res = greedy_search(view, lcfg, x, k=1, l=cfg.ef_construction)
        st = _link(st, cfg, lvl, slot, x, res.visited_ids, res.visited_dists)
        cur = jnp.where(res.topk_ids[0] >= 0, res.topk_ids[0], cur)
    new_entry = node_level > entry_level
    return st._replace(
        entry=jnp.where(new_entry, slot, st.entry),
        entry_level=jnp.maximum(entry_level, node_level),
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def _repair_replaced(st: HNSWState, cfg: HNSWConfig, p) -> HNSWState:
    """Pre-insert repair of a tombstoned slot p (the §4 replace procedure)."""
    sp = clip_ids(p, cfg.n_cap)
    for lvl in range(cfg.max_level + 1):
        lcfg = cfg.level_cfg(lvl)
        view = _level_view(st, cfg, lvl)
        row = view.adj[sp]                       # (m,)
        srow = clip_ids(row, cfg.n_cap)
        two_hop = view.adj[srow]                 # (m, m)
        two_hop = jnp.where((row >= 0)[:, None], two_hop, INVALID)
        flat = two_hop.reshape(-1)

        def fix_one(z):
            zrow = view.adj[clip_ids(z, cfg.n_cap)]
            cand = jnp.concatenate([zrow, flat])
            cand = jnp.where(cand == p, INVALID, cand)
            return robust_prune(
                view, lcfg,
                read_vectors(st.vectors, clip_ids(z, cfg.n_cap), cfg.dim),
                cand, p_id=z
            )

        new_rows = jax.vmap(fix_one)(row)
        idx = jnp.where(row >= 0, row, cfg.n_cap)
        adj = view.adj.at[idx].set(new_rows, mode="drop")
        adj = adj.at[sp].set(jnp.full((lcfg.r,), INVALID, jnp.int32))
        st = _put_adj(st, lvl, adj)
    return st._replace(
        tombstone=st.tombstone.at[sp].set(False),
        level=st.level.at[sp].set(INVALID),
        n_pending=st.n_pending - 1,
        entry=jnp.where(st.entry == p,
                        jnp.argmax(st.active).astype(jnp.int32), st.entry),
    )


class HNSWIndex:
    """Host-orchestrated HNSW with external ids, mirroring StreamingIndex.

    Duck-type compatible with ``run_runbook``'s index surface (``mode``,
    ``batch_updates``, ``counters``, ``eval_counters``, insert / delete /
    recall / ``n_active``) so the §4 baseline replays the same runbooks
    through the same harness as the update policies.  The pre-counters
    float attributes (``insert_s`` etc.) survive as read-only properties.
    """

    mode = "hnsw"
    batch_updates = False

    def __init__(self, cfg: HNSWConfig, max_external_id: Optional[int] = None,
                 seed: int = 0):
        self.cfg = cfg
        self.state = init_hnsw(cfg)
        self.rng = np.random.default_rng(seed)
        n_ext = max_external_id or cfg.n_cap * 4
        self._ext2slot = np.full((n_ext,), INVALID, np.int64)
        self._slot2ext = np.full((cfg.n_cap,), INVALID, np.int64)
        self._replace_queue: list = []
        self.counters = OpCounters()
        self.eval_counters = EvalCounters()
        self._ml = 1.0 / np.log(cfg.m)

    # pre-counters accounting surface, kept for existing callers
    @property
    def insert_s(self) -> float:
        return self.counters.insert_s

    @property
    def search_s(self) -> float:
        return self.counters.search_s

    @property
    def search_comps(self) -> int:
        return self.counters.search_comps

    @property
    def n_inserts(self) -> int:
        return self.counters.n_inserts

    @property
    def n_queries(self) -> int:
        return self.counters.n_queries

    def _sample_level(self) -> int:
        return min(int(-np.log(self.rng.uniform(1e-12, 1.0)) * self._ml),
                   self.cfg.max_level)

    def insert(self, ext_ids, vectors) -> None:
        t0 = time.perf_counter()
        n_pending = int(self.state.n_pending)
        use_replace = n_pending > self.cfg.consolidation_threshold * max(
            int(self.state.n_active), 1
        )
        if use_replace and not self._replace_queue:
            self._replace_queue = list(
                np.nonzero(np.asarray(self.state.tombstone))[0]
            )
        for ext, x in zip(np.asarray(ext_ids), np.asarray(vectors)):
            if self._replace_queue:
                slot = int(self._replace_queue.pop())
                self.state = _repair_replaced(
                    self.state, self.cfg, jnp.int32(slot)
                )
            else:
                ft = int(self.state.free_top)
                if ft <= 0:
                    raise RuntimeError("hnsw capacity exhausted")
                slot = int(self.state.free_stack[ft - 1])
                self.state = self.state._replace(free_top=self.state.free_top - 1)
            lvl = self._sample_level()
            self.state = _insert_at_levels(
                self.state, self.cfg, jnp.asarray(x, jnp.float32),
                jnp.int32(slot), lvl,
            )
            self._ext2slot[int(ext)] = slot
            self._slot2ext[slot] = int(ext)
        jax.block_until_ready(self.state.adj0)
        self.counters.insert_s += time.perf_counter() - t0
        self.counters.n_inserts += len(np.asarray(ext_ids))

    def delete(self, ext_ids) -> None:
        # mark-deleted; cost is charged to insertion via replacement (§4)
        t0 = time.perf_counter()
        slots = self._ext2slot[np.asarray(ext_ids)]
        act = self.state.active.at[jnp.asarray(slots)].set(False)
        tomb = self.state.tombstone.at[jnp.asarray(slots)].set(True)
        self.state = self.state._replace(
            active=act, tombstone=tomb,
            n_active=self.state.n_active - len(slots),
            n_pending=self.state.n_pending + len(slots),
        )
        self._ext2slot[np.asarray(ext_ids)] = INVALID
        self._slot2ext[slots] = INVALID
        # mark-delete cost is charged to insertion via replacement (§4)
        self.counters.insert_s += time.perf_counter() - t0
        self.counters.n_deletes += len(slots)

    def search(self, queries, k: int = 10, ef: Optional[int] = None):
        t0 = time.perf_counter()
        x = jnp.asarray(queries, jnp.float32)
        ef = ef or self.cfg.ef_search
        # descend through upper levels with the batch's shared entry
        view0 = _level_view(self.state, self.cfg, 0)
        entry_lvl = int(self.state.entry_level)
        starts = None
        for lvl in range(min(entry_lvl, self.cfg.max_level), 0, -1):
            lcfg = self.cfg.level_cfg(lvl)
            view = _level_view(self.state, self.cfg, lvl)
            if starts is not None:
                res = jax.vmap(
                    lambda q, s: greedy_search(
                        view._replace(start=s), lcfg, q, k=1, l=1,
                        max_visits=64)
                )(x, starts)
            else:
                res = search_batch(view, lcfg, x, k=1, l=1)
            starts = jnp.where(res.topk_ids[:, 0] >= 0, res.topk_ids[:, 0],
                               self.state.entry)
        lcfg0 = self.cfg.level_cfg(0)
        if starts is not None:
            res = jax.vmap(
                lambda q, s: greedy_search(
                    view0._replace(start=s), lcfg0, q, k=k, l=ef)
            )(x, starts)
        else:
            res = search_batch(view0, lcfg0, x, k=k, l=ef)
        ids = np.asarray(res.topk_ids)
        self.counters.search_comps += int(np.asarray(res.n_comps).sum())
        self.counters.search_s += time.perf_counter() - t0
        self.counters.n_queries += x.shape[0]
        ext = np.where(ids >= 0, self._slot2ext[np.clip(ids, 0, None)], INVALID)
        return ext, np.asarray(res.topk_dists), ids

    def recall(self, queries, k: int = 10) -> float:
        """Evaluation sweep: books into ``eval_counters`` (moving the
        serving counters back afterwards), matching StreamingIndex."""
        from .recall import brute_force_topk, recall_at_k

        t0 = time.perf_counter()
        c0_comps = self.counters.search_comps
        c0_s = self.counters.search_s
        c0_q = self.counters.n_queries
        _, _, slot_ids = self.search(queries, k=k)
        self.eval_counters.search_comps += self.counters.search_comps - c0_comps
        self.eval_counters.n_queries += self.counters.n_queries - c0_q
        self.counters.search_comps = c0_comps
        self.counters.search_s = c0_s
        self.counters.n_queries = c0_q
        self.eval_counters.search_s += time.perf_counter() - t0
        view0 = _level_view(self.state, self.cfg, 0)
        lcfg0 = self.cfg.level_cfg(0)
        true_ids, _ = brute_force_topk(
            view0, lcfg0, jnp.asarray(queries, jnp.float32), k=k
        )
        return recall_at_k(slot_ids, true_ids, k)

    @property
    def n_active(self) -> int:
        return int(self.state.n_active)
