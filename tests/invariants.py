"""Shared graph-invariant oracle for the streaming index test suites.

``check_graph_invariants`` inspects a ``GraphState`` (or a full
``IndexState``) on the host and returns a list of human-readable violation
strings — empty means healthy.  It encodes the structural contracts every
update policy must preserve:

- adjacency hygiene: ids in range, no self loops, no duplicates within a
  row, rows front-compacted (``append_edges`` writes at ``row_count``);
- no out-edges into free slots, ever.  Edges into tombstoned (fresh) or
  quarantined (ip) slots are legal only pre-consolidation, and only for
  the policy that produces that limbo state; the ``local`` policy promises
  neither (deletes release slots directly, so a healthy local graph has
  edges into active slots only);
- the free stack: ``free_stack[:free_top]`` unique, in range, and disjoint
  from live (active | tombstone | quarantine) slots;
- accounting: ``free_top + #active + #tombstone + #quarantine == n_cap``,
  ``n_active == #active``, ``n_pending == #tombstone + #quarantine``;
- a navigable entry point whenever the graph is non-empty;
- (IndexState only) ``ext2slot`` / ``slot2ext`` mutually inverse on mapped
  entries, and every mapped slot live;
- (quantized tier) quant leaf shapes in lockstep with the vector store.

The checker is pure read-only host code — call it after any update, not
just at teardown.  ``assert_graph_invariants`` wraps it into one assert so
test failures show every violation at once.
"""
from __future__ import annotations

from typing import List, Optional, Union

import numpy as np

from repro.core import INVALID, ANNConfig, GraphState, IndexState


def _graph_of(state: Union[GraphState, IndexState]) -> GraphState:
    return state.graph if isinstance(state, IndexState) else state


def check_graph_invariants(
    state: Union[GraphState, IndexState],
    cfg: ANNConfig,
    *,
    policy: Optional[str] = None,
    consolidated: bool = False,
) -> List[str]:
    """Return a list of invariant violations (empty = healthy).

    ``policy`` narrows which limbo states are legal edge targets:
    ``"fresh"`` tolerates edges into tombstones, ``"ip"`` tolerates edges
    into quarantined slots — both only while ``consolidated`` is False.
    ``None`` accepts either limbo (mixed-policy states), ``"local"``
    accepts neither.
    """
    g = _graph_of(state)
    errs: List[str] = []

    adj = np.asarray(g.adj)
    active = np.asarray(g.active)
    tombstone = np.asarray(g.tombstone)
    quarantine = np.asarray(g.quarantine)
    free_stack = np.asarray(g.free_stack)
    free_top = int(g.free_top)
    n_active = int(g.n_active)
    n_pending = int(g.n_pending)
    start = int(g.start)
    n_cap = cfg.n_cap

    if adj.shape != (n_cap, cfg.r):
        errs.append(f"adj shape {adj.shape} != ({n_cap}, {cfg.r})")
        return errs  # everything below indexes by this shape

    valid = adj != INVALID

    # -- adjacency hygiene ---------------------------------------------------
    if valid.any():
        tgt = adj[valid]
        if (tgt < 0).any() or (tgt >= n_cap).any():
            errs.append("adjacency entry outside [0, n_cap)")
    self_loop = valid & (adj == np.arange(n_cap)[:, None])
    if self_loop.any():
        rows = np.flatnonzero(self_loop.any(axis=1))[:8]
        errs.append(f"self loop(s) in rows {rows.tolist()}")
    # duplicates within a row: compare sorted neighbours pairwise, pushing
    # INVALID padding to +inf so it can't collide
    keyed = np.where(valid, adj, n_cap + np.arange(cfg.r)[None, :])
    srt = np.sort(keyed, axis=1)
    dup = (srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] < n_cap)
    if dup.any():
        rows = np.flatnonzero(dup.any(axis=1))[:8]
        errs.append(f"duplicate out-edge(s) in rows {rows.tolist()}")
    # front compaction: no valid entry to the right of an INVALID one
    holes = (~valid[:, :-1]) & valid[:, 1:]
    if holes.any():
        rows = np.flatnonzero(holes.any(axis=1))[:8]
        errs.append(f"non-front-compacted row(s) {rows.tolist()}")

    # -- edge targets --------------------------------------------------------
    live = active | tombstone | quarantine
    free_mask = ~live
    clipped = np.clip(adj, 0, n_cap - 1)
    into_free = valid & free_mask[clipped]
    if into_free.any():
        rows = np.flatnonzero(into_free.any(axis=1))[:8]
        errs.append(f"out-edge(s) into free slot(s) from rows {rows.tolist()}")
    tomb_ok = not consolidated and policy in (None, "fresh")
    quar_ok = not consolidated and policy in (None, "ip")
    if not tomb_ok:
        into_tomb = valid & tombstone[clipped]
        if into_tomb.any():
            rows = np.flatnonzero(into_tomb.any(axis=1))[:8]
            errs.append(
                f"out-edge(s) into tombstoned slot(s) from rows "
                f"{rows.tolist()} (policy={policy}, "
                f"consolidated={consolidated})"
            )
    if not quar_ok:
        into_quar = valid & quarantine[clipped]
        if into_quar.any():
            rows = np.flatnonzero(into_quar.any(axis=1))[:8]
            errs.append(
                f"out-edge(s) into quarantined slot(s) from rows "
                f"{rows.tolist()} (policy={policy}, "
                f"consolidated={consolidated})"
            )

    # -- slot-state partition ------------------------------------------------
    overlap = (active & tombstone) | (active & quarantine) | (
        tombstone & quarantine
    )
    if overlap.any():
        errs.append(
            f"slot(s) in more than one of active/tombstone/quarantine: "
            f"{np.flatnonzero(overlap)[:8].tolist()}"
        )

    # -- free stack ----------------------------------------------------------
    if not (0 <= free_top <= n_cap):
        errs.append(f"free_top {free_top} outside [0, n_cap]")
    else:
        entries = free_stack[:free_top]
        if entries.size:
            if (entries < 0).any() or (entries >= n_cap).any():
                errs.append("free_stack entry outside [0, n_cap)")
            elif len(np.unique(entries)) != len(entries):
                errs.append("duplicate free_stack entries")
            elif live[entries].any():
                bad = entries[live[entries]][:8]
                errs.append(
                    f"free_stack entry(ies) point at live slot(s) "
                    f"{bad.tolist()}"
                )

    # -- accounting ----------------------------------------------------------
    if n_active != int(active.sum()):
        errs.append(f"n_active {n_active} != #active {int(active.sum())}")
    pend = int(tombstone.sum()) + int(quarantine.sum())
    if n_pending != pend:
        errs.append(f"n_pending {n_pending} != #tombstone+#quarantine {pend}")
    total = free_top + int(live.sum())
    if total != n_cap:
        errs.append(
            f"free_top + live = {total} != n_cap {n_cap} (leaked slot?)"
        )

    # -- entry point ---------------------------------------------------------
    if n_active > 0:
        if not (0 <= start < n_cap):
            errs.append(f"start {start} invalid with n_active {n_active} > 0")
        elif not live[start]:
            errs.append(f"start {start} points at a free slot")
    elif pend == 0 and start != INVALID:
        errs.append(f"start {start} != INVALID on an empty graph")

    # -- quantized tier ------------------------------------------------------
    if cfg.quantized:
        if g.quant is None:
            errs.append("cfg.quantized=True but quant leaf is None")
        else:
            codes = np.asarray(g.quant.codes)
            if codes.shape[0] != n_cap:
                errs.append(
                    f"quant codes rows {codes.shape[0]} != n_cap {n_cap}"
                )
    elif g.quant is not None:
        errs.append("cfg.quantized=False but quant leaf present")

    # -- id maps (IndexState only) ------------------------------------------
    if isinstance(state, IndexState):
        ext2slot = np.asarray(state.ext2slot)
        slot2ext = np.asarray(state.slot2ext)
        if slot2ext.shape[0] != n_cap:
            errs.append(f"slot2ext rows {slot2ext.shape[0]} != n_cap {n_cap}")
        else:
            mapped_ext = np.flatnonzero(ext2slot != INVALID)
            slots = ext2slot[mapped_ext]
            if slots.size and ((slots < 0).any() or (slots >= n_cap).any()):
                errs.append("ext2slot maps to slot outside [0, n_cap)")
            else:
                back = slot2ext[slots]
                bad = back != mapped_ext
                if bad.any():
                    errs.append(
                        f"ext2slot/slot2ext not inverse for ext id(s) "
                        f"{mapped_ext[bad][:8].tolist()}"
                    )
                if slots.size and ~live[slots].all():
                    dead = mapped_ext[~live[slots]][:8]
                    errs.append(
                        f"mapped ext id(s) {dead.tolist()} point at free "
                        f"slot(s)"
                    )
            mapped_slot = np.flatnonzero(slot2ext != INVALID)
            exts = slot2ext[mapped_slot]
            if exts.size:
                if (exts < 0).any() or (exts >= ext2slot.shape[0]).any():
                    errs.append("slot2ext maps to ext id outside range")
                else:
                    fwd = ext2slot[exts]
                    bad = fwd != mapped_slot
                    if bad.any():
                        errs.append(
                            f"slot2ext/ext2slot not inverse for slot(s) "
                            f"{mapped_slot[bad][:8].tolist()}"
                        )

    return errs


def assert_graph_invariants(
    state: Union[GraphState, IndexState],
    cfg: ANNConfig,
    *,
    policy: Optional[str] = None,
    consolidated: bool = False,
    context: str = "",
) -> None:
    """Raise ``AssertionError`` listing every violated invariant."""
    errs = check_graph_invariants(
        state, cfg, policy=policy, consolidated=consolidated
    )
    if errs:
        where = f" [{context}]" if context else ""
        raise AssertionError(
            f"graph invariants violated{where}:\n  " + "\n  ".join(errs)
        )
