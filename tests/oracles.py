"""Pure-numpy oracles for the core graph algorithms (test references)."""
from __future__ import annotations

import numpy as np

INVALID = -1


def dist(metric: str, a: np.ndarray, b: np.ndarray) -> float:
    if metric == "l2":
        d = a.astype(np.float32) - b.astype(np.float32)
        return float(np.dot(d, d))
    return float(-np.dot(a, b))


def robust_prune_oracle(
    metric: str,
    alpha: float,
    r: int,
    p_vec: np.ndarray,
    cand_ids: np.ndarray,
    cand_vecs_all: np.ndarray,   # full slot table
    live_mask: np.ndarray,       # navigable slots
    p_id: int | None = None,
    cand_dists: np.ndarray | None = None,
) -> list[int]:
    """Algorithm 3 with this codebase's candidate hygiene (dedupe keep-first,
    drop dead slots / p itself), matching repro.core.prune.robust_prune.
    A finite ``cand_dists[k]`` stands for the distance of ``cand_ids[k]``
    to p, as the device code takes it."""
    seen: set[int] = set()
    ids: list[int] = []
    given: dict[int, float] = {}
    for k, i in enumerate(cand_ids):
        i = int(i)
        if i < 0 or i in seen:
            continue
        seen.add(i)
        if p_id is not None and i == p_id:
            continue
        if not live_mask[i]:
            continue
        ids.append(i)
        if cand_dists is not None and np.isfinite(cand_dists[k]):
            given[i] = float(cand_dists[k])
    # distance-from-p, matmul form (norms + q2 - 2 dot) to match device math
    def d_p(i):
        if i in given:
            return given[i]
        if metric == "l2":
            x = cand_vecs_all[i]
            return (
                float(np.dot(p_vec, p_vec))
                + float(np.dot(x, x))
                - 2.0 * float(np.dot(x, p_vec))
            )
        return float(-np.dot(cand_vecs_all[i], p_vec))

    alive = {i: d_p(i) for i in ids}
    out: list[int] = []
    while alive and len(out) < r:
        v = min(alive, key=lambda i: (alive[i], ids.index(i)))
        dv = alive.pop(v)
        if not np.isfinite(dv):
            break
        out.append(v)
        vv = cand_vecs_all[v]
        drop = []
        for u, du in alive.items():
            if metric == "l2":
                x = cand_vecs_all[u]
                duv = (
                    float(np.dot(vv, vv))
                    + float(np.dot(x, x))
                    - 2.0 * float(np.dot(x, vv))
                )
            else:
                # ip: the test compares squared euclidean distances,
                # |u|^2 + |v|^2 + 2 * (ip distance), on both sides
                x = cand_vecs_all[u]
                nx = float(np.dot(x, x))
                duv = nx + float(np.dot(vv, vv)) - 2.0 * float(np.dot(x, vv))
                du = nx + float(np.dot(p_vec, p_vec)) + 2.0 * du
            if alpha * duv <= du:
                drop.append(u)
        for u in drop:
            alive.pop(u)
    return out


def append_edges_oracle(
    metric: str,
    alpha: float,
    r: int,
    adj: np.ndarray,             # (n, r) front-compacted rows
    vecs: np.ndarray,
    live_mask: np.ndarray,       # active | tombstone
    vs: np.ndarray,
    us: np.ndarray,
) -> np.ndarray:
    """Algorithm 2 lines 5-8 one edge at a time, in order: ``v -> u`` is
    skipped when either end is INVALID or dead, u == v or u is already in
    v's row; else appended, or the row plus u RobustPruned when it is full.
    Returns the new adjacency (matching repro.core.edges.append_edges)."""
    adj = adj.copy()
    for v, u in zip(np.ravel(vs), np.ravel(us)):
        v, u = int(v), int(u)
        if v < 0 or u < 0 or v == u or not live_mask[v] or not live_mask[u]:
            continue
        row = [int(x) for x in adj[v] if x >= 0]
        if u in row:
            continue
        if len(row) < r:
            row.append(u)
        else:
            row = robust_prune_oracle(metric, alpha, r, vecs[v],
                                      np.array(row + [u]), vecs, live_mask,
                                      p_id=v)
        adj[v] = row + [INVALID] * (r - len(row))
    return adj


def brute_topk_oracle(metric, queries, vecs, active, k):
    out = []
    for q in queries:
        if metric == "l2":
            d = ((vecs - q) ** 2).sum(1)
        else:
            d = -(vecs @ q)
        d = np.where(active, d, np.inf)
        out.append(np.argsort(d, kind="stable")[:k])
    return np.stack(out)
