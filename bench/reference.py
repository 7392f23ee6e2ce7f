"""The plain reference and the comparison that decides ``correct``.

The reference is an exact scan over the live set at each query's moment,
independent of the program: candidates from one matmul at JAX's
``highest`` precision on the default device, then an exact float64
re-ranking on the host (so the truth does not rest on the device's
rounding).  The control is the same scan in bfloat16, the precision below
the configuration's float32, put in the program's place.

Numbers compared, each against its limit in the configuration file (a
configuration compares those it gives a limit):

    recall_miss   1 - mean recall@k over every query of the window
    dist_err      widest gap between a returned distance and the float64
                  distance of the returned id, over the mean float64
                  distance of that query's true top-k
    bad_ids       returned ids that were not live at the query's moment,
                  repeated in one answer, or missing
    failed_ops    update lanes the program did not apply
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from traffic import replay

CANDIDATES = 64
QUERY_BLOCK = 512


def _scan(x, q, metric, dtype, precision):
    """Distances of every row of ``x`` to every query, as the device
    computes them at ``dtype`` / ``precision``."""
    xd, qd = x.astype(dtype), q.astype(dtype)
    dot = jnp.matmul(qd, xd.T, precision=precision,
                     preferred_element_type=jnp.float32)
    if metric == "ip":
        return -dot
    xf, qf = xd.astype(jnp.float32), qd.astype(jnp.float32)
    xn = jnp.sum(xf * xf, axis=1)
    qn = jnp.sum(qf * qf, axis=1)
    return qn[:, None] + xn[None, :] - 2.0 * dot


def exact_f64(x: np.ndarray, q: np.ndarray, metric: str) -> np.ndarray:
    """Float64 distances between paired rows: ``x[i, j]`` to ``q[i]``."""
    x64, q64 = x.astype(np.float64), q.astype(np.float64)
    if metric == "ip":
        return -np.einsum("ijd,id->ij", x64, q64)
    diff = x64 - q64[:, None, :]
    return np.einsum("ijd,ijd->ij", diff, diff)


@functools.partial(jax.jit, static_argnames=("k", "metric", "control"))
def _block(x, qb, *, k, metric, control):
    if control:
        d = _scan(x, qb, metric, jnp.bfloat16, jax.lax.Precision.DEFAULT)
    else:
        d = _scan(x, qb, metric, jnp.float32, jax.lax.Precision.HIGHEST)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


def topk(vecs: np.ndarray, ext: np.ndarray, queries: np.ndarray, k: int,
         metric: str, *, control: bool = False):
    """``(ext_ids, dists)`` of the ``k`` nearest live points per query.

    Exact (``control=False``): device candidates at ``highest`` precision,
    re-ranked in float64.  Control: the whole scan in bfloat16, its own
    top-k and distances returned as they come."""
    x = jnp.asarray(vecs)
    c = min(k if control else max(k, CANDIDATES), len(vecs))
    ids_out, d_out = [], []
    for lo in range(0, len(queries), QUERY_BLOCK):
        qb = queries[lo:lo + QUERY_BLOCK]
        pad = np.zeros((QUERY_BLOCK, qb.shape[1]), np.float32)
        pad[:len(qb)] = qb
        d, idx = _block(x, jnp.asarray(pad), k=c, metric=metric,
                        control=control)
        d, idx = np.asarray(d)[:len(qb)], np.asarray(idx)[:len(qb)]
        if not control:
            d = exact_f64(vecs[idx], qb, metric)
            order = np.argsort(d, axis=1, kind="stable")[:, :k]
            idx = np.take_along_axis(idx, order, axis=1)
            d = np.take_along_axis(d, order, axis=1)
        ids_out.append(ext[idx])
        d_out.append(d)
    return np.concatenate(ids_out), np.concatenate(d_out)


def score(got_ids, got_d, true_ids, true_d, qs, vecs, row, metric):
    """``(hits, bad, bad_rows, worst_gap)`` of one live set's answers.
    ``row`` maps an external id to its stream position while live, else
    -1."""
    got_ids = np.asarray(got_ids, np.int64)
    k = true_ids.shape[1]
    inside = (got_ids >= 0) & (got_ids < len(row))
    pos = np.where(inside, row[np.where(inside, got_ids, 0)], -1)
    valid = pos >= 0
    srt = np.sort(np.where(valid, got_ids, -1 - np.arange(got_ids.shape[1])),
                  axis=1)
    dup = srt[:, 1:] == srt[:, :-1]
    short = max(0, k - got_ids.shape[1])
    bad = int(np.sum(~valid)) + int(np.sum(dup)) + short * len(qs)
    bad_rows = len(qs) if short else int(np.sum((~valid).any(1) | dup.any(1)))
    hits = int(np.sum((true_ids[:, :, None]
                       == np.where(valid, got_ids, -2)[:, None, :]).any(2)))
    worst = 0.0
    if valid.any():
        d_ex = exact_f64(vecs[np.maximum(pos, 0)], qs, metric)
        scale = np.mean(np.abs(true_d), axis=1, keepdims=True)
        gap = np.abs(np.asarray(got_d, np.float64) - d_ex) / np.where(
            scale > 0, scale, 1.0)
        worst = float(np.max(np.where(valid, gap, 0.0)))
    return hits, bad, bad_rows, worst


def compare(stream, answers, ops_failed: int, *, control: bool = False):
    """Replay the stream's live sets, answer its queries with the
    reference, and return ``(checks, bad_queries)``: the numbers compared,
    as a dict, and how many queries had a bad id in their answer.  ``answers``
    lists ``(ext_ids, dists)`` per query batch in log order; the control
    answers the queries itself, in bfloat16, and ``answers`` is ignored."""
    vecs = stream.vectors()
    metric = stream.metric
    row = np.full(stream.max_ext, -1, np.int64)
    hits = total = bad = bad_rows = 0
    worst = 0.0
    it = iter(answers)
    for alive, batches in replay(stream.log, len(stream.chunks[0]),
                                 len(vecs)):
        pos = np.flatnonzero(alive)
        ext = stream.ext(pos)
        qs = np.concatenate([q for q, _ in batches])
        k = max(kk for _, kk in batches)
        true_ids, true_d = topk(vecs[pos], ext, qs, k, metric)
        if control:
            got_ids, got_d = topk(vecs[pos], ext, qs, k, metric,
                                  control=True)
        else:
            got = [next(it) for _ in batches]
            got_ids = np.concatenate([np.asarray(g[0]) for g in got])
            got_d = np.concatenate([np.asarray(g[1]) for g in got])
        row[ext] = pos
        h, b, br, w = score(got_ids, got_d, true_ids, true_d, qs, vecs, row,
                        metric)
        row[ext] = -1
        hits, bad, worst = hits + h, bad + b, max(worst, w)
        bad_rows += br
        total += k * len(qs)
    return {
        "recall_miss": 1.0 - hits / total if total else 1.0,
        "dist_err": worst,
        "bad_ids": bad,
        "failed_ops": int(ops_failed),
    }, bad_rows


def judge(checks: dict, limits: dict) -> tuple:
    """``(correct, shown)``: each number that has a limit, beside its
    limit; the run is correct when none exceeds its limit."""
    shown = {n: {"value": checks[n], "limit": limits[n]} for n in limits}
    return all(v["value"] <= v["limit"] for v in shown.values()), shown
