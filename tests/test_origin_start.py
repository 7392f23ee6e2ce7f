"""The frozen start at the origin (``ANNConfig.origin_start``).

Slot 0 holds the zero vector as a permanent tombstone: the entry point of
every search, navigable and linkable, never allocated, returned, deleted
or released.  On unit vectors in clusters of near-equal products (a
full-rank mixture at high dim) a first-point start's row fills with its own
cluster and the other clusters become unreachable; the origin's prune
keeps one neighbour per cluster, so every cluster stays one hop away.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from invariants import assert_graph_invariants
from repro.checkpoint import CheckpointManager, CheckpointMismatchError
from repro.core import (
    ANNConfig,
    apply,
    delete_batch,
    init_index_state,
    insert_batch,
    restore_index,
    save_index,
    search_index,
)
from repro.core import persist
from repro.core.types import ORIGIN

DIM = 256


def _cfg(origin=True, backend="jnp", n_cap=512):
    return ANNConfig(dim=DIM, n_cap=n_cap, r=8, l_build=16, l_search=16,
                     l_delete=16, k_delete=8, n_copies=2, alpha=1.2,
                     metric="ip", backend=backend, origin_start=origin)


def _clusters(rng, n, cents):
    x = cents[rng.integers(0, len(cents), n)] + 0.35 * rng.normal(
        size=(n, cents.shape[1])).astype(np.float32)
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def _build(cfg, data, policy="ip", boot=32, window=32):
    """A serial bootstrap, then windows, as a deployment's build."""
    state = init_index_state(cfg, 1024)
    state, _ = apply(state, cfg, insert_batch(np.arange(boot), data[:boot]),
                     policy=policy, sequential=True)
    for lo in range(boot, len(data), window):
        ids = np.arange(lo, min(lo + window, len(data)))
        state, _ = apply(state, cfg, insert_batch(ids, data[ids]),
                         policy=policy)
    return state


@pytest.mark.parametrize("policy", ["ip", "local"])
def test_origin_is_a_frozen_tombstone(policy):
    cfg = _cfg()
    state = init_index_state(cfg, 1024)
    g = state.graph
    assert int(g.start) == ORIGIN and bool(g.tombstone[ORIGIN])
    assert not bool(g.active[ORIGIN]) and int(g.n_pending) == 0
    assert int(g.free_top) == cfg.n_cap - 1
    assert_graph_invariants(state, cfg, policy=policy)

    rng = np.random.default_rng(1)
    data = _clusters(rng, 96, rng.normal(size=(3, DIM)).astype(np.float32))
    state = _build(cfg, data, policy=policy)
    slots = np.asarray(state.ext2slot)[:96]
    assert (slots != ORIGIN).all() and (slots >= 0).all()
    assert (np.asarray(state.graph.adj[ORIGIN]) >= 0).any()
    assert_graph_invariants(state, cfg, policy=policy)
    # deleting every point leaves the origin as it was
    state, res = apply(state, cfg, delete_batch(np.arange(96), DIM),
                       policy=policy)
    assert bool(np.asarray(res.ok)[:96].all())
    g = state.graph
    assert int(g.start) == ORIGIN and bool(g.tombstone[ORIGIN])
    assert int(g.n_active) == 0
    assert_graph_invariants(state, cfg, policy=policy)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_origin_start_reaches_every_cluster(backend):
    """Four clusters of 64 unit vectors at 256-d, R 8, after the oldest 64
    (the first point among them) are deleted in place: from the origin the
    search finds each query's cluster (recall 0.93 here), where a
    first-point start finds only its own (0.40)."""
    rng = np.random.default_rng(0)
    cents = rng.normal(size=(4, DIM)).astype(np.float32)
    data = _clusters(rng, 256, cents)
    queries = _clusters(rng, 128, cents)
    live = np.arange(64, 256)
    exact = -(queries.astype(np.float64) @ data[live].astype(np.float64).T)
    true = live[np.argsort(exact, axis=1, kind="stable")[:, :10]]
    recall = {}
    for origin in (True, False):
        cfg = _cfg(origin, backend)
        state = _build(cfg, data)
        state, _ = apply(state, cfg, delete_batch(np.arange(64), DIM))
        assert_graph_invariants(state, cfg, policy="ip")
        ext, _, _ = search_index(state, cfg, jnp.asarray(queries), k=10)
        ext = np.asarray(ext)
        assert np.isin(ext, live).all()       # the origin is never returned
        recall[origin] = np.mean(
            [len(set(a) & set(t)) / 10 for a, t in zip(ext, true)])
    assert recall[True] >= 0.85, recall
    assert recall[True] > recall[False] + 0.3, recall


def test_fresh_policy_refuses_origin_start():
    cfg = _cfg()
    state = init_index_state(cfg, 1024)
    x = np.ones((1, DIM), np.float32) / np.sqrt(DIM)
    with pytest.raises(ValueError, match="origin_start"):
        apply(state, cfg, insert_batch(np.arange(1), x), policy="fresh",
              sequential=True)


def test_checkpoint_holds_origin_start(tmp_path):
    """``origin_start`` is checkpoint-critical: a restore under the other
    setting is refused, and a manifest written before the field existed
    reads as False."""
    cfg = _cfg(n_cap=128)
    rng = np.random.default_rng(2)
    data = _clusters(rng, 48, rng.normal(size=(2, DIM)).astype(np.float32))
    state = _build(cfg, data)
    mgr = CheckpointManager(tmp_path / "a")
    save_index(mgr, 1, state, cfg)
    _, got, _ = restore_index(mgr, cfg)
    np.testing.assert_array_equal(np.asarray(got.graph.adj),
                                  np.asarray(state.graph.adj))
    assert int(got.graph.start) == ORIGIN
    with pytest.raises(CheckpointMismatchError, match="origin_start"):
        restore_index(mgr, dataclasses.replace(cfg, origin_start=False))

    plain = dataclasses.replace(cfg, origin_start=False)
    state = _build(plain, data)
    meta = persist._index_meta(state, plain, "ip")
    del meta["config"]["origin_start"]
    old = CheckpointManager(tmp_path / "b")
    old.save(2, state, extra={"index": meta, "user": {}})
    step, _, _ = restore_index(old, plain)
    assert step == 2
    with pytest.raises(CheckpointMismatchError, match="origin_start"):
        restore_index(old, cfg)
