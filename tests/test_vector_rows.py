"""The vector table's 128-lane rows (core/types.py) and what runs over them.

  * the table is ``f32[n_cap * ceil(dim / 128), 128]``: at dim 128 the
    plain ``f32[n_cap, 128]``, at 768 six rows a vector, at 100 and 16 one
    zero-padded row;
  * ``write_vectors`` / ``read_vectors`` round-trip any width, leave the
    lanes past ``dim`` zero, drop out-of-range writes, and never make an
    array of the table's plain ``(n_cap, dim)`` shape;
  * a churn of inserts and oldest-first deletes at 768-d inner product,
    through ``apply_segment``, answers ``search_index`` as an exact scan of
    the live set does, on the pallas (interpret) and jnp engines, and leaves
    a healthy graph (``tests/invariants.py``);
  * checkpoints round-trip the table; a checkpoint of the old plain
    ``(n_cap, dim)`` layout (schema 1) restores at dim 128 only.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from invariants import assert_graph_invariants
from repro.checkpoint import CheckpointManager, CheckpointMismatchError

from repro.core import (
    ANNConfig,
    apply,
    apply_segment,
    init_index_state,
    init_state,
    insert_batch,
    mixed_update_batch,
    restore_index,
    save_index,
    search_index,
)
from repro.core import persist
from repro.core.types import (
    ROW_LANES,
    read_vectors,
    row_blocks,
    stack_update_batches,
    write_vectors,
)

WIDTHS = [16, 100, 128, 256, 768]


@pytest.mark.parametrize("dim", WIDTHS)
def test_table_is_rows_of_128_lanes(dim):
    cfg = ANNConfig(dim=dim, n_cap=64, r=8)
    vectors = init_state(cfg).vectors
    assert vectors.shape == (64 * -(-dim // 128), ROW_LANES)
    assert row_blocks(dim) == -(-dim // 128)
    if dim == 128:
        assert vectors.shape == (64, 128)   # the plain table, as before


@pytest.mark.parametrize("dim", WIDTHS)
def test_write_then_read_round_trips(dim):
    n_cap = 32
    rng = np.random.default_rng(dim)
    xs = rng.normal(size=(5, dim)).astype(np.float32)
    slots = np.array([7, 0, 31, 12, 3])
    table = write_vectors(jnp.zeros((n_cap * row_blocks(dim), ROW_LANES)),
                          jnp.asarray(slots), jnp.asarray(xs), dim)
    np.testing.assert_array_equal(
        np.asarray(read_vectors(table, jnp.asarray(slots), dim)), xs)
    # a scalar id reads one vector; untouched slots stay zero
    np.testing.assert_array_equal(
        np.asarray(read_vectors(table, jnp.int32(31), dim)), xs[2])
    assert not np.asarray(read_vectors(table, jnp.int32(1), dim)).any()
    # the lanes past dim are zero, so they add nothing to either metric
    rows = np.asarray(table).reshape(n_cap, -1)
    assert not rows[:, dim:].any()
    np.testing.assert_array_equal(rows[slots, :dim], xs)
    # out-of-range slots drop their writes
    dropped = write_vectors(table, jnp.asarray([n_cap, 1]),
                            jnp.asarray(xs[:2] + 1), dim, mode="drop")
    got = np.asarray(dropped).reshape(n_cap, -1)
    np.testing.assert_array_equal(got[1, :dim], xs[1] + 1)
    np.testing.assert_array_equal(np.delete(got, 1, axis=0),
                                  np.delete(rows, 1, axis=0))


def test_helpers_touch_only_the_rows_they_are_given():
    """At 768-d neither helper's program holds an array of the table's
    plain (n_cap, dim) shape: no relayout of the whole table."""
    dim, n_cap = 768, 4096
    table = jax.ShapeDtypeStruct((n_cap * row_blocks(dim), ROW_LANES),
                                 jnp.float32)
    ids = jax.ShapeDtypeStruct((64,), jnp.int32)
    xs = jax.ShapeDtypeStruct((64, dim), jnp.float32)
    texts = [
        jax.jit(lambda t, i: read_vectors(t, i, dim)).lower(table, ids),
        jax.jit(lambda t, i, x: write_vectors(t, i, x, dim, mode="drop"),
                donate_argnums=0).lower(table, ids, xs),
    ]
    for lowered in texts:
        text = lowered.compile().as_text()
        assert f"f32[{n_cap},{dim}]" not in text
        assert "transpose(" not in text


# ---- a churn at 768-d inner product, against an exact scan ----------------

DIM, N_CAP, K = 768, 1024, 10


def _cfg(backend):
    # a frozen start at the origin, as the 768-d deployment runs: clusters
    # of near-equal products link to each other only through it
    return ANNConfig(dim=DIM, n_cap=N_CAP, r=16, l_build=32, l_search=48,
                     l_delete=32, k_delete=16, n_copies=2, alpha=1.2,
                     metric="ip", backend=backend, origin_start=True)


def _unit(rng, n):
    cents = rng.normal(size=(8, DIM)).astype(np.float32)
    x = cents[rng.integers(0, 8, n)] + 0.35 * rng.normal(
        size=(n, DIM)).astype(np.float32)
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_churn_at_768_ip_matches_an_exact_scan(backend):
    cfg = _cfg(backend)
    rng = np.random.default_rng(768)
    n0, steps, b = 192, 3, 32
    data = _unit(rng, n0 + steps * b)
    state = init_index_state(cfg, 4096)
    # a serial bootstrap, as a deployment's first points go in
    state, res = apply(state, cfg, insert_batch(np.arange(n0), data[:n0]),
                       sequential=True)
    assert bool(np.asarray(res.ok)[:n0].all())
    live = list(range(n0))
    for t in range(steps):
        ins = np.arange(n0 + t * b, n0 + (t + 1) * b)
        dels = np.asarray(live[:b])            # the oldest first
        batch, split = mixed_update_batch(ins, data[ins], dels, DIM)
        state, res = apply_segment(state, cfg, stack_update_batches([batch]),
                                   split=split)
        assert bool(np.asarray(res.ok)[0][np.asarray(batch.valid)].all())
        live = live[b:] + ins.tolist()
    assert_graph_invariants(state, cfg, policy="ip")

    queries = _unit(rng, 16)
    ext, dists, _ = search_index(state, cfg, jnp.asarray(queries), k=K)
    ext, dists = np.asarray(ext), np.asarray(dists)
    live = np.asarray(live)
    exact = -(queries.astype(np.float64) @ data[live].astype(np.float64).T)
    true = live[np.argsort(exact, axis=1, kind="stable")[:, :K]]
    assert np.isin(ext, live).all()              # no deleted or unknown id
    recall = np.mean([len(set(a) & set(t)) / K for a, t in zip(ext, true)])
    assert recall >= 0.9, recall
    # every returned distance is the float32 distance over the 768 lanes
    want = -np.einsum("qd,qkd->qk", queries.astype(np.float64),
                      data[ext].astype(np.float64))
    np.testing.assert_allclose(dists, want, rtol=1e-5, atol=1e-5)
    # the stored rows are the inserted vectors, read back exactly
    slots = np.asarray(state.ext2slot)[live]
    np.testing.assert_array_equal(
        np.asarray(read_vectors(state.graph.vectors, jnp.asarray(slots),
                                DIM)), data[live])


# ---- checkpoints -----------------------------------------------------------


def _trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _filled(cfg, n=96, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(n, cfg.dim)).astype(np.float32)
    if cfg.metric == "ip":
        data /= np.linalg.norm(data, axis=1, keepdims=True)
    state, _ = apply(init_index_state(cfg, 512), cfg,
                     insert_batch(np.arange(n), data))
    return state


def test_checkpoint_round_trips_a_768_table(tmp_path):
    cfg = dataclasses.replace(_cfg("jnp"), n_cap=256)
    state = _filled(cfg)
    mgr = CheckpointManager(tmp_path)
    save_index(mgr, 3, state, cfg)
    assert mgr.manifest(3)["extra"]["index"]["schema"] == \
        persist.SCHEMA_VERSION
    _, got, _ = restore_index(mgr, cfg)
    assert got.graph.vectors.shape == (256 * 6, ROW_LANES)
    _trees_equal(state, got)


@pytest.mark.parametrize("dim", [100, 128, 768])
def test_restore_of_a_plain_table_checkpoint(tmp_path, dim):
    """A checkpoint written in the old (n_cap, dim) layout (schema 1)
    restores at dim 128, where the plain table and the rows are the same
    bytes, to the state it was written from, and into a larger capacity
    bucket; at any other dim it is refused with the typed error."""
    cfg = ANNConfig(dim=dim, n_cap=128, r=8, l_build=16, l_search=16,
                    l_delete=16, k_delete=8, n_copies=2, metric="ip")
    state = _filled(cfg, n=48, seed=dim)
    plain = read_vectors(state.graph.vectors, jnp.arange(cfg.n_cap), dim)
    old = state._replace(graph=state.graph._replace(vectors=plain))
    meta = dict(persist._index_meta(state, cfg, "ip"),
                schema=persist.PLAIN_TABLE_SCHEMA)
    mgr = CheckpointManager(tmp_path)
    mgr.save(5, old, extra={"index": meta, "user": {}})
    if dim != ROW_LANES:
        with pytest.raises(CheckpointMismatchError, match="schema 1"):
            restore_index(mgr, cfg)
        return
    step, got, _ = restore_index(mgr, cfg)
    assert step == 5
    _trees_equal(state, got)
    big = dataclasses.replace(cfg, n_cap=256)
    _, grown, _ = restore_index(mgr, big)
    assert grown.graph.vectors.shape == (256, ROW_LANES)
    np.testing.assert_array_equal(
        np.asarray(read_vectors(grown.graph.vectors, jnp.arange(128), dim)),
        np.asarray(plain))
