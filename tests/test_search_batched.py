"""Batched beam engine vs. per-query greedy search: the parity matrix.

The natively batched engine (core/search_batched.py) replaces
vmap-over-while_loop everywhere, so it must traverse the graph *identically*
lane by lane: same pops, same tie-breaks, same visited accounting, same
comparison/hop counters.  The matrix covers {jnp, pallas, ref} backends x
{l2, ip} metrics x a deliberately nasty batch: duplicate queries, a
tombstoned entry point, and start < 0 empty-graph lanes.  Distances are
compared to f32 tolerance (XLA reduces a batched matmul in a different
order than a matvec); ids and counters must match exactly.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ANNConfig,
    StreamingIndex,
    batched_greedy_search,
    greedy_search,
    init_state,
    make_dataset,
    next_bucket,
    pad_batch,
    search_batch,
    search_batch_vmap,
)
from repro.core.batched import insert_many_batched
from repro.core.search_batched import TRACE_COUNTER
from repro.core.types import read_vectors

BACKENDS = ("jnp", "pallas", "ref")
DIM = 20  # deliberately not a multiple of 128 (nor of 8)

EXACT_FIELDS = ("topk_ids", "visited_ids", "n_visited", "n_comps", "n_hops")


def _cfg(metric, backend="jnp"):
    return ANNConfig(
        dim=DIM, n_cap=256, r=8, l_build=16, l_search=16, l_delete=16,
        k_delete=8, n_copies=2, alpha=1.2, metric=metric, backend=backend,
    )


def _built_index(metric, mode="ip"):
    data, queries = make_dataset(160, DIM, metric, n_queries=8, seed=3)
    idx = StreamingIndex(_cfg(metric), mode=mode, max_external_id=400)
    idx.insert(np.arange(160), data)
    return idx, data, queries


def _assert_lane_parity(res_b, state, cfg, queries, k, l, lane_slice=None):
    """Each lane of ``res_b`` must equal per-query greedy_search exactly."""
    n = queries.shape[0] if lane_slice is None else lane_slice
    for i in range(n):
        res_1 = greedy_search(state, cfg, jnp.asarray(queries[i]), k=k, l=l)
        for field in EXACT_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(res_b, field)[i]),
                np.asarray(getattr(res_1, field)),
                err_msg=f"lane {i} field {field} backend {cfg.backend}",
            )
        np.testing.assert_allclose(
            np.asarray(res_b.topk_dists[i]),
            np.asarray(res_1.topk_dists),
            rtol=2e-5, atol=2e-5, err_msg=f"lane {i} backend {cfg.backend}",
        )


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_matches_per_query(metric, backend):
    idx, data, queries = _built_index(metric)
    cfg = _cfg(metric, backend)
    # ragged batch (B=5) with a duplicated query riding along
    qs = jnp.asarray(
        np.concatenate([queries[:4], queries[:1]], axis=0)
    )
    res_b = batched_greedy_search(idx.state, cfg, qs, k=5, l=16)
    _assert_lane_parity(res_b, idx.state, cfg, qs, k=5, l=16)
    # the duplicate lanes agree with each other exactly
    for field in EXACT_FIELDS + ("topk_dists",):
        np.testing.assert_array_equal(
            np.asarray(getattr(res_b, field)[0]),
            np.asarray(getattr(res_b, field)[4]),
        )


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_with_tombstoned_start(metric, backend):
    """Search parity when the entry point itself is a tombstone."""
    idx, data, queries = _built_index(metric, mode="fresh")
    start = int(idx.state.start)
    ext = int(np.asarray(idx._slot2ext)[start])
    idx.delete(np.array([ext]))
    assert bool(idx.state.tombstone[start]), "start should be tombstoned"
    assert int(idx.state.start) == start, "fresh delete keeps the start"
    cfg = _cfg(metric, backend)
    qs = jnp.asarray(queries[:3])
    res_b = batched_greedy_search(idx.state, cfg, qs, k=5, l=16)
    _assert_lane_parity(res_b, idx.state, cfg, qs, k=5, l=16)
    # tombstones are navigated but never returned
    ids = np.asarray(res_b.topk_ids)
    assert not (ids == start).any()


@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_empty_graph_lanes(backend):
    """start < 0: every lane terminates instantly with INVALID results."""
    cfg = _cfg("l2", backend)
    state = init_state(cfg)
    qs = jnp.zeros((3, DIM), jnp.float32)
    res = batched_greedy_search(state, cfg, qs, k=5, l=16)
    assert np.all(np.asarray(res.topk_ids) == -1)
    assert np.all(np.asarray(res.n_comps) == 0)
    assert np.all(np.asarray(res.n_hops) == 0)
    assert np.all(np.asarray(res.n_visited) == 0)
    _assert_lane_parity(res, state, cfg, qs, k=5, l=16)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_backends_agree_on_batched_ids(metric):
    idx, _, queries = _built_index(metric)
    qs = jnp.asarray(queries)
    out = {}
    for name in BACKENDS:
        res = batched_greedy_search(idx.state, _cfg(metric, name), qs,
                                    k=5, l=16)
        out[name] = np.asarray(res.topk_ids)
    np.testing.assert_array_equal(out["pallas"], out["jnp"])
    np.testing.assert_array_equal(out["ref"], out["jnp"])


def test_search_batch_matches_vmap_baseline():
    """The engine behind search_batch returns what the old vmap path did."""
    idx, _, queries = _built_index("l2")
    cfg = _cfg("l2")
    qs = jnp.asarray(queries)
    res_new = search_batch(idx.state, cfg, qs, k=5, l=16)
    res_old = search_batch_vmap(idx.state, cfg, qs, k=5, l=16)
    for field in EXACT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(res_new, field)),
            np.asarray(getattr(res_old, field)),
        )


# ---------------------------------------------------------------------------
# batch-size bucketing
# ---------------------------------------------------------------------------


def test_next_bucket_and_pad_batch():
    assert [next_bucket(b) for b in (1, 2, 3, 5, 8, 9, 64, 65)] == [
        1, 2, 4, 8, 8, 16, 64, 128,
    ]
    x = jnp.ones((5, 3))
    padded = pad_batch(x, 5)
    assert padded.shape == (8, 3)
    assert np.all(np.asarray(padded[5:]) == 0)
    assert pad_batch(x[:4], 4) is x[:4] or pad_batch(x[:4], 4).shape == (4, 3)


def test_pad_batch_fill_by_dtype():
    """Padding must not invent valid payloads: integer lanes pad with
    INVALID (a 0 fill is slot id 0, a real slot), bools with False,
    floats with 0.0 — and an explicit ``fill`` always wins."""
    from repro.core.types import INVALID

    ids = jnp.array([[3, 4], [5, 6], [7, 8]], jnp.int32)
    padded = pad_batch(ids, 3)
    assert padded.shape == (4, 2)
    assert np.all(np.asarray(padded[3:]) == INVALID)

    valid = jnp.array([True, True, True])
    pv = pad_batch(valid, 3)
    assert pv.dtype == jnp.bool_
    assert not np.asarray(pv[3:]).any()

    qs = jnp.ones((3, 5), jnp.float32)
    assert np.all(np.asarray(pad_batch(qs, 3)[3:]) == 0.0)

    forced = pad_batch(ids, 3, fill=-7)
    assert np.all(np.asarray(forced[3:]) == -7)


def test_ragged_batches_share_one_compile():
    """B in {5, 6, 7} all ride the B=8 bucket: exactly one trace."""
    data, queries = make_dataset(120, 17, "l2", n_queries=8, seed=11)
    cfg = ANNConfig(dim=17, n_cap=160, r=8, l_build=16, l_search=16,
                    l_delete=16, k_delete=8, n_copies=2)
    idx = StreamingIndex(cfg, max_external_id=200)
    idx.insert(np.arange(120), data)
    qs = jnp.asarray(queries)

    before = TRACE_COUNTER["batched_greedy_search"]
    for b in (5, 6, 7, 8):
        res = search_batch(idx.state, cfg, qs[:b], k=5, l=16)
        assert res.topk_ids.shape[0] == b
    traces = TRACE_COUNTER["batched_greedy_search"] - before
    assert traces == 1, f"expected one shared trace for the B=8 bucket, got {traces}"


def test_padded_lanes_do_not_change_results():
    idx, _, queries = _built_index("l2")
    cfg = _cfg("l2")
    qs = jnp.asarray(queries[:5])
    res_pad = search_batch(idx.state, cfg, qs, k=5, l=16, bucket=True)
    res_raw = search_batch(idx.state, cfg, qs, k=5, l=16, bucket=False)
    for field in EXACT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(res_pad, field)),
            np.asarray(getattr(res_raw, field)),
        )


def test_insert_many_batched_valid_mask():
    """Masked no-op lanes leave the state exactly as an unpadded batch."""
    data, _ = make_dataset(64, DIM, "l2", n_queries=1, seed=5)
    cfg = _cfg("l2")
    base = init_state(cfg)
    base, _ = insert_many_batched(base, cfg, jnp.asarray(data[:16]))

    xs = jnp.asarray(data[16:19])
    st_plain, stats_plain = insert_many_batched(base, cfg, xs)
    xs_pad = jnp.concatenate([xs, jnp.zeros((5, DIM), jnp.float32)], axis=0)
    valid = jnp.arange(8) < 3
    st_mask, stats_mask = insert_many_batched(base, cfg, xs_pad, valid)

    np.testing.assert_array_equal(
        np.asarray(stats_plain.slot), np.asarray(stats_mask.slot[:3])
    )
    assert np.all(np.asarray(stats_mask.slot[3:]) == -1)
    for a, b in zip(st_plain, st_mask):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_masked_lane_never_clobbers_slot_zero():
    """Padded lanes' clipped scatter index is 0; when a valid lane is handed
    slot 0 in the same batch the masked writes must be dropped, not rewrite
    the stale pre-batch value (duplicate-index scatter order is undefined)."""
    data, _ = make_dataset(16, DIM, "l2", n_queries=1, seed=6)
    cfg = _cfg("l2")
    state = init_state(cfg)
    # a fresh free stack hands out slots b-1..0, so lane 2 gets slot 0 here
    xs_pad = jnp.concatenate(
        [jnp.asarray(data[:3]), jnp.zeros((5, DIM), jnp.float32)], axis=0
    )
    state, stats = insert_many_batched(state, cfg, xs_pad, jnp.arange(8) < 3)
    slots = np.asarray(stats.slot[:3])
    assert 0 in slots.tolist()
    for lane, slot in enumerate(slots):
        np.testing.assert_array_equal(
            np.asarray(read_vectors(state.vectors, slot, DIM)), data[lane],
            err_msg=f"lane {lane} slot {slot} lost its vector",
        )
        np.testing.assert_allclose(
            float(state.norms[slot]), float((data[lane] ** 2).sum()),
            rtol=1e-6,
        )


def test_graph_recall_matches_index_recall():
    from repro.core import graph_recall

    idx, _, queries = _built_index("l2")
    qs = jnp.asarray(queries)
    r_state = graph_recall(idx.state, idx.cfg, qs, k=5, l=16)
    r_index = idx.recall(queries, k=5, l=16)
    assert r_state == pytest.approx(r_index, abs=1e-9)


def test_streaming_index_ragged_batched_inserts():
    """Ragged insert batches ride the padded batched path end to end."""
    data, queries = make_dataset(200, DIM, "l2", n_queries=4, seed=8)
    idx = StreamingIndex(_cfg("l2"), max_external_id=300, batch_updates=True)
    # bootstrap + windows + a ragged 37-point tail
    idx.insert(np.arange(163), data[:163])
    idx.insert(np.arange(163, 200), data[163:])
    assert idx.n_active == 200
    r = idx.recall(queries, k=5)
    assert r >= 0.9, r
