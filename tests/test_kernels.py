"""Per-kernel allclose vs the pure-jnp oracle, with shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st

from repro.kernels import ops
from repro.kernels.ref import (
    gather_distance_batched_ref,
    gather_distance_ref,
    topk_score_ref,
)


def _data(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, d)).astype(np.float32)


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("n,d,k", [(64, 16, 8), (200, 100, 33), (128, 128, 128)])
def test_gather_distance_matches_ref(metric, n, d, k):
    rng = np.random.default_rng(1)
    vecs = jnp.asarray(_data(n, d))
    q = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, n, size=(k,)).astype(np.int32))
    got = ops.gather_distances(ids, q, vecs, metric=metric, interpret=True)
    want = gather_distance_ref(ids, q, vecs, metric=metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=1e-5)


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("n,d,b,k", [(64, 16, 3, 8), (200, 100, 5, 33),
                                     (128, 128, 1, 128)])
def test_gather_distance_batched_matches_ref(metric, n, d, b, k):
    """The 2-D-grid kernel equals the oracle and the per-lane 1-D kernel."""
    rng = np.random.default_rng(4)
    vecs = jnp.asarray(_data(n, d))
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, n, size=(b, k)).astype(np.int32))
    norms = jnp.sum(vecs * vecs, axis=1)
    got = ops.gather_distances_batched(ids, qs, vecs, norms, metric=metric,
                                       interpret=True)
    want = gather_distance_batched_ref(ids, qs, vecs, metric=metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=1e-5)
    for lane in range(b):
        lane_1d = ops.gather_distances(ids[lane], qs[lane], vecs, norms,
                                       metric=metric, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[lane]),
                                      np.asarray(lane_1d))


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("d", [100])
def test_gather_distance_row_table_matches_ref(metric, d):
    """The kernels over the index's table of 128-lane rows (one zero-padded
    row a vector, the query zero-padded to the row) equal the oracle over
    the plain table; INVALID ids come back +inf, at full and short tiles."""
    from repro.core.types import write_vectors

    rng = np.random.default_rng(d + 1)     # queries apart from the rows
    n, b = 150, 3
    vecs = _data(n, d, seed=d)
    rows = write_vectors(jnp.zeros((n, 128)), jnp.arange(n),
                         jnp.asarray(vecs), d)
    vecs = jnp.asarray(vecs)
    norms = jnp.sum(vecs * vecs, axis=1)
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    qs_rows = jnp.pad(qs, ((0, 0), (0, 128 - d)))
    for k in (70, 3):
        ids = jnp.asarray(rng.integers(-1, n, size=(b, k)).astype(np.int32))
        ids = ids.at[:, 0].set(-1)
        want = gather_distance_batched_ref(ids, qs, vecs, metric=metric)
        for nrm in (norms, None):
            got = ops.gather_distances_batched(ids, qs_rows, rows, nrm,
                                               metric=metric, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-4)
            assert np.all(np.isinf(np.asarray(got)[:, 0]))
            for lane in range(b):
                lane_1d = ops.gather_distances(ids[lane], qs_rows[lane], rows,
                                               nrm, metric=metric,
                                               interpret=True)
                np.testing.assert_array_equal(np.asarray(got[lane]),
                                              np.asarray(lane_1d))


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("d", [256, 768])
def test_pallas_backend_gathers_multi_row_vectors(metric, d):
    """At several table rows a vector the pallas backend takes the jnp
    engine's XLA row gather (the kernel takes one row a vector): the same
    distances, bit for bit, equal to the oracle over the plain table."""
    from repro.core import ANNConfig, init_state
    from repro.core.backend import get_backend
    from repro.core.types import write_vectors

    rng = np.random.default_rng(d)
    n, b, k = 150, 3, 70
    cfg = ANNConfig(dim=d, n_cap=n, metric=metric)
    vecs = jnp.asarray(_data(n, d, seed=d))
    st = init_state(cfg)
    st = st._replace(vectors=write_vectors(st.vectors, jnp.arange(n), vecs, d),
                     norms=jnp.sum(vecs * vecs, axis=1),
                     active=jnp.ones((n,), bool))
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, n, size=(b, k)).astype(np.int32))
    got = get_backend("pallas").dists_to_ids_batched(st, cfg, qs, ids)
    jnp_d = get_backend("jnp").dists_to_ids_batched(st, cfg, qs, ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(jnp_d))
    want = gather_distance_batched_ref(ids, qs, vecs, metric=metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-4)
    one = get_backend("pallas").dists_to_ids(st, cfg, qs[0], ids[0])
    np.testing.assert_array_equal(np.asarray(one), np.asarray(got[0]))


def test_gather_distance_batched_all_invalid():
    vecs = jnp.asarray(_data(32, 8))
    ids = jnp.full((4, 16), -1, jnp.int32)
    got = ops.gather_distances_batched(ids, jnp.zeros((4, 8)), vecs,
                                       interpret=True)
    assert np.all(np.isinf(np.asarray(got)))


def test_gather_distance_all_invalid():
    vecs = jnp.asarray(_data(32, 8))
    ids = jnp.full((16,), -1, jnp.int32)
    got = ops.gather_distances(ids, jnp.zeros(8), vecs, interpret=True)
    assert np.all(np.isinf(np.asarray(got)))


def _sparse_ids(case, rng, n, b):
    """(B, K) id tiles of the shapes a hop hands the kernel (tile_k 64)."""
    k = {"k96": 96, "k50": 50}.get(case, 64)
    ids = rng.integers(1, n, size=(b, k))
    if case in ("hop_mask", "nan_row0", "k96", "k50"):
        # about 85% INVALID: seen, not navigable or in a finished lane
        ids = np.where(rng.random((b, k)) < 0.85, -1, ids)
    elif case == "dead_lane":
        ids[1] = -1
    elif case == "last_only":
        ids[:, :-1] = -1
    elif case == "repeats":
        ids = np.where(rng.random((b, k)) < 0.3, -1,
                       rng.choice(ids[0, :3], size=(b, k)))
    return jnp.asarray(ids.astype(np.int32))


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("case", ["hop_mask", "dead_lane", "last_only",
                                  "repeats", "k96", "k50", "nan_row0"])
def test_gather_distance_sparse_tiles(metric, case):
    """Tiles of mostly INVALID ids, as a hop sees them: the kernels fetch
    only the ids >= 0, and the batched and the 1-D kernel equal the oracle
    and each other per lane, bit for bit.  A NaN row 0 never reaches an
    INVALID position: those read exactly +inf."""
    rng = np.random.default_rng(16)
    n, d, b = 150, 128, 4
    vecs = _data(n, d, seed=5)
    if case == "nan_row0":
        vecs[0] = np.nan
    vecs = jnp.asarray(vecs)
    norms = jnp.sum(vecs * vecs, axis=1)
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    ids = _sparse_ids(case, rng, n, b)
    invalid = np.asarray(ids) < 0
    want = gather_distance_batched_ref(ids, qs, vecs, metric=metric)
    for nrm in (norms, None):
        got = ops.gather_distances_batched(ids, qs, vecs, nrm, metric=metric,
                                           interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-4)
        assert not np.any(np.isnan(np.asarray(got)))
        assert np.all(np.asarray(got)[invalid] == np.inf)
        for lane in range(b):
            lane_1d = ops.gather_distances(ids[lane], qs[lane], vecs, nrm,
                                           metric=metric, interpret=True)
            np.testing.assert_array_equal(np.asarray(got[lane]),
                                          np.asarray(lane_1d))


@settings(max_examples=12, deadline=None)
@given(
    n=st.integers(8, 96),
    d=st.integers(4, 48),
    k=st.integers(1, 40),
    metric=st.sampled_from(["l2", "ip"]),
    seed=st.integers(0, 100),
)
def test_gather_distance_property(n, d, k, metric, seed):
    rng = np.random.default_rng(seed)
    vecs = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, n, size=(k,)).astype(np.int32))
    got = ops.gather_distances(ids, q, vecs, metric=metric, interpret=True)
    want = gather_distance_ref(ids, q, vecs, metric=metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5,
                               atol=3e-5)


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize(
    "n,d,b,k,tile", [(256, 32, 4, 10, 64), (100, 16, 1, 7, 32), (512, 64, 2, 100, 128)]
)
def test_topk_score_matches_ref(metric, n, d, b, k, tile):
    rng = np.random.default_rng(2)
    vecs = jnp.asarray(_data(n, d, seed=3))
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    norms = jnp.sum(vecs * vecs, axis=1)
    gd, gi = ops.topk_search(qs, vecs, norms, k=k, metric=metric,
                             tile_n=tile, interpret=True)
    wd, wi = topk_score_ref(qs, vecs, norms, k=k, metric=metric)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), rtol=2e-5,
                               atol=1e-5)
    # ids may differ on exact ties; compare as sets per query
    for gq, wq in zip(np.asarray(gi), np.asarray(wi)):
        assert set(gq.tolist()) == set(wq.tolist())


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(16, 200),
    d=st.integers(4, 32),
    b=st.integers(1, 4),
    k=st.integers(1, 16),
    metric=st.sampled_from(["l2", "ip"]),
    seed=st.integers(0, 100),
)
def test_topk_score_property(n, d, b, k, metric, seed):
    k = min(k, n)
    rng = np.random.default_rng(seed)
    vecs = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    gd, gi = ops.topk_search(qs, vecs, k=k, metric=metric, tile_n=64,
                             interpret=True)
    wd, wi = topk_score_ref(qs, vecs, jnp.sum(vecs * vecs, axis=1), k=k,
                            metric=metric)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), rtol=5e-5,
                               atol=5e-5)


def test_kernel_distance_fn_plugs_into_search(small_cfg, small_data):
    """End-to-end: greedy search with the Pallas distance kernel returns the
    same neighbours as the jnp path."""
    from repro.core import StreamingIndex, greedy_search
    from repro.kernels.ops import make_kernel_distance_fn

    data, queries = small_data
    idx = StreamingIndex(small_cfg, max_external_id=len(data))
    idx.insert(np.arange(200), data[:200])
    q = jnp.asarray(queries[0])
    res_jnp = greedy_search(idx.state, small_cfg, q, k=5, l=16)
    res_ker = greedy_search(
        idx.state, small_cfg, q, k=5, l=16,
        distance_fn=make_kernel_distance_fn(interpret=True),
    )
    np.testing.assert_array_equal(
        np.asarray(res_jnp.topk_ids), np.asarray(res_ker.topk_ids)
    )
