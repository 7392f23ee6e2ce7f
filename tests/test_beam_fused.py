"""Fused multi-hop beam engine: the hop_fused parity matrix.

The super-step engines (generic H-composed hop body; fused Pallas kernel)
must leave traversal LANE-EXACT against the unfused engine: grouping hops
only changes how often the while_loop predicate is evaluated, never which
vertices are popped, compared, visited or returned.  Within one backend
that parity is bitwise — distances included — because every hop runs the
same ops in the same shapes.  The matrix covers {jnp, pallas, ref} x
{l2, ip} x {duplicate neighbour ids, tombstoned entry point, masked/empty
lanes, H not dividing the total hop count}, plus bitpacked-seen property
tests (``core/bitset.py``) and kernel-vs-oracle parity for
``kernels/beam_hop.py``.  ``N_CAP`` is deliberately NOT a multiple of 32
so the packed bitmap's tail word is always in play.
"""
import dataclasses
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ANNConfig,
    StreamingIndex,
    batched_greedy_search,
    bitset,
    greedy_search,
    init_state,
    make_dataset,
    resolved_hop_fused,
)
from repro.core.search_batched import DEFAULT_FUSED_HOPS

BACKENDS = ("jnp", "pallas", "ref")
DIM = 20
N_CAP = 250  # not a multiple of 32 (nor of 8)

EXACT_FIELDS = (
    "topk_ids", "topk_dists", "visited_ids", "visited_dists",
    "n_visited", "n_comps", "n_hops",
)
ID_FIELDS = ("topk_ids", "visited_ids", "n_visited", "n_comps", "n_hops")


def _cfg(metric, backend="jnp", hop_fused=-1):
    return ANNConfig(
        dim=DIM, n_cap=N_CAP, r=8, l_build=16, l_search=16, l_delete=16,
        k_delete=8, n_copies=2, alpha=1.2, metric=metric, backend=backend,
        hop_fused=hop_fused,
    )


@functools.lru_cache(maxsize=None)
def _built(metric, mode="ip"):
    data, queries = make_dataset(140, DIM, metric, n_queries=6, seed=3)
    idx = StreamingIndex(_cfg(metric, "jnp", 0), mode=mode,
                        max_external_id=400)
    idx.insert(np.arange(140), data)
    return idx, queries


def _assert_fused_equals_unfused(state, metric, backend, h, qs, k=5, l=16,
                                 valid=None):
    """hop_fused=h must be bitwise identical to hop_fused=0 on ``backend``
    (same backend => same ops per hop => same floats)."""
    base = batched_greedy_search(
        state, _cfg(metric, backend, 0), qs, k=k, l=l, valid=valid
    )
    res = batched_greedy_search(
        state, _cfg(metric, backend, h), qs, k=k, l=l, valid=valid
    )
    for field in EXACT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(res, field)),
            np.asarray(getattr(base, field)),
            err_msg=f"{backend} {metric} H={h} field {field}",
        )
    return res


# ---------------------------------------------------------------------------
# the parity matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_matches_unfused(metric, backend):
    """H=3 never divides the total hop count evenly here: the last
    super-step runs masked no-op hops past lane convergence."""
    idx, queries = _built(metric)
    qs = jnp.asarray(queries[:4])
    res = _assert_fused_equals_unfused(idx.state, metric, backend, 3, qs)
    # and the per-query engine (bool seen, one hop per iteration) agrees
    # lane by lane on ids and counters
    cfg0 = _cfg(metric, backend, 0)
    for i in range(4):
        ref = greedy_search(idx.state, cfg0, qs[i], k=5, l=16)
        for field in ID_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(res, field)[i]),
                np.asarray(getattr(ref, field)),
                err_msg=f"lane {i} field {field}",
            )


@pytest.mark.parametrize("h", [1, 2, 5, DEFAULT_FUSED_HOPS])
def test_fused_h_sweep(h):
    idx, queries = _built("l2")
    qs = jnp.asarray(queries)
    _assert_fused_equals_unfused(idx.state, "l2", "jnp", h, qs)


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_duplicate_neighbours(backend):
    """Adjacency rows carrying the same id twice: both copies pass the
    fresh-mask in one hop (seen is updated after), so the packed scatter-OR
    must stay exact under in-row duplicates."""
    idx, queries = _built("l2")
    state = idx.state
    adj = np.asarray(state.adj).copy()
    rows = np.nonzero((adj[:, 0] >= 0) & (adj[:, 1] >= 0))[0]
    assert rows.size > 50
    adj[rows, 1] = adj[rows, 0]
    state = state._replace(adj=jnp.asarray(adj))
    qs = jnp.asarray(queries[:4])
    res = _assert_fused_equals_unfused(state, "l2", backend, 3, qs)
    for i in range(4):
        ref = greedy_search(state, _cfg("l2", backend, 0), qs[i], k=5, l=16)
        for field in ID_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(res, field)[i]),
                np.asarray(getattr(ref, field)),
                err_msg=f"dup-adj lane {i} field {field}",
            )


@functools.lru_cache(maxsize=None)
def _built_tombstoned_start():
    idx, queries = _built("l2", mode="fresh")
    start = int(idx.state.start)
    ext = int(np.asarray(idx._slot2ext)[start])
    idx.delete(np.array([ext]))
    return idx, queries, start


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_tombstoned_start(backend):
    idx, queries, start = _built_tombstoned_start()
    assert bool(idx.state.tombstone[start])
    qs = jnp.asarray(queries[:3])
    res = _assert_fused_equals_unfused(idx.state, "l2", backend, 4, qs)
    assert not (np.asarray(res.topk_ids) == start).any()


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_masked_and_empty_lanes(backend):
    """valid=False lanes and an all-empty batch are no-ops under fusion
    exactly as without it."""
    idx, queries = _built("l2")
    qs = jnp.asarray(queries[:4])
    valid = jnp.asarray([True, False, True, False])
    res = _assert_fused_equals_unfused(
        idx.state, "l2", backend, 3, qs, valid=valid
    )
    for i in (1, 3):
        assert np.all(np.asarray(res.topk_ids[i]) == -1)
        assert int(res.n_comps[i]) == 0
        assert int(res.n_hops[i]) == 0
        assert int(res.n_visited[i]) == 0
    # empty graph: every lane exits before the first super-step
    empty = init_state(_cfg("l2"))
    res_e = _assert_fused_equals_unfused(
        empty, "l2", backend, 3, jnp.zeros((3, DIM), jnp.float32)
    )
    assert np.all(np.asarray(res_e.topk_ids) == -1)
    assert np.all(np.asarray(res_e.n_hops) == 0)


def test_hop_fused_auto_selection():
    """-1 resolves to unfused on every backend (the fused kernel does not
    compile with Mosaic yet); explicit values always win."""
    assert resolved_hop_fused(_cfg("l2", "jnp")) == 0
    assert resolved_hop_fused(_cfg("l2", "ref")) == 0
    assert resolved_hop_fused(_cfg("l2", "pallas")) == 0
    assert resolved_hop_fused(_cfg("l2", "jnp", 5)) == 5
    assert resolved_hop_fused(_cfg("l2", "pallas", 0)) == 0
    assert resolved_hop_fused(_cfg("l2", "pallas", 2)) == 2
    with pytest.raises(AssertionError):
        _cfg("l2", "jnp", -2)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_fused_request_refused_under_mosaic(monkeypatch, metric):
    """An explicit hop_fused > 0 on the pallas engine, with the kernels
    compiled by Mosaic, raises a clear error naming the knob instead of
    silently running another engine."""
    from repro.core import backend as backend_mod

    monkeypatch.setattr(backend_mod.PallasBackend, "interpret", False)
    cfg = _cfg(metric, "pallas", DEFAULT_FUSED_HOPS)
    state = init_state(cfg)
    with pytest.raises(NotImplementedError, match="hop_fused"):
        batched_greedy_search(state, cfg, jnp.zeros((2, DIM)), k=5, l=16)


@pytest.mark.parametrize("h", [0, DEFAULT_FUSED_HOPS])
def test_quantized_refused_under_mosaic(monkeypatch, h):
    """The int8 gather kernel's single-row DMAs do not compile with Mosaic:
    quantized=True on the pallas engine refuses there, naming the knob."""
    from repro.core import backend as backend_mod

    monkeypatch.setattr(backend_mod.PallasBackend, "interpret", False)
    cfg = dataclasses.replace(_cfg("l2", "pallas", h), quantized=True)
    state = init_state(cfg)
    with pytest.raises(NotImplementedError, match="quantized"):
        batched_greedy_search(state, cfg, jnp.zeros((2, DIM)), k=5, l=16)


# ---------------------------------------------------------------------------
# kernel vs. oracle (kernels layer, synthetic carries)
# ---------------------------------------------------------------------------


def test_beam_hop_kernel_matches_ref_oracle():
    from repro.kernels import ops
    from repro.kernels.beam_hop import beam_hop_ref

    rng = np.random.default_rng(7)
    n_cap, r, d, b, l, mv, w = 70, 6, 9, 5, 8, 12, bitset.n_words(70)
    vectors = rng.standard_normal((n_cap, d)).astype(np.float32)
    norms = (vectors ** 2).sum(axis=1).astype(np.float32)
    adj = rng.integers(-1, n_cap, (n_cap, r)).astype(np.int32)
    active = rng.random(n_cap) < 0.8
    tomb = ~active & (rng.random(n_cap) < 0.5)
    nav_words = bitset.pack_bits(jnp.asarray(active | tomb))
    ret_words = bitset.pack_bits(jnp.asarray(active))
    queries = rng.standard_normal((b, d)).astype(np.float32)

    beam_ids = rng.integers(-1, n_cap, (b, l)).astype(np.int32)
    beam_dists = np.where(
        beam_ids >= 0, rng.random((b, l)).astype(np.float32), np.inf
    ).astype(np.float32)
    beam_exp = (rng.random((b, l)) < 0.4).astype(np.int32)
    seen = rng.integers(0, 2 ** 32, (b, w), dtype=np.uint32)
    vis_ids = np.full((b, mv), -1, np.int32)
    vis_dists = np.full((b, mv), np.inf, np.float32)
    n_vis = np.zeros((b,), np.int32)
    n_comps = rng.integers(0, 50, (b,)).astype(np.int32)
    n_hops = np.array([0, 3, mv, 1, mv - 1], np.int32)  # incl. at-bound lanes

    args = [jnp.asarray(a) for a in (
        queries, beam_ids, beam_dists, beam_exp, seen, vis_ids, vis_dists,
        n_vis, n_comps, n_hops, adj, vectors, norms,
    )] + [nav_words, ret_words]
    for metric in ("l2", "ip"):
        for h in (1, 3):
            out_k = ops.beam_hop(*args, metric=metric, h=h, interpret=True)
            out_r = beam_hop_ref(*args, metric=metric, h=h)
            for j, (a, c) in enumerate(zip(out_k, out_r)):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(c),
                    err_msg=f"metric {metric} h={h} output {j}",
                )


# ---------------------------------------------------------------------------
# bitpacked seen properties (bitpacked vs. bool reference)
# ---------------------------------------------------------------------------


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    for n in (1, 31, 32, 33, 250, 256):
        bits = rng.random((4, n)) < 0.3
        packed = bitset.pack_bits(jnp.asarray(bits))
        assert packed.shape == (4, bitset.n_words(n))
        assert packed.dtype == jnp.uint32
        np.testing.assert_array_equal(
            np.asarray(bitset.unpack_rows(packed, n)), bits
        )


def test_setbits_rows_matches_bool_reference():
    """Property test: packed scatter-OR == the bool bitmap's idempotent
    ``.set(True)``, including duplicate ids within a row and n (bitmap
    width) not divisible by 32."""
    rng = np.random.default_rng(1)
    for _ in range(8):
        n = int(rng.integers(33, 300))
        b, k = 4, 9
        base = rng.random((b, n)) < 0.2
        ids = rng.integers(0, n, (b, k)).astype(np.int32)
        ids[:, 1] = ids[:, 0]          # forced in-row duplicate
        ids[0, 2] = ids[0, 0]          # triplicate on row 0
        mask = rng.random((b, k)) < 0.7
        packed = bitset.setbits_rows(
            bitset.pack_bits(jnp.asarray(base)),
            jnp.asarray(ids), jnp.asarray(mask),
        )
        ref = base.copy()
        for i in range(b):
            for j in range(k):
                if mask[i, j]:
                    ref[i, ids[i, j]] = True
        np.testing.assert_array_equal(
            np.asarray(bitset.unpack_rows(packed, n)), ref, err_msg=f"n={n}"
        )
        # the row-aligned bit test sees exactly the bool gather's values
        np.testing.assert_array_equal(
            np.asarray(bitset.getbit_rows(packed, jnp.asarray(ids))),
            ref[np.arange(b)[:, None], ids],
        )
        # tail bits past n stay clear (packed compare needs no masking)
        np.testing.assert_array_equal(
            np.asarray(packed),
            np.asarray(bitset.pack_bits(jnp.asarray(ref))),
        )


def test_getbit_1d_masks():
    rng = np.random.default_rng(2)
    n = 250
    mask = rng.random(n) < 0.5
    words = bitset.pack_bits(jnp.asarray(mask))
    ids = rng.integers(0, n, (3, 7)).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(bitset.getbit(words, jnp.asarray(ids))), mask[ids]
    )
    assert bool(bitset.getbit(words, jnp.int32(int(np.argmax(mask)))))


def test_empty_rows_shape():
    assert bitset.empty_rows(3, 33).shape == (3, 2)
    assert bitset.empty_rows(1, 32).shape == (1, 1)
    assert int(bitset.empty_rows(2, 65).sum()) == 0
