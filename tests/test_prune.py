"""RobustPrune vs numpy oracle + properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st

from oracles import robust_prune_oracle
from repro.core import ANNConfig, init_state, robust_prune
from repro.core import prune as prune_mod
from repro.core.types import INVALID, write_vectors


def _mk_state(cfg, vecs, active=None):
    n = vecs.shape[0]
    state = init_state(cfg)
    active = np.ones(n, bool) if active is None else active
    return state._replace(
        vectors=write_vectors(state.vectors, jnp.arange(n), jnp.asarray(vecs),
                              cfg.dim),
        norms=state.norms.at[:n].set(jnp.asarray((vecs * vecs).sum(1))),
        active=state.active.at[:n].set(jnp.asarray(active)),
    )


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prune_matches_oracle(metric, seed):
    rng = np.random.default_rng(seed)
    n, dim, r, c = 80, 16, 8, 40
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    if metric == "ip":
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    cfg = ANNConfig(dim=dim, n_cap=n, r=r, metric=metric, alpha=1.2)
    state = _mk_state(cfg, vecs)
    p_vec = rng.normal(size=(dim,)).astype(np.float32)
    if metric == "ip":
        p_vec /= np.linalg.norm(p_vec)
    cand = rng.integers(-1, n, size=(c,)).astype(np.int32)

    got = np.asarray(robust_prune(state, cfg, jnp.asarray(p_vec), jnp.asarray(cand)))
    got = [int(x) for x in got if x >= 0]
    want = robust_prune_oracle(
        metric, 1.2, r, p_vec, cand, vecs, np.ones(n, bool)
    )
    assert got == want


def test_prune_respects_degree_and_dedup():
    rng = np.random.default_rng(3)
    n, dim, r = 64, 8, 6
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    cfg = ANNConfig(dim=dim, n_cap=n, r=r)
    state = _mk_state(cfg, vecs)
    cand = np.concatenate([np.arange(20), np.arange(20)]).astype(np.int32)
    out = np.asarray(robust_prune(state, cfg, jnp.asarray(vecs[0]), jnp.asarray(cand), p_id=0))
    valid = out[out >= 0]
    assert len(valid) <= r
    assert len(set(valid.tolist())) == len(valid)
    assert 0 not in valid  # p excluded


def test_prune_drops_dead_slots():
    rng = np.random.default_rng(4)
    n, dim, r = 32, 8, 8
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    active = np.ones(n, bool)
    active[5:15] = False
    cfg = ANNConfig(dim=dim, n_cap=n, r=r)
    state = _mk_state(cfg, vecs, active)
    cand = np.arange(n).astype(np.int32)
    out = np.asarray(robust_prune(state, cfg, jnp.asarray(vecs[0]), jnp.asarray(cand), p_id=0))
    valid = set(out[out >= 0].tolist())
    assert not valid.intersection(range(5, 15))


def test_alpha_one_keeps_fewer_or_equal_edges():
    """alpha > 1 relaxes occlusion, so it must keep at least as many edges."""
    rng = np.random.default_rng(5)
    n, dim, r = 128, 12, 16
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    p = rng.normal(size=(dim,)).astype(np.float32)
    cand = np.arange(n).astype(np.int32)
    counts = {}
    for alpha in (1.0, 1.2, 2.0):
        cfg = ANNConfig(dim=dim, n_cap=n, r=r, alpha=alpha)
        state = _mk_state(cfg, vecs)
        out = np.asarray(robust_prune(state, cfg, jnp.asarray(p), jnp.asarray(cand)))
        counts[alpha] = int((out >= 0).sum())
    assert counts[1.0] <= counts[1.2] <= counts[2.0]


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_prune_property_first_is_nearest(seed):
    """The first retained edge is always the closest live candidate."""
    rng = np.random.default_rng(seed)
    n, dim, r = 40, 8, 8
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    cfg = ANNConfig(dim=dim, n_cap=n, r=r)
    state = _mk_state(cfg, vecs)
    p = rng.normal(size=(dim,)).astype(np.float32)
    cand = rng.choice(n, size=20, replace=False).astype(np.int32)
    out = np.asarray(robust_prune(state, cfg, jnp.asarray(p), jnp.asarray(cand)))
    d = ((vecs[cand] - p) ** 2).sum(1)
    assert out[0] == cand[np.argmin(d)]


# -- the block path (C <= BLOCK_MAX) and the loop path, case by case -------

def _basis_case(rng, r, dim):
    """r + 1 candidates no one occludes (scaled basis vectors around p = 0),
    so the degree cap alone stops the selection, at exactly r."""
    c = r + 1
    vecs = np.zeros((c, dim), np.float32)
    vecs[np.arange(c), np.arange(c)] = 1.0 + 0.01 * rng.permutation(c)
    return vecs, np.zeros(dim, np.float32), np.arange(c, dtype=np.int32), None


def _cluster_case(rng, r, dim):
    """A tight cluster far from p: the nearest candidate occludes the rest."""
    c = r + 1
    centre = np.full(dim, 10.0 / np.sqrt(dim), np.float32)
    vecs = centre + 1e-2 * rng.normal(size=(c, dim)).astype(np.float32)
    return vecs, np.zeros(dim, np.float32), np.arange(c, dtype=np.int32), None


def _random_case(rng, r, dim, c=None, invalid=0.1):
    c = r + 1 if c is None else c
    n = 2 * c
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    cand = rng.choice(n, size=c, replace=False).astype(np.int32)
    cand[rng.random(c) < invalid] = INVALID
    return vecs, rng.normal(size=dim).astype(np.float32), cand, None


def _twin_case(rng, r, dim):
    """The same vector at two slots: a tie in d_p, the lower index first."""
    vecs, p, cand, _ = _random_case(rng, r, dim, invalid=0.0)
    vecs[cand[3]] = vecs[cand[1]]
    vecs[cand[7]] = vecs[cand[5]]
    return vecs, p, cand, None


def _given_case(rng, r, dim):
    """``cand_dists`` with inf entries (recomputed) and ties among the
    finite ones (visited in index order)."""
    vecs, p, cand, _ = _random_case(rng, r, dim)
    dists = np.full(len(cand), np.inf, np.float32)
    pick = rng.random(len(cand)) < 0.5
    dists[pick] = rng.choice([0.5, 1.0, 2.0], size=int(pick.sum()))
    return vecs, p, cand, dists


def _invalid_case(rng, r, dim):
    vecs, p, cand, _ = _random_case(rng, r, dim)
    return vecs, p, np.full_like(cand, INVALID), None


CASES = {
    "random": _random_case, "cap": _basis_case, "one_occludes_all": _cluster_case,
    "twins": _twin_case, "given_dists": _given_case, "all_invalid": _invalid_case,
}


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("alpha", [1.0, 1.2])
@pytest.mark.parametrize("r", [8, 32, 64])
@pytest.mark.parametrize("case", sorted(CASES))
def test_block_prune_matches_oracle(case, r, alpha, metric):
    """C = r + 1 (every edge append's width) takes the block path and gives
    the oracle's ids in the oracle's order."""
    rng = np.random.default_rng(
        [r, int(alpha * 10), metric == "ip", sorted(CASES).index(case)])
    dim = 80 if case == "cap" else 16
    vecs, p, cand, dists = CASES[case](rng, r, dim)
    if metric == "ip":
        vecs = vecs / np.maximum(np.linalg.norm(vecs, axis=1, keepdims=True),
                                 1e-6)
    n = vecs.shape[0]
    cfg = ANNConfig(dim=dim, n_cap=n, r=r, metric=metric, alpha=alpha)
    assert len(cand) <= prune_mod.BLOCK_MAX
    state = _mk_state(cfg, vecs)
    got = np.asarray(robust_prune(
        state, cfg, jnp.asarray(p), jnp.asarray(cand),
        None if dists is None else jnp.asarray(dists)))
    want = robust_prune_oracle(metric, alpha, r, p, cand, vecs,
                               np.ones(n, bool), cand_dists=dists)
    assert [int(x) for x in got if x >= 0] == want
    assert (got[len(want):] == INVALID).all()
    if case == "cap" and metric == "l2":
        assert len(want) == r
    if case == "one_occludes_all" and metric == "l2":
        assert len(want) == 1


@pytest.mark.parametrize("c", [129, 192])
def test_loop_prune_matches_oracle(c):
    """Wider candidate sets (the insert's visited list, consolidation's
    splice) keep the loop and give the oracle's ids in order."""
    rng = np.random.default_rng(c)
    r, dim = 16, 16
    vecs, p, cand, _ = _random_case(rng, r, dim, c=c)
    n = vecs.shape[0]
    cfg = ANNConfig(dim=dim, n_cap=n, r=r, alpha=1.2)
    got = np.asarray(robust_prune(_mk_state(cfg, vecs), cfg, jnp.asarray(p),
                                  jnp.asarray(cand)))
    want = robust_prune_oracle("l2", 1.2, r, p, cand, vecs, np.ones(n, bool))
    assert [int(x) for x in got if x >= 0] == want


@pytest.mark.parametrize("c,loop", [
    (33, False),           # an edge append at R 32
    (65, False),           # an edge append at R 64
    (128, False),          # the insert's visited list at L 64
    (192, True),           # the insert's visited list at L 128
    (64 + 64 * 64, True),  # consolidation's splice at R 64
])
def test_prune_path_follows_width(c, loop):
    """The candidate width alone picks the path: the lowered program holds a
    while loop above BLOCK_MAX and none at or below it."""
    cfg = ANNConfig(dim=128, n_cap=1024, r=64, alpha=1.2)
    state = jax.eval_shape(lambda: init_state(cfg))
    text = robust_prune.lower(
        state, cfg, jax.ShapeDtypeStruct((128,), jnp.float32),
        jax.ShapeDtypeStruct((c,), jnp.int32),
    ).as_text()
    assert ("stablehlo.while" in text) == loop


def test_block_prune_occlusion_product_at_full_precision():
    """The block's (C, C) inner products ask for HIGHEST precision: on TPU a
    default-precision product rounds its operands to bfloat16, where the
    loop's matvec (a float32 multiply and sum there) does not."""
    cfg = ANNConfig(dim=128, n_cap=1024, r=64, alpha=1.2)
    state = jax.eval_shape(lambda: init_state(cfg))
    text = robust_prune.lower(
        state, cfg, jax.ShapeDtypeStruct((128,), jnp.float32),
        jax.ShapeDtypeStruct((65,), jnp.int32),
    ).as_text()
    dots = [ln for ln in text.splitlines() if "stablehlo.dot_general" in ln
            and "tensor<65x128xf32>, tensor<128x65xf32>" in ln]
    assert dots and all("HIGHEST" in ln for ln in dots), dots


@pytest.mark.parametrize("c", [65, 192])
def test_ip_prune_on_unit_vectors_selects_as_l2(c):
    """A cluster of unit vectors with near-equal products (a full-rank
    mixture at 768-d): under ``ip`` alpha > 1 must keep more, not prune
    harder, so the ip prune keeps what the l2 prune keeps on the same unit
    vectors -- a full row, where a test on negated products keeps one or
    two -- on both the block (65) and the loop (192) paths."""
    rng = np.random.default_rng(c)
    r, dim = 16, 768
    centre = rng.normal(size=(dim,)).astype(np.float32)
    pts = centre + 0.35 * rng.normal(size=(c + 1, dim)).astype(np.float32)
    pts /= np.linalg.norm(pts, axis=1, keepdims=True)
    vecs, p = pts[:c], pts[c]
    cand = np.arange(c, dtype=np.int32)
    rows = {}
    for metric in ("l2", "ip"):
        cfg = ANNConfig(dim=dim, n_cap=c, r=r, metric=metric, alpha=1.2)
        got = np.asarray(robust_prune(_mk_state(cfg, vecs), cfg,
                                      jnp.asarray(p), jnp.asarray(cand)))
        rows[metric] = [int(x) for x in got if x >= 0]
        assert rows[metric] == robust_prune_oracle(
            metric, 1.2, r, p, cand, vecs, np.ones(c, bool))
    assert rows["ip"] == rows["l2"]
    assert len(rows["ip"]) == r
