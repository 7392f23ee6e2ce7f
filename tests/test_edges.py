"""``append_edges`` against appending the same edges one at a time."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oracles import append_edges_oracle
from repro.core import ANNConfig, init_state
from repro.core.edges import LANES, append_edges
from repro.core.types import INVALID, write_vectors

N, DIM, R = 192, 16, 8


def _graph(rng, fill, metric):
    """Random vectors, 16 dead and 16 tombstoned slots, and rows of ``fill``
    live out-neighbours each (a count, or a range to draw from)."""
    vecs = rng.normal(size=(N, DIM)).astype(np.float32)
    if metric == "ip":
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    slots = rng.permutation(N)
    dead, tomb = slots[:16], slots[16:32]
    active = np.ones(N, bool)
    active[dead] = active[tomb] = False
    tombstone = np.zeros(N, bool)
    tombstone[tomb] = True
    live = np.flatnonzero(active | tombstone)
    adj = np.full((N, R), INVALID, np.int32)
    for v in live:
        k = fill if np.isscalar(fill) else rng.integers(*fill)
        nbrs = rng.choice(live[live != v], size=k, replace=False)
        adj[v, :k] = nbrs
    return vecs, adj, active, tombstone


def _edges(rng, case, live, dead):
    if case == "wide_round":
        # more than LANES rows in one round, so a round takes several batches
        vs = rng.choice(live, size=LANES + 40, replace=False)
        return vs, rng.choice(live, size=vs.size)
    if case == "one_row":
        # one row receives 30 edges (30 rounds), among others
        vs = np.concatenate([np.full(30, live[0]), rng.choice(live, size=30)])
        return rng.permutation(vs), rng.choice(live, size=60)
    if case == "degenerate":
        vs, us = rng.choice(live, size=48), rng.choice(live, size=48)
        vs[:4] = INVALID                      # no source
        us[4:8] = INVALID                     # no target
        us[8:12] = vs[8:12]                   # self loops
        vs[12:16], us[12:16] = vs[16:20], us[16:20]   # the same edge twice
        us[20:24] = rng.choice(dead, size=4)  # dead ends
        vs[24:28] = rng.choice(dead, size=4)  # from a dead slot
        return vs, us
    return rng.choice(live, size=120), rng.choice(live, size=120)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _append(state, cfg, vs, us):
    return append_edges(state, cfg, vs, us).adj


@pytest.mark.parametrize("seed,case,fill,metric", [
    (0, "mixed", 0, "l2"),               # no row full
    (1, "mixed", (R - 2, R + 1), "l2"),  # some rows full
    (2, "mixed", R, "l2"),               # every row full
    (3, "mixed", R, "ip"),
    (4, "wide_round", (R - 1, R + 1), "l2"),
    (5, "one_row", (R - 3, R + 1), "l2"),
    (6, "degenerate", (R - 1, R + 1), "l2"),
])
def test_append_edges_matches_serial_appends(seed, case, fill, metric):
    rng = np.random.default_rng(seed)
    vecs, adj, active, tombstone = _graph(rng, fill, metric)
    live_mask = active | tombstone
    vs, us = _edges(rng, case, np.flatnonzero(live_mask),
                    np.flatnonzero(~live_mask))
    # existing rows: an edge already present is skipped, not duplicated
    vs, us = np.append(vs, vs[-1]), np.append(us, adj[vs[-1], 0])
    cfg = ANNConfig(dim=DIM, n_cap=N, r=R, metric=metric, alpha=1.2)
    state = init_state(cfg)
    state = state._replace(
        vectors=write_vectors(state.vectors, jnp.arange(N), jnp.asarray(vecs),
                              DIM),
        norms=jnp.asarray((vecs * vecs).sum(1)),
        adj=jnp.asarray(adj), active=jnp.asarray(active),
        tombstone=jnp.asarray(tombstone),
    )
    got = np.asarray(_append(state, cfg, jnp.asarray(vs, jnp.int32),
                             jnp.asarray(us, jnp.int32)))
    want = append_edges_oracle(metric, 1.2, R, adj, vecs, live_mask, vs, us)
    assert (got == want).all(), np.flatnonzero((got != want).any(axis=1))
    assert (got != adj).any()
