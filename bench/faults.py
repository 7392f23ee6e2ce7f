"""Faults planted under the timed path, for the checks that ``correct``
catches them: each stands in for one of the program's entry points
(``repro.core.apply_segment`` or ``repro.core.search_index``) and breaks
what it returns.

    state_unchanged     an update step that returns its state unchanged
    half_the_updates    half of an update batch left out, reported applied
    half_the_queries    half of a query batch answered with another's answer
    ids_altered         ids shifted by one place in every answer
    dists_altered       distances scaled by 1.01
    beam_cut            the search beam cut from L to k
    hop_cap             the hop loop stopped after HOP_CAP expansions

``plant(name)`` puts one in the program's place for the rest of the
process; ``bench/control.py --fault`` reads its numbers on the chip and
``tests/test_faults.py`` drives whole runs with each.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import repro.core as core

REAL_APPLY = core.apply_segment
REAL_SEARCH = core.search_index

HOP_CAP = 4


def state_unchanged(state, cfg, ops, **kw):
    keep = jax.tree.map(jnp.copy, state)
    _, res = REAL_APPLY(state, cfg, ops, **kw)
    return keep, res


def half_the_updates(state, cfg, ops, **kw):
    b = ops.valid.shape[1]
    lanes = jnp.arange(b) % 2 == 0
    new, res = REAL_APPLY(state, cfg, ops._replace(valid=ops.valid & lanes),
                          **kw)
    return new, res._replace(ok=ops.valid)


def half_the_queries(state, cfg, queries, **kw):
    ext, d, res = REAL_SEARCH(state, cfg, queries, **kw)
    half = queries.shape[0] // 2
    ext = ext.at[half:].set(ext[:1].repeat(queries.shape[0] - half, 0))
    d = d.at[half:].set(d[:1].repeat(queries.shape[0] - half, 0))
    return ext, d, res


def ids_altered(state, cfg, queries, **kw):
    ext, d, res = REAL_SEARCH(state, cfg, queries, **kw)
    return jnp.roll(ext, 1, axis=1), d, res


def dists_altered(state, cfg, queries, **kw):
    ext, d, res = REAL_SEARCH(state, cfg, queries, **kw)
    return ext, d * 1.01, res


def beam_cut(state, cfg, queries, *, k=10, **kw):
    return REAL_SEARCH(state, cfg, queries, k=k, l=k)


def hop_cap(state, cfg, queries, *, k=10, l=None, **kw):
    l = l or cfg.l_search
    capped = dataclasses.replace(cfg, max_visit_slack=HOP_CAP - l)
    return REAL_SEARCH(state, capped, queries, k=k, l=l)


FAULTS = {
    "state_unchanged": ("apply_segment", state_unchanged),
    "half_the_updates": ("apply_segment", half_the_updates),
    "half_the_queries": ("search_index", half_the_queries),
    "ids_altered": ("search_index", ids_altered),
    "dists_altered": ("search_index", dists_altered),
    "beam_cut": ("search_index", beam_cut),
    "hop_cap": ("search_index", hop_cap),
}


def plant(name: str) -> None:
    """Put the fault ``name`` in its entry point's place."""
    entry, fn = FAULTS[name]
    setattr(core, entry, fn)
