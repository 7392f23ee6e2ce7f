"""The program's instrumentation: its trace spans and scopes, by name, and
its retrace counters.

Two kinds of span, both prefixed ``ann.``:

  * device scopes (``device_scope``, a ``jax.named_scope``): they cost
    nothing at run time.  Each becomes part of the HLO ``op_name``
    metadata of every operation traced inside it, which a profiler trace
    of the device carries, so a trace can be split by program phase.
    Every operation of ``apply_segment`` lies under exactly one of
    ``UPDATE_PHASES``; ``EDGES_APPEND`` and ``PRUNE`` nest beneath them,
    and the search program's ``SEARCH_HOPS`` / ``SEARCH_SELECT`` nest
    beneath a phase when an update runs the search.  ``VECTOR_ROWS`` (the
    row gathers of ``types.read_vectors``) and ``GATHER`` (the gather
    kernel's call in ``backend.PallasBackend``) nest wherever their code
    runs;
  * host spans (``host_span``, a ``jax.profiler.TraceAnnotation``): one
    inactive ``TraceMe`` each when no trace is running.  They mark the
    host steps of the query front door (``core/api.py::search``).

Nothing is buffered or exported here: ``jax.profiler.start_trace`` /
``stop_trace`` record both kinds and write them out when the trace stops.
``docs/ARCHITECTURE.md`` ("Spans") says where each sits; the benchmark's
readers (``bench/metrics/``) read them by these names.
"""
from __future__ import annotations

import jax

# ---- device scopes: the update program's phases ---------------------------
INSERT_SEARCH = "ann.insert.search"   # slot allocation, vector writes, search
INSERT_LINK = "ann.insert.link"       # the new rows' prunes, the link scan
DELETE_SEARCH = "ann.delete.search"   # the deleted points' searches
DELETE_REPAIR = "ann.delete.repair"   # the repair scan
MAP = "ann.map"                       # the external-id map, per-lane results
CONSOLIDATE = "ann.consolidate"       # the trigger and the device sweep
UPDATE_PHASES = (INSERT_SEARCH, INSERT_LINK, DELETE_SEARCH, DELETE_REPAIR,
                 MAP, CONSOLIDATE)

# ---- device scopes nested beneath a phase ---------------------------------
EDGES_APPEND = "ann.edges.append"     # edges.append_edges
PRUNE = "ann.prune"                   # prune.robust_prune
SEARCH_HOPS = "ann.search.hops"       # the shared hop loop, gathers included
SEARCH_SELECT = "ann.search.select"   # the final top-k and rescore
VECTOR_ROWS = "ann.vectors.rows"      # types.read_vectors: XLA row gathers
GATHER = "ann.gather"                 # the Pallas gather-distance kernel
DEVICE_SCOPES = UPDATE_PHASES + (EDGES_APPEND, PRUNE, SEARCH_HOPS,
                                 SEARCH_SELECT, VECTOR_ROWS, GATHER)

# ---- host spans: the query front door, a parent and its children in order -
SEARCH = "ann.search"
SEARCH_PAD = "ann.search.pad"             # bucket padding and lane mask
SEARCH_DISPATCH = "ann.search.dispatch"   # the call into the search program
SEARCH_MAP_IDS = "ann.search.map_ids"     # slot -> external id, eagerly
HOST_SPANS = (SEARCH, SEARCH_PAD, SEARCH_DISPATCH, SEARCH_MAP_IDS)


def device_scope(name: str):
    """A scope over the operations traced inside it (``jax.named_scope``)."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f"unknown device scope {name!r}")
    return jax.named_scope(name)


def host_span(name: str):
    """A host span on the profiler's timeline (``TraceAnnotation``)."""
    if name not in HOST_SPANS:
        raise ValueError(f"unknown host span {name!r}")
    return jax.profiler.TraceAnnotation(name)


# ---- retrace counters ------------------------------------------------------
# Incremented once per trace (not per call) of a jitted program, by the
# program's name: the bucketing regression tests assert that ragged batch
# sizes and segment lengths share one compiled program per bucket.
# ``core/api.py`` counts ``apply`` / ``apply_segment``,
# ``core/search_batched.py`` the shared hop loop, and
# ``core/distributed.py`` its SPMD programs, whose traced op-tensor shapes
# go to ``TRACE_SHAPES`` (``segment_pack`` is its one host-side entry: the
# owner-compaction packs of single stream steps).
SHARDED_PROGRAMS = ("update_compact", "segment_compact", "segment_pack",
                    "update_replicate", "segment_replicate",
                    "search_replicate", "search_partition")
TRACE_COUNTER = {"apply": 0, "apply_segment": 0, "batched_greedy_search": 0,
                 **{k: 0 for k in SHARDED_PROGRAMS}}
TRACE_SHAPES: dict = {k: [] for k in SHARDED_PROGRAMS}

# (T, B) -> the unroll ``apply_segment`` resolved when it traced with
# ``unroll=None``; the auto-unroll regression test pins the bucket keys.
TRACE_UNROLL: dict = {}
