"""Durability layer: checkpoint/restore of ``IndexState`` and supervised
crash-recoverable update streams.

The paper's deployment story is a service that absorbs updates forever with
no consolidation pauses; a service holding millions of users' vectors is
only real if it also survives a crash (FreshDiskANN treats recoverable
persistence as a first-class design constraint).  This module wires the
device-resident index handle into the repo's checkpoint/supervision stack:

  * ``save_index(manager, step, state, cfg)`` checkpoints the full
    ``IndexState`` pytree — graph, vectors, id maps, counters, free stack —
    through ``checkpoint/manager.py``'s atomic commit protocol, with the
    config/policy/capacity metadata recorded in the manifest ``extra`` so a
    restore can validate before it trusts a single tensor.  Works for both
    the single handle and ``ShardedIndex``'s stacked (L, ...) state (the
    logical-shard count rides the manifest, enabling elastic
    reshard-on-restore — see ``core/distributed.py``);
  * ``restore_index(manager, cfg)`` validates schema version, config
    (dim / n_cap / r / metric), policy, external-id capacity and every
    leaf's shape/dtype against the manifest, raising the typed
    ``CheckpointMismatchError`` on any drift — never an ``assert`` (which
    vanishes under ``python -O``) and never a shape error thrown from deep
    inside jit;
  * ``run_segments_supervised`` drives a ``SegmentPlan`` under a restart
    loop: checkpoint every K segments, and on failure (injected
    ``SimulatedFailure``s stand in for process death, including kills
    mid-checkpoint-write) restore the latest complete checkpoint and
    deterministically replay the plan tail.  Segments are pure functions of
    ``(state, ops)`` and the ``.npy`` round trip is bit-exact, so the
    recovered final state is BIT-IDENTICAL to an uninterrupted run — the
    contract ``tests/test_persist.py`` pins for both update policies.

Determinism contract: replay is bit-exact because (a) ``segment_step`` is
the same compiled program on both paths, (b) checkpoints round-trip every
leaf exactly (f32/i32/bool through ``.npy``), and (c) the plan itself is
host data, outside the failure domain.  Callers streaming from an external
source must persist their op log at least ``checkpoint_every`` segments
deep — the checkpoint pins the state, the runbook pins the tail.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..checkpoint.manager import CheckpointManager, CheckpointMismatchError
from ..ft.supervisor import SimulatedFailure
from .api import SegmentPlan, segment_step
from .grow import grow_index
from .types import ROW_LANES, ANNConfig, IndexState, init_index_state

# Bumped whenever the IndexState pytree layout changes incompatibly; a
# restore of a foreign schema is a typed error, not a shape crash mid-jit.
# Schema 2 stores ``GraphState.vectors`` as rows of 128 lanes
# (``core/types.py``).  A schema-1 table is the plain ``(n_cap, dim)``: at
# dim 128 those are the same bytes, so it restores as it is; at any other
# dim it is refused.
SCHEMA_VERSION = 2
PLAIN_TABLE_SCHEMA = 1

# Config fields that must match bit-for-bit between writer and reader: they
# size the state tensors (dim, r), change distance semantics (metric), the
# pytree structure (quantized) or what slot 0 holds (origin_start).  Beam
# widths / thresholds are serving knobs — they may differ across a restore
# and are recorded but not enforced.
# ``n_cap`` is validated separately: online growth (core/grow.py) walks
# capacities through power-of-two buckets, so a checkpoint restores into any
# bucket >= the one it was written under (the state is grown after load);
# only a SHRINK is a mismatch.
CFG_CRITICAL = ("dim", "r", "metric", "quantized", "origin_start")
# the value of a critical field in a manifest written before it existed
CFG_BEFORE = {"origin_start": False}


def _index_meta(state: IndexState, cfg: ANNConfig, policy: str) -> dict:
    stacked = state.graph.vectors.ndim == 3
    return {
        "kind": "index_state",
        "schema": SCHEMA_VERSION,
        "config": dataclasses.asdict(cfg),
        "policy": policy,
        "max_external_id": int(state.ext2slot.shape[-1]),
        # 0 = a single IndexState; L >= 1 = a stacked (L, ...) state of L
        # logical shards (ShardedIndex) — restorable onto any mesh whose
        # size divides L
        "n_logical": int(state.graph.vectors.shape[0]) if stacked else 0,
    }


def save_index(
    manager: CheckpointManager,
    step: int,
    state: IndexState,
    cfg: ANNConfig,
    *,
    policy: str = "ip",
    extra: Optional[dict] = None,
    on_event: Optional[Callable[[str], None]] = None,
):
    """Checkpoint the full ``IndexState`` pytree (single or stacked) at
    ``step``.  The manifest ``extra`` carries schema/config/policy/capacity
    metadata under ``"index"`` (validated by ``restore_index``) and the
    caller's ``extra`` dict under ``"user"``.  ``on_event`` forwards to
    ``CheckpointManager.save`` for crash-injection tests.

    Reads the state (``device_get``), never donates it — safe to call
    between donated update steps as long as it runs BEFORE the next update
    invalidates the handle."""
    payload = {"index": _index_meta(state, cfg, policy), "user": extra or {}}
    return manager.save(step, state, extra=payload, on_event=on_event)


def _index_template(cfg: ANNConfig, meta: dict) -> IndexState:
    mk = lambda: init_index_state(cfg, meta["max_external_id"])  # noqa: E731
    if meta["n_logical"]:
        return jax.vmap(lambda _: mk())(jnp.arange(meta["n_logical"]))
    return mk()


def validate_index_manifest(manifest: dict, cfg: ANNConfig,
                            policy: Optional[str] = None) -> dict:
    """Check a manifest's ``extra["index"]`` metadata against the caller's
    expectations; returns the metadata dict.  Typed errors, no asserts."""
    extra = manifest.get("extra", {})
    meta = extra.get("index")
    if not isinstance(meta, dict) or meta.get("kind") != "index_state":
        raise CheckpointMismatchError(
            "checkpoint does not hold an IndexState (no index metadata in "
            "the manifest — was it written by save_index?)"
        )
    schema = meta.get("schema")
    plain_same = schema == PLAIN_TABLE_SCHEMA and cfg.dim == ROW_LANES
    if schema != SCHEMA_VERSION and not plain_same:
        raise CheckpointMismatchError(
            f"checkpoint schema {schema!r} != supported {SCHEMA_VERSION} "
            f"(schema {PLAIN_TABLE_SCHEMA} restores only at dim {ROW_LANES})"
        )
    saved = meta.get("config", {})
    mine = dataclasses.asdict(cfg)
    drift = {
        k: (saved.get(k, CFG_BEFORE.get(k)), mine[k])
        for k in CFG_CRITICAL
        if saved.get(k, CFG_BEFORE.get(k)) != mine[k]
    }
    if drift:
        raise CheckpointMismatchError(
            "config mismatch (checkpoint vs caller): "
            + ", ".join(f"{k}={a!r} vs {b!r}" for k, (a, b) in drift.items())
        )
    # n_cap: manifest <= caller is a GROW (restore_index grows the loaded
    # state into the caller's bucket); manifest > caller would shrink, which
    # growth cannot express — typed mismatch
    if saved.get("n_cap", mine["n_cap"]) > mine["n_cap"]:
        raise CheckpointMismatchError(
            f"checkpoint capacity n_cap={saved.get('n_cap')} exceeds the "
            f"caller's {mine['n_cap']} (capacity buckets only grow; restore "
            f"with n_cap >= the checkpoint's)"
        )
    if policy is not None and meta.get("policy") != policy:
        raise CheckpointMismatchError(
            f"checkpoint was written under policy {meta.get('policy')!r}, "
            f"caller requested {policy!r} (pass policy=None to adopt the "
            f"checkpoint's)"
        )
    return meta


def restore_index(
    manager: CheckpointManager,
    cfg: ANNConfig,
    *,
    step: Optional[int] = None,
    policy: Optional[str] = None,
    device: bool = True,
) -> Tuple[int, IndexState, dict]:
    """Restore an ``IndexState`` checkpoint written by ``save_index``.

    Validates — raising ``CheckpointMismatchError``, never asserting —
    the schema version, the shape/semantics-critical config fields
    (``CFG_CRITICAL``), the policy (when one is requested), and every
    leaf's shape/dtype against both the manifest and a freshly-initialised
    template of the expected pytree.  Returns ``(step, state, extra)``
    where ``extra`` is the manifest extra (``extra["index"]`` holds the
    metadata: policy, max_external_id, n_logical, saved config).

    A checkpoint written under a SMALLER capacity bucket restores cleanly:
    the state is loaded against a template of the manifest's ``n_cap`` and
    grown (``core/grow.py::grow_index`` — pure, deterministic) into the
    caller's bucket, so ``grow(restore(save(s)))`` is bit-identical to
    ``restore(save(grow(s)))``.  A LARGER manifest capacity is a typed
    mismatch (growth cannot shrink).

    ``device=False`` returns host numpy leaves (``ShardedIndex.restore``
    device_puts them itself, under the restore mesh's sharding)."""
    if step is None:
        step = manager.latest()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {manager.dir}")
    meta = validate_index_manifest(manager.manifest(step), cfg, policy)
    saved_cap = int(meta.get("config", {}).get("n_cap", cfg.n_cap))
    load_cfg = dataclasses.replace(cfg, n_cap=saved_cap)
    template = _index_template(load_cfg, meta)
    step, tree, extra = manager.load(step, like=template)
    if saved_cap != cfg.n_cap:
        tree, _ = grow_index(
            jax.tree.map(jnp.asarray, tree), load_cfg, cfg.n_cap
        )
    if device:
        tree = jax.tree.map(jnp.asarray, tree)
    return step, tree, extra


# ---------------------------------------------------------------------------
# Supervised streaming: segments under a checkpoint/restart loop
# ---------------------------------------------------------------------------


def run_segments_supervised(
    manager: CheckpointManager,
    state: IndexState,
    cfg: ANNConfig,
    plan: SegmentPlan,
    *,
    policy: str = "ip",
    sequential: bool = False,
    unroll: Optional[int] = None,
    checkpoint_every: int = 4,
    max_restarts: int = 10,
    max_restarts_per_step: int = 3,
    fail_at: Optional[Dict[int, int]] = None,
    crash_in_save: Optional[Dict[int, str]] = None,
    log: Optional[Callable[[str], None]] = None,
):
    """Run a ``SegmentPlan`` to completion under restart supervision.

    The state is checkpointed through ``save_index`` every
    ``checkpoint_every`` segments (and once up front, so a crash before the
    first periodic checkpoint still restores rather than silently losing
    the caller's initial state — the updating front doors DONATE their
    input, so the caller cannot re-supply it).  Any exception — including
    injected ``SimulatedFailure``s — restores the latest complete
    checkpoint and deterministically replays the plan tail; the final state
    is bit-identical to an uninterrupted ``run_segments`` over the same
    plan.

    ``fail_at`` maps segment index -> how many times to inject a failure
    just before applying that segment.  ``crash_in_save`` maps checkpoint
    step -> a commit-protocol event name (``"leaf:<i>"``, ``"manifest"``,
    ``"rename"`` — see ``CheckpointManager.save``) at which to kill that
    save; a kill before the rename leaves only the previous complete step
    for ``latest()`` to fall back to.  Both knobs exist for tests and
    chaos drills.

    Budgets mirror ``ft.Supervisor``: ``max_restarts`` bounds total
    restarts, ``max_restarts_per_step`` bounds restarts attributable to one
    segment index (a deterministic crash raises after N attempts instead of
    draining the global budget).  Returns
    ``(state, [SegmentResult, ...], info)`` with one result per plan
    segment (replayed segments report their replayed results — identical,
    by the determinism contract, to what the failed attempt computed)."""
    log = log or (lambda _s: None)
    fail_budget = dict(fail_at or {})
    crash_budget = dict(crash_in_save or {})
    n = len(plan.segments)
    results: list = [None] * n
    restarts = 0
    per_step: Dict[int, int] = {}
    t = 0

    def save(step: int) -> None:
        ev = crash_budget.pop(step, None)
        hook = None
        if ev is not None:
            def hook(event: str, _ev: str = ev, _step: int = step) -> None:
                if event == _ev:
                    raise SimulatedFailure(
                        f"injected kill during save({_step}) at {event!r}"
                    )
        save_index(manager, step, state, cfg, policy=policy, on_event=hook)
        log(f"checkpointed segment {step}")

    save(0)
    while t < n:
        try:
            if fail_budget.get(t, 0) > 0:
                fail_budget[t] -= 1
                raise SimulatedFailure(f"injected failure at segment {t}")
            state, res = segment_step(
                state, cfg, plan.segments[t], policy=policy,
                sequential=sequential, unroll=unroll,
            )
            results[t] = res
            t += 1
            if t % checkpoint_every == 0 or t == n:
                save(t)
        except Exception as e:  # noqa: BLE001 — restart loop, as Supervisor
            restarts += 1
            per_step[t] = per_step.get(t, 0) + 1
            if restarts > max_restarts:
                raise
            if per_step[t] > max_restarts_per_step:
                log(f"segment {t} failed {per_step[t]} times; giving up")
                raise
            # simulate process death: the in-memory state is gone (and may
            # hold donated-dead buffers anyway) — everything comes back
            # from the latest COMPLETE checkpoint
            step, state, _ = restore_index(manager, cfg, policy=policy)
            log(f"failure at segment {t} ({e}); restored checkpoint "
                f"{step}, replaying {step}..{n}")
            t = step
    return state, results, {"restarts": restarts, "final_segment": t}


__all__ = [
    "CFG_CRITICAL",
    "CheckpointMismatchError",
    "SCHEMA_VERSION",
    "restore_index",
    "run_segments_supervised",
    "save_index",
    "validate_index_manifest",
]
