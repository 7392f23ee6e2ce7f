"""The program's spans and scopes (core/spans.py).

  * the compiled update and search programs carry every device scope in
    their HLO ``op_name`` metadata (the gather kernel's ``ann.gather`` on
    the pallas engine, the one that calls it);
  * the vector-row reads and the gather kernel lie beneath an update phase
    in the update program, the kernel beneath the hop loop;
  * every operation of the update program that comes from the program's
    own code lies under exactly one phase scope, so a device trace splits
    the program's time among the phases without remainder or overlap;
  * a profiler trace of one ``search_index`` call holds the front door's
    host span and its three children, nested and in order;
  * the retrace counters are one table, shared by the modules that count.
"""
import dataclasses
import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core.api as api_mod
import repro.core.distributed as dist_mod
import repro.core.search_batched as sb_mod
from repro.core import (
    ANNConfig,
    apply,
    apply_segment,
    init_index_state,
    insert_batch,
    make_dataset,
    mixed_update_batch,
    search_index,
    spans,
)
from repro.core.search_batched import batched_greedy_search
from repro.core.types import stack_update_batches

CFG = ANNConfig(dim=16, n_cap=256, r=8, l_build=16, l_search=16,
                l_delete=16, k_delete=10, n_copies=2, alpha=1.2)
TRIVIAL = ("parameter", "constant", "tuple", "get-tuple-element")


def _segment(b=4):
    ins = np.arange(b)
    batch, split = mixed_update_batch(ins, np.zeros((b, CFG.dim),
                                                   np.float32),
                                      ins + 100, CFG.dim)
    return stack_update_batches([batch]), split


def _update_hlo(policy, cfg=CFG):
    ops, split = _segment()
    state = init_index_state(cfg, 512)
    return apply_segment.lower(state, cfg, ops, policy=policy,
                               split=split).compile().as_text()


@pytest.fixture(scope="module")
def update_hlo():
    return _update_hlo("ip")


@pytest.fixture(scope="module")
def pallas_update_hlo():
    """The update program on the pallas engine (interpret mode here), whose
    hop loops call the gather kernel."""
    return _update_hlo("ip", dataclasses.replace(CFG, backend="pallas"))


@pytest.fixture(scope="module")
def search_hlo():
    graph = init_index_state(CFG, 512).graph
    return batched_greedy_search.lower(
        graph, CFG, jnp.zeros((8, CFG.dim)), k=4, l=CFG.l_search,
        valid=jnp.ones((8,), bool)).compile().as_text()


def _computations(text):
    """``{name: [instruction lines]}`` and the entry computation's name."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur and line.strip():
            comps[cur].append(line)
    return comps, entry


def _executed(text):
    """``(opcode, op_name)`` of every instruction that runs as an operation
    of its own: those of the entry computation and of the computations
    that loops, conditionals and calls run, not the bodies of fusions or
    of reductions."""
    comps, entry = _computations(text)
    seen, todo = {entry}, [entry]
    ref = r"(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    while todo:
        for line in comps[todo.pop()]:
            names = re.findall(ref, line)
            branches = re.search(r"branch_computations=\{([^}]*)\}", line)
            if branches:
                names += re.findall(r"%?([\w.\-]+)", branches.group(1))
            if re.search(r" call\(", line):
                names += re.findall(r"to_apply=%?([\w.\-]+)", line)
            for n in names:
                if n not in seen:
                    seen.add(n)
                    todo.append(n)
    out = []
    for c in seen:
        for line in comps[c]:
            m = re.match(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S.*? ([a-z][\w\-]*)\(",
                         line)
            if m and m.group(1) not in TRIVIAL:
                name = re.search(r'op_name="([^"]*)"', line)
                out.append((m.group(1), name.group(1) if name else ""))
    return out


def _phases(op_name):
    return [p for p in op_name.split("/") if p in spans.UPDATE_PHASES]


def _from_code(op_name, program):
    """True for an operation traced from the program's code: its op_name
    runs from the program down to a primitive.  The compiler's own
    instructions (layout copies, hoisted constants, the bitcast of an
    argument) carry no op_name, or one that stops at a function."""
    last = op_name.rsplit("/", 1)[-1]
    return (op_name.startswith(f"jit({program})/")
            and last != "closed_call" and not last.startswith("jit("))


@pytest.mark.parametrize("scope", spans.DEVICE_SCOPES)
def test_compiled_programs_carry_every_scope(request, update_hlo, search_hlo,
                                             scope):
    if scope == spans.GATHER:
        update_hlo = request.getfixturevalue("pallas_update_hlo")
    names = {n for _, n in _executed(update_hlo)}
    if scope in (spans.SEARCH_HOPS, spans.SEARCH_SELECT):
        names |= {n for _, n in _executed(search_hlo)}
    assert any(scope in n.split("/") for n in names), scope


@pytest.mark.parametrize("scope", [spans.VECTOR_ROWS, spans.GATHER])
def test_row_and_gather_scopes_nest_in_their_phases(pallas_update_hlo,
                                                    scope):
    """Every operation under ``ann.vectors.rows`` or ``ann.gather`` lies
    beneath exactly one update phase, and the kernel beneath the hop loop
    of the insert or delete search."""
    under = [n for _, n in _executed(pallas_update_hlo)
             if scope in n.split("/")]
    assert under, scope
    for n in under:
        parts = n.split("/")
        assert len(_phases(n)) == 1, n
        assert parts.index(_phases(n)[0]) < parts.index(scope), n
        if scope == spans.GATHER:
            assert _phases(n)[0] in (spans.INSERT_SEARCH,
                                     spans.DELETE_SEARCH), n
            assert spans.SEARCH_HOPS in parts[:parts.index(scope)], n


@pytest.mark.parametrize("policy", ["ip", "local", "fresh"])
def test_update_program_ops_lie_under_exactly_one_phase(update_hlo, policy):
    text = update_hlo if policy == "ip" else _update_hlo(policy)
    ops = _executed(text)
    traced = [(op, n) for op, n in ops if _from_code(n, "apply_segment")]
    assert len(traced) > 0.8 * len(ops), (len(traced), len(ops))
    outside = [(op, n) for op, n in traced if len(_phases(n)) != 1]
    assert not outside, outside[:10]
    # what the compiler made lies under no two phases either
    assert all(len(_phases(n)) <= 1 for _, n in ops)


def test_search_program_ops_lie_under_hops_or_select(search_hlo):
    traced = [n for _, n in _executed(search_hlo)
              if _from_code(n, "batched_greedy_search")]
    scopes = (spans.SEARCH_HOPS, spans.SEARCH_SELECT)
    assert traced
    assert all(sum(s in n.split("/") for s in scopes) == 1 for n in traced)


def test_front_door_host_spans_nest_in_order(tmp_path):
    data, queries = make_dataset(48, CFG.dim, "l2", seed=3)
    state = init_index_state(CFG, 512)
    state, _ = apply(state, CFG, insert_batch(np.arange(48), data),
                     sequential=True)
    q = jnp.asarray(queries[:5])
    jax.block_until_ready(search_index(state, CFG, q, k=4))   # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(search_index(state, CFG, q, k=4))
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name in spans.HOST_SPANS]
    parent = [e for e in events if e[0] == spans.SEARCH]
    assert len(parent) == 1
    _, s0, e0 = parent[0]
    children = sorted((e for e in events if e[0] != spans.SEARCH),
                      key=lambda e: e[1])
    assert [c[0] for c in children] == [spans.SEARCH_PAD,
                                        spans.SEARCH_DISPATCH,
                                        spans.SEARCH_MAP_IDS]
    assert all(s0 <= s <= e <= e0 for _, s, e in children)
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))


def test_unknown_names_are_refused():
    with pytest.raises(ValueError):
        spans.device_scope("ann.nothing")
    with pytest.raises(ValueError):
        spans.host_span("bench.update.call")
    assert all(n.startswith("ann.")
               for n in spans.DEVICE_SCOPES + spans.HOST_SPANS)


def test_retrace_counters_are_one_table():
    assert api_mod.TRACE_COUNTER is spans.TRACE_COUNTER
    assert sb_mod.TRACE_COUNTER is spans.TRACE_COUNTER
    assert dist_mod.TRACE_COUNTER is spans.TRACE_COUNTER
    assert dist_mod.TRACE_SHAPES is spans.TRACE_SHAPES
    assert api_mod.TRACE_UNROLL is spans.TRACE_UNROLL
    assert set(spans.TRACE_SHAPES) <= set(spans.TRACE_COUNTER)
