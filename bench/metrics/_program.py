"""The program's own spans and scopes in a traced run, for the readers that
split the program's time by phase.

``trace_reduce.load_xplane`` keeps the benchmark's host spans and each
device operation's name, times and program.  The program marks its phases
itself (``src/repro/core/spans.py``): device scopes become part of each
HLO instruction's ``op_name``, and host spans of the query front door sit
on the host plane.  A TPU trace's operation events carry no ``op_name``:
an event is named by its HLO instruction (``%fusion.12 = ...``), and the
trace holds each program's HLO (the ``Hlo Proto`` stats of its
``/host:metadata`` plane), whose instructions carry their ``op_name``.
``load`` joins the two, reading the ``.xplane.pb`` that the traced run
wrote under ``bench/.cache/trace/<cell>``, into plain data:

    {"devices": [{scopes: [[start_ns, dur_ns], ...], ...}, ...],
     "spans": [[name, start_ns, end_ns], ...]}

per device plane, the operations whose ``op_name`` holds an ``ann.``
scope, in time order under their scopes (outermost first, joined by
``/``); and every host event named ``ann.*`` or ``bench.*``.  A program
without the scopes (an older checkout) leaves both lists empty, and the
readers then read nothing.

A device operation counts toward the outermost phase scope on its path.
A scope's time is the union of its operations' intervals, so a loop and
the operations of its body count once, averaged over device planes and
divided by the traced calls of the kind that runs it.
"""
from __future__ import annotations

import bisect
import glob
import os

import numpy as np

import spec
import trace_reduce

PREFIX = "ann."
# the program's names (src/repro/core/spans.py), read here by name only, so
# that a checkout without them reads nothing instead of failing
INSERT_SEARCH = "ann.insert.search"
INSERT_LINK = "ann.insert.link"
DELETE_SEARCH = "ann.delete.search"
DELETE_REPAIR = "ann.delete.repair"
MAP = "ann.map"
CONSOLIDATE = "ann.consolidate"
UPDATE_PHASES = (INSERT_SEARCH, INSERT_LINK, DELETE_SEARCH, DELETE_REPAIR,
                 MAP, CONSOLIDATE)
EDGES_APPEND = "ann.edges.append"
PRUNE = "ann.prune"
SEARCH_HOPS = "ann.search.hops"
SEARCH = "ann.search"
SEARCH_DISPATCH = "ann.search.dispatch"
SPAN_PREFIXES = (PREFIX, trace_reduce.SPAN_PREFIX)
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def trace_dir(run) -> str:
    """Where the harness writes a traced run's profile."""
    return os.path.join(spec.BENCH_DIR, ".cache", "trace", run.cell.name)


def scopes_of(op_name: str) -> str:
    """The ``ann.`` scopes of an op_name, outermost first, joined by /."""
    return "/".join(c for c in op_name.split("/") if c.startswith(PREFIX))


# ---- the protobuf wire format, as far as the trace's HLO needs it --------
# XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry: value 2),
# .stat_metadata 5 (map entry: value 2); XEventMetadata.name 2, .stats 5;
# XStat.metadata_id 1, .bytes_value 6; XStatMetadata.id 1, .name 2;
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# .metadata 7; OpMetadata.op_name 2.

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, span=None):
    """``(number, value)`` of each field of the message ``buf[lo:hi]``: an
    int for a number, a ``(lo, hi)`` span for a length-delimited field."""
    i, hi = span if span else (0, len(buf))
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"protobuf wire type {kind} at {i}")
        yield key >> 3, value


def _field(buf, span, number, default=None):
    return next((v for n, v in _fields(buf, span) if n == number), default)


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _instruction_op_names(buf, span) -> dict:
    """``{instruction name: op_name}`` of one serialized ``HloProto``."""
    out = {}
    module = _field(buf, span, 1)
    for n, comp in _fields(buf, module):
        if n != 3:
            continue
        for m, inst in _fields(buf, comp):
            if m != 2:
                continue
            name = op_name = None
            for k, v in _fields(buf, inst):
                if k == 1:
                    name = _text(buf, v)
                elif k == 7:
                    op = _field(buf, v, 2)
                    op_name = _text(buf, op) if op else None
            if name and op_name:
                out[name] = op_name
    return out


def hlo_op_names(buf) -> dict:
    """``{program name: {instruction name: op_name}}`` from the HLO protos
    in a serialized XSpace's metadata plane."""
    out = {}
    for n, plane in _fields(buf):
        if n != 1 or _text(buf, _field(buf, plane, 2, (0, 0))) \
                != METADATA_PLANE:
            continue
        entries = [(k, _field(buf, e, 2)) for k, e in _fields(buf, plane)
                   if k in (4, 5)]
        proto_ids = {_field(buf, v, 1, 0) for k, v in entries
                     if k == 5 and _text(buf, _field(buf, v, 2, (0, 0)))
                     == HLO_PROTO_STAT}
        for k, meta in entries:
            if k != 4:
                continue
            name = _text(buf, _field(buf, meta, 2, (0, 0)))
            for m, stat in _fields(buf, meta):
                if m == 5 and _field(buf, stat, 1, 0) in proto_ids:
                    proto = _field(buf, stat, 6)
                    if proto:
                        out[name] = _instruction_op_names(buf, proto)
    return out


def load(log_dir: str) -> dict:
    """The newest trace under ``log_dir`` as plain data (module doc)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    with open(paths[-1], "rb") as f:
        raw = f.read()
    op_names = hlo_op_names(memoryview(raw))
    devices, spans = [], []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith("/device:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.end_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
            continue
        lines = {line.name: line for line in plane.lines}
        if trace_reduce.OPS_LINE not in lines:
            continue
        devices.append(scoped_ops(lines[trace_reduce.OPS_LINE],
                                  lines.get(trace_reduce.MODULES_LINE),
                                  op_names))
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def scoped_ops(ops_line, modules_line, op_names: dict) -> dict:
    """``{scopes: [[start_ns, dur_ns], ...]}`` of the scoped operations of
    one device: each operation event is named by its HLO instruction
    (``%name = ...``), inside the program run whose interval holds it."""
    mods = sorted((e.start_ns, e.end_ns, e.name)
                  for e in (modules_line.events if modules_line else ()))
    starts = [m[0] for m in mods]
    scopes = {}                  # (program, instruction) -> scopes
    out = {}
    for e in ops_line.events:
        start = e.start_ns
        i = bisect.bisect_right(starts, start) - 1
        program = mods[i][2] if i >= 0 and start < mods[i][1] else ""
        key = (program, e.name)
        path = scopes.get(key)
        if path is None:
            inst = e.name.split(" ", 1)[0].lstrip("%")
            path = scopes[key] = scopes_of(
                op_names.get(program, {}).get(inst, ""))
        if path:
            out.setdefault(path, []).append([start, e.duration_ns])
    return out


def program(run):
    """The run's scoped operations and spans (``load``), read once per
    run; None when the run holds no trace."""
    if run.trace is None:
        return None
    if not hasattr(run, "program_trace"):
        try:
            run.program_trace = load(trace_dir(run))
        except FileNotFoundError:
            run.program_trace = None
    return run.program_trace


def traced_calls(calls) -> int:
    return sum(1 for c in calls if c["traced"])


def union_ms(trace: dict, keep) -> float:
    """Milliseconds in which an operation whose scopes satisfy ``keep``
    ran, averaged over the device planes."""
    planes = trace["devices"]
    if not planes:
        return 0.0
    total = sum(trace_reduce.length(trace_reduce.merge(
        (s, s + d) for path, ops in by_path.items()
        if keep(path.split("/")) for s, d in ops))
        for by_path in planes)
    return total / len(planes) / 1e6


def outermost_phase(scopes) -> str:
    return next((s for s in scopes if s in UPDATE_PHASES), "")


def per_call_ms(run, keep, calls):
    """``union_ms`` per traced call of ``calls``; None without a trace,
    without such calls, or without a matching operation."""
    trace = program(run)
    n = traced_calls(calls)
    if trace is None or n == 0:
        return None
    ms = union_ms(trace, keep)
    return ms / n if ms > 0 else None


def phase_ms(run, phase: str):
    """Device ms per traced update call under the phase scope ``phase``."""
    return per_call_ms(run, lambda sc: outermost_phase(sc) == phase,
                       run.updates)


def nested(outer: str, inner: str):
    """``keep`` for operations under ``inner`` somewhere beneath
    ``outer``."""
    def keep(scopes):
        return outer in scopes and inner in scopes[scopes.index(outer):]
    return keep


def phase_coverage(run):
    """Share of the device-busy time inside ``apply_segment`` programs that
    lies under a phase scope (%); not a metric, a check of the scopes."""
    trace = program(run)
    busy = trace_reduce.busy_in_programs(run.trace, "apply_segment") \
        if trace is not None else 0.0
    if busy <= 0:
        return None
    return 100.0 * union_ms(trace, outermost_phase) / 1e3 / busy


def self_ms(trace: dict, parent: str, child: str) -> list:
    """Each ``parent`` host span's duration less that of the ``child``
    spans inside it (ms)."""
    spans = trace["spans"]
    out = []
    for name, s, e in spans:
        if name != parent:
            continue
        inner = sum(ce - cs for cn, cs, ce in spans
                    if cn == child and s <= cs and ce <= e)
        out.append((e - s - inner) / 1e6)
    return out


def front_door_self_ms(run):
    """Median host ms of ``ann.search`` less its ``ann.search.dispatch``."""
    trace = program(run)
    if trace is None:
        return None
    values = self_ms(trace, SEARCH, SEARCH_DISPATCH)
    return float(np.median(values)) if values else None


def idle_gaps(run, top: int = 10) -> list:
    """The longest idle gaps of the first device, each named by the
    innermost host span of either kind (``ann.*`` or ``bench.*``) that the
    host was in at the gap's middle: ``[[name, seconds], ...]``.  Not a
    metric: PERF.md's table of where the device waits."""
    trace = program(run)
    planes = trace_reduce.device_planes(run.trace) if trace else []
    if not planes:
        return []
    lines = {line["name"]: line["events"] for line in planes[0]["lines"]}
    ops = lines.get(trace_reduce.OPS_LINE) or \
        lines.get(trace_reduce.MODULES_LINE, [])
    busy = trace_reduce.merge((s, s + d) for _, s, d, _ in ops)
    gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _)
                   in zip(busy, busy[1:])), reverse=True)[:top]
    out = []
    for gap, e0, s1 in gaps:
        mid = (e0 + s1) / 2
        inside = [sp for sp in trace["spans"] if sp[1] <= mid <= sp[2]]
        name = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside \
            else "no span"
        out.append([name, gap / 1e9])
    return out
