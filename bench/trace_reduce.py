"""From a profiler trace to device busy time, per-program busy time and the
breakdown.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain data, and every function after it works on that data alone, so the
reduction can be checked on a small recorded trace:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns, module]]}]}]}

Device planes are those named ``/device:...``; their ``XLA Ops`` line holds
one event per operation run (``XLA Modules`` one per program run).  Host
spans are the events named ``bench.*`` on the host plane, written by
``jax.profiler.TraceAnnotation`` in the benchmark's own files.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def load_xplane(log_dir: str) -> dict:
    """The newest trace under ``log_dir`` as plain data."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for e in line.events:
                if not device and not e.name.startswith(SPAN_PREFIX):
                    continue
                module = ""
                if device and line.name == OPS_LINE:
                    module = dict(e.stats).get("hlo_module", "") or ""
                events.append([e.name, float(e.start_ns),
                               float(e.duration_ns), str(module)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if p["name"].startswith("/device:")]


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _busy_intervals(plane: dict) -> list:
    ops = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
    return merge((s, s + d) for _, s, d, _ in ops)


def busy_s(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    planes = device_planes(trace)
    if not planes:
        return 0.0
    return sum(length(_busy_intervals(p)) for p in planes) / len(planes) / 1e9


def busy_in_programs(trace: dict, pattern: str) -> float:
    """Seconds in which an operation ran inside a program whose name holds
    ``pattern`` (e.g. ``apply_segment``), averaged over device planes."""
    planes = device_planes(trace)
    if not planes:
        return 0.0
    total = 0.0
    for p in planes:
        mods = merge((s, s + d) for n, s, d, _ in _line(p, MODULES_LINE)
                     if pattern in n)
        ops = _line(p, OPS_LINE)
        if mods:
            total += length(intersect(_busy_intervals(p), mods))
        else:
            total += length(merge((s, s + d) for _, s, d, m in ops
                                  if pattern in m))
    return total / len(planes) / 1e9


def host_spans(trace: dict) -> list:
    """``(name, start_ns, end_ns)`` of every benchmark span on the host."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for line in p["lines"]:
            out.extend((n, s, s + d) for n, s, d, _ in line["events"]
                       if n.startswith(SPAN_PREFIX))
    return sorted(out, key=lambda x: x[1])


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time (seconds summed over the
    device planes, averaged per plane), and the longest idle gaps of the
    first device, each named by the innermost benchmark span the host was
    in at the gap's middle."""
    planes = device_planes(trace)
    if not planes:
        return {"device_ops": [], "idle_gaps": []}
    per_op = {}
    for p in planes:
        for n, _, d, _ in _line(p, OPS_LINE) or _line(p, MODULES_LINE):
            per_op[n] = per_op.get(n, 0.0) + d
    ops = sorted(per_op.items(), key=lambda x: -x[1])[:top]
    busy = _busy_intervals(planes[0])
    spans = host_spans(trace)
    gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _)
                   in zip(busy, busy[1:])), reverse=True)[:top]
    named = []
    for gap, e0, s1 in gaps:
        mid = (e0 + s1) / 2
        inside = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside \
            else "no benchmark span"
        named.append([name, gap / 1e9])
    return {
        "device_ops": [[n, d / len(planes) / 1e9] for n, d in ops],
        "idle_gaps": named,
    }
