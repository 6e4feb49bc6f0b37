"""From a profiler trace to device busy time, op times and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
only what the reduction needs, as plain lists (``Trace``): the benchmark's
own host spans (``bench.*``) and, for each device, its op and program
(module) events, all in nanoseconds on the profiler's one clock.  The
functions below work on that form alone, so the tests check them on a
small recorded trace committed beside them.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # (name, start_ns, end_ns)
Trace = Dict[str, object]                 # {"host": [Event], "devices": [..]}

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def _op_name(text: str) -> str:
    """The instruction name of a TPU op event, whose name is the whole
    HLO instruction ('%fusion.3 = bf16[...] fusion(...)')."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced to a Trace."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    host: List[Event] = []
    devices: List[Dict[str, object]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev: Dict[str, object] = {"name": plane.name, "ops": [],
                                      "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [(_op_name(e.name), float(e.start_ns),
                                 float(e.start_ns + e.duration_ns))
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, float(e.start_ns),
                          float(e.start_ns + e.duration_ns))
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    devices.sort(key=lambda d: int(str(d["name"]).rsplit(":", 1)[-1]))
    return {"host": sorted(host, key=lambda e: e[1]), "devices": devices}


def window_of(trace: Trace, name: str = "bench.window") -> Interval:
    spans = [(s, e) for n, s, e in trace["host"] if n == name]
    if not spans:
        raise ValueError(f"no {name} span in the trace")
    return spans[-1]


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """The merged intervals, clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def busy_ns(dev: Dict[str, object], lo: float, hi: float) -> float:
    """Nanoseconds in [lo, hi] in which some op ran on the device."""
    return length(union(((s, e) for _, s, e in dev["ops"]), lo, hi))


def gaps(dev: Dict[str, object], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of the device in [lo, hi]."""
    out, t = [], lo
    for s, e in union(((s, e) for _, s, e in dev["ops"]), lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def idle_by_span(trace: Trace, lo: float, hi: float,
                 window: str = "bench.window") -> Dict[str, float]:
    """Idle seconds of the devices, averaged over them, by the host span
    that was open meanwhile ('unspanned' where none was)."""
    spans = [(n, s, e) for n, s, e in trace["host"] if n != window]
    devs = trace["devices"]
    out: Dict[str, float] = {}
    for dev in devs:
        for gs, ge in gaps(dev, lo, hi):
            covered = 0.0
            for n, s, e in spans:
                ov = min(e, ge) - max(s, gs)
                if ov > 0:
                    out[n] = out.get(n, 0.0) + ov
                    covered += ov
            rest = (ge - gs) - covered
            if rest > 0:
                out["unspanned"] = out.get("unspanned", 0.0) + rest
    return {k: v / 1e9 / len(devs) for k, v in out.items()}


def op_seconds(trace: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds of each op name starting in [lo, hi], mean over
    devices."""
    out: Dict[str, float] = {}
    devs = trace["devices"]
    for dev in devs:
        for n, s, e in dev["ops"]:
            if lo <= s < hi:
                out[n] = out.get(n, 0.0) + (e - s) / 1e9
    return {k: v / len(devs) for k, v in out.items()}


def top(d: Dict[str, float], n: int = 10) -> List[List[object]]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def module_runs(dev: Dict[str, object], lo: float, hi: float,
                match: Optional[str] = None) -> List[Event]:
    """Program executions lying wholly in [lo, hi]: of the program named
    ``match`` (a substring), else of the program with most time there."""
    runs = [m for m in dev["modules"] if lo <= m[1] and m[2] <= hi]
    if match is None:
        tot: Dict[str, float] = {}
        for n, s, e in runs:
            tot[n] = tot.get(n, 0.0) + e - s
        if not tot:
            return []
        match = max(tot, key=tot.get)
        return [m for m in runs if m[0] == match]
    return [m for m in runs if match in m[0]]


def ops_within(dev: Dict[str, object], runs: Sequence[Event],
               names: Optional[Set[str]] = None) -> List[Event]:
    """The device's ops that start inside one of ``runs``, and whose name
    is in ``names`` where given."""
    out, i = [], 0
    runs = sorted(runs, key=lambda r: r[1])
    for op in sorted(dev["ops"], key=lambda o: o[1]):
        while i < len(runs) and runs[i][2] <= op[1]:
            i += 1
        if i == len(runs):
            break
        if runs[i][1] <= op[1] and (names is None or op[0] in names):
            out.append(op)
    return out


def exposed_ns(dev: Dict[str, object], runs: Sequence[Event],
               collectives: Set[str]) -> float:
    """Time inside ``runs`` in which a collective runs on the device and
    no other op does."""
    inside = ops_within(dev, runs)
    coll = [(s, e) for n, s, e in inside if n in collectives]
    other = [(s, e) for n, s, e in inside if n not in collectives]
    lo = min((r[1] for r in runs), default=0.0)
    hi = max((r[2] for r in runs), default=0.0)
    c = union(coll, lo, hi)
    o = union(other, lo, hi)
    overlap = 0.0
    for s, e in c:
        for os_, oe in o:
            ov = min(e, oe) - max(s, os_)
            if ov > 0:
                overlap += ov
    return length(c) - overlap
