"""The program's own spans and op scopes from a traced run's profile.

``load`` reads the ``.xplane.pb`` that the run's profiler wrote (the one
``devtrace.load`` reads) and keeps, as plain data (``ProgTrace``):

- ``spans``: the program's host spans (``repro.obs.trace.span`` while the
  profiler collects), named up to their first ``#`` and kept where the
  name starts with one of ``PREFIXES``, as (name, start_ns, end_ns) on the
  profiler's one clock;
- ``window``: the benchmark's ``bench.window`` span;
- ``hlo``: for each program the trace holds, by its name as the device's
  'XLA Modules' line gives it ('jit_train_step(3917...)'), its serialized
  ``HloProto``, from which ``op_names`` reads each instruction's
  ``op_name`` (the named scopes it was traced under).  A fusion has an
  ``op_name`` of its own, and counts by it.

Device ops and program runs come from ``devtrace``.  ``of(result)`` loads
a run's ProgTrace once.  The functions below work on that form alone and
take only what lies in the window, so the tests check them on small
synthetic traces.  A run of a program without these spans or scopes
reads nothing: each reader then returns None.
"""
from __future__ import annotations

import glob
import os
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

Span = Tuple[str, float, float]            # (name, start_ns, end_ns)
ProgTrace = Dict[str, Any]

PREFIXES = ("serve.", "train.", "data.")
WINDOW = "bench.window"
#: where ``run.py`` has the profiler write a traced run
TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def span_name(text: str) -> str:
    """A host event's name without the ``#key=value,...#`` that carries
    its arguments where the trace keeps them in the name."""
    return text.split("#", 1)[0]


def _newest(trace_dir) -> str:
    paths = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(trace_dir: str | os.PathLike = TRACE_DIR) -> ProgTrace:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced."""
    from jax.profiler import ProfileData
    path = _newest(trace_dir)
    spans: List[Span] = []
    window: Optional[Tuple[float, float]] = None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = span_name(e.name)
                if name.startswith(PREFIXES):
                    spans.append((name, float(e.start_ns), float(e.end_ns)))
                elif name == WINDOW:
                    window = (float(e.start_ns), float(e.end_ns))
    with open(path, "rb") as f:
        hlo = hlo_protos(f.read())
    return {"spans": sorted(spans, key=lambda s: (s[1], -s[2])),
            "window": window, "hlo": hlo}


# -- the programs' HLO, read from the serialized XSpace ------------------------
# Field numbers: tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto,
# xla/xla_data.proto (OpMetadata).

def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of each field of a serialized protobuf
    message: an int for a varint, a memoryview for a length-delimited
    field; fixed-width fields are skipped."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield key >> 3, value


def _field(buf, number: int, default=None):
    return next((v for f, v in _fields(buf) if f == number), default)


def hlo_protos(xspace: bytes) -> Dict[str, bytes]:
    """Each program's serialized ``HloProto`` in an XSpace, by the name of
    its event metadata on the metadata plane."""
    out: Dict[str, bytes] = {}
    for f, plane in _fields(xspace):                  # XSpace.planes
        if f != 1 or bytes(_field(plane, 2, b"")).decode() != METADATA_PLANE:
            continue
        stat_ids = {_field(m, 1, 0) for f2, e in _fields(plane) if f2 == 5
                    for m in [_field(e, 2, b"")]
                    if bytes(_field(m, 2, b"")).decode() == HLO_STAT}
        for f2, entry in _fields(plane):
            if f2 != 4:                               # XPlane.event_metadata
                continue
            meta = _field(entry, 2, b"")
            name = bytes(_field(meta, 2, b"")).decode()
            for f3, stat in _fields(meta):
                if f3 == 5 and _field(stat, 1, 0) in stat_ids:  # XStat
                    out[name] = bytes(_field(stat, 6, b""))
    return out


def op_names(hlo_proto: bytes) -> Dict[str, str]:
    """Each instruction's ``op_name`` in a serialized ``HloProto``, by the
    instruction's name, over all its computations."""
    out: Dict[str, str] = {}
    module = _field(hlo_proto, 1, b"")                # HloProto.hlo_module
    for f, comp in _fields(module):
        if f != 3:                                    # .computations
            continue
        for f2, inst in _fields(comp):
            if f2 != 2:                               # .instructions
                continue
            name = meta = None
            for f3, v in _fields(inst):
                if f3 == 1:
                    name = bytes(v).decode()
                elif f3 == 7:
                    meta = v
            if name is not None:
                out[name] = bytes(_field(meta, 2, b"")).decode() if (
                    meta is not None) else ""
    return out


def of(result: Dict[str, Any]) -> Optional[ProgTrace]:
    """The run's ProgTrace, loaded on first use; None for an untraced run
    or one whose trace has no window."""
    if result.get("trace") is None:
        return None
    if "progtrace" not in result:
        result["progtrace"] = load()
    pt = result["progtrace"]
    return pt if pt["window"] is not None else None


def named(pt: ProgTrace, name: str) -> List[Span]:
    """The spans called ``name`` lying wholly in the window."""
    lo, hi = pt["window"]
    return [s for s in pt["spans"] if s[0] == name and lo <= s[1]
            and s[2] <= hi]


def inside(spans: Sequence[Span], outer: Tuple[Any, float, float],
           name: str) -> List[Span]:
    """The spans called ``name`` lying wholly in ``outer``."""
    return [s for s in spans if s[0] == name and outer[1] <= s[1]
            and s[2] <= outer[2]]


def _length(s: Span) -> float:
    return s[2] - s[1]


def _mean(xs: Sequence[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None


# -- the serve engine: one ``serve.generate`` span a round ---------------------

def rounds(pt: ProgTrace) -> List[Dict[str, Any]]:
    """Each round in the window: its ``serve.generate`` span, its prefill
    and decode spans and, in order, the ``serve.step`` spans of each with
    the ``serve.sync`` spans inside every step."""
    out = []
    spans = pt["spans"]
    for g in named(pt, "serve.generate"):
        r: Dict[str, Any] = {"generate": g}
        for part in ("prefill", "decode"):
            found = inside(spans, g, f"serve.{part}")
            r[part] = found[0] if found else None
            steps = inside(spans, found[0], "serve.step") if found else []
            r[f"{part}_steps"] = [(s, inside(spans, s, "serve.sync"))
                                  for s in steps]
        out.append(r)
    return out


def ttft_ms(pt: ProgTrace) -> Optional[float]:
    """Mean over rounds of ``serve.generate`` start to ``serve.prefill``
    end: the time to the first new token's logits."""
    return _mean([(r["prefill"][2] - r["generate"][1]) / 1e6
                  for r in rounds(pt) if r["prefill"] is not None])


def token_gap_ms(pt: ProgTrace) -> Optional[float]:
    """Mean over rounds of the ``serve.decode`` span's length over the
    number of ``serve.step`` spans in it."""
    return _mean([_length(r["decode"]) / len(r["decode_steps"]) / 1e6
                  for r in rounds(pt) if r["decode_steps"]])


def first_step_ms(pt: ProgTrace) -> Optional[float]:
    """Mean over rounds of each round's first ``serve.step`` span."""
    firsts = []
    for r in rounds(pt):
        steps = r["prefill_steps"] + r["decode_steps"]
        if steps:
            firsts.append(_length(steps[0][0]) / 1e6)
    return _mean(firsts)


def dispatch_ms(pt: ProgTrace) -> Optional[float]:
    """Mean over every ``serve.step`` but each round's first of the step
    span less the ``serve.sync`` spans in it."""
    xs = []
    for r in rounds(pt):
        for step, syncs in (r["prefill_steps"] + r["decode_steps"])[1:]:
            xs.append((_length(step) - sum(map(_length, syncs))) / 1e6)
    return _mean(xs)


# -- training ------------------------------------------------------------------

def in_scope(op_name: str, scope: str) -> bool:
    """Whether an HLO ``op_name`` ('jit(f)/train.optimizer/mul') lies
    under the named scope ``scope``, a whole component of its path."""
    return scope in op_name.split("/")


def scope_ms_per_run(ops: Sequence[Tuple[str, float, float]],
                     names: Dict[str, str], scope: str, runs: int
                     ) -> Optional[float]:
    """Device time of the ``ops`` (a device's ops inside ``runs``
    executions of one program, ``devtrace.ops_within``) whose ``op_name``
    (``names``, from ``op_names``) lies under ``scope``, per execution, in
    ms; None where no instruction of the program carries the scope."""
    if not runs or not any(in_scope(n, scope) for n in names.values()):
        return None
    return sum(e - s for n, s, e in ops
               if in_scope(names.get(n, ""), scope)) / runs / 1e6


def span_ms(pt: ProgTrace, name: str) -> Optional[float]:
    """Mean length of the spans called ``name`` in the window, in ms."""
    return _mean([_length(s) / 1e6 for s in named(pt, name)])


# -- the device's idle time by the program span open meanwhile -----------------

def _nest(spans: Sequence[Span]) -> List[Dict[str, Any]]:
    """The spans as a forest, each node holding the spans inside it."""
    roots: List[Dict[str, Any]] = []
    stack: List[Dict[str, Any]] = []
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and not (stack[-1]["s"] <= s and e <= stack[-1]["e"]):
            stack.pop()
        node = {"name": name, "s": s, "e": e, "kids": []}
        (stack[-1]["kids"] if stack else roots).append(node)
        stack.append(node)
    return roots


def _segments(node: Dict[str, Any], out: List[Tuple[float, float, str]]
              ) -> None:
    kids, name = node["kids"], node["name"]
    if not kids:
        out.append((node["s"], node["e"], name))
        return
    out.append((node["s"], kids[0]["s"], f"{name}/before {kids[0]['name']}"))
    for a, b in zip(kids, kids[1:]):
        out.append((a["e"], b["s"], f"{name}/between"))
    out.append((kids[-1]["e"], node["e"], f"{name}/after {kids[-1]['name']}"))
    for k in kids:
        _segments(k, out)


def innermost(spans: Sequence[Span], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut where a span opens or closes, each piece labelled by
    the innermost span open over it: its name where it holds no span, else
    '<name>/before <first inside>', '<name>/between' or '<name>/after
    <last inside>' by where the piece lies among the spans inside it;
    'unspanned' where no span is open."""
    segs: List[Tuple[float, float, str]] = []
    t = lo
    for root in _nest([s for s in spans if s[2] > lo and s[1] < hi]):
        segs.append((t, root["s"], "unspanned"))
        _segments(root, segs)
        t = root["e"]
    segs.append((t, hi, "unspanned"))
    return sorted((max(s, lo), min(e, hi), n) for s, e, n in segs
                  if min(e, hi) > max(s, lo))


def idle_by_innermost(spans: Sequence[Span],
                      gaps_per_device: Sequence[Sequence[Tuple[float,
                                                               float]]],
                      lo: float, hi: float) -> Dict[str, float]:
    """Idle seconds of the devices, mean over them, by the innermost
    program span open meanwhile (labels as ``innermost`` gives them).
    ``gaps_per_device``: each device's idle intervals, sorted
    (``devtrace.gaps``)."""
    segs = innermost(spans, lo, hi)
    out: Dict[str, float] = {}
    for gaps in gaps_per_device:
        i = 0
        for gs, ge in gaps:
            while i < len(segs) and segs[i][1] <= gs:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < ge:
                ov = min(ge, segs[j][1]) - max(gs, segs[j][0])
                if ov > 0:
                    out[segs[j][2]] = out.get(segs[j][2], 0.0) + ov
                j += 1
    n = max(len(gaps_per_device), 1)
    return {k: v / 1e9 / n for k, v in out.items()}
