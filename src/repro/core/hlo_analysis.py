"""Extract Ridgeline work-unit terms (F, B_M, B_N) from compiled XLA artifacts.

``F`` and ``B_M`` come from ``compiled.cost_analysis()`` — XLA reports
``flops`` and ``bytes accessed`` for the *partitioned per-device module*
(calibrated by ``tests/test_hlo_analysis.py::test_cost_analysis_is_per_device``).

``B_N`` (network wire bytes) is NOT in cost_analysis.  We parse the optimized
HLO text of the compiled module and sum, over every collective op, the
per-device *wire bytes* — operand bytes scaled by the collective's ring
algorithm factor:

    all-reduce          2 (n-1)/n   (reduce-scatter + all-gather phases)
    all-gather            (n-1)/n   (operand is the per-device shard)
    reduce-scatter        (n-1)/n   (operand is the full per-device buffer)
    all-to-all            (n-1)/n   (each device keeps 1/n locally)
    collective-permute    1         (point-to-point)

where n is the replica-group size parsed from the op attributes.  This is the
standard alpha-beta wire-byte accounting used by collective cost models.

Shapes like ``bf16[2048,512]{1,0}`` are parsed structurally; tuple-shaped
all-reduces sum their element buffers.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Mapping, Optional, Tuple

_DTYPE_BYTES: Dict[str, float] = {
    "pred": 1, "s2": 0.25, "s4": 0.5, "s8": 1, "s16": 2, "s32": 4, "s64": 8,
    "u2": 0.25, "u4": 0.5, "u8": 1, "u16": 2, "u32": 4, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1, "f8e3m4": 1, "f4e2m1fn": 0.5,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

#: collective op kinds we account for, mapped to their per-device wire-byte
#: factor fn(n) *applied to the result-buffer bytes*:
#:   all-reduce: result = full buffer S, ring wire = 2 S (n-1)/n
#:   all-gather: result = gathered S, each device ships its shard to n-1 peers
#:               around the ring = S (n-1)/n
#:   reduce-scatter: result = the SHARD S/n; full buffer = n*result, wire =
#:               (n*result)(n-1)/n = result (n-1)
#:   all-to-all: result size = input size S, (n-1)/n of it crosses the wire
#:   collective-permute / broadcast: point-to-point, factor 1
_COLLECTIVE_KINDS = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n if n > 1 else 0.0,
    "all-gather": lambda n: (n - 1) / n if n > 1 else 0.0,
    "reduce-scatter": lambda n: float(n - 1) if n > 1 else 0.0,
    "all-to-all": lambda n: (n - 1) / n if n > 1 else 0.0,
    "collective-permute": lambda n: 1.0,
    "ragged-all-to-all": lambda n: (n - 1) / n if n > 1 else 0.0,
    "collective-broadcast": lambda n: 1.0,
}

_SHAPE_RE = re.compile(
    r"\b([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?"
)
# matches e.g. `bf16[4,2048,512]{2,1,0}` or `f32[]`


def _shape_bytes(dtype: str, dims: str) -> float:
    if dtype not in _DTYPE_BYTES:
        return 0.0
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _result_shapes(line: str, kind_start: int) -> List[Tuple[str, str]]:
    """Result shapes of an HLO instruction: ``%name = <shape> op(...)``.

    The shape(s) sit between the first ``=`` and the op name; tuple results
    list several shapes there.  ``kind_start`` is the index where the op-name
    match begins, so attribute strings (``channel_id=1``…) are never scanned.
    """
    eq = line.find("=")
    if eq < 0 or eq >= kind_start:
        return []
    return _SHAPE_RE.findall(line[eq + 1:kind_start])


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    bytes_result: float       # per-device result-buffer bytes
    group_size: int           # replica group size n
    wire_bytes: float         # bytes on the wire per device (ring factor applied)
    cross_pod_fraction: float = 0.0   # share of ring hops crossing pods
    channel: Optional[int] = None

    @property
    def cross_pod_wire_bytes(self) -> float:
        return self.wire_bytes * self.cross_pod_fraction


@dataclasses.dataclass
class CollectiveSummary:
    ops: List[CollectiveOp]

    @property
    def total_wire_bytes(self) -> float:
        return sum(o.wire_bytes for o in self.ops)

    @property
    def cross_pod_wire_bytes(self) -> float:
        return sum(o.cross_pod_wire_bytes for o in self.ops)

    @property
    def total_buffer_bytes(self) -> float:
        return sum(o.bytes_result for o in self.ops)

    def by_kind(self) -> Dict[str, Tuple[int, float]]:
        out: Dict[str, Tuple[int, float]] = {}
        for o in self.ops:
            cnt, byt = out.get(o.kind, (0, 0.0))
            out[o.kind] = (cnt + 1, byt + o.wire_bytes)
        return out

    def pretty(self) -> str:
        rows = [f"  {k:<22} n={c:<4d} wire={b / 1e9:.4f} GB"
                for k, (c, b) in sorted(self.by_kind().items())]
        rows.append(f"  {'TOTAL':<22}        wire={self.total_wire_bytes / 1e9:.4f} GB")
        return "\n".join(rows)


_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=(\[[0-9,]+\])(?:T\(([0-9,]+)\))?")
_START_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=")


def _parse_groups(line: str, default_n: int):
    """Parse replica groups: returns (group_size, groups ndarray or None).

    Handles both the iota format ``replica_groups=[G,n]<=[d0,d1,..]T(perm)``
    (materialized exactly — the permuted-iota encodes which mesh axes the
    collective spans) and the explicit ``{{0,1},{2,3}}`` format.
    """
    import numpy as _np

    m = _GROUPS_IOTA_RE.search(line)
    if m:
        G, n = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).strip("[]").split(",") if d]
        total = 1
        for d in dims:
            total *= d
        ids = _np.arange(total).reshape(dims)
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",")]
            ids = ids.transpose(perm)
        groups = ids.reshape(G, n)
        return max(1, n), groups
    m = _GROUPS_RE.search(line)
    if m:
        body = m.group(1)
        rows = re.findall(r"\{([0-9,\s]*)\}", body)
        if rows:
            parsed = [[int(t) for t in r.split(",") if t.strip()]
                      for r in rows]
            n = max((len(r) for r in parsed), default=default_n)
            width = max(len(r) for r in parsed)
            if all(len(r) == width for r in parsed):
                return max(1, n), _np.asarray(parsed)
            return max(1, n), None
    return default_n, None


def _cross_pod_fraction(groups, pod_size: int) -> float:
    """Fraction of each group's ring traffic that crosses a pod boundary.

    With groups materialized, count the ring edges (i -> i+1 within the
    group, wrap included) whose endpoints sit in different pods.
    """
    if groups is None or pod_size <= 0:
        return 0.0
    import numpy as _np

    g = _np.asarray(groups)
    if g.shape[1] < 2:
        return 0.0
    pods = g // pod_size
    nxt = _np.roll(pods, -1, axis=1)
    crossings = (pods != nxt).mean()
    return float(crossings)


def parse_collectives(hlo_text: str, num_devices: int,
                      pod_size: int = 0) -> CollectiveSummary:
    """Sum per-device collective wire bytes over an HLO module text.

    ``pod_size`` > 0 additionally attributes each op's ring traffic to
    intra-pod (ICI) vs cross-pod (DCI) hops from its materialized replica
    groups (multi-pod meshes).
    """
    ops: List[CollectiveOp] = []
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if "=" not in line or not _START_RE.match(line):
            continue
        # op kind appears right after '= <shape>' as the instruction name
        kind, kind_start = None, -1
        for k in _COLLECTIVE_KINDS:
            # match `all-reduce(`, `all-reduce-start(`, `all-gather(` etc.
            m = re.search(rf"[\]\)\s]({re.escape(k)})(?:-start)?\(", line)
            if m:
                kind, kind_start = k, m.start(1)
                break
        if kind is None:
            continue
        if re.search(rf"{re.escape(kind)}-done\(", line):
            continue  # -done carries no new traffic; -start already counted
        shapes = _result_shapes(line, kind_start)
        if not shapes:
            continue
        if "-start(" in line:
            # async form returns a tuple aliasing operand+result (+contexts):
            # take the largest element to avoid double-counting the buffer.
            nbytes = max(_shape_bytes(dt, dims) for dt, dims in shapes)
        else:
            # sync tuple collectives (gradient buckets) genuinely carry the
            # sum of their element buffers.
            nbytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        n, groups = _parse_groups(line, num_devices)
        factor = _COLLECTIVE_KINDS[kind](n)
        ops.append(
            CollectiveOp(kind=kind, bytes_result=nbytes, group_size=n,
                         wire_bytes=nbytes * factor,
                         cross_pod_fraction=_cross_pod_fraction(groups,
                                                                pod_size))
        )
    return CollectiveSummary(ops=ops)


#: direct param convert:      %x = f32[...] convert(%param.N)
#: loop-hoisted wrapped form: %x = f32[...] fusion(%param.N), ...,
#:                                 calls=%wrapped_convert_computation.K
_PARAM_CONVERT_RE = re.compile(
    r"%(\S+) = f32\[([0-9,]+)\]\S*\s+"
    r"(?:convert\(%param[.\d]*\)"
    r"|fusion\(%param[.\d]*\),[^\n]*calls=%wrapped_convert)")


def float_normalization_overhead(hlo_text: str,
                                 min_bytes: int = 32 * 1024 * 1024) -> float:
    """Bytes of bf16->f32 PARAMETER upcasts XLA:CPU materializes.

    The CPU backend's float-normalization pass rewrites bf16 compute to f32.
    For module *parameters* (weights, KV caches) this materializes a
    whole-buffer f32 copy at entry that is then carried through the layer
    loop — purely a CPU-backend artifact: on the TPU target these buffers
    stay bf16 end-to-end.  In-graph f32 converts of computed values (the
    fp32 softmax scores etc.) are legitimate on TPU too and are NOT counted.

    The TPU-corrected peak-memory estimate subtracts half of the sum (the
    f32-vs-bf16 delta).
    """
    seen = {}
    for m in _PARAM_CONVERT_RE.finditer(hlo_text):
        name, dims = m.group(1), m.group(2)
        n = 1
        for d in dims.split(","):
            n *= int(d)
        nbytes = n * 4
        if nbytes >= min_bytes:
            seen[name] = nbytes
    return float(sum(seen.values()))


@dataclasses.dataclass
class StepCosts:
    """Per-device costs of one compiled step, ready for Ridgeline analysis."""

    flops: float                     # per-device HLO flops
    mem_bytes: float                 # per-device HLO bytes accessed
    wire_bytes: float                # per-device collective wire bytes
    collectives: CollectiveSummary
    peak_memory_per_device: float    # from memory_analysis, bytes
    num_devices: int
    # raw blobs for the record
    cost_raw: Mapping[str, float] = dataclasses.field(default_factory=dict)
    float_norm_overhead: float = 0.0  # CPU-backend bf16->f32 inflation, bytes

    @property
    def total_flops(self) -> float:
        return self.flops * self.num_devices


def _extract_cost(cost: Mapping[str, float]) -> Tuple[float, float]:
    flops = float(cost.get("flops", 0.0))
    # XLA reports "bytes accessed" under this key
    mem = float(cost.get("bytes accessed", 0.0))
    if mem == 0.0:
        # fall back: sum operand/result byte keys if aggregate missing
        mem = sum(v for k, v in cost.items()
                  if k.startswith("bytes accessed"))
    return flops, mem


def _memory_stats(mem_analysis) -> float:
    """Peak per-device bytes: args + temps + outputs − donated aliases.

    ``alias_size_in_bytes`` is the portion of outputs that share a buffer
    with donated arguments (the decode cache) — counting it in both args
    and outputs would double it.
    """
    return float(mem_analysis.temp_size_in_bytes
                 + mem_analysis.argument_size_in_bytes
                 + mem_analysis.output_size_in_bytes
                 + mem_analysis.generated_code_size_in_bytes
                 - mem_analysis.alias_size_in_bytes)


def analyze_compiled(compiled, num_devices: int,
                     pod_size: int = 0) -> StepCosts:
    """Build StepCosts from a ``jax.stages.Compiled`` object."""
    cost = compiled.cost_analysis() or {}
    flops, mem = _extract_cost(cost)
    hlo = compiled.as_text()
    coll = parse_collectives(hlo, num_devices, pod_size=pod_size)
    peak = _memory_stats(compiled.memory_analysis())
    return StepCosts(
        flops=flops,
        mem_bytes=mem,
        wire_bytes=coll.total_wire_bytes,
        collectives=coll,
        peak_memory_per_device=peak,
        float_norm_overhead=float_normalization_overhead(hlo),
        num_devices=num_devices,
        cost_raw={k: float(v) for k, v in cost.items()
                  if isinstance(v, (int, float))},
    )
