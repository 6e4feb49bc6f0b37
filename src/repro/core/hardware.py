"""Hardware resource book for Ridgeline analysis.

A ``HardwareSpec`` carries the three bandwidth-like quantities the Ridgeline
model (paper §II) needs — peak compute throughput, memory bandwidth, and
network bandwidth, all *per compute entity* (chip / socket) — plus the α
(latency) terms of the α–β extension: a fixed per-execution overhead for
compute and memory, and a per-hop latency for the network, so collective
time is ``α·steps + bytes/bandwidth`` (Chan et al.) instead of
bandwidth-only.  Multi-level networks (ICI within a pod, DCI between pods)
are expressed as a dict of named network links so the multi-pod analysis can
take per-axis terms; each named link can carry its own α.

Specs come from two sources:

  * **datasheet** presets (``PRESETS`` below) — vendor peaks, the classic
    roofline inputs;
  * **calibrated** specs — achievable ceilings fitted from real timings by
    ``repro.measure.calibrate`` and persisted as JSON under
    ``artifacts/calibration/``.  ``get_hardware(name, calibrated=True)``
    resolves the calibrated twin of a datasheet preset;
    ``list_hardware()`` enumerates both.

This module stays jax- and numpy-free so the planner CLI and the sweep
engine can resolve any spec without pulling in an accelerator runtime.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Dict, Mapping, Optional


@dataclasses.dataclass(frozen=True)
class EfficiencyModel:
    """Size-dependent achievable fraction of a peak: ``eff(q)`` in (0, 1].

    The datasheet roofline prices every work unit at full PEAK; real machines
    only reach it asymptotically — a small GEMM pays its dispatch/fill
    overhead as a *reduced achievable rate*, not as a constant everyone-pays
    latency (Wang et al., time-based roofline).  This is the parametric
    saturating form the calibration fits from the sized-GEMM microbenches:

        eff(q) = eff_min + (1 - eff_min) / (1 + (f_half / q) ** p)

    a Hill curve in the work-unit quantity ``q`` (FLOPs for the compute
    ceiling): ``f_half`` is the size recovering half the headroom, ``p`` the
    sharpness, ``eff_min`` the floor as q → 0.  ``p == 1`` is exactly the
    α–β intercept model in disguise (t = q/(peak·eff) = q/peak + f_half/peak);
    ``p < 1`` gives the heavier small-size tail real kernel suites show.
    With ``p ≤ 1`` (or ``eff_min > 0``) the priced time ``q/(peak·eff(q))``
    stays monotone non-decreasing in q; ``p > 1`` with a zero floor would
    make tinier work *slower* without bound, so the calibration fit never
    selects it (``calibrate._EFF_P_RANGE``).

    The default (``f_half == 0``) is the **identity** model ``eff ≡ 1``,
    which reproduces the paper's constant-ceiling times bit-for-bit — every
    datasheet preset uses it.  ``eff`` is monotone non-decreasing in q and
    bounded in (0, 1] for q > 0 (property-tested).
    """

    f_half: float = 0.0      # quantity at half headroom; 0 => identity
    p: float = 1.0           # Hill sharpness exponent
    eff_min: float = 0.0     # efficiency floor as q -> 0

    def __post_init__(self):
        if self.f_half < 0 or self.p <= 0 or not 0.0 <= self.eff_min <= 1.0:
            raise ValueError(
                f"EfficiencyModel needs f_half >= 0, p > 0, eff_min in "
                f"[0, 1]; got {self}")

    @property
    def is_identity(self) -> bool:
        return self.f_half == 0.0

    def eff(self, quantity: float) -> float:
        """Achievable fraction of peak for a work unit of size ``quantity``.

        Scalar and pure-math (this module stays numpy-free); the vectorized
        twin lives in ``core/sweep`` and is property-tested against this.
        """
        if self.f_half <= 0.0:
            return 1.0
        q = float(quantity)
        if q <= 0.0:
            return self.eff_min
        if math.isinf(q):
            return 1.0
        try:
            ratio = (self.f_half / q) ** self.p   # -> inf for tiny q
        except OverflowError:                     # float ** raises past 1e308
            return self.eff_min
        return self.eff_min + (1.0 - self.eff_min) / (1.0 + ratio)

    def to_dict(self) -> Dict[str, float]:
        return {"f_half": self.f_half, "p": self.p, "eff_min": self.eff_min}

    @staticmethod
    def from_dict(d: Optional[Mapping]) -> "EfficiencyModel":
        """Registry JSON -> model; None/empty (pre-v3 entries) -> identity."""
        if not d:
            return EfficiencyModel()
        return EfficiencyModel(f_half=float(d.get("f_half", 0.0)),
                               p=float(d.get("p", 1.0)),
                               eff_min=float(d.get("eff_min", 0.0)))


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip resource peaks used as Ridgeline balance points.

    Attributes:
      name: human-readable identifier.
      peak_flops: peak compute throughput, FLOP/s (in the dtype of interest).
      hbm_bw: main-memory bandwidth, bytes/s.
      net_bw: primary network bandwidth, bytes/s per chip (for TPU this is the
        per-link ICI bandwidth; collectives ride multiple links but the
        per-device wire-byte accounting in ``hlo_analysis`` is normalized to a
        single link so the division is consistent).
      extra_links: optional named slower links (e.g. ``{"dci": 25e9}``) for
        multi-level network analysis; keys are mesh-axis tags.
      alpha_compute: fixed launch/dispatch overhead per work-unit execution,
        seconds (the α in ``t_C = α + F/PEAK``); 0 for pure-bandwidth specs.
      alpha_memory: fixed per-execution memory-system overhead, seconds.
      alpha_network: per-hop network latency, seconds per serialized
        collective step (the α in ``t_N = α·steps + B_N/bw``).
      link_alphas: optional per-link α overrides keyed like ``extra_links``;
        a link without an entry inherits ``alpha_network``.
      model_rel_error: median |relative error| of this spec's calibration on
        whole-step validation points (0 for datasheet presets); consumers
        like the planner widen point estimates into uncertainty bands by it.
      compute_eff: size-dependent achievable-PEAK curve ``eff(F)`` — the
        effective compute ceiling of an F-FLOP work unit is
        ``peak_flops · compute_eff.eff(F)``.  Datasheet presets use the
        identity model (``eff ≡ 1``, paper-exact); calibration can fit the
        saturating form from sized-GEMM measurements.
      vmem_bytes: fast scratchpad capacity per core (VMEM for TPU), used by
        kernel block-shape planning, not by the Ridgeline itself.
      hbm_capacity_bytes: device main-memory *capacity* per chip, bytes.
        The Ridgeline bounds time; capacity bounds which candidates can run
        at all — the planner's working-set model (``launch/memory``) prunes
        meshes whose per-chip footprint exceeds it.  ``0`` means unknown
        (no constraint), which every pre-existing custom spec gets for free.
      ckpt_bw: sustained per-chip bandwidth to checkpoint storage, bytes/s.
        Each chip persists its own shard of the training state (params +
        optimizer states under the candidate's ZeRO/tp/pp/ep sharding), so
        checkpoint time is ``persisted bytes per chip / ckpt_bw`` — the
        input to the failure-aware goodput model (``repro.resilience``).
        ``0`` means unknown: goodput planning refuses rather than divides.
    """

    name: str
    peak_flops: float
    hbm_bw: float
    net_bw: float
    extra_links: Mapping[str, float] = dataclasses.field(default_factory=dict)
    alpha_compute: float = 0.0
    alpha_memory: float = 0.0
    alpha_network: float = 0.0
    link_alphas: Mapping[str, float] = dataclasses.field(default_factory=dict)
    model_rel_error: float = 0.0
    compute_eff: EfficiencyModel = EfficiencyModel()
    vmem_bytes: int = 128 * 1024 * 1024 // 8  # 16 MiB (v5e VMEM per core)
    hbm_capacity_bytes: float = 0.0           # 0 = unknown, no feasibility cut
    ckpt_bw: float = 0.0                      # 0 = unknown, no goodput model

    def effective_peak(self, flops: float) -> float:
        """The achievable compute ceiling for an ``flops``-sized unit."""
        return self.peak_flops * self.compute_eff.eff(flops)

    # ---- machine balance points (paper §II, Fig. 2) -------------------------
    @property
    def ridge_arithmetic(self) -> float:
        """y* = Peak / HBM_bw: the classic roofline ridge (FLOP/mem-byte)."""
        return self.peak_flops / self.hbm_bw

    @property
    def ridge_memory(self) -> float:
        """x* = HBM_bw / Net_bw: memory-network balance (mem-byte/net-byte)."""
        return self.hbm_bw / self.net_bw

    @property
    def ridge_network(self) -> float:
        """k* = Peak / Net_bw: compute-network balance (FLOP/net-byte).

        The hyperbola x*y = k* is the straight separation line (in log-log)
        of the upper-left quadrant (paper Fig. 2d).
        """
        return self.peak_flops / self.net_bw

    #: names that always resolve to the primary link
    PRIMARY_LINKS = (None, "ici", "net")

    def bandwidth_for(self, link: str | None = None) -> float:
        """Bandwidth of a named link; unknown names raise with the options."""
        if link in self.PRIMARY_LINKS:
            return self.net_bw
        try:
            return float(self.extra_links[link])
        except KeyError:
            raise KeyError(
                f"hardware spec {self.name!r} has no network link {link!r}; "
                f"available links: primary ('net'/'ici'/None at "
                f"{self.net_bw:.3g} B/s) plus extra_links "
                f"{sorted(self.extra_links) or '{}'}") from None

    def alpha_for(self, link: str | None = None) -> float:
        """Per-hop α of a named link (falls back to ``alpha_network``)."""
        if link not in self.PRIMARY_LINKS and link not in self.extra_links:
            self.bandwidth_for(link)           # raise the actionable KeyError
        return float(self.link_alphas.get(link, self.alpha_network))


# --- Presets -----------------------------------------------------------------

#: TPU v5e — the target deployment chip for this framework.  Peaks from the
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
#: 819 GB/s, 1,600 Gbit/s of ICI per chip (~50 GB/s on each of 4 links).  The
#: multi-pod ``pod`` axis rides data-center interconnect, modelled at
#: 25 GB/s/chip.
TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    net_bw=50e9,
    extra_links={"pod": 25e9},
    hbm_capacity_bytes=16e9,      # 16 GB HBM per v5e chip (datasheet)
    ckpt_bw=1e9,                  # ~1 GB/s/chip sustained to blob storage
)

#: Intel Xeon Cascade Lake socket exactly as in the paper's case study (§III):
#: 4.2 TF/s FP32, 105 GB/s DRAM, 12 GB/s network per socket.
CLX = HardwareSpec(
    name="clx",
    peak_flops=4.2e12,
    hbm_bw=105e9,
    net_bw=12e9,
    vmem_bytes=36 * 1024 * 1024,  # LLC, unused in analysis
    hbm_capacity_bytes=192e9,     # 6-channel DDR4 socket, 32 GB DIMMs
    ckpt_bw=2e9,                  # local NVMe per socket
)

PRESETS: Dict[str, HardwareSpec] = {"tpu_v5e": TPU_V5E, "clx": CLX}

#: ``jax.Device.device_kind`` -> the preset whose peaks describe that chip
#: (JAX's own mesh code knows a v5e by either name)
DEVICE_KIND_PRESETS: Dict[str, str] = {"TPU v5 lite": "tpu_v5e",
                                       "TPU v5e": "tpu_v5e"}


def hardware_for_device_kind(device_kind: str) -> HardwareSpec:
    """Datasheet preset of a device as JAX names it; unlisted kinds raise."""
    try:
        return PRESETS[DEVICE_KIND_PRESETS[device_kind]]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; known kinds: "
            f"{sorted(DEVICE_KIND_PRESETS)}") from None


# --- calibration registry -----------------------------------------------------

#: JSON schema tag the calibration registry *writes* (v3: v2's α–β fit plus
#: the size-dependent ``compute_eff`` achievable-PEAK curve)
CALIBRATION_SCHEMA = "repro.calibration/v3"

#: schema tags the registry *reads*; v1 entries (bandwidth-only fit, extra
#: links scaled by the primary-NET ratio) load with all α = 0, and both v1
#: and v2 entries (which predate the efficiency model) load with ``eff ≡ 1``
CALIBRATION_SCHEMAS = ("repro.calibration/v1", "repro.calibration/v2",
                       CALIBRATION_SCHEMA)

#: suffix convention: the calibrated twin of preset ``clx`` is ``clx_cal``
CALIBRATED_SUFFIX = "_cal"


def calibration_dir(registry_dir: Optional[str] = None) -> str:
    """Where calibrated specs live: explicit arg > env > repo default.

    The default resolves relative to this source tree
    (``<repo>/artifacts/calibration``) so CLIs work from any cwd.
    """
    if registry_dir is not None:
        return registry_dir
    env = os.environ.get("REPRO_CALIBRATION_DIR")
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))   # src/repro/core -> repo
    return os.path.join(root, "artifacts", "calibration")


def spec_from_calibration(d: Mapping) -> HardwareSpec:
    """Build a HardwareSpec from one calibration-registry JSON dict.

    Accepts any schema in :data:`CALIBRATION_SCHEMAS`; v1 entries predate
    the α–β fit, so their α terms default to 0 (bandwidth-only behaviour is
    preserved bit-for-bit), and v1/v2 entries predate the efficiency model,
    so ``compute_eff`` defaults to the identity curve.
    """
    schema = d.get("schema")
    if schema not in CALIBRATION_SCHEMAS:
        raise ValueError(
            f"calibration entry {d.get('name')!r} has schema {schema!r}, "
            f"expected one of {CALIBRATION_SCHEMAS}")
    validation = d.get("validation", {}) or {}
    # capacity passthrough: entries written before the field existed fall
    # back to their base preset's datasheet capacity (calibration measures
    # rates, not capacity — the committed registry never needs a rewrite)
    base = PRESETS.get(str(d.get("base", "")))
    capacity = d.get("hbm_capacity_bytes",
                     base.hbm_capacity_bytes if base is not None else 0.0)
    ckpt_bw = d.get("ckpt_bw", base.ckpt_bw if base is not None else 0.0)
    return HardwareSpec(
        name=str(d["name"]),
        peak_flops=float(d["peak_flops"]),
        hbm_bw=float(d["hbm_bw"]),
        net_bw=float(d["net_bw"]),
        extra_links={k: float(v)
                     for k, v in dict(d.get("extra_links", {})).items()},
        alpha_compute=float(d.get("alpha_compute", 0.0)),
        alpha_memory=float(d.get("alpha_memory", 0.0)),
        alpha_network=float(d.get("alpha_network", 0.0)),
        link_alphas={k: float(v)
                     for k, v in dict(d.get("link_alphas", {})).items()},
        model_rel_error=float(validation.get("median_abs_rel_error", 0.0)),
        compute_eff=EfficiencyModel.from_dict(d.get("compute_eff")),
        vmem_bytes=int(d.get("vmem_bytes", HardwareSpec.vmem_bytes)),
        hbm_capacity_bytes=float(capacity),
        ckpt_bw=float(ckpt_bw),
    )


def _read_calibration_entry(path: str) -> Optional[Dict]:
    """One registry file as a dict, or None if unreadable/off-schema."""
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(d, dict) or d.get("schema") not in CALIBRATION_SCHEMAS:
        return None
    return d


def load_calibrated(name: str,
                    registry_dir: Optional[str] = None) -> HardwareSpec:
    """Load a calibrated spec by its own name or by its base preset's name.

    Only ever raises KeyError on failure (corrupt or off-schema registry
    entries are skipped), so callers can treat the registry like a dict.
    """
    cdir = calibration_dir(registry_dir)
    candidates = [os.path.join(cdir, name + ".json"),
                  os.path.join(cdir, name + CALIBRATED_SUFFIX + ".json")]
    if os.path.isdir(cdir):
        candidates += [os.path.join(cdir, fn)
                       for fn in sorted(os.listdir(cdir))
                       if fn.endswith(".json")]
    for path in candidates:
        d = _read_calibration_entry(path) if os.path.isfile(path) else None
        if d is None:
            continue
        base = os.path.basename(path)[:-len(".json")]
        if base == name or d.get("name") == name or d.get("base") == name:
            return spec_from_calibration(d)
    calibrated = sorted(n for n, src in list_hardware(registry_dir).items()
                        if src == "calibrated")
    raise KeyError(
        f"no calibration for {name!r} under {cdir}; run "
        f"`python -m repro.measure.calibrate` first "
        f"(calibrated specs available: {calibrated or 'none'})")


def list_hardware(registry_dir: Optional[str] = None) -> Dict[str, str]:
    """All resolvable spec names -> source ('datasheet' | 'calibrated').

    A registry entry whose name shadows a datasheet preset is skipped:
    ``get_hardware`` would resolve that name to the preset, and listing it
    as calibrated would misattribute the numbers.
    """
    out = {name: "datasheet" for name in PRESETS}
    cdir = calibration_dir(registry_dir)
    if os.path.isdir(cdir):
        for fn in sorted(os.listdir(cdir)):
            if not fn.endswith(".json"):
                continue
            d = _read_calibration_entry(os.path.join(cdir, fn))
            if d is not None and "name" in d and d["name"] not in PRESETS:
                out[str(d["name"])] = "calibrated"
    return out


def get_hardware(name: str, *, calibrated: bool = False,
                 registry_dir: Optional[str] = None) -> HardwareSpec:
    """Resolve a spec by name.

    ``calibrated=True`` demands the measured twin (KeyError if never
    calibrated).  With the default ``calibrated=False``, datasheet presets
    win, but names only present in the calibration registry (e.g.
    ``clx_cal``) still resolve — so every name in :func:`list_hardware` is
    directly usable.
    """
    if calibrated:
        return load_calibrated(name, registry_dir)
    if name in PRESETS:
        return PRESETS[name]
    try:
        return load_calibrated(name, registry_dir)
    except KeyError:
        pass
    raise KeyError(f"unknown hardware spec {name!r}; "
                   f"have {sorted(list_hardware(registry_dir))}")
