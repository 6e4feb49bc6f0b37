"""Observability: tracing, metrics, and plan cost-attribution.

Three zero-dependency layers (stdlib only — importable everywhere the
planner is, including jax-free CLI paths):

  * :mod:`repro.obs.trace` — nested context-manager span tracer with
    thread-safe counters, exporting Chrome-trace-event JSON that loads
    directly into Perfetto (``ui.perfetto.dev``) or ``chrome://tracing``.
    Off by default and engineered to stay near-free when off; enabled via
    env ``REPRO_TRACE=/path.json`` or CLI ``--trace PATH``.  While JAX's
    profiler is collecting, every span also lands in its trace as a
    ``jax.profiler.TraceAnnotation``, on the device ops' clock.
  * :mod:`repro.obs.metrics` — a process-wide registry of counters,
    gauges and histograms with JSON snapshot export, plus run-provenance
    capture (git sha, library versions, hostname, wall clock) stamped
    into ``BENCH_ridgeline.json`` and calibration registries.
  * :mod:`repro.obs.explain` — the attribution layer:
    ``plan_grid(..., explain=True)`` / CLI ``--explain`` decompose each
    surviving candidate's projected step time into additive terms
    (compute, memory, per-axis α·steps vs bytes/bw network, pipeline
    bubble, ZeRO sync) and report structured prune reasons.
"""
from repro.obs import metrics, trace  # noqa: F401  (stable import surface)
from repro.obs.metrics import REGISTRY, provenance  # noqa: F401
from repro.obs.trace import count, enabled, span  # noqa: F401

__all__ = ["trace", "metrics", "span", "count", "enabled", "REGISTRY",
           "provenance"]
