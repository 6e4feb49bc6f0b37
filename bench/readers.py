"""Helpers for the metric readers in ``metrics/``: the traced window and
the device peaks."""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from bench import devtrace, flops


def peak(run: Dict[str, Any]) -> Dict[str, float]:
    return flops.peak_for(run["devices"][0].device_kind)


def traced(result: Dict[str, Any]) -> Optional[Tuple[Dict, float, float]]:
    """(trace, lo, hi) of a traced run's window, else None."""
    tr = result.get("trace")
    if tr is None or not tr["devices"]:
        return None
    lo, hi = devtrace.window_of(tr)
    return tr, lo, hi


def idle_percent(result: Dict[str, Any]) -> Optional[float]:
    t = traced(result)
    if t is None:
        return None
    tr, lo, hi = t
    busy = [devtrace.busy_ns(d, lo, hi) for d in tr["devices"]]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))


def step_runs(result: Dict[str, Any], match: Optional[str] = None
              ) -> Optional[List[Tuple[Dict, List]]]:
    """For each device, its executions of the step program in the window."""
    t = traced(result)
    if t is None:
        return None
    tr, lo, hi = t
    out = [(d, devtrace.module_runs(d, lo, hi, match)) for d in tr["devices"]]
    return out if all(runs for _, runs in out) else None
