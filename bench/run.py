"""Run one cell of ``BENCHMARK.json`` on the accelerator and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, backend, weights, compile or compile-cache load, the
cell's first steps or rounds) is timed as ``setup_s``; then the window runs
for ``--seconds`` with the garbage collector off.  With ``--trace 1`` the
profiler records the first ``trace_seconds`` of the traffic's window, which
is then all the window there is, and the per-layer metrics are read from
it.  After the window the program's state is freed and the reference
checks what the window's path produced.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``), its last key ``checks``: each number compared, with its
limit.  The same numbers end standard error.  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

_T_START = __import__("time").perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    pass


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _paths() -> None:
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    # the persistent compile cache lives in the checkout, at a fixed path:
    # the path is part of the cache key
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devices)}")
    return devices[:n]


def plan(workload: str, seed: int) -> dict:
    """Everything the cell's name selects: its entry and its files."""
    from bench import manifest
    cell = manifest.find_cell(manifest.load_manifest(), workload)
    return {"cell": cell, "seed": seed, "n_chips": cell["chips"],
            "config": manifest.load_config(cell["config"]),
            "traffic": manifest.load_traffic(cell["traffic"])}


class CompileLog:
    """Compilations and compile-cache loads, counted by phase from JAX's
    monitoring events: there should be none in the window."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compile_s",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s"}

    def __init__(self):
        import jax
        self.phase = "setup"
        self.book: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        key = self.EVENTS.get(event)
        if key:
            b = self.book.setdefault(self.phase, {})
            b[key] = b.get(key, 0.0) + secs
            n = key.replace("_s", "s")
            b[n] = b.get(n, 0) + 1


def measure(run: dict, seconds: float, trace: bool) -> dict:
    """Set up, run the window, free the program's state, check."""
    from bench import common, devtrace, manifest
    import jax
    # cache every program, so that a cell's second run compiles nothing; and
    # never evict: eviction reads an access-time file beside every entry,
    # and one entry left without it (by another process or a copy of the
    # directory) makes every write fail
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    log = CompileLog()
    driver = manifest.load_driver(run["traffic"]["kind"])
    ctx = driver.setup(run)
    length = seconds
    if trace:
        length = min(seconds, run["traffic"]["trace_seconds"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    gc.collect()
    gc.disable()
    setup_s = time.perf_counter() - _T_START
    log.phase = "window"
    try:
        window = driver.window(ctx, length, common.spans(trace))
    finally:
        gc.enable()
    log.phase = "after"
    out = {"setup_s": setup_s, "window": window, "ctx": ctx,
           "compiles": log.book}
    if trace:
        jax.profiler.stop_trace()
        out["trace"] = devtrace.load(str(TRACE_DIR))
    out["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in run["devices"])
    driver.release(ctx)
    t = time.perf_counter()
    out["checks"] = driver.check(ctx)
    out["check_s"] = time.perf_counter() - t
    return out


def read_metrics(run: dict, result: dict, trace: bool) -> dict:
    """Each metric of the cell, by its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    from bench import manifest
    m = manifest.load_manifest()
    kind = "per_layer" if trace else "end_to_end"
    entries = {e["name"]: e for e in m[kind]}
    out = {}
    for name in manifest.metrics_of(m[kind], run["cell"]["name"]):
        value = manifest.load_metric(name).read(run, result)
        if value is None:
            if kind == "end_to_end":
                raise RuntimeError(f"no value for end-to-end metric {name}")
            continue
        out[name] = {"value": float(value), "unit": entries[name]["unit"]}
    return out


def device_block(run: dict, result: dict) -> dict:
    d = run["devices"][0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(run["devices"]),
           "memory_peak_bytes": int(result["memory_peak_bytes"])}
    if "trace" in result:
        from bench import devtrace
        lo, hi = devtrace.window_of(result["trace"])
        devs = result["trace"]["devices"]
        out["busy_s"] = sum(devtrace.busy_ns(x, lo, hi) for x in devs) / len(
            devs) / 1e9
        out["window_s"] = (hi - lo) / 1e9
    return out


def breakdown(result: dict) -> dict:
    from bench import devtrace
    tr = result["trace"]
    lo, hi = devtrace.window_of(tr)
    return {"device_ops": devtrace.top(devtrace.op_seconds(tr, lo, hi)),
            "idle_gaps": devtrace.top(devtrace.idle_by_span(tr, lo, hi))}


def verdict(checks: dict, limits: dict) -> dict:
    """Each number compared beside its limit; correct when every one is
    finite and at or under it.  A number whose limit is null is read but
    not compared (PERF.md says why)."""
    out = {}
    for name, c in checks.items():
        if limits[name] is None:
            continue
        v = c["value"]
        out[name] = {"value": v, "limit": limits[name],
                     "ok": bool(math.isfinite(v) and v <= limits[name])}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    _paths()
    run = plan(args.workload, args.seed)
    times = {}
    t = time.perf_counter()
    import jax  # noqa: F401
    times["import_jax"] = time.perf_counter() - t
    try:
        t = time.perf_counter()
        run["devices"] = require_chips(run["n_chips"])
        times["backend"] = time.perf_counter() - t
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    run.update(times=times, trace=bool(args.trace))
    result = measure(run, args.seconds, bool(args.trace))
    checks = verdict(result["checks"], run["config"]["limits"])
    correct = all(c["ok"] for c in checks.values())
    metrics = read_metrics(run, result, bool(args.trace))
    w = result["window"]
    attempted = w.get("requests", w.get("steps"))
    line = {"correct": correct, "attempted": attempted, "failed": 0,
            "metrics": metrics, "device": device_block(run, result)}
    if args.trace:
        line["breakdown"] = breakdown(result)
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    times["total"] = result["setup_s"]
    diag = {"setup": times, "compiles": result["compiles"],
            "check_s": result["check_s"],
            "window": {k: v for k, v in w.items()
                       if isinstance(v, (int, float))},
            "checks": result["checks"]}
    print("setup " + json.dumps(diag), flush=True)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
