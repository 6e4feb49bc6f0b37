"""obs subsystem: tracer round-trips, metrics registry, explain attribution.

The property that matters most here is pinned twice: the per-candidate
``breakdown`` terms must sum to the planner's priced step time (within
float tolerance — the engine adds them in a different order), and the
qwen2-7b explain JSON is golden-pinned byte-for-byte so an accidental
re-pricing shows up as a diff, not a silent drift.
"""
import json
import math
import os

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.hardware import get_hardware
from repro.launch.plan_grid import plan_grid
from repro.measure import timers
from repro.measure.microbench import Measurement, WorkUnit
from repro.obs import explain, metrics, trace

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


# --- trace: spans, counters, export, validation -------------------------------


def test_trace_roundtrip_and_validation(tmp_path):
    t = trace.Tracer()
    with t.span("outer", arch="x"):
        with t.span("inner") as sp:
            sp.set(n=3)
        with t.span("inner2"):
            pass
    t.count("things", 2)
    t.count("things", 3)
    path = t.write(str(tmp_path / "t.json"))
    summary = trace.validate_chrome_trace(path)
    assert summary["n_spans"] == 3
    assert summary["n_counter_events"] == 2
    assert summary["max_depth"] == 2
    assert summary["n_threads"] == 1
    assert summary["counters"] == {"things": 5.0}
    with open(path) as f:
        doc = json.load(f)
    args = {e["name"]: e.get("args", {}) for e in doc["traceEvents"]
            if e["ph"] == "X"}
    assert args["inner"] == {"n": 3}          # set() args survive export
    assert "provenance" in doc["otherData"]


def test_trace_write_is_atomic_and_makes_dirs(tmp_path):
    t = trace.Tracer(str(tmp_path / "deep" / "nested" / "t.json"))
    with t.span("s"):
        pass
    path = t.write()
    assert os.path.exists(path) and not os.path.exists(path + ".tmp")


def test_validate_rejects_missing_fields():
    with pytest.raises(ValueError, match="missing 'dur'"):
        trace.validate_chrome_trace(
            {"traceEvents": [{"name": "a", "ph": "X", "ts": 0,
                              "pid": 1, "tid": 1}]})
    with pytest.raises(ValueError, match="negative dur"):
        trace.validate_chrome_trace(
            {"traceEvents": [{"name": "a", "ph": "X", "ts": 0, "dur": -1,
                              "pid": 1, "tid": 1}]})
    with pytest.raises(ValueError, match="traceEvents"):
        trace.validate_chrome_trace({"events": []})


def test_validate_rejects_partial_overlap():
    # [0, 10] and [5, 15] on one thread: neither disjoint nor nested
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 5, "dur": 10, "pid": 1, "tid": 1}]}
    with pytest.raises(ValueError, match="partially overlaps"):
        trace.validate_chrome_trace(bad)


def test_disabled_module_span_is_shared_noop():
    assert not trace.enabled()
    sp = trace.span("anything", heavy_arg=object())
    sp2 = trace.span("other")
    # one shared singleton, no allocation per call site on the hot path
    assert sp is sp2 is trace._NULL_SPAN
    with sp as s:
        s.set(n=1)
    assert trace.count("c") is None
    assert trace.counters() == {}
    assert trace.write() is None


def _host_event_names(profile_dir):
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(profile_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return [e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events]


def test_span_lands_in_the_profilers_trace(tmp_path):
    import jax
    assert not trace.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("obs.profiled", n=3) as sp:
            assert sp is not trace._NULL_SPAN
            sp.set(found=1)
    finally:
        jax.profiler.stop_trace()
    assert trace.span("obs.after") is trace._NULL_SPAN
    names = [n.split("#", 1)[0] for n in _host_event_names(tmp_path)]
    assert "obs.profiled" in names and "obs.after" not in names


def test_profiled_span_still_records_into_the_tracer(tmp_path):
    import jax
    try:
        t = trace.enable()
        jax.profiler.start_trace(str(tmp_path / "prof"))
        try:
            with trace.span("obs.both"):
                pass
        finally:
            jax.profiler.stop_trace()
        assert [e["name"] for e in t.to_dict()["traceEvents"]] == [
            "obs.both"]
    finally:
        trace.disable()
    names = [n.split("#", 1)[0] for n in
             _host_event_names(tmp_path / "prof")]
    assert "obs.both" in names


def test_obs_imports_and_spans_without_jax():
    import subprocess
    import sys
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro.obs import trace\n"
            "assert trace.span('x') is trace._NULL_SPAN\n"
            "assert 'jax.profiler' not in sys.modules")
    import repro
    src = os.path.dirname(os.path.dirname(repro.__file__))
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ, PYTHONPATH=src, REPRO_TRACE=""))


def test_enable_disable_module_tracer(tmp_path):
    try:
        t = trace.enable(str(tmp_path / "m.json"))
        assert trace.enabled() and trace.active() is t
        with trace.span("top", k=1):
            trace.count("seen")
        assert t.n_events == 2
        assert trace.counters() == {"seen": 1}
        path = trace.write()
        assert trace.validate_chrome_trace(path)["n_spans"] == 1
    finally:
        assert trace.disable() is t
    assert not trace.enabled()


# --- metrics registry ---------------------------------------------------------


def test_counter_gauge_histogram():
    reg = metrics.MetricsRegistry()
    c = reg.counter("c")
    assert reg.counter("c") is c          # create-or-get
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    assert g.value is None
    g.set(2.5)
    h = reg.histogram("h")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 2.5
    hs = snap["histograms"]["h"]
    assert hs["count"] == 4 and hs["min"] == 1.0 and hs["max"] == 4.0
    assert hs["p50"] == pytest.approx(2.5)
    assert json.dumps(snap)               # JSON-clean by construction
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_histogram_time_and_section():
    reg = metrics.MetricsRegistry()
    with reg.histogram("lat").time():
        pass
    assert reg.histogram("lat").count == 1
    with reg.section("section.x_s"):
        pass
    assert reg.gauge("section.x_s").value >= 0.0


def test_histogram_window_bounds_memory():
    h = metrics.Histogram("h")
    for i in range(metrics._HIST_WINDOW + 100):
        h.observe(float(i))
    assert len(h._window) == metrics._HIST_WINDOW
    assert h.count == metrics._HIST_WINDOW + 100   # exact stats keep counting


def test_provenance_keys():
    p = metrics.provenance()
    assert set(p) == {"git_sha", "hostname", "wall_clock_utc", "python",
                      "platform", "numpy", "jax"}
    assert p["numpy"] is not None
    assert json.dumps(p)


# --- timers: degenerate-sample spread (satellite a) ---------------------------


def test_rel_spread_nan_below_min_samples():
    for n in (1, 2):
        st = timers.robust_stats([0.5] * n)
        assert math.isnan(st.rel_spread)
        assert "spread not measurable" in st.summary()
    st3 = timers.robust_stats([0.5, 0.5, 0.5])
    assert st3.rel_spread == 0.0          # measured, genuinely stable
    assert "not measurable" not in st3.summary()


def test_rel_spread_nan_fails_noise_gates():
    st = timers.robust_stats([0.5])
    # the reason NaN (not 0.0): an acceptance check must FAIL, not pass
    assert not (st.rel_spread < 0.1)


def test_measurement_nan_spread_json_roundtrip():
    w = WorkUnit("probe", 1e9, 1e6, 0.0)
    m = Measurement(work=w, category="compute", seconds=1.0,
                    best_seconds=1.0, rel_spread=math.nan)
    d = m.to_dict()
    assert d["rel_spread"] is None        # NaN is not valid JSON
    json.dumps(d)
    m2 = Measurement.from_dict(d)
    assert math.isnan(m2.rel_spread)
    # and the non-degenerate path is untouched
    m3 = Measurement.from_dict(Measurement(
        work=w, category="compute", seconds=1.0, best_seconds=1.0,
        rel_spread=0.25).to_dict())
    assert m3.rel_spread == 0.25


# --- explain: attribution terms, prune reasons, golden ------------------------


QWEN = dict(seq=128, zero_stages=(0, 1, 2, 3))


def _qwen_grid(**kw):
    return plan_grid(get_config("qwen2-7b"), get_hardware("tpu_v5e"),
                     [16], [8], **QWEN, **kw)


def test_explain_terms_sum_to_step_time():
    cfg = get_config("dlrm-mlp")
    grid = plan_grid(cfg, get_hardware("clx"), [8, 16], [512, 1024],
                     max_pp=4, zero_stages=(0, 1), explain=True)
    d = explain.explain_dict(grid)
    n = 0
    for point in d["points"]:
        for rec in point["candidates"]:
            total = sum(rec["breakdown"].values())
            assert total == pytest.approx(rec["runtime"], rel=1e-9), \
                f"{rec['mesh']} z{rec['zero_stage']} ({rec['bottleneck']})"
            # the full terms reconstruct each resource time too
            t = rec["terms"]
            assert t["compute"]["alpha"] + t["compute"]["flops"] == \
                pytest.approx(rec["t_compute"], rel=1e-9)
            assert t["memory"]["alpha"] + t["memory"]["bytes"] == \
                pytest.approx(rec["t_memory"], rel=1e-9)
            net = sum(ax["total"] for ax in t["network"].values())
            assert net == pytest.approx(rec["t_network"], rel=1e-9)
            n += 1
    assert n == grid.n_candidates         # every candidate is explained


def test_explain_prune_reasons_match_capacity_mask():
    grid = _qwen_grid(explain=True)
    point = explain.explain_point(grid)
    assert point["prune_reasons"]["capacity"] == int(grid.n_pruned.sum())
    assert point["min_zero_to_fit"] == 2  # qwen2-7b@16 v5e needs ZeRO-2
    kept = point["prune_reasons"]["kept_mesh_tuples"]
    assert kept * len(QWEN["zero_stages"]) == grid.n_enumerated


def test_explain_off_by_default_and_bit_identical():
    g0 = _qwen_grid()
    assert g0.explain_terms is None and g0.prune_reasons is None
    with pytest.raises(ValueError, match="explain=True"):
        explain.explain_dict(g0)
    g1 = _qwen_grid(explain=True)
    # attribution must observe the pricing, never perturb it
    np.testing.assert_array_equal(g0.runtime, g1.runtime)
    np.testing.assert_array_equal(g0.n_pruned, g1.n_pruned)


def test_explain_golden_qwen2_7b():
    grid = _qwen_grid(explain=True)
    got = json.loads(explain.to_json(grid))
    with open(os.path.join(GOLDEN_DIR,
                           "explain_qwen2_7b_c16_zero.json")) as f:
        want = json.load(f)
    assert got == want, (
        "explain attribution drifted from tests/golden/"
        "explain_qwen2_7b_c16_zero.json — if the pricing change is "
        "intentional, regenerate the golden and say so in the PR")


def test_explain_table_and_prune_line_render():
    grid = _qwen_grid(explain=True)
    point = explain.explain_point(grid)
    table = explain.format_explain_table(point["candidates"])
    assert "step ms" in table and "dp4xtp4" in table
    line = explain.format_prune_reasons(point)
    assert "capacity=5" in line and "ZeRO-2" in line


def test_plan_grid_emits_spans_when_traced(tmp_path):
    try:
        trace.enable(str(tmp_path / "plan.json"))
        _qwen_grid(explain=True)
        names = {e["name"] for e in trace.active().to_dict()["traceEvents"]}
    finally:
        trace.disable()
    assert {"plan_grid", "plan_grid.enumerate", "plan_grid.feasibility",
            "plan_grid.price_collectives", "plan_grid.sweep_classify",
            "core.sweep"} <= names
    assert {"planner.candidates_enumerated",
            "planner.candidates_evaluated"} <= names  # counter tracks


def test_explain_cli_json(capsys):
    from repro.launch import plan as plan_mod
    rc = plan_mod.main(["--arch", "qwen2-7b", "--hardware", "tpu_v5e",
                        "--chips", "16", "--batch", "8", "--seq", "128",
                        "--zero", "auto", "--explain", "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    ex = doc["explain"]
    assert ex["schema"] == explain.EXPLAIN_SCHEMA
    recs = ex["points"][0]["candidates"]
    assert [r["mesh"] for r in recs][0] == doc["plans"][0]["mesh"]
