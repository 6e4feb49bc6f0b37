"""The reduction from a profiler trace to busy time, idle share, idle
gaps by host span, op time and exposed collective time."""
import pytest

from bench import devtrace

MS = 1e6


def trace_of(ops, modules, host):
    return {"host": host,
            "devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}]}


OPS = [("fusion.1", 0 * MS, 4 * MS), ("all-reduce.1", 3 * MS, 6 * MS),
       ("fusion.2", 7 * MS, 9 * MS), ("fusion.1", 8 * MS, 8.5 * MS)]
MODS = [("jit_step(1)", 0 * MS, 6 * MS), ("jit_step(1)", 7 * MS, 9 * MS),
        ("jit_other(2)", 9.5 * MS, 9.6 * MS)]
HOST = [("bench.window", 0, 10 * MS), ("bench.wait", 6 * MS, 6.5 * MS),
        ("bench.dispatch", 9 * MS, 9.2 * MS)]


def test_busy_is_the_union_of_ops_in_the_window():
    dev = trace_of(OPS, MODS, HOST)["devices"][0]
    # [0, 6] and [7, 9]: 8 ms of 10
    assert devtrace.busy_ns(dev, 0, 10 * MS) == pytest.approx(8 * MS)
    assert devtrace.busy_ns(dev, 2 * MS, 8 * MS) == pytest.approx(5 * MS)
    assert devtrace.gaps(dev, 0, 10 * MS) == [(6 * MS, 7 * MS),
                                              (9 * MS, 10 * MS)]


def test_idle_gaps_go_to_the_host_span_open_meanwhile():
    tr = trace_of(OPS, MODS, HOST)
    idle = devtrace.idle_by_span(tr, *devtrace.window_of(tr))
    assert idle == pytest.approx({"bench.wait": 0.5e-3,
                                  "bench.dispatch": 0.2e-3,
                                  "unspanned": 1.3e-3})


def test_op_seconds_and_top():
    tr = trace_of(OPS, MODS, HOST)
    secs = devtrace.op_seconds(tr, 0, 10 * MS)
    assert secs["fusion.1"] == pytest.approx(4.5e-3)
    assert devtrace.top(secs, 1) == [["fusion.1", secs["fusion.1"]]]


def test_module_runs_pick_the_main_program():
    dev = trace_of(OPS, MODS, HOST)["devices"][0]
    assert devtrace.module_runs(dev, 0, 10 * MS) == MODS[:2]
    assert devtrace.module_runs(dev, 0, 10 * MS, match="other") == MODS[2:]
    # a run cut by the window's edge is left out
    assert devtrace.module_runs(dev, 1 * MS, 10 * MS) == MODS[1:2]


def test_ops_within_runs_by_name():
    dev = trace_of(OPS, MODS, HOST)["devices"][0]
    got = devtrace.ops_within(dev, MODS[:2], {"fusion.1"})
    assert got == [OPS[0], OPS[3]]


def test_exposed_collective_time():
    dev = trace_of(OPS, MODS, HOST)["devices"][0]
    # all-reduce [3, 6] overlaps fusion.1 until 4: 2 ms exposed
    assert devtrace.exposed_ns(dev, MODS[:2], {"all-reduce.1"}) == \
        pytest.approx(2 * MS)


def test_op_names_are_the_instruction_names():
    assert devtrace._op_name(
        "%convolution_add_fusion.7 = bf16[8192,4096]{1,0} fusion(%a)") == \
        "convolution_add_fusion.7"
    assert devtrace._op_name("dot_general.1") == "dot_general.1"


DATA = __import__("pathlib").Path(__file__).parent / "data"


def recorded():
    """Three steps of dlrm-mlp's train step at batch 8192 on one TPU v5
    lite, cut from a traced window, and the HLO text of that step."""
    import gzip
    import json
    trace = json.loads((DATA / "dlrm_step_trace.json").read_text())
    hlo = gzip.open(DATA / "dlrm_step_hlo.txt.gz", "rt").read()
    return trace, hlo


def test_recorded_trace_busy_and_steps():
    tr, _ = recorded()
    lo, hi = devtrace.window_of(tr)
    dev = tr["devices"][0]
    busy = devtrace.busy_ns(dev, lo, hi)
    assert 0.99 * (hi - lo) < busy <= hi - lo
    runs = devtrace.module_runs(dev, lo, hi)
    assert len(runs) == 3
    assert all(r[0].startswith("jit_train_step") for r in runs)
    assert all(40e6 < r[2] - r[1] < 41e6 for r in runs)


def test_recorded_trace_gemm_roofline():
    from bench import flops, hlo_ops
    tr, hlo = recorded()
    lo, hi = devtrace.window_of(tr)
    dev = tr["devices"][0]
    runs = devtrace.module_runs(dev, lo, hi)
    gemms = hlo_ops.select(hlo, "gemm")
    ops = devtrace.ops_within(dev, runs, gemms)
    # forward 8 + head, weight gradients, activation gradients: XLA fuses
    # them into 23 ops a step
    assert len(ops) == 3 * len(gemms) == 69
    gemm_s = sum(e - s for _, s, e in ops) / 1e9
    step_s = sum(e - s for _, s, e in runs) / 1e9
    assert 0.8 < gemm_s / step_s < 0.95
    share = 3 * flops.mlp_train_flops(8192, [4096] * 8) / 197e12 / gemm_s
    assert 0.85 < share < 1.0
