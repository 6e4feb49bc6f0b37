"""The metric arithmetic: rates over the whole window, percentiles over
all requests, FLOP counts and utilisation."""
import statistics
import types

import pytest

from bench import flops, manifest, stats


def _run(n_chips=1, **over):
    dev = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    run = {"n_chips": n_chips, "devices": [dev] * n_chips}
    run.update(over)
    return run


def test_rate_is_work_over_the_whole_window():
    assert stats.rate(8192 * 10, 2.0) == 40960.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_p95_counts_every_request():
    # 19 requests at 1 s and one at 3 s: the tail is interpolated
    # between the 19th and 20th order statistics
    xs = [1.0] * 19 + [3.0]
    assert stats.percentile(xs, 95) == pytest.approx(1.0 + 0.05 * 2.0)
    assert stats.percentile(list(range(101)), 95) == 95.0


def test_spread_uses_statistics_quantiles():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 12.5)


def test_mlp_flops_count_each_gemm_once():
    # widths (4, 4): fwd 2*B*(16+16+4), wgrad the same, act-grad of the
    # second layer and the head only
    B = 3
    fwd = 2 * B * (4 * 4 + 4 * 4 + 4 * 1)
    act = 2 * B * (4 * 4 + 4 * 1)
    assert flops.mlp_train_flops(B, [4, 4]) == 2 * fwd + act


def test_train_mfu_reads_samples_per_second_times_flops_over_peak():
    reader = manifest.load_metric("train_mfu")
    window = {"steps": 100, "flops_per_step": 197e12 * 0.04,
              "seconds": 5.0, "samples": 819200}
    # 100 steps of 0.04 s of peak work each in 5 s: 80% of one chip
    assert reader.read(_run(), {"window": window}) == pytest.approx(80.0)
    assert reader.read(_run(4), {"window": window}) == pytest.approx(20.0)


def test_serve_metrics_read_the_window():
    window = {"tokens": 64 * 32 * 3, "seconds": 6.0,
              "latencies": [1.5] * 64 + [2.0] * 64 + [2.5] * 64,
              "serve_steps": 477, "serve_step_seconds": 4.77}
    res = {"window": window}
    run = _run(config={"model": {"d_model": 576, "n_layers": 30,
                                 "n_heads": 9, "n_kv_heads": 3, "d_ff": 1536,
                                 "vocab_size": 49152,
                                 "compute_dtype": "bfloat16"}})
    read = lambda n: manifest.load_metric(n).read(run, res)
    assert read("serve_tokens_per_s") == pytest.approx(1024.0)
    assert read("serve_latency_p95_s") == pytest.approx(2.5)
    assert read("serve_step_ms") == pytest.approx(10.0)
    n = flops.lm_param_counts(run["config"]["model"])["total"]
    assert 134e6 < n < 135e6          # SmolLM-135M: 134,515,008
    assert read("serve_mfu") == pytest.approx(100 * 1024 * 2 * n / 197e12)


def test_decode_step_least_time_is_the_larger_bound():
    m = {"d_model": 576, "n_layers": 30, "n_heads": 9, "n_kv_heads": 3,
         "d_ff": 1536, "vocab_size": 49152, "compute_dtype": "bfloat16"}
    peak = flops.peak_for("TPU v5 lite")
    one = flops.lm_decode_step(m, 64, 0)
    assert one["bytes"] > 2 * 134e6       # every bf16 weight read once
    t = flops.least_seconds(one["flops"], one["bytes"], peak)
    assert t == max(one["flops"] / 197e12, one["bytes"] / 819e9)


def test_a_device_kind_without_peaks_is_an_error():
    with pytest.raises(KeyError):
        flops.peak_for("TPU v9 imaginary")


def test_serve_step_roofline_reads_the_serve_step_runs():
    ms = 1e6
    runs = [("jit_serve_step(1)", i * 10 * ms, i * 10 * ms + 4 * ms)
            for i in range(4)]
    trace = {"host": [["bench.window", 0, 40 * ms]],
             "devices": [{"name": "/device:TPU:0", "modules": runs,
                          "ops": [("fusion", s, e) for _, s, e in runs]}]}
    m = {"d_model": 576, "n_layers": 30, "n_heads": 9, "n_kv_heads": 3,
         "d_ff": 1536, "vocab_size": 49152, "compute_dtype": "bfloat16"}
    traffic = {"clients": 64, "prompt_len": 2, "new_tokens": 3}
    run = _run(config={"model": m}, traffic=traffic)
    got = manifest.load_metric("serve_step_roofline").read(
        run, {"trace": trace})
    peak = flops.peak_for("TPU v5 lite")
    least = [flops.least_seconds(**{"flops": s["flops"], "nbytes": s["bytes"],
                                    "peak": peak})
             for s in (flops.lm_decode_step(m, 64, p) for p in range(4))]
    assert got == pytest.approx(100 * sum(least) / 4 / 4e-3)
