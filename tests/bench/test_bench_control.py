"""The control, the reference in 8-bit floats put in the program's place,
comes out not correct under the cells' limits.  On the CPU at sizes a test
run holds (the chip readings at the cells' own sizes are in PERF.md): the
control's error grows with depth, so the training case keeps all eight of
dlrm-mlp's layers at a quarter of their width."""
import jax
import pytest

from bench import control, run as bench_run


def fails(numbers, limits):
    return any(limits[k] is not None and v["value"] > limits[k]
               for k, v in numbers.items())


def cpu_run(workload, seed, model, traffic):
    run = bench_run.plan(workload, seed)
    run["config"]["model"].update(model)
    run["traffic"].update(traffic)
    run.update(devices=jax.devices()[:1], times={}, trace=False)
    return run


@pytest.mark.parametrize("seed", [2147483651, 4294967311])
def test_training_control_is_not_correct(seed):
    run = cpu_run("dlrm-train-b8192-dev", seed,
                  dict(n_layers=8, d_model=1024, mlp_widths=[1024] * 8),
                  dict(global_batch=1024))
    got = control.train_numbers(run, "fp8")
    assert fails(got, run["config"]["limits"]), got


@pytest.mark.parametrize("seed", [2147483651, 4294967311])
def test_serving_control_is_not_correct_where_the_program_is(seed):
    run = cpu_run("smollm-serve-b64-p128-g32", seed,
                  dict(n_layers=4, d_model=192, n_heads=3, n_kv_heads=1,
                       d_ff=512, vocab_size=8192),
                  dict(clients=16, prompt_len=32, new_tokens=16,
                       check_requests=16, check_block=8))
    got = control.serve_numbers(run, ["program", "fp8"])
    limits = run["config"]["limits"]
    assert not fails(got["program"], limits), got
    assert fails(got["fp8"], limits), got
