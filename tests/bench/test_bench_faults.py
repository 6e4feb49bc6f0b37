"""A run with its timed path broken underneath comes out not correct.

Each test plants one fault the cell can have in the program as the
harness drives it, at a reduced size on the CPU, and runs the rest of a
run (set-up, window, release, check) past the harness's look for a chip.
The sound run beside them comes out correct under the same limits.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench import run as bench_run

ROOT = Path(__file__).resolve().parents[2]


def small_run(workload, seed=2147483649):
    run = bench_run.plan(workload, seed)
    c, t = run["config"], run["traffic"]
    if c["name"] == "dlrm-mlp":
        c["model"].update(n_layers=3, d_model=256, mlp_widths=[256] * 3)
        t["global_batch"] = 256
    else:
        c["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                          d_ff=128, vocab_size=512)
        t.update(clients=8, prompt_len=16, new_tokens=8, check_requests=8,
                 check_block=4)
    run.update(devices=jax.devices()[:run["n_chips"]], times={}, trace=False)
    return run


def correct(run) -> bool:
    res = bench_run.measure(run, 0.3, False)
    checks = bench_run.verdict(res["checks"], run["config"]["limits"])
    return all(c["ok"] for c in checks.values())


def planted_step(monkeypatch, fault):
    import repro.launch.train as launch_train
    real = launch_train.build_train_step

    def build(cfg, opt, ts_cfg):
        good = real(cfg, opt, ts_cfg)
        if fault == "unchanged":
            return lambda state, batch: (state, good(state, batch)[1])
        half = lambda b: jax.tree.map(lambda x: x[: x.shape[0] // 2], b)
        return lambda state, batch: good(state, half(batch))

    monkeypatch.setattr(launch_train, "build_train_step", build)


@pytest.mark.parametrize("workload", ["dlrm-train-b8192-dev",
                                      "dlrm-train-b8192-host",
                                      "smollm-serve-b64-p128-g32"])
def test_sound_run_is_correct(workload):
    assert correct(small_run(workload))


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("workload", ["dlrm-train-b8192-dev",
                                      "dlrm-train-b8192-host"])
def test_broken_step_is_not_correct(monkeypatch, fault, workload):
    planted_step(monkeypatch, fault)
    assert not correct(small_run(workload))


def test_altered_token_is_not_correct(monkeypatch):
    import repro.serve.engine as engine
    real = engine.greedy_generate

    def altered(params, cfg, prompt, steps, max_len, **kw):
        tokens, logits = real(params, cfg, prompt, steps, max_len, **kw)
        at = prompt.shape[1] + steps // 2
        return tokens.at[:, at].set((tokens[:, at] + 1) % cfg.vocab_size), logits

    monkeypatch.setattr(engine, "greedy_generate", altered)
    assert not correct(small_run("smollm-serve-b64-p128-g32"))


@pytest.mark.parametrize("fault", ["none", "local_grad"])
def test_exchange_left_out_is_not_correct(fault):
    """On four virtual devices, in a process of its own: each device
    steps on its own quarter of the batch, with no all-reduce."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(Path(__file__).parent /
                                            "four_device_run.py"), fault],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().splitlines()[-1] == (
        "correct True" if fault == "none" else "correct False")
