"""Drive the device-fed training cell on a 4x1 data mesh, at a reduced
size on four virtual CPU devices, with the step sound or with each
device's gradient kept local (the all-reduce left out), and print whether
the run came out correct.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python tests/bench/four_device_run.py none|local_grad
"""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / p)
                for p in ("src", ".")]

import jax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import repro.distributed.sharding as sharding  # noqa: E402
import repro.launch.train as launch_train  # noqa: E402
from bench import run as bench_run  # noqa: E402


def main(fault: str) -> None:
    if fault == "local_grad":
        real_jit, real_build = launch_train.jit_train_step, \
            launch_train.build_train_step

        def jit_local(cfg, opt, step_cfg, mesh, batch):
            def build(cfg, opt, ts_cfg):
                good = real_build(cfg, opt, ts_cfg)

                def local(state, batch):
                    # the model's sharding hints name the data axis, which
                    # is manual inside shard_map: trace the step without
                    prev = sharding._state.binding
                    sharding._state.binding = None
                    try:
                        return good(state, batch)
                    finally:
                        sharding._state.binding = prev

                return jax.shard_map(local, mesh=mesh,
                                     in_specs=(P(), P("data")),
                                     out_specs=(P(), P()), check_vma=False)
            launch_train.build_train_step = build
            try:
                return real_jit(cfg, opt, step_cfg, mesh, batch)
            finally:
                launch_train.build_train_step = real_build

        launch_train.jit_train_step = jit_local
    # the device-fed training cell on a 4x1 data mesh, as the 4-chip cell
    # runs it
    run = bench_run.plan("dlrm-train-b8192-dev", 2147483659)
    run["n_chips"] = 4
    run["config"]["model"].update(n_layers=3, d_model=256,
                                  mlp_widths=[256] * 3)
    run["traffic"]["global_batch"] = 256
    run.update(devices=jax.devices()[:4], times={}, trace=False)
    res = bench_run.measure(run, 0.3, False)
    checks = bench_run.verdict(res["checks"], run["config"]["limits"])
    print({k: c["value"] for k, c in checks.items()})
    print("correct", all(c["ok"] for c in checks.values()))


if __name__ == "__main__":
    main(sys.argv[1])
