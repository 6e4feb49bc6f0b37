"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 200 --batch 8 --seq 64 --reduced --ckpt-dir /tmp/run1

On the CPU container, use ``--reduced`` (CPU-sized config of the same
family); on a real pod, omit it and pass ``--mesh data,model`` sizes.  The
launcher wires together the full substrate: mesh + logical sharding rules,
state and batch placed on the mesh (replicated state, batch split over
"data"), deterministic per-host data pipeline, AdamW with warmup-cosine,
the fault-tolerant runner (auto-resume from the latest committed checkpoint
in ``--ckpt-dir``, periodic async saves, straggler flags), and a closing
Ridgeline report of the compiled step.
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint.checkpointer import Checkpointer
from repro.compile_cache import use_compile_cache
from repro.configs import get_config, get_reduced
from repro.core import TPU_V5E, HardwareSpec, WorkUnit, analyze
from repro.core.hardware import hardware_for_device_kind
from repro.core.hlo_analysis import analyze_compiled
from repro.data.pipeline import DataConfig, make_stream
from repro.distributed.sharding import (gqa_safe_rules, specs_to_shardings,
                                        use_sharding)
from repro.launch.mesh import make_mesh
from repro.launch.specs import abstract_train_state, train_state_specs
from repro.models.common import ModelConfig
from repro.optim.optimizer import AdamW, warmup_cosine
from repro.train.fault_tolerance import ResilientRunner, RunnerConfig
from repro.train.loop import TrainStepConfig, build_train_step, init_train_state


class TrainRun(NamedTuple):
    state: Any
    history: List[Dict[str, float]]   # one entry per step taken
    compile_seconds: float            # lowering + compiling the step
    report: str                       # Ridgeline report of the compiled step


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config of the same family")
    ap.add_argument("--mesh", default="1x1",
                    help="data x model split, e.g. 16x16 on a pod")
    ap.add_argument("--ckpt-dir", required=True,
                    help="checkpoint directory; a run resumes from the "
                         "latest step committed there")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def report_peaks() -> Tuple[HardwareSpec, str]:
    """Peaks to report the step against, and what they stand for."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return (hardware_for_device_kind(dev.device_kind),
                f"peaks of this device ({dev.device_kind})")
    return TPU_V5E, (f"projection onto {TPU_V5E.name}, not a reading of "
                     f"this {dev.platform} device")


def jit_train_step(cfg: ModelConfig, opt, step_cfg: TrainStepConfig,
                   mesh: Mesh, batch: Any):
    """The launcher's jitted step and its state placement on ``mesh``.

    The state is placed per ``train_state_specs`` without ZeRO (dlrm-mlp:
    replicated) and ``batch`` (arrays or ShapeDtypeStructs) is split over
    "data"; a mesh axis that doesn't divide a dimension is dropped from its
    sharding.  Call inside ``use_sharding(mesh, ...)``.
    """
    state_sh = specs_to_shardings(
        train_state_specs(cfg, zero1=False, optimizer=opt),
        abstract_train_state(cfg, opt), mesh)
    batch_sh = specs_to_shardings(jax.tree.map(lambda _: ("batch",), batch),
                                  batch, mesh)
    step = jax.jit(build_train_step(cfg, opt, step_cfg),
                   in_shardings=(state_sh, batch_sh),
                   out_shardings=(state_sh, None), donate_argnums=(0,))
    return step, state_sh


def run(args: argparse.Namespace) -> TrainRun:
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.reduced:
        cfg = cfg.replace(compute_dtype=jnp.float32)
    dims = tuple(int(d) for d in args.mesh.split("x"))
    mesh = make_mesh(dims, ("data", "model"))

    opt = AdamW(learning_rate=warmup_cosine(args.lr, 20, args.steps))
    step_cfg = TrainStepConfig(n_micro=args.n_micro)

    with use_sharding(mesh, gqa_safe_rules(cfg.n_kv_heads, mesh)):
        stream = make_stream(cfg, DataConfig(
            seed=args.seed, global_batch=args.batch, seq_len=args.seq))
        batch = stream.batch(0)
        train_step, state_sh = jit_train_step(cfg, opt, step_cfg, mesh, batch)
        state = jax.jit(lambda k: init_train_state(k, cfg, opt),
                        out_shardings=state_sh)(
            jax.random.PRNGKey(args.seed))
        t0 = time.perf_counter()
        # the runner times this executable, the one the report describes
        compiled = train_step.lower(state, batch).compile()
        compile_seconds = time.perf_counter() - t0
        runner = ResilientRunner(
            compiled, Checkpointer(args.ckpt_dir, keep=3),
            RunnerConfig(ckpt_every=args.ckpt_every),
            on_straggler=lambda ev: print(
                f"[straggler] step {ev.step}: {ev.step_time:.2f}s "
                f"vs EWMA {ev.ewma:.2f}s", file=sys.stderr))
        state, history = runner.run(state, stream, n_steps=args.steps)

    costs = analyze_compiled(compiled, mesh.size)
    hw, basis = report_peaks()
    report = analyze(WorkUnit(f"{args.arch}/train", costs.flops,
                              costs.mem_bytes, costs.wire_bytes),
                     hw).summary()
    return TrainRun(state, history, compile_seconds, f"{report} [{basis}]")


def main(argv=None) -> int:
    use_compile_cache()
    result = run(parse_args(argv))
    history = result.history
    if history:
        first = np.mean([h["ce"] for h in history[:10]])
        last = np.mean([h["ce"] for h in history[-10:]])
        print(f"steps {history[0]['step']}..{history[-1]['step']}  "
              f"CE {first:.4f} -> {last:.4f}")
    print(result.report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
