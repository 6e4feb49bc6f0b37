"""Readings that the limits of ``correct`` are set from; not part of a run.

    python3 bench/control.py --workload <name> --seeds 11,12,... [--what program,fp8,...]

For each seed it prints one JSON line of the numbers a run compares:

  program     the program as the cell runs it, against the reference
              (the lower reading of each limit);
  fp8         the control: the reference itself with its GEMMs in 8-bit
              floats, the precision below the configuration's bfloat16,
              against the reference in float32 (an upper reading);
  half_batch  training only: the reference's step on the first half of
              each batch, the mean over the rest (a planted fault);
  local_grad  training on several chips only: the reference's step on the
              first chip's rows alone, as one chip whose gradient missed
              the all-reduce would take it (a planted fault);
  quarter_batch  the same as local_grad for a 4-chip data mesh, read on
              one chip.

A state left unchanged needs no run: its parameters' change reads 1.
Serving reads the control at the positions of the program's own served
tokens, from one round at the cell's load.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def train_numbers(run: dict, what: str) -> dict:
    from bench import common, manifest
    driver = manifest.load_driver("train")
    if what == "program":
        ctx = driver.setup(run)
        driver.release(ctx)
        return driver.check(ctx)
    want = driver.reference_readings(run)
    B = run["traffic"]["global_batch"]
    variant = {"fp8": ("fp8", None), "half_batch": ("f32", B // 2),
               "local_grad": ("f32", B // run["n_chips"]),
               "quarter_batch": ("f32", B // 4)}[what]
    return common.compare_train(
        driver.reference_readings(run, *variant), want)


def serve_numbers(run: dict, whats) -> dict:
    import numpy as np
    from bench import manifest
    driver = manifest.load_driver("serve")
    ctx = driver.setup(run)
    first = ctx["round"]
    driver.serve_round(ctx)
    ctx["window_rounds"] = (first, ctx["round"])
    driver.release(ctx)
    seqs = driver.sample(ctx)
    P = run["traffic"]["prompt_len"]
    ref = driver.reference_logits(run, seqs)
    out = {}
    if "program" in whats:
        out["program"] = {"logit_gap": {"value": driver.widest_gap(ref, seqs, P)}}
    if "fp8" in whats:
        low = driver.reference_logits(run, seqs, "fp8")
        G = seqs.shape[1] - P
        choice = np.argmax(low[:, P - 1:P - 1 + G], axis=-1)
        out["fp8"] = {"logit_gap": {"value": driver.widest_gap(
            ref, seqs, P, choice)}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,fp8")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run as bench_run
    bench_run._paths()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    whats = args.what.split(",")
    for seed in (int(s) for s in args.seeds.split(",")):
        run = bench_run.plan(args.workload, seed)
        run.update(devices=bench_run.require_chips(run["n_chips"]),
                   times={}, trace=False)
        if run["traffic"]["kind"] == "serve":
            res = serve_numbers(run, whats)
        else:
            res = {w: train_numbers(run, w) for w in whats}
        print(json.dumps({"seed": seed, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
