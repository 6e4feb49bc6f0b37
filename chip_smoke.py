#!/usr/bin/env python3
"""Bring-up check that the main paths run on a TPU, at full width.

    python3 chip_smoke.py               # one chip: train, serve, kernels
    python3 chip_smoke.py --four-chips  # dlrm-mlp data-parallel on 4 chips,
                                        # compared with one of those chips

Phases, each printed before the last line:

  train    dlrm-mlp (8 x 4096, AdamW, bf16 compute, global batch 8192)
           through ``repro.launch.train``: every step taken, losses finite
           and falling.
  serve    smollm-135m at its published widths through
           ``repro.launch.serve`` (batch 8, 64-token prompt, 32 new
           tokens): the decode logits at every position agree with
           ``models.transformer.forward`` over the same tokens.
  kernels  the Pallas ``blocked_matmul`` (4096^3, bias + ReLU) and
           ``flash_attention_bhsd`` (smollm-135m heads, S = 2048), compiled
           for the chip, against ``kernels/ref.py``.

The last line of standard output is one JSON object naming the device.
Any failed check raises, so the script exits non-zero and prints no such
line; it does the same on any platform other than a TPU.  Times printed
here are bring-up readings, not benchmarks.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import statistics
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TRAIN_ARCH = "dlrm-mlp"
TRAIN_SEED = 0
TRAIN_BATCH = 8192
TRAIN_STEPS = 30
FOUR_CHIP_STEPS = 10
SERVE_ARGS = ["--arch", "smollm-135m", "--batch", "8", "--prompt-len", "64",
              "--new-tokens", "32"]
MATMUL_DIM = 4096
FLASH_SHAPE = dict(B=1, H=9, K=3, S=2048, dh=64)   # smollm-135m's heads

#: max |got - want| / max |want|.  bf16 keeps 8 bits of mantissa (a relative
#: step of 2^-8 = 3.9e-3); the bounds allow a few such roundings to stack.
SERVE_TOL = 5e-2      # 30 layers of bf16 activations, two evaluation orders
MATMUL_TOL = 2e-2     # bf16 output of an f32-accumulated product
FLASH_TOL = 3e-2      # bf16 probabilities in the p @ v product
#: |loss(4 chips) - loss(1 chip)| / loss(1 chip), per step: the gradient's
#: bf16 partial sums are reduced across chips in another order
FOUR_CHIP_LOSS_RTOL = 1e-2
#: the same for the gradient norm of step 0, taken from the same parameters
#: and batch on both, so only the summation order differs (rel 4e-5 for a
#: bf16 dlrm-mlp of 4 x 1024 on four CPU devices, where a gradient of one
#: device's quarter of the batch alone moved it by 3e-2)
FOUR_CHIP_GRAD_RTOL = 2e-3
#: |params(4 chips) - params(1 chip)| / |params(1 chip) - params(init)|
#: after the run.  AdamW's first update is lr * sign(gradient), so only the
#: elements whose gradient sign the summation order flips differ; a state
#: left unchanged scores exactly 1, a gradient of another batch about 1
FOUR_CHIP_PARAM_RTOL = 0.5


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(line: str) -> None:
    print(line, flush=True)


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                     1e-6))


def bytes_in_use(devices) -> list:
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def on_host(params) -> list:
    import jax
    import numpy as np
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(params)]


def distance(xs, ys) -> float:
    """Euclidean distance between two lists of arrays."""
    import numpy as np
    return math.sqrt(sum(float(np.sum(np.square(x - y)))
                         for x, y in zip(xs, ys)))


def require_tpu():
    import jax
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"needs a TPU; JAX found platform {dev.platform!r}")
    return dev


def train(steps: int, mesh: str = "1x1"):
    """One training run through the launcher, in a fresh checkpoint dir."""
    from repro.launch import train as train_launcher
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        result = train_launcher.run(train_launcher.parse_args(
            ["--arch", TRAIN_ARCH, "--seed", str(TRAIN_SEED),
             "--batch", str(TRAIN_BATCH), "--steps", str(steps),
             "--mesh", mesh, "--ckpt-dir", ckpt]))
    taken = [h["step"] for h in result.history]
    check(taken == list(range(steps)),
          f"train on {mesh}: took steps {taken}, asked for 0..{steps - 1}")
    losses = [h["loss"] for h in result.history]
    check(all(math.isfinite(x) for x in losses),
          f"train on {mesh}: non-finite loss in {losses}")
    return result, losses


def phase_train() -> None:
    result, losses = train(TRAIN_STEPS)
    steady = statistics.median(h["seconds"] for h in result.history[1:])
    say(f"train dlrm-mlp batch={TRAIN_BATCH} steps={TRAIN_STEPS} "
        f"loss {losses[0]} -> {losses[-1]} | "
        f"compile_s={result.compile_seconds} "
        f"steady_step_s={steady} (bring-up reading, not a benchmark)")
    say(f"train losses {losses}")
    say(f"train report {result.report}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall ({losses[0]} -> {losses[-1]})")


def phase_serve() -> None:
    import jax
    import numpy as np
    from repro.launch import serve as serve_launcher
    from repro.models import transformer

    args = serve_launcher.parse_args(SERVE_ARGS)
    out = serve_launcher.run(args)
    cfg = out.cfg
    total = args.prompt_len + args.new_tokens
    check(out.tokens.shape == (args.batch, total),
          f"serve: tokens {out.tokens.shape}")
    check(out.logits.shape == (args.batch, total - 1, cfg.vocab_size),
          f"serve: logits {out.logits.shape}")
    check(bool(np.array_equal(out.tokens[:, :args.prompt_len], out.prompt)),
          "serve: the prompt is not the head of the output")
    want, _ = jax.jit(functools.partial(transformer.forward, cfg=cfg))(
        out.params, out.tokens[:, :-1])
    check(bool(np.isfinite(np.asarray(out.logits, np.float32)).all()),
          "serve: non-finite decode logits")
    err = rel_err(out.logits, want)
    agree = float(np.mean(np.argmax(np.asarray(out.logits, np.float32), -1)
                          == np.argmax(np.asarray(want, np.float32), -1)))
    say(f"serve smollm-135m batch={args.batch} prompt={args.prompt_len} "
        f"new={args.new_tokens} | decode vs forward logits: "
        f"rel_err={err} tol={SERVE_TOL} argmax_agree={agree} | "
        f"generate_s={out.seconds} (compile included)")
    check(err <= SERVE_TOL, f"serve: decode logits off by {err}")


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.blocked_matmul import blocked_matmul
    from repro.kernels.flash_attention import flash_attention_bhsd

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    n = MATMUL_DIM
    a = jax.random.normal(keys[0], (n, n), jnp.bfloat16)
    b = jax.random.normal(keys[1], (n, n), jnp.bfloat16)
    bias = jax.random.normal(keys[2], (n,), jnp.bfloat16)
    mm = jax.jit(functools.partial(blocked_matmul, act="relu",
                                   interpret=False)).lower(a, b, bias).compile()
    check("tpu_custom_call" in mm.as_text(),
          "kernels: blocked_matmul compiled without a tpu_custom_call")
    got = mm(a, b, bias)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(ref.ref_matmul, act="relu"))(
            a, b, bias)
    mm_err = rel_err(got, want)

    s = FLASH_SHAPE
    q = jax.random.normal(keys[3], (s["B"], s["H"], s["S"], s["dh"]),
                          jnp.bfloat16)
    k = jax.random.normal(keys[4], (s["B"], s["K"], s["S"], s["dh"]),
                          jnp.bfloat16)
    v = jax.random.normal(keys[5], (s["B"], s["K"], s["S"], s["dh"]),
                          jnp.bfloat16)
    fa = jax.jit(functools.partial(flash_attention_bhsd, causal=True,
                                   interpret=False)).lower(q, k, v).compile()
    check("tpu_custom_call" in fa.as_text(),
          "kernels: flash_attention_bhsd compiled without a tpu_custom_call")
    got = fa(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(ref.ref_flash_attention,
                                         causal=True))(
            *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)))
    fa_err = rel_err(jnp.swapaxes(got, 1, 2), want)
    say(f"kernels blocked_matmul {n}^3 bf16 bias+relu rel_err={mm_err} "
        f"tol={MATMUL_TOL} | flash_attention_bhsd H={s['H']} K={s['K']} "
        f"dh={s['dh']} S={s['S']} bf16 rel_err={fa_err} tol={FLASH_TOL} | "
        f"tpu_custom_call in both")
    check(mm_err <= MATMUL_TOL, f"kernels: blocked_matmul off by {mm_err}")
    check(fa_err <= FLASH_TOL, f"kernels: flash_attention off by {fa_err}")


def phase_four_chips() -> None:
    import jax
    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, found "
                             f"{len(devices)}")
    dp, dp_losses = train(FOUR_CHIP_STEPS, mesh="4x1")
    placed = {d for leaf in jax.tree.leaves(dp.state)
              for d in leaf.sharding.device_set}
    in_use = bytes_in_use(devices)
    say(f"four-chips dlrm-mlp 4x1 batch={TRAIN_BATCH} "
        f"({TRAIN_BATCH // len(devices)} per chip) "
        f"steps={FOUR_CHIP_STEPS} compile_s={dp.compile_seconds} "
        f"bytes_in_use_per_device={in_use}")
    check(placed == set(devices),
          f"four chips: the state sits on {sorted(d.id for d in placed)}")
    check(min(in_use) > 0.5 * max(in_use),
          f"four chips: bytes in use per device {in_use}")
    dp_params, dp_grad0 = on_host(dp.state.params), dp.history[0]["grad_norm"]
    del dp
    one, one_losses = train(FOUR_CHIP_STEPS, mesh="1x1")
    one_params = on_host(one.state.params)
    one_grad0 = one.history[0]["grad_norm"]
    del one
    from repro.configs import get_config
    from repro.train.loop import init_params
    init = on_host(init_params(jax.random.PRNGKey(TRAIN_SEED),
                               get_config(TRAIN_ARCH)))
    worst = max(abs(x - y) / abs(y) for x, y in zip(dp_losses, one_losses))
    grad_diff = abs(dp_grad0 - one_grad0) / one_grad0
    param_diff = (distance(dp_params, one_params)
                  / distance(one_params, init))
    say(f"four-chips losses 4x1 {dp_losses}")
    say(f"four-chips losses 1x1 {one_losses}")
    say(f"four-chips per-step loss agreement: max rel diff {worst} "
        f"tol={FOUR_CHIP_LOSS_RTOL}")
    say(f"four-chips step-0 grad norm 4x1 {dp_grad0} 1x1 {one_grad0}: "
        f"rel diff {grad_diff} tol={FOUR_CHIP_GRAD_RTOL}")
    say(f"four-chips params after {FOUR_CHIP_STEPS} steps: "
        f"|4x1 - 1x1| / |1x1 - init| = {param_diff} "
        f"tol={FOUR_CHIP_PARAM_RTOL}")
    check(worst <= FOUR_CHIP_LOSS_RTOL,
          f"four chips: losses differ from one chip by {worst}")
    check(grad_diff <= FOUR_CHIP_GRAD_RTOL,
          f"four chips: step-0 gradient norm differs by {grad_diff}")
    check(param_diff <= FOUR_CHIP_PARAM_RTOL,
          f"four chips: parameters differ from one chip by {param_diff} "
          f"of their change")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel phase")
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    dev = require_tpu()
    if args.four_chips:
        phase_four_chips()
    else:
        phase_train()
        phase_serve()
        phase_kernels()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
