"""Training cells: the launcher's compiled step, kept a few steps ahead.

Set-up builds one object, the executable of ``launch/train.jit_train_step``
with its state, and drives it through its first ``CHECK_STEPS`` steps on
the cell's own feed; the window then carries on from there with the same
executable, state and feed.  The reference repeats those first steps.

The window never blocks on a step except to keep at most ``IN_FLIGHT``
steps queued: before dispatching step i + IN_FLIGHT it waits for the loss
of step i.  It stops dispatching when the time is up and waits for the
last step; the rate counts completed steps over the time from the first
dispatch to that completion.  Nothing else runs on the host meanwhile: no
checkpoint, no metric copied to the host, no file written.  The host's
clock at each completion is kept, so that a run that reads low shows
whether one long gap or slower steps took the time.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Dict

import jax
import numpy as np

from bench import common, ctr_stream, flops, hlo_ops, manifest

IN_FLIGHT = 3
CHECK_STEPS = 3


def setup(run: Dict[str, Any]) -> Dict[str, Any]:
    times = run["times"]
    t = time.perf_counter()
    from repro.distributed.sharding import gqa_safe_rules, use_sharding
    from repro.launch.mesh import make_mesh
    from repro.launch.train import jit_train_step
    from repro.train.loop import TrainStepConfig, init_train_state
    times["import_program"] = time.perf_counter() - t

    config, traffic = run["config"], run["traffic"]
    cfg = common.model_config(config)
    opt = common.optimizer(config)
    B = traffic["global_batch"]
    d_in = cfg.mlp_widths[0]
    mesh = make_mesh((run["n_chips"], 1), ("data", "model"))
    keys = common.keys(run["seed"])
    abstract = {"features": jax.ShapeDtypeStruct((B, d_in), np.float32),
                "click": jax.ShapeDtypeStruct((B,), np.float32)}
    ctx: Dict[str, Any] = {"run": run, "mesh": mesh, "global_batch": B}
    with use_sharding(mesh, gqa_safe_rules(cfg.n_kv_heads, mesh)):
        t = time.perf_counter()
        step, state_sh = jit_train_step(cfg, opt, TrainStepConfig(), mesh,
                                        abstract)
        state = jax.jit(lambda k: init_train_state(k, cfg, opt),
                        out_shardings=state_sh)(keys["weights"])
        jax.block_until_ready(state)
        times["weights"] = time.perf_counter() - t

        t = time.perf_counter()
        compiled = step.lower(state, abstract).compile()
        times["compile"] = time.perf_counter() - t
        if run["trace"]:
            text = compiled.as_text()
            ctx["gemm_ops"] = hlo_ops.select(text, "gemm")
            ctx["collective_ops"] = hlo_ops.select(text, "collective")
        batch_sh = compiled.input_shardings[0][1]

        t = time.perf_counter()
        if traffic["feed"] == "device_pool":
            pool = jax.jit(lambda k: common.ctr_pool(k, traffic["pool"], B,
                                                     d_in),
                           out_shardings=[batch_sh] * traffic["pool"])(
                keys["inputs"])
            jax.block_until_ready(pool)
            feed = lambda i: pool[i % len(pool)]
        elif traffic["feed"] == "host_pipeline":
            from repro.data.pipeline import DataConfig, make_stream
            stream = make_stream(cfg, DataConfig(seed=run["seed"],
                                                 global_batch=B))
            feed = lambda i: jax.device_put(stream.batch(i), batch_sh)
        else:
            raise ValueError(f"unknown feed {traffic['feed']!r}")
        times["inputs"] = time.perf_counter() - t

        t = time.perf_counter()
        b1 = config["optimizer"]["b1"]
        copy = jax.jit(lambda s: jax.tree.map(lambda x: x + 0, s.params))
        first_grads = jax.jit(lambda s: jax.tree.map(
            lambda m: m / (1 - b1), s.opt_state.mu))
        change_norms = jax.jit(lambda s, p0: common.leaf_norms(jax.tree.map(
            lambda a, b: a - b, s.params, p0)))
        p0 = copy(state)
        losses = []
        for i in range(CHECK_STEPS):
            state, metrics = compiled(state, feed(i))
            losses.append(metrics["loss"])
            if i == 0:
                g1 = first_grads(state)
        c3 = change_norms(state, p0)
        got = jax.device_get({"losses": losses, "grads": g1,
                              "change_norms": c3})
        del p0, g1
        times["first_steps"] = time.perf_counter() - t
    ctx.update(state=state, compiled=compiled, feed=feed, next_step=CHECK_STEPS,
               got={"losses": [float(x) for x in got["losses"]],
                    "grads": common.by_path(got["grads"]),
                    "change_norms": {k: float(v) for k, v in
                                     got["change_norms"].items()}},
               flops_per_step=flops.mlp_train_flops(B, cfg.mlp_widths))
    return ctx


def window(ctx: Dict[str, Any], seconds: float, span) -> Dict[str, Any]:
    step, feed, state = ctx["compiled"], ctx["feed"], ctx["state"]
    i = ctx["next_step"]
    inflight: collections.deque = collections.deque()
    input_s, done_at = [], []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with span("bench.window"):
        while time.perf_counter() < deadline:
            if len(inflight) >= IN_FLIGHT:
                with span("bench.wait"):
                    inflight.popleft().block_until_ready()
                done_at.append(time.perf_counter())
                continue
            ti = time.perf_counter()
            with span("bench.input"):
                batch = feed(i)
            input_s.append(time.perf_counter() - ti)
            with span("bench.dispatch"):
                state, metrics = step(state, batch)
            inflight.append(metrics["loss"])
            i += 1
        with span("bench.drain"):
            while inflight:
                inflight.popleft().block_until_ready()
                done_at.append(time.perf_counter())
    t1 = time.perf_counter()
    ctx["state"], ctx["next_step"] = state, i
    B, completed = ctx["global_batch"], len(done_at)
    gaps = np.diff(done_at) if completed > 1 else np.zeros(1)
    return {"seconds": t1 - t0, "steps": completed, "samples": completed * B,
            "input_seconds": input_s, "flops_per_step": ctx["flops_per_step"],
            "t0": t0, "t1": t1, "gap_median_s": float(np.median(gaps)),
            "gap_max_s": float(gaps.max())}


def release(ctx: Dict[str, Any]) -> None:
    for k in ("state", "compiled", "feed"):
        ctx.pop(k, None)


def reference_batches(run: Dict[str, Any], n: int):
    """The cell's first ``n`` batches, made without the code under test."""
    traffic, config = run["traffic"], run["config"]
    B = traffic["global_batch"]
    d_in = config["model"]["mlp_widths"][0]
    if traffic["feed"] == "device_pool":
        return jax.jit(lambda k: common.ctr_pool(k, n, B, d_in))(
            common.keys(run["seed"])["inputs"])
    return [ctr_stream.batch(run["seed"], i, B, d_in) for i in range(n)]


def reference_readings(run: Dict[str, Any], matmul: str = "f32",
                       rows: int | None = None) -> Dict[str, Any]:
    ref = manifest.load_reference(run["config"]["name"])
    return ref.train_readings(common.keys(run["seed"])["weights"],
                              reference_batches(run, CHECK_STEPS),
                              run["config"], matmul=matmul, rows=rows)


def check(ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    run = ctx["run"]
    want = reference_readings(run)
    return common.compare_train(ctx["got"], want)
