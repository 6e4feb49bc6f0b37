"""Paper §III case study: DLRM MLP on CLX — Figs 4a, 4b, 4c, 6a, 6b.

Each ``fig*`` function returns (rows, derived-claims) where rows are the
figure's data points.  Claims are checked against the paper's stated
numbers; ``benchmarks.run`` prints them as CSV and asserts them, and
EXPERIMENTS.md §Paper-validation is generated from here.

Since the sweep-engine PR, every figure is a thin call into
``repro.core.sweep`` over a vectorized batch grid, with the all-reduce wire
bytes priced by ``repro.distributed.collectives`` (ring algorithm at the
paper's large-n asymptote: exactly 2·payload per chip) instead of a
hardcoded factor.

Two term sources:
  * analytic — the paper's own accounting (models/mlp_dlrm.analytic_work_unit)
  * compiled — FLOPs/bytes of the real jitted train step via cost_analysis
    (single CPU device; network volume stays analytic)
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

from repro.core import CLX, WorkUnit, analyze, ascii_plot, svg_plot
from repro.core import sweep as sweep_mod
from repro.distributed import collectives
from repro.models.mlp_dlrm import analytic_work_unit

WIDTH, LAYERS = 4096, 8
BATCHES = (4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)

#: the paper counts the ring all-reduce at its large-n asymptote 2·payload
PAPER_DP_GROUP = math.inf


def mlp_unit(batch: int, per_layer: bool = True) -> WorkUnit:
    layers = 1 if per_layer else LAYERS
    f, bm, bn = analytic_work_unit(batch, WIDTH, layers)
    return WorkUnit(f"mlp_b{batch}", f, bm, bn)


def batch_sweep(batches=BATCHES, per_layer: bool = True,
                net: bool = True) -> sweep_mod.SweepResult:
    """The whole batch grid in one vectorized Ridgeline pass."""
    b = np.asarray(batches, dtype=np.float64)
    layers = 1 if per_layer else LAYERS
    # single source of the paper's accounting: F is batch-linear, B_M is
    # batch-constant, and the gradient payload equals the weight bytes B_M
    flops_b1, mem_bytes, _ = analytic_work_unit(1, WIDTH, layers)
    flops = flops_b1 * b
    net_bytes = collectives.all_reduce_bytes(
        mem_bytes, PAPER_DP_GROUP, "ring") if net else 0.0
    return sweep_mod.sweep(flops, mem_bytes, net_bytes, CLX)


def fig4a_intensity() -> Tuple[List[Dict], Dict]:
    res = batch_sweep()
    rows = [{"batch": b, "arithmetic_intensity": float(res.y[i]),
             "clx_ridge": CLX.ridge_arithmetic}
            for i, b in enumerate(BATCHES)]
    crossing = min(b for i, b in enumerate(BATCHES)
                   if res.y[i] >= CLX.ridge_arithmetic)
    return rows, {"ridge_crossing_batch": crossing, "paper_claim": 32}


def fig4b_roofline() -> Tuple[List[Dict], Dict]:
    # the classic roofline is the Ridgeline's B_N -> 0 limit
    res = batch_sweep(net=False)
    labels = res.labels()
    rows = [{"batch": b, "intensity": float(res.y[i]),
             "attainable_gflops": float(res.attained_flops[i]) / 1e9,
             "bound": str(labels[i])}
            for i, b in enumerate(BATCHES)]
    first_compute = min(r["batch"] for r in rows if r["bound"] == "compute")
    return rows, {"first_compute_bound_batch": first_compute,
                  "paper_claim": 32}


def fig4c_allreduce_vs_compute() -> Tuple[List[Dict], Dict]:
    res = batch_sweep(per_layer=False)
    rows = [{"batch": b, "t_compute_ms": float(res.t_compute[i]) * 1e3,
             "t_allreduce_ms": float(res.t_network[i]) * 1e3}
            for i, b in enumerate(BATCHES)]
    # t_network is batch-constant and t_compute batch-linear, so the linear
    # interpolation in ridge_crossing is the *exact* analytic crossover:
    #   6 B* W^2 L / C = 8 W^2 L / N  ->  B* = (8/6)·C/N = 4/3·k* (= 466.7)
    b_star = sweep_mod.ridge_crossing(res, BATCHES, log_x=False)
    # paper (Fig 4c): "up to batch size 512 ... more time to do the
    # all-reduce"; it also places 512 "on the ridgeline" (xy=384 vs
    # k*=350, ~10% above) — so the claim is approximate by construction.
    # We accept the exact crossover within 10% of 512.
    return rows, {"crossover_batch": b_star,
                  "within_10pct_of_512": abs(b_star / 512 - 1) < 0.12,
                  "paper_claim": 512}


def fig6_ridgeline() -> Tuple[List[Dict], Dict]:
    batches = [b for b in BATCHES if b >= 256]
    res = batch_sweep(batches)                       # per-layer points (plane)
    res_full = batch_sweep(batches, per_layer=False)  # full-step runtimes
    labels = res.labels()
    rows = [{"batch": b, "x_mem_intensity": float(res.x[i]),
             "y_arith_intensity": float(res.y[i]),
             "region": str(labels[i]),
             "projected_runtime_ms": float(res_full.runtime[i]) * 1e3}
            for i, b in enumerate(batches)]
    trans = sweep_mod.transitions(res, batches)
    net_to_compute = [(batches[i - 1], batches[i]) for i, frm, to in trans
                      if frm == "network" and to == "compute"]
    derived = {
        "b256": rows[0]["region"], "b512": rows[1]["region"],
        "b1024": rows[2]["region"],
        "paper_claim": "256:network 512:~ridge 1024:compute",
        "xy_at_512": float(res.flops[1] / res.net_bytes[1]),
        "k_star": CLX.ridge_network,
        "network_to_compute_between": net_to_compute[0]
        if net_to_compute else None,
    }
    return rows, derived


def compiled_terms(batch: int) -> Dict[str, float]:
    """F/B_M from the real compiled train step (1 CPU device)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.optim.optimizer import SGD
    from repro.train.loop import (TrainStepConfig, build_train_step,
                                  init_train_state)
    cfg = get_config("dlrm-mlp").replace(compute_dtype=jnp.float32)
    opt = SGD(learning_rate=1e-2)
    step = build_train_step(cfg, opt, TrainStepConfig())
    state_abs = jax.eval_shape(
        lambda k: init_train_state(k, cfg, opt),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    batch_abs = {"features": jax.ShapeDtypeStruct((batch, WIDTH), jnp.float32),
                 "click": jax.ShapeDtypeStruct((batch,), jnp.float32)}
    compiled = jax.jit(step).lower(state_abs, batch_abs).compile()
    cost = compiled.cost_analysis()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state_abs.params))
    return {"flops": float(cost["flops"]),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "analytic_flops": 6.0 * batch * WIDTH * WIDTH * LAYERS,
            "net_bytes": float(collectives.all_reduce_bytes(
                4.0 * n_params, PAPER_DP_GROUP, "ring"))}


def write_plots(outdir: str) -> List[str]:
    import os
    os.makedirs(outdir, exist_ok=True)
    analyses = [analyze(mlp_unit(b), CLX) for b in BATCHES if b >= 64]
    paths = []
    p = os.path.join(outdir, "fig6_ridgeline.svg")
    with open(p, "w") as f:
        f.write(svg_plot(analyses, CLX))
    paths.append(p)
    p = os.path.join(outdir, "fig6_ridgeline.txt")
    with open(p, "w") as f:
        f.write(ascii_plot(analyses, CLX))
    paths.append(p)
    return paths
