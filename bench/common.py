"""Pieces the drivers, the references and the controls share.

Nothing here imports the program under test; ``model_config`` and
``optimizer`` import it only when called.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` alone drops the
    bits above 32)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned whole number")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def model_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for a benchmark configuration: its
    registry entry with the file's ``model`` sizes applied over it."""
    from repro.configs import get_config
    sizes = {k: (tuple(v) if isinstance(v, list) else
                 DTYPES.get(v, v) if k.endswith("_dtype") else v)
             for k, v in config["model"].items()}
    return get_config(config["arch"]).replace(**sizes)


def optimizer(config: Dict[str, Any]):
    """The program's AdamW with ``warmup_cosine``, as the launcher builds it,
    from the file's ``optimizer`` settings."""
    from repro.optim.optimizer import AdamW, warmup_cosine
    o = config["optimizer"]
    return AdamW(learning_rate=warmup_cosine(o["peak_lr"], o["warmup_steps"],
                                             o["total_steps"], o["lr_floor"]),
                 b1=o["b1"], b2=o["b2"], eps=o["eps"],
                 weight_decay=o["weight_decay"], clip_norm=o["clip_norm"])


def spans(enabled: bool):
    """``span(name)``: a profiler annotation on the trace's host timeline
    when tracing, else nothing."""
    if enabled:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def ctr_pool(key: jax.Array, n: int, batch: int, d_in: int):
    """``n`` click batches: features x ~ N(0, 1) and clicks
    y ~ Bernoulli(sigmoid(4 x.w)) for a hidden w ~ N(0, 1/d), as the data
    pipeline's ``SyntheticCTRStream`` draws them."""
    w = jax.random.normal(jax.random.fold_in(key, 0), (d_in,)) / jnp.sqrt(
        jnp.float32(d_in))
    out = []
    for i in range(n):
        kx, ky = jax.random.split(jax.random.fold_in(key, i + 1))
        x = jax.random.normal(kx, (batch, d_in), jnp.float32)
        logit = 4.0 * jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
        y = (jax.random.uniform(ky, (batch,)) < jax.nn.sigmoid(logit))
        out.append({"features": x, "click": y.astype(jnp.float32)})
    return out


def by_path(tree) -> Dict[str, Any]:
    """The leaves of ``tree`` by their paths."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): x for p, x in flat}


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """The 2-norm of every leaf, by its path."""
    return {k: jnp.linalg.norm(jnp.ravel(x).astype(jnp.float32))
            for k, x in by_path(tree).items()}


def _norm(x) -> float:
    return float(jnp.linalg.norm(jnp.ravel(jnp.asarray(x, jnp.float32))))


def keys(seed: int) -> Dict[str, jax.Array]:
    """The run's keys: one for the weights, one for the inputs."""
    k = seed_key(seed)
    return {"weights": jax.random.fold_in(k, 1),
            "inputs": jax.random.fold_in(k, 2)}


def worst_gap(got: Dict[str, float], want: Dict[str, float],
              keep=None) -> Dict[str, Any]:
    """The worst leaf's |got - want| over the larger of ``want`` and the
    median of ``want``, over the leaves in ``keep`` (all if None)."""
    import statistics
    names = [n for n in want if keep is None or n in keep]
    med = statistics.median(want[n] for n in names)
    gaps = {n: abs(got[n] - want[n]) / max(want[n], med) for n in names}
    worst = max(gaps, key=gaps.get)
    return {"value": gaps[worst], "leaf": worst,
            "norms": {n: [got[n], want[n]] for n in want}}


def compare_train(got: Dict[str, Any], want: Dict[str, Any]
                  ) -> Dict[str, Dict[str, Any]]:
    """The numbers read for a training cell's ``correct``.

    ``got`` and ``want`` each hold ``losses`` (steps 1-3), ``grads`` (step
    1's gradient as the optimizer takes it, by leaf) and ``change_norms``
    (each leaf's norm of the change over steps 1-3).  Leaves whose
    reference gradient is under a thousandth of the median leaf's move
    under AdamW by round-off alone, and are left out of the change.
    ``grad_diff_gap`` is the worst leaf's norm of the difference of the two
    gradients, over the larger of the reference's norm of that leaf and of
    the median leaf.
    """
    import statistics
    steps = [abs(g - w) / abs(w) for g, w in zip(got["losses"],
                                                 want["losses"])]
    gw = {n: _norm(x) for n, x in want["grads"].items()}
    gg = {n: _norm(got["grads"][n]) for n in gw}
    med = statistics.median(gw.values())
    diff = {n: _norm(jnp.asarray(got["grads"][n]) - want["grads"][n])
            for n in gw}
    worst = max(gw, key=lambda n: diff[n] / max(gw[n], med))
    moved = {n for n, v in gw.items() if v >= 1e-3 * med}
    return {"loss_gap": {"value": max(steps), "steps": steps},
            "grad_norm_gap": worst_gap(gg, gw),
            "grad_diff_gap": {"value": diff[worst] / max(gw[worst], med),
                              "leaf": worst},
            "change_norm_gap": worst_gap(got["change_norms"],
                                         want["change_norms"], moved)}
