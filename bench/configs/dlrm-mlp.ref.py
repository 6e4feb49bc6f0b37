"""Plain reference of dlrm-mlp training, in float32 at the highest matmul
precision: the MLP tower with a one-logit head, binary cross-entropy over
the batch, and AdamW with global-norm clipping, decoupled weight decay and
the warm-up-then-cosine learning rate.  It imports nothing of the program:
its weights are drawn from the seed by the same recipe (N(0, 1/d_in)
weights, zero biases, the head's from the key folded with 7).

``matmul='fp8'`` is the control: every GEMM of the forward and backward
passes takes operands rounded to 8-bit floats (E4M3 for weights and
activations, E5M2 for gradients), each tensor scaled by its largest
magnitude, products accumulated in float32.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from bench import common

HI = jax.lax.Precision.HIGHEST


def init_params(key, widths) -> Dict[str, Any]:
    ks = jax.random.split(key, len(widths))
    layers = []
    for i, w in enumerate(widths):
        d_in = widths[i - 1] if i else widths[0]
        layers.append({"w": jax.random.normal(ks[i], (d_in, w), jnp.float32)
                       * (1.0 / math.sqrt(d_in)),
                       "b": jnp.zeros((w,), jnp.float32)})
    hw = jax.random.normal(jax.random.fold_in(key, 7), (widths[-1], 1),
                           jnp.float32) * (1.0 / math.sqrt(widths[-1]))
    return {"layers": layers,
            "head": {"w": hw, "b": jnp.zeros((1,), jnp.float32)}}


def _q(x, dtype):
    s = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _mm8(a, b):
    return jnp.dot(_q(a, jnp.float8_e4m3fn), _q(b, jnp.float8_e4m3fn),
                   precision=HI)


def _mm8_fwd(a, b):
    return _mm8(a, b), (a, b)


def _mm8_bwd(res, g):
    a, b = res
    g8 = _q(g, jnp.float8_e5m2)
    return (jnp.dot(g8, _q(b, jnp.float8_e4m3fn).T, precision=HI),
            jnp.dot(_q(a, jnp.float8_e4m3fn).T, g8, precision=HI))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


MATMULS = {"f32": lambda a, b: jnp.dot(a, b, precision=HI), "fp8": _mm8}


def loss_fn(params, x, y, matmul: str = "f32"):
    mm = MATMULS[matmul]
    h = x
    for lyr in params["layers"]:
        h = jax.nn.relu(mm(h, lyr["w"]) + lyr["b"])
    z = (mm(h, params["head"]["w"]) + params["head"]["b"])[:, 0]
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def learning_rate(step, o):
    step = jnp.float32(step)
    warm = o["peak_lr"] * step / max(o["warmup_steps"], 1)
    t = jnp.clip((step - o["warmup_steps"])
                 / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    cos = o["peak_lr"] * (o["lr_floor"] + (1 - o["lr_floor"]) * 0.5
                          * (1 + jnp.cos(jnp.pi * t)))
    return jnp.where(step < o["warmup_steps"], warm, cos)


def _step(params, mu, nu, x, y, step, o, matmul):
    loss, g = jax.value_and_grad(loss_fn)(params, x, y, matmul)
    gnorm = jnp.sqrt(sum(jnp.sum(l * l) for l in jax.tree.leaves(g)))
    g = jax.tree.map(lambda l: l * jnp.minimum(
        1.0, o["clip_norm"] / (gnorm + 1e-9)), g)
    mu = jax.tree.map(lambda m, l: o["b1"] * m + (1 - o["b1"]) * l, mu, g)
    nu = jax.tree.map(lambda v, l: o["b2"] * v + (1 - o["b2"]) * l * l, nu, g)
    bc1 = 1 - o["b1"] ** jnp.float32(step)
    bc2 = 1 - o["b2"] ** jnp.float32(step)
    lr = learning_rate(step, o)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
                                  + o["weight_decay"] * p), params, mu, nu)
    return params, mu, nu, loss, g


def train_readings(key, batches: List[Dict[str, Any]], config: Dict[str, Any],
                   matmul: str = "f32", rows: Optional[int] = None
                   ) -> Dict[str, Any]:
    """Losses of the first steps, step 1's clipped gradient (on the
    device, by leaf path), and each leaf's norm of the change over all the
    steps.  ``rows`` keeps only a batch's first rows (a planted fault)."""
    o = config["optimizer"]
    params = jax.jit(lambda k: init_params(k, config["model"]["mlp_widths"]))(
        key)
    p0 = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    step = jax.jit(lambda p, m, v, x, y, s: _step(p, m, v, x, y, s, o, matmul))
    losses, g1 = [], None
    for i, b in enumerate(batches):
        x = jnp.asarray(b["features"])[:rows]
        y = jnp.asarray(b["click"])[:rows]
        params, mu, nu, loss, gn = step(params, mu, nu, x, y, i + 1)
        losses.append(loss)
        g1 = gn if g1 is None else g1
    change = jax.jit(lambda a, b: common.leaf_norms(
        jax.tree.map(lambda u, v: u - v, a, b)))(params, p0)
    out = jax.device_get({"losses": losses, "change_norms": change})
    return {"losses": [float(v) for v in out["losses"]],
            "grads": common.by_path(g1),
            "change_norms": {k: float(v) for k, v in
                             out["change_norms"].items()}}
