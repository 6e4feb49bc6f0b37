"""Plain reference of smollm-135m's forward pass, in float32 at the
highest matmul precision: token embeddings, then per layer RMSNorm,
grouped-query causal attention with rotary positions (the two halves of
each head rotated), a residual, RMSNorm, a SwiGLU feed-forward and a
residual; a final RMSNorm and the tied embedding as the output head.  It
imports nothing of the program: its weights are drawn from the seed by the
same recipe (N(0, 0.02^2) embeddings, N(0, 1/d_in) projections, unit norm
scales).

``matmul='fp8'`` is the control: every weight GEMM takes operands rounded
to E4M3 8-bit floats, each scaled by its tensor's largest magnitude.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _dims(m):
    dh = m.get("head_dim") or m["d_model"] // m["n_heads"]
    return dh, m["n_heads"] * dh, m["n_kv_heads"] * dh


def _dense(key, d_in, d_out):
    return jax.random.normal(key, (d_in, d_out), jnp.float32) * (
        1.0 / math.sqrt(d_in))


def _block(key, m):
    d, f = m["d_model"], m["d_ff"]
    _, q, kv = _dims(m)
    ka, kf = jax.random.split(key, 2)
    a = jax.random.split(ka, 4)
    g = jax.random.split(kf, 3)
    return {"attn_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": {"wq": _dense(a[0], d, q), "wk": _dense(a[1], d, kv),
                     "wv": _dense(a[2], d, kv), "wo": _dense(a[3], q, d)},
            "ffn_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "ffn": {"w_gate": _dense(g[0], d, f), "w_up": _dense(g[1], d, f),
                    "w_down": _dense(g[2], f, d)}}


def init_params(key, m) -> Dict[str, Any]:
    ks = jax.random.split(key, m["n_layers"] + 3)
    return {"embed": jax.random.normal(ks[0], (m["vocab_size"], m["d_model"]),
                                       jnp.float32) * 0.02,
            "blocks": jax.vmap(lambda k: _block(k, m))(
                jnp.stack(ks[1:1 + m["n_layers"]])),
            "final_norm": {"scale": jnp.ones((m["d_model"],), jnp.float32)}}


def _q8(x):
    s = jnp.max(jnp.abs(x)) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


MATMULS = {"f32": lambda a, b: jnp.matmul(a, b, precision=HI),
           "fp8": lambda a, b: jnp.matmul(_q8(a), _q8(b), precision=HI)}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (n, s, h, dh): rotate the pair (x[i], x[i + dh/2]) by pos * f_i."""
    dh = x.shape[-1]
    f = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * f
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits(params, tokens, m, matmul: str = "f32"):
    """tokens (n, s) -> logits (n, s, vocab) at every position."""
    mm = MATMULS[matmul]
    dh, _, _ = _dims(m)
    H, K = m["n_heads"], m["n_kv_heads"]
    n, s = tokens.shape
    eps, theta = m["norm_eps"], m["rope_theta"]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, b):
        h = _rms(x, b["attn_norm"]["scale"], eps)
        q = _rope(mm(h, b["attn"]["wq"]).reshape(n, s, H, dh), theta)
        k = _rope(mm(h, b["attn"]["wk"]).reshape(n, s, K, dh), theta)
        v = mm(h, b["attn"]["wv"]).reshape(n, s, K, dh)
        k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
        sc = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HI) / math.sqrt(dh)
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        a = jnp.einsum("nhqk,nkhd->nqhd", w, v, precision=HI)
        x = x + mm(a.reshape(n, s, H * dh), b["attn"]["wo"])
        h = _rms(x, b["ffn_norm"]["scale"], eps)
        f = b["ffn"]
        x = x + mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"]),
                   f["w_down"])
        return x, None

    x, _ = jax.lax.scan(layer, params["embed"][tokens], params["blocks"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return mm(x, params["embed"].T)
