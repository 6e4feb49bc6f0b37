"""Plain reference of Moonlight-16B-A3B's forward pass (DeepSeek-V3
architecture) on one chip's share, in float32 at the highest matmul
precision, with no cache, no batching of experts and nothing of the
program imported.

Per layer: RMSNorm; multi-head latent attention, expanded: queries
x W_q split per head into a part without rope (nope) and a rope'd part;
x W_kv_a gives the latent c_kv (RMSNorm'd) and one rope'd key k_pe shared
by the heads; c_kv W_kv_b gives each head's k_nope and v; scores
q_nope.k_nope + q_pe.k_pe over sqrt(nope + rope), causal softmax, values,
W_o; a residual; RMSNorm; then the layer's FFN and a residual.  The first
``first_dense_layers`` layers have a dense SwiGLU FFN.  The others route
every token over all ``n_experts`` router outputs: sigmoid scores, the
top-k of scores plus a per-expert correction bias, gates the chosen
scores renormalised and scaled by ``routed_scale``; each held expert
(``first_expert`` .. + ``experts_held``) is a SwiGLU evaluated on every
token and weighed by the token's gate for it (0 where not chosen), with
no capacity, sorting or grouping; the shared experts are one SwiGLU of
``n_shared_experts`` x ``moe_d_ff`` added for every token.  A final
RMSNorm and an untied output head over the vocabulary slice.

Departures from the published model, all the configuration's: the
experts that other chips hold add nothing (their part is left out, as on
the chip); the vocabulary is a slice; the weights and the correction bias
are drawn from the seed, by the program's recipe: N(0, 0.02^2)
embeddings, N(0, 1/d_in) projections and experts (expert e's from its own
key folded from e), bias N(0, 0.05^2), unit norm scales.  Rope rotates the
pairs (x[i], x[i + r/2]) of the r rope'd values; the published code pairs
them after a fixed permutation of those columns, the same model up to a
permutation of random weights.

``matmul='fp8'`` is the control: every weight GEMM takes operands rounded
to E4M3 8-bit floats, each scaled by its tensor's largest magnitude.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
BIAS_SCALE = 0.05


def _dense(key, d_in, d_out):
    return jax.random.normal(key, (d_in, d_out), jnp.float32) * (
        1.0 / math.sqrt(d_in))


def _swiglu_params(key, d, f):
    g = jax.random.split(key, 3)
    return {"w_gate": _dense(g[0], d, f), "w_up": _dense(g[1], d, f),
            "w_down": _dense(g[2], f, d)}


def _experts(key, held, d_in, d_out):
    return jax.vmap(lambda e: jax.random.normal(
        jax.random.fold_in(key, e), (d_in, d_out)) / jnp.sqrt(d_in))(held)


def _block(key, m, moe: bool):
    d, H, C = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    ka, kf = jax.random.split(key, 2)
    a = jax.random.split(ka, 4)
    p = {"attn_norm": {"scale": jnp.ones((d,), jnp.float32)},
         "attn": {"wq": _dense(a[0], d, H * (dn + dr)),
                  "wkv_a": _dense(a[1], d, C + dr),
                  "kv_norm": jnp.ones((C,), jnp.float32),
                  "wkv_b": _dense(a[2], C, H * (dn + dv)),
                  "wo": _dense(a[3], H * dv, d)},
         "ffn_norm": {"scale": jnp.ones((d,), jnp.float32)}}
    if not moe:
        p["ffn"] = _swiglu_params(kf, d, m["d_ff"])
        return p
    f = m["moe_d_ff"]
    k = jax.random.split(kf, 6)
    held = m["first_expert"] + jnp.arange(m["experts_held"])
    p["moe"] = {"router": _dense(k[0], d, m["n_experts"]),
                "bias": jax.random.normal(k[1], (m["n_experts"],)) * BIAS_SCALE,
                "w_gate": _experts(k[2], held, d, f),
                "w_up": _experts(k[3], held, d, f),
                "w_down": _experts(k[4], held, f, d),
                "shared": _swiglu_params(k[5], d, f * m["n_shared_experts"])}
    return p


def init_params(key, m) -> Dict[str, Any]:
    L, F = m["n_layers"], m["first_dense_layers"]
    ks = jax.random.split(key, L + 3)
    blk = jnp.stack(ks[1:1 + L])
    return {"embed": jax.random.normal(ks[0], (m["vocab_size"], m["d_model"]),
                                       jnp.float32) * 0.02,
            "dense_blocks": jax.vmap(lambda k: _block(k, m, False))(blk[:F]),
            "blocks": jax.vmap(lambda k: _block(k, m, True))(blk[F:]),
            "final_norm": {"scale": jnp.ones((m["d_model"],), jnp.float32)},
            "lm_head": _dense(ks[-1], m["d_model"], m["vocab_size"])}


def _q8(x):
    s = jnp.max(jnp.abs(x)) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


MATMULS = {"f32": lambda a, b: jnp.matmul(a, b, precision=HI),
           "fp8": lambda a, b: jnp.matmul(_q8(a), _q8(b), precision=HI)}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (n, s, h, r): rotate the pair (x[i], x[i + r/2]) by pos * f_i."""
    r = x.shape[-1]
    f = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * f
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(mm, h, f):
    return mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"]), f["w_down"])


def attention(mm, a, h, m):
    """Expanded latent attention over the whole sequence, causal."""
    n, s, _ = h.shape
    H, C = m["n_heads"], m["kv_lora_rank"]
    dn, dr = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    theta = m["rope_theta"]
    q = mm(h, a["wq"]).reshape(n, s, H, dn + dr)
    kv = mm(h, a["wkv_a"])
    c = _rms(kv[..., :C], a["kv_norm"], m["norm_eps"])
    k_pe = _rope(kv[..., None, C:], theta)
    kvb = mm(c, a["wkv_b"]).reshape(n, s, H, -1)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(k_pe, (n, s, H, dr))],
                        -1)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    sc = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HI) / math.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", w, kvb[..., dn:], precision=HI)
    return mm(o.reshape(n, s, -1), a["wo"])


def routed(mm, e, h, m):
    """The held experts' part for every token of h (t, d): each held
    expert on every token, weighed by the token's gate for it."""
    scores = jax.nn.sigmoid(mm(h, e["router"]))
    _, idx = jax.lax.top_k(scores + e["bias"], m["moe_top_k"])
    chosen = jnp.take_along_axis(scores, idx, -1)
    gates = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    gates = gates * m["routed_scale"]
    out = jnp.zeros_like(h)
    for j in range(m["experts_held"]):
        weight = jnp.sum(jnp.where(idx == m["first_expert"] + j, gates, 0.0), -1)
        f = {n: e[n][j] for n in ("w_gate", "w_up", "w_down")}
        out = out + weight[:, None] * _swiglu(mm, h, f)
    return out


def logits(params, tokens, m, matmul: str = "f32"):
    """tokens (n, s) -> logits (n, s, vocab) at every position."""
    mm = MATMULS[matmul]
    eps = m["norm_eps"]
    n, s = tokens.shape

    def layer(x, b):
        x = x + attention(mm, b["attn"], _rms(x, b["attn_norm"]["scale"], eps),
                          m)
        h = _rms(x, b["ffn_norm"]["scale"], eps)
        if "ffn" in b:
            return x + _swiglu(mm, h, b["ffn"]), None
        e = b["moe"]
        flat = h.reshape(n * s, -1)
        out = routed(mm, e, flat, m) + _swiglu(mm, flat, e["shared"])
        return x + out.reshape(x.shape), None

    x = params["embed"][tokens]
    for name in ("dense_blocks", "blocks"):
        x, _ = jax.lax.scan(layer, x, params[name])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return mm(x, params["lm_head"])
