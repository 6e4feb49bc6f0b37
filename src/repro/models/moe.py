"""Mixture-of-Experts FFN: top-k routing with capacity-based dense dispatch.

TPU-idiomatic GShard/Switch formulation: token->expert assignment becomes a
dense one-hot dispatch tensor contracted with einsum, so expert compute is a
batched GEMM (E, C, D) x (E, D, F) that shards cleanly over the ``expert``
logical axis (EP over the mesh ``model`` axis).  No torch-style NCCL
emulation: the all-to-all pattern emerges from GSPMD propagation on the
sharded einsum.

Supports the two assigned MoE archs:
  * qwen2-moe-a2.7b  — 60 routed top-4 + 4 shared experts (d_ff 1408)
  * qwen3-moe-30b-a3b — 128 routed top-8, no shared (d_ff 768)
Routing = softmax-then-topk with renormalized gates (Qwen convention), plus
the standard load-balancing auxiliary loss (Switch §4) exposed for training.

``cfg.moe_dispatch == "dropless"`` is the other layer (DeepSeek-V3 and
Moonlight): sigmoid scores, the top-k chosen on scores plus a per-expert
correction bias, gates renormalised and scaled.  It holds experts
``first_expert .. first_expert + experts_held - 1`` of the router's
``n_experts``, routes over all of them, and computes its own experts' part
for every (token, expert) pair routed to them: pairs are sorted by expert
and run as grouped GEMMs (``jax.lax.ragged_dot``), so no token is dropped.
The absent experts' part is left out; nothing stands in for their chips.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, Params, Specs, dense_init
from repro.models import ffn as ffn_mod


def _padded_e(cfg: ModelConfig) -> int:
    return max(cfg.n_experts, cfg.pad_experts_to)


def init_moe(key, cfg: ModelConfig) -> Params:
    if cfg.moe_dispatch == "dropless":
        return _init_held(key, cfg)
    # optional expert padding: allocating E_pad >= E experts (the extra ones
    # are never routed to) buys EP divisibility on the mesh model axis —
    # e.g. qwen2-moe's 60 experts pad to 64 for a 16-wide axis.  FLOP cost:
    # zero (dispatch one-hots never select them); memory: E_pad/E.
    E, D, F = _padded_e(cfg), cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(D)
    p = {
        # router logits stay at the TRUE expert count (padding experts must
        # never receive routing mass)
        "router": dense_init(ks[0], D, cfg.n_experts),
        "w_gate": jax.random.normal(ks[1], (E, D, F)) * scale,
        "w_up": jax.random.normal(ks[2], (E, D, F)) * scale,
        "w_down": jax.random.normal(ks[3], (E, F, D)) / jnp.sqrt(F),
    }
    if cfg.n_shared_experts:
        shared_ff = cfg.moe_d_ff * cfg.n_shared_experts
        p["shared"] = ffn_mod.init_ffn(ks[4], cfg, d_ff=shared_ff)
    return p


def moe_specs(cfg: ModelConfig) -> Specs:
    if cfg.moe_dispatch == "dropless":
        return {"router": ("embed", None), "bias": (None,),
                "w_gate": ("experts", "embed", "expert_ffn"),
                "w_up": ("experts", "embed", "expert_ffn"),
                "w_down": ("experts", "expert_ffn", "embed"),
                "shared": ffn_mod.ffn_specs(cfg)}
    # "experts" is EP (mesh model axis) when the count divides it; otherwise
    # the launcher maps "expert_ffn" to the model axis instead (per-expert
    # hidden TP — 60-expert qwen2-moe vs a 16-wide axis).
    p = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "expert_ffn"),
        "w_up": ("experts", "embed", "expert_ffn"),
        "w_down": ("experts", "expert_ffn", "embed"),
    }
    if cfg.n_shared_experts:
        p["shared"] = ffn_mod.ffn_specs(cfg)
    return p


def _capacity(group_tokens: int, cfg: ModelConfig) -> int:
    cap = int(group_tokens * cfg.moe_top_k * cfg.capacity_factor / cfg.n_experts)
    return max(cap, cfg.moe_top_k)


def route(router_logits: jnp.ndarray, cfg: ModelConfig
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Return (gates (T,k), expert_idx (T,k), aux_loss scalar)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e, with the
    # assignment fraction f_e counting ALL k routed choices (normalized by
    # k so f sums to 1) — the top-1 Switch convention undercounts load for
    # the k=4/8 Qwen routers, leaving k-1 of every token's assignments
    # invisible to the loss
    E, k = cfg.n_experts, cfg.moe_top_k
    me = jnp.mean(probs, axis=0)                               # (E,)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1),
                  axis=0) / k                                  # (E,)
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def apply_moe(p: Params, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss).

    Tokens are flattened and re-grouped into fixed ``cfg.moe_group_tokens``
    windows (GShard §3.2 groups: capacity buffers are sized per group,
    keeping the dispatch tensor linear in total tokens); each group
    dispatches into per-expert capacity buffers
    via one-hot einsum.  Capacity-dropped tokens pass through the residual
    (their expert contribution is zero) — the standard GShard behaviour.
    The group axis carries the ``batch`` logical sharding (DP), the expert
    axis carries ``experts`` (EP over mesh ``model``).
    """
    dt = cfg.compute_dtype
    B, S, D = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.moe_top_k
    Tg = min(cfg.moe_group_tokens, T)
    if T % Tg:
        # fall back to one group per sequence for odd smoke-test sizes
        Tg = S if T % S == 0 else T
    G = T // Tg
    C = _capacity(Tg, cfg)
    xt = x.reshape(G, Tg, D).astype(dt)

    E_pad = p["w_gate"].shape[0]
    logits = jnp.einsum("gtd,de->gte", xt, p["router"].astype(dt))
    gates, idx, aux = route(logits.reshape(T, E), cfg)         # (T,k) fp32
    gates = gates.reshape(G, Tg, k)
    idx = idx.reshape(G, Tg, k)

    # position of each (token, choice) inside its expert's capacity buffer,
    # computed per group via masked cumulative sum over the flattened choices
    onehot = jax.nn.one_hot(idx, E_pad, dtype=jnp.float32)     # (G, Tg, k, E_pad)
    flat = onehot.reshape(G, Tg * k, E_pad)
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat            # (G, Tg*k, E_pad)
    pos = jnp.sum(pos_in_expert * flat, axis=-1).reshape(G, Tg, k)
    keep = (pos < C).astype(jnp.float32)
    gates = gates * keep

    # dispatch/combine tensors (G, Tg, E, C)
    pos_oh = jax.nn.one_hot(pos, C, dtype=dt) * keep[..., None].astype(dt)
    dispatch = jnp.einsum("gtke,gtkc->gtec", onehot.astype(dt), pos_oh)
    combine = jnp.einsum("gtec,gtk->gtec", dispatch, gates.astype(dt))

    xin = jnp.einsum("gtec,gtd->gecd", dispatch, xt)           # (G, E, C, D)
    g = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin, p["w_gate"].astype(dt)))
    u = jnp.einsum("gecd,edf->gecf", xin, p["w_up"].astype(dt))
    h = jnp.einsum("gecf,efd->gecd", g * u, p["w_down"].astype(dt))
    out = jnp.einsum("gtec,gecd->gtd", combine, h)             # (G, Tg, D)

    out = out.reshape(B, S, D)
    if cfg.n_shared_experts:
        out = out + ffn_mod.apply_ffn(p["shared"], x.astype(dt), cfg)
    return out, aux.astype(jnp.float32)


# --- dropless routing over held experts --------------------------------------

#: scale of the seeded per-expert correction bias (a trained model learns it)
BIAS_SCALE = 0.05


def _init_held(key, cfg: ModelConfig) -> Params:
    """Router over all ``n_experts``, its correction bias, the held experts
    (expert e's weights from its own key, so a share holds the same numbers
    as the uncut layer) and the shared experts as one SwiGLU FFN."""
    D, F = cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 6)
    held = cfg.first_expert + jnp.arange(cfg.n_held)

    def experts(k, d_in, d_out):
        return jax.vmap(lambda e: jax.random.normal(
            jax.random.fold_in(k, e), (d_in, d_out)) / jnp.sqrt(d_in))(held)

    return {"router": dense_init(ks[0], D, cfg.n_experts),
            "bias": jax.random.normal(ks[1], (cfg.n_experts,)) * BIAS_SCALE,
            "w_gate": experts(ks[2], D, F), "w_up": experts(ks[3], D, F),
            "w_down": experts(ks[4], F, D),
            "shared": ffn_mod.init_ffn(ks[5], cfg,
                                       d_ff=F * cfg.n_shared_experts)}


def route_sigmoid(x: jnp.ndarray, p: Params, cfg: ModelConfig
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (T, D) -> (gates (T, k), experts (T, k)), all in float32: the
    router GEMM at full precision, so that bfloat16 rounding flips no
    choice; top-k of sigmoid scores plus the bias, whose gates are the
    scores alone, renormalised and scaled."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["bias"], cfg.moe_top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return gates * cfg.routed_scale, idx


def _held_chunk(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The held experts' part (T, D) for the tokens x (T, D): every (token,
    expert) pair routed to a held expert, sorted by expert, through one
    grouped GEMM per weight."""
    dt = cfg.compute_dtype
    T, D = x.shape
    k, n = cfg.moe_top_k, cfg.n_held
    gates, idx = route_sigmoid(x, p, cfg)
    local = idx.reshape(-1) - cfg.first_expert                 # (T*k,)
    held = (local >= 0) & (local < n)
    group = jnp.where(held, local, n)             # the others sort last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=n + 1)[:n].astype(jnp.int32)
    rows = jnp.take(x.astype(dt), order // k, axis=0)          # (T*k, D)
    g = jax.lax.ragged_dot(rows, p["w_gate"].astype(dt), sizes)
    u = jax.lax.ragged_dot(rows, p["w_up"].astype(dt), sizes)
    y = jax.lax.ragged_dot(jax.nn.silu(g) * u, p["w_down"].astype(dt), sizes)
    # rows past the held pairs belong to no group: the grouped GEMM leaves
    # them undefined, so they are selected away, never multiplied
    y = jnp.take(y, jnp.argsort(order), axis=0).reshape(T, k, D)
    w = jnp.where(held.reshape(T, k), gates, 0.0).astype(dt)
    return jnp.einsum("tkd,tk->td", jnp.where(held.reshape(T, k, 1), y, 0), w)


def apply_held(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """x (B, S, D) -> the routed experts' part.  Tokens go in chunks of
    ``cfg.moe_group_tokens``, which bound the sorted buffers (T*k rows of
    D), not what is computed."""
    B, S, D = x.shape
    T = B * S
    Tg = min(cfg.moe_group_tokens, T)
    if T % Tg:
        Tg = T
    xt = x.reshape(T // Tg, Tg, D)
    if T == Tg:
        out = _held_chunk(p, xt[0], cfg)
    else:
        out = jax.lax.map(lambda c: _held_chunk(p, c, cfg), xt)
    return out.reshape(B, S, D)


def apply_dropless(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The whole layer: the held experts' part plus the shared experts
    for every token."""
    with jax.named_scope("moe"):
        return apply_held(p, x, cfg) + ffn_mod.apply_ffn(p["shared"], x, cfg)
