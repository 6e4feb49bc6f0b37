"""Decoder-only LM assembling the block families, with scan-over-layers.

Families:
  dense  — GQA attention + SwiGLU FFN (llama/qwen style)
  moe    — GQA attention + top-k MoE FFN (shared experts optional)
  hybrid — Hymba parallel attention ∥ mamba blocks
  ssm    — xLSTM (mLSTM blocks + sLSTM at cfg.slstm_layers), unrolled

Deep homogeneous stacks scan over stacked per-layer params (O(1) HLO size —
this is what keeps 512-device dry-run compiles tractable and is also the
production layout).  xLSTM is shallow and heterogeneous -> unrolled.

``forward`` returns (logits, aux) where aux is the MoE load-balance loss
(0 for non-MoE).  ``decode_step`` performs one-token decode against the
cache pytree built by ``init_cache``; ``prefill`` fills a dense model's
cache from a whole prompt in one pass.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_hint
from repro.models import attention as attn_mod
from repro.models import ffn as ffn_mod
from repro.models import hybrid as hybrid_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (ModelConfig, Params, Specs, apply_norm,
                                 embed_init, init_norm, norm_specs,
                                 dense_init)


# --- block init/specs -------------------------------------------------------------

def init_block(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    if cfg.family == "hybrid":
        return hybrid_mod.init_hymba_block(key, cfg)
    p = {
        "attn_norm": init_norm(cfg),
        "attn": attn_mod.init_attention(ks[0], cfg),
        "ffn_norm": init_norm(cfg),
    }
    if cfg.family == "moe":
        p["moe"] = moe_mod.init_moe(ks[1], cfg)
    else:
        p["ffn"] = ffn_mod.init_ffn(ks[1], cfg)
    return p


def block_specs(cfg: ModelConfig) -> Specs:
    if cfg.family == "hybrid":
        return hybrid_mod.hymba_block_specs(cfg)
    p = {
        "attn_norm": norm_specs(cfg),
        "attn": attn_mod.attention_specs(cfg),
        "ffn_norm": norm_specs(cfg),
    }
    if cfg.family == "moe":
        p["moe"] = moe_mod.moe_specs(cfg)
    else:
        p["ffn"] = ffn_mod.ffn_specs(cfg)
    return p


def init_xlstm_block(key, cfg: ModelConfig, layer: int) -> Params:
    if layer in cfg.slstm_layers:
        return {"norm": init_norm(cfg),
                "slstm": ssm_mod.init_slstm(key, cfg)}
    return {"norm": init_norm(cfg), "mlstm": ssm_mod.init_mlstm(key, cfg)}


def xlstm_block_specs(cfg: ModelConfig, layer: int) -> Specs:
    if layer in cfg.slstm_layers:
        return {"norm": norm_specs(cfg), "slstm": ssm_mod.slstm_specs(cfg)}
    return {"norm": norm_specs(cfg), "mlstm": ssm_mod.mlstm_specs(cfg)}


# --- model init/specs ----------------------------------------------------------------

def init_lm(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, cfg.n_layers + 3)
    p: Dict[str, Any] = {"embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model)}
    if cfg.family == "ssm":
        p["blocks"] = [init_xlstm_block(ks[1 + i], cfg, i)
                       for i in range(cfg.n_layers)]
    elif cfg.scan_layers:
        blk_keys = jnp.stack(ks[1:1 + cfg.n_layers])
        p["blocks"] = jax.vmap(lambda k: init_block(k, cfg))(blk_keys)
    else:
        p["blocks"] = [init_block(ks[1 + i], cfg) for i in range(cfg.n_layers)]
    p["final_norm"] = init_norm(cfg)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[-1], cfg.d_model, cfg.vocab_size)
    if cfg.pos_emb == "learned":
        p["pos_embed"] = embed_init(ks[-2], cfg.max_seq_len, cfg.d_model)
    return p


def lm_specs(cfg: ModelConfig) -> Specs:
    p: Dict[str, Any] = {"embed": ("vocab", "embed")}
    if cfg.family == "ssm":
        p["blocks"] = [xlstm_block_specs(cfg, i) for i in range(cfg.n_layers)]
    else:
        blk = block_specs(cfg)
        if cfg.scan_layers:
            blk = jax.tree.map(lambda axes: ("layers",) + tuple(axes), blk,
                               is_leaf=lambda x: isinstance(x, tuple))
        p["blocks"] = blk if cfg.scan_layers else [blk] * cfg.n_layers
    p["final_norm"] = norm_specs(cfg)
    if not cfg.tie_embeddings:
        p["lm_head"] = ("embed", "vocab")
    if cfg.pos_emb == "learned":
        p["pos_embed"] = (None, "embed")
    return p


# --- forward (train / prefill) ----------------------------------------------------------

def _embed(params: Params, tokens: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    dt = cfg.compute_dtype
    x = jnp.take(params["embed"].astype(dt), tokens, axis=0)
    if cfg.pos_emb == "learned":
        S = tokens.shape[1]
        x = x + params["pos_embed"][:S].astype(dt)
    return x


def _apply_dense_block(blk: Params, x: jnp.ndarray, cfg: ModelConfig
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = apply_norm(blk["attn_norm"], x, cfg)
    a = attn_mod.apply_attention(blk["attn"], h, cfg,
                                 window=cfg.sliding_window)
    if cfg.sp_outputs:
        # Megatron-SP: constrain the row-parallel sublayer OUTPUT (a partial
        # sum over the model axis) to seq-sharded before the residual add —
        # GSPMD then lowers the sync as reduce-scatter (wire /2 vs the
        # all-reduce it otherwise inserts to make the output replicated).
        a = shard_hint(a, ("batch", "seq", "embed"))
    x = x + a
    x = shard_hint(x, ("batch", "seq", "embed"))
    return _apply_ffn_residual(blk, x, cfg)


def _apply_ffn_residual(blk: Params, x: jnp.ndarray, cfg: ModelConfig
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A dense block's second half: norm, FFN (or MoE) and the residual."""
    h = apply_norm(blk["ffn_norm"], x, cfg)
    if "moe" in blk:
        out, aux = moe_mod.apply_moe(blk["moe"], h, cfg)
    else:
        out, aux = ffn_mod.apply_ffn(blk["ffn"], h, cfg), jnp.float32(0.0)
    if cfg.sp_outputs:
        out = shard_hint(out, ("batch", "seq", "embed"))
    x = shard_hint(x + out, ("batch", "seq", "embed"))
    return x, aux


def _apply_xlstm_block(blk: Params, x: jnp.ndarray, cfg: ModelConfig
                       ) -> jnp.ndarray:
    h = apply_norm(blk["norm"], x, cfg)
    if "slstm" in blk:
        return x + ssm_mod.apply_slstm(blk["slstm"], h, cfg)
    return x + ssm_mod.apply_mlstm(blk["mlstm"], h, cfg)


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    return fn


def forward(params: Params, tokens: jnp.ndarray, cfg: ModelConfig
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens (B, S) int32 -> (logits (B, S, V), aux scalar)."""
    x = _embed(params, tokens, cfg)
    x = shard_hint(x, ("batch", "seq", "embed"))
    aux = jnp.float32(0.0)

    if cfg.family == "ssm":
        for blk in params["blocks"]:
            x = _maybe_remat(
                lambda c, b: _apply_xlstm_block(b, c, cfg), cfg)(x, blk)
            x = shard_hint(x, ("batch", "seq", "embed"))
    elif cfg.family == "hybrid":
        S = tokens.shape[1]
        windows = hybrid_mod.layer_windows(cfg, S)

        def hybrid_body(carry, inp):
            blk, w = inp
            return _maybe_remat(
                lambda c, b: hybrid_mod.apply_hymba_block(b, c, cfg, w),
                cfg)(carry, blk), None

        if cfg.scan_layers:
            x, _ = jax.lax.scan(hybrid_body, x, (params["blocks"], windows))
        else:
            for i in range(cfg.n_layers):
                blk = jax.tree.map(lambda a: a[i], params["blocks"]) \
                    if not isinstance(params["blocks"], list) else params["blocks"][i]
                x = hybrid_mod.apply_hymba_block(blk, x, cfg, windows[i])
    else:
        def body(carry, blk):
            x, aux = carry
            fn = _maybe_remat(
                lambda c, b: _apply_dense_block(b, c, cfg), cfg)
            x, a = fn(x, blk)
            return (x, aux + a), None

        if cfg.scan_layers:
            (x, aux), _ = jax.lax.scan(body, (x, aux), params["blocks"])
        else:
            for blk in params["blocks"]:
                x, a = _maybe_remat(
                    lambda c, b: _apply_dense_block(b, c, cfg), cfg)(x, blk)
                aux = aux + a

    x = apply_norm(params["final_norm"], x, cfg)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(cfg.compute_dtype)
    logits = shard_hint(logits, ("batch", "seq", "vocab"))
    return logits, aux


# --- decode ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    if cfg.family == "ssm":
        cache: Dict[str, Any] = {}
        for i in range(cfg.n_layers):
            if i in cfg.slstm_layers:
                cache[f"layer{i}"] = ssm_mod.init_slstm_state(cfg, batch)
            else:
                M, n = ssm_mod.init_mlstm_state(cfg, batch)
                cache[f"layer{i}"] = {"M": M, "n": n}
        return cache
    if cfg.family == "hybrid":
        return hybrid_mod.init_hymba_cache(cfg, batch, max_len)
    return attn_mod.init_kv_cache(cfg, batch, max_len)


def prefill(params: Params, tokens: jnp.ndarray, cache: Dict[str, Any],
            cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """tokens (B, S) + an empty KV cache -> (logits (B, S, V), the cache
    with rows [0, S) written): the whole prompt in one causal pass, the
    same numbers as S ``decode_step`` calls at positions 0..S-1.  Dense
    family only: the others carry recurrent state or route tokens."""
    if cfg.family != "dense":
        raise ValueError(f"prefill is for the dense family, not {cfg.family}")
    x = _embed(params, tokens, cfg)
    x = shard_hint(x, ("batch", "seq", "embed"))

    def body(x, inp):
        blk, krow, vrow = inp
        h = apply_norm(blk["attn_norm"], x, cfg)
        a, kv = attn_mod.prefill_attention(
            blk["attn"], h, {"k": krow, "v": vrow}, cfg,
            window=cfg.sliding_window)
        x, _ = _apply_ffn_residual(blk, x + a, cfg)
        return x, (kv["k"], kv["v"])

    if cfg.scan_layers:
        x, (k, v) = jax.lax.scan(
            body, x, (params["blocks"], cache["k"], cache["v"]))
    else:
        kvs = []
        for i, blk in enumerate(params["blocks"]):
            x, kv = body(x, (blk, cache["k"][i], cache["v"][i]))
            kvs.append(kv)
        k, v = (jnp.stack(t) for t in zip(*kvs))

    x = apply_norm(params["final_norm"], x, cfg)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return x @ head.astype(cfg.compute_dtype), {"k": k, "v": v}


def decode_step(params: Params, tokens: jnp.ndarray, cache: Dict[str, Any],
                pos: jnp.ndarray, cfg: ModelConfig
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """tokens (B, 1) + cache + scalar pos -> (logits (B, 1, V), new cache)."""
    x = _embed_decode(params, tokens, pos, cfg)
    x = shard_hint(x, ("batch", None, "embed"))

    if cfg.family == "ssm":
        new_cache: Dict[str, Any] = {}
        for i, blk in enumerate(params["blocks"]):
            h = apply_norm(blk["norm"], x, cfg)
            st = cache[f"layer{i}"]
            if "slstm" in blk:
                y, st = ssm_mod.decode_slstm(blk["slstm"], h, st, cfg)
            else:
                y, (M, n) = ssm_mod.decode_mlstm(blk["mlstm"], h,
                                                 (st["M"], st["n"]), cfg)
                st = {"M": M, "n": n}
            x = x + y
            new_cache[f"layer{i}"] = st
    elif cfg.family == "hybrid":
        new_cache = {}
        for i in range(cfg.n_layers):
            blk = jax.tree.map(lambda a: a[i], params["blocks"]) \
                if not isinstance(params["blocks"], list) else params["blocks"][i]
            x, row = hybrid_mod.decode_hymba_block(
                blk, x, cache[f"layer{i}"], pos, cfg,
                is_global=i in cfg.global_attn_layers)
            new_cache[f"layer{i}"] = row
    else:
        def body(x, inp):
            blk, krow, vrow = inp
            h = apply_norm(blk["attn_norm"], x, cfg)
            a, kv = attn_mod.decode_attention(
                blk["attn"], h, {"k": krow, "v": vrow}, pos, cfg,
                window=cfg.sliding_window)
            x = x + a
            h = apply_norm(blk["ffn_norm"], x, cfg)
            if "moe" in blk:
                out, _ = moe_mod.apply_moe(blk["moe"], h, cfg)
            else:
                out = ffn_mod.apply_ffn(blk["ffn"], h, cfg)
            return x + out, (kv["k"], kv["v"])

        if cfg.scan_layers:
            x, (k, v) = jax.lax.scan(
                body, x, (params["blocks"], cache["k"], cache["v"]))
            new_cache = {"k": k, "v": v}
        else:
            ks, vs = [], []
            for i, blk in enumerate(params["blocks"]):
                x, (k, v) = body(x, (blk, cache["k"][i], cache["v"][i]))
                ks.append(k)
                vs.append(v)
            new_cache = {"k": jnp.stack(ks), "v": jnp.stack(vs)}

    x = apply_norm(params["final_norm"], x, cfg)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(cfg.compute_dtype)
    return logits, new_cache


def _embed_decode(params: Params, tokens: jnp.ndarray, pos: jnp.ndarray,
                  cfg: ModelConfig) -> jnp.ndarray:
    dt = cfg.compute_dtype
    x = jnp.take(params["embed"].astype(dt), tokens, axis=0)
    if cfg.pos_emb == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], pos, 1, axis=0).astype(dt)
    return x
