"""Decoder-only LM assembling the block families, with scan-over-layers.

Families:
  dense  — GQA attention + SwiGLU FFN (llama/qwen style)
  moe    — GQA or latent (MLA) attention + top-k MoE FFN (shared experts
           optional), after ``cfg.first_dense_layers`` dense layers
  hybrid — Hymba parallel attention ∥ mamba blocks
  ssm    — xLSTM (mLSTM blocks + sLSTM at cfg.slstm_layers), unrolled

Deep homogeneous stacks scan over stacked per-layer params (O(1) HLO size —
this is what keeps 512-device dry-run compiles tractable and is also the
production layout).  xLSTM is shallow and heterogeneous -> unrolled.  A
model whose leading layers are dense scans two stacks, ``dense_blocks``
then ``blocks``; its cache is then keyed by stack too, as is every latent
(MLA) cache.

``forward`` returns (logits, aux) where aux is the MoE load-balance loss
(0 for non-MoE).  ``decode_step`` performs one-token decode against the
cache pytree built by ``init_cache``; ``prefill`` fills the cache from a
whole prompt in one pass where ``prefills(cfg)``.
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

#: the scanned stacks of blocks, in layer order
STACKS = ("dense_blocks", "blocks")


def init_block(key, cfg: ModelConfig, moe: bool = True) -> Params:
    """A block; a moe model's block has a dense FFN where ``moe`` is False."""
    ks = jax.random.split(key, 2)
    if cfg.family == "hybrid":
        return hybrid_mod.init_hymba_block(key, cfg)
    p = {
        "attn_norm": init_norm(cfg),
        "attn": (attn_mod.init_mla(ks[0], cfg) if cfg.kv_lora_rank
                 else attn_mod.init_attention(ks[0], cfg)),
        "ffn_norm": init_norm(cfg),
    }
    if cfg.family == "moe" and moe:
        p["moe"] = moe_mod.init_moe(ks[1], cfg)
    else:
        p["ffn"] = ffn_mod.init_ffn(ks[1], cfg)
    return p


def block_specs(cfg: ModelConfig, moe: bool = True) -> Specs:
    if cfg.family == "hybrid":
        return hybrid_mod.hymba_block_specs(cfg)
    p = {
        "attn_norm": norm_specs(cfg),
        "attn": (attn_mod.mla_specs(cfg) if cfg.kv_lora_rank
                 else attn_mod.attention_specs(cfg)),
        "ffn_norm": norm_specs(cfg),
    }
    if cfg.family == "moe" and moe:
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
        F = cfg.first_dense_layers
        if F:
            p["dense_blocks"] = jax.vmap(
                lambda k: init_block(k, cfg, moe=False))(blk_keys[:F])
            blk_keys = blk_keys[F:]
        p["blocks"] = jax.vmap(lambda k: init_block(k, cfg))(blk_keys)
    else:
        p["blocks"] = [init_block(ks[1 + i], cfg,
                                  moe=i >= cfg.first_dense_layers)
                       for i in range(cfg.n_layers)]
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
        F = cfg.first_dense_layers
        blk = block_specs(cfg)
        if cfg.scan_layers:
            stacked = lambda t: jax.tree.map(
                lambda axes: ("layers",) + tuple(axes), t,
                is_leaf=lambda x: isinstance(x, tuple))
            if F:
                p["dense_blocks"] = stacked(block_specs(cfg, moe=False))
            p["blocks"] = stacked(blk)
        else:
            p["blocks"] = ([block_specs(cfg, moe=False)] * F
                           + [blk] * (cfg.n_layers - F))
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
    if cfg.kv_lora_rank:
        a = attn_mod.apply_mla(blk["attn"], h, cfg)
    else:
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


def _ffn(blk: Params, h: jnp.ndarray, cfg: ModelConfig
         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The FFN (or MoE) of a block: (out, aux loss)."""
    if "moe" not in blk:
        return ffn_mod.apply_ffn(blk["ffn"], h, cfg), jnp.float32(0.0)
    if cfg.moe_dispatch == "dropless":
        return moe_mod.apply_dropless(blk["moe"], h, cfg), jnp.float32(0.0)
    return moe_mod.apply_moe(blk["moe"], h, cfg)


def _apply_ffn_residual(blk: Params, x: jnp.ndarray, cfg: ModelConfig
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A dense block's second half: norm, FFN (or MoE) and the residual."""
    h = apply_norm(blk["ffn_norm"], x, cfg)
    out, aux = _ffn(blk, h, cfg)
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
            for name in STACKS:
                if name in params:
                    (x, aux), _ = jax.lax.scan(body, (x, aux), params[name])
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

def _keyed(cfg: ModelConfig) -> bool:
    """Whether the cache is keyed by stack (``STACKS``), as a latent (MLA)
    cache is."""
    return bool(cfg.kv_lora_rank or cfg.first_dense_layers)


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
    if not _keyed(cfg):
        return attn_mod.init_kv_cache(cfg, batch, max_len)
    if not cfg.scan_layers:
        raise ValueError("a cache keyed by stack needs scan_layers")
    F = cfg.first_dense_layers
    cache = {}
    for name, n in (("dense_blocks", F), ("blocks", cfg.n_layers - F)):
        if n:
            cache[name] = (
                attn_mod.init_latent_cache(cfg, batch, max_len, n)
                if cfg.kv_lora_rank else
                attn_mod.init_kv_cache(cfg, batch, max_len, n_layers=n))
    return cache


def prefills(cfg: ModelConfig) -> bool:
    """Whether ``prefill`` takes this model: attention caches only, and no
    layer whose result depends on how many tokens a call holds (the
    capacity of a GShard expert layer does)."""
    if cfg.family == "dense":
        return True
    return cfg.family == "moe" and cfg.moe_dispatch == "dropless"


def _over_layers(step, x: jnp.ndarray, params: Params, cache: Dict[str, Any],
                 cfg: ModelConfig):
    """x through every block with its layer's cache, by stack:
    ``step(x, blk, layer_cache) -> (x, new layer cache)``.  Returns (x,
    the new cache)."""
    if not _keyed(cfg):
        return _over_stack(step, x, params["blocks"], cache, cfg)
    new = {}
    for name in STACKS:
        if name in params:
            x, new[name] = _over_stack(step, x, params[name], cache[name], cfg)
    return x, new


def _over_stack(step, x, blocks, cache, cfg: ModelConfig):
    if cfg.scan_layers:
        return jax.lax.scan(lambda x, inp: step(x, *inp), x, (blocks, cache))
    outs = []
    for i, blk in enumerate(blocks):
        x, layer_cache = step(x, blk, jax.tree.map(lambda a: a[i], cache))
        outs.append(layer_cache)
    return x, jax.tree.map(lambda *a: jnp.stack(a), *outs)


def prefill(params: Params, tokens: jnp.ndarray, cache: Dict[str, Any],
            cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """tokens (B, S) + an empty cache -> (logits (B, S, V), the cache with
    rows [0, S) written): the whole prompt in one causal pass, the same
    numbers as S ``decode_step`` calls at positions 0..S-1.  For the models
    ``prefills`` names: the others carry recurrent state or route tokens
    by how many a call holds."""
    if not prefills(cfg):
        raise ValueError(f"prefill does not take {cfg.name} "
                         f"({cfg.family}, {cfg.moe_dispatch} dispatch)")
    x = _embed(params, tokens, cfg)
    x = shard_hint(x, ("batch", "seq", "embed"))

    def step(x, blk, layer_cache):
        h = apply_norm(blk["attn_norm"], x, cfg)
        if cfg.kv_lora_rank:
            a, layer_cache = attn_mod.prefill_mla(blk["attn"], h, layer_cache,
                                                  cfg)
        else:
            a, layer_cache = attn_mod.prefill_attention(
                blk["attn"], h, layer_cache, cfg, window=cfg.sliding_window)
        x, _ = _apply_ffn_residual(blk, x + a, cfg)
        return x, layer_cache

    x, new = _over_layers(step, x, params, cache, cfg)
    x = apply_norm(params["final_norm"], x, cfg)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return x @ head.astype(cfg.compute_dtype), new


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
        def step(x, blk, layer_cache):
            h = apply_norm(blk["attn_norm"], x, cfg)
            if cfg.kv_lora_rank:
                a, layer_cache = attn_mod.decode_mla(blk["attn"], h,
                                                     layer_cache, pos, cfg)
            else:
                a, layer_cache = attn_mod.decode_attention(
                    blk["attn"], h, layer_cache, pos, cfg,
                    window=cfg.sliding_window)
            x = x + a
            h = apply_norm(blk["ffn_norm"], x, cfg)
            out, _ = _ffn(blk, h, cfg)
            return x + out, layer_cache

        x, new_cache = _over_layers(step, x, params, cache, cfg)

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
