"""Grouped-query attention: init, full-sequence apply, whole-prompt prefill
and single-token decode into a KV cache.

Supports: GQA (kv heads < q heads), optional QKV bias (Qwen2), per-head QK
RMS-norm (Qwen3), RoPE / learned / no positions, causal or bidirectional,
sliding-window masks (Hymba local layers), cross-attention (Whisper decoder),
and a Pallas flash-attention fast path (``cfg.use_flash``).

Shapes: activations (B, S, D); per-head tensors (B, S, H, dh).
KV cache for decode: dict(k=(B, S_max, Hkv, dh), v=..., pos scalar index).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (ModelConfig, Params, Specs, apply_rope,
                                 dense_init, ones, rms_norm_head, zeros)

NEG_INF = -0.7 * jnp.finfo(jnp.float32).max


def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.q_dim),
        "wk": dense_init(ks[1], cfg.d_model, cfg.kv_dim),
        "wv": dense_init(ks[2], cfg.d_model, cfg.kv_dim),
        "wo": dense_init(ks[3], cfg.q_dim, cfg.d_model),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros((cfg.q_dim,))
        p["bk"] = zeros((cfg.kv_dim,))
        p["bv"] = zeros((cfg.kv_dim,))
    if cfg.qk_norm:
        p["q_norm"] = ones((cfg.dh,))
        p["k_norm"] = ones((cfg.dh,))
    return p


def attention_specs(cfg: ModelConfig) -> Specs:
    p = {
        "wq": ("embed", "q_proj"),
        "wk": ("embed", "kv_proj"),
        "wv": ("embed", "kv_proj"),
        "wo": ("q_proj", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = ("q_proj",)
        p["bk"] = ("kv_proj",)
        p["bv"] = ("kv_proj",)
    if cfg.qk_norm:
        p["q_norm"] = (None,)
        p["k_norm"] = (None,)
    return p


def _project_qkv(p: Params, x: jnp.ndarray, kv_src: jnp.ndarray,
                 cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    dt = cfg.compute_dtype
    B, S = x.shape[0], x.shape[1]
    Skv = kv_src.shape[1]
    q = x @ p["wq"].astype(dt)
    k = kv_src @ p["wk"].astype(dt)
    v = kv_src @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, S, cfg.n_heads, cfg.dh)
    k = k.reshape(B, Skv, cfg.n_kv_heads, cfg.dh)
    v = v.reshape(B, Skv, cfg.n_kv_heads, cfg.dh)
    if cfg.qk_norm:
        q = rms_norm_head(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm_head(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _mask_bias(sq: int, skv: int, causal: bool, window: int,
               offset: int = 0) -> Optional[jnp.ndarray]:
    """(sq, skv) additive fp32 mask; None if fully visible.

    ``offset`` = absolute position of query 0 minus position of key 0
    (decode: q_pos - 0).
    """
    if not causal and window <= 0:
        return None
    qpos = jnp.arange(sq)[:, None] + offset
    kpos = jnp.arange(skv)[None, :]
    ok = jnp.ones((sq, skv), bool)
    if causal:
        ok = ok & (kpos <= qpos)
    if window > 0:
        ok = ok & (kpos > qpos - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
          bias: Optional[jnp.ndarray], cfg: ModelConfig) -> jnp.ndarray:
    """Reference dot-product attention with GQA head grouping.

    q: (B, Sq, H, dh), k/v: (B, Skv, K, dh) -> (B, Sq, H, dh).
    GQA is realized by repeating K/V up to H heads rather than splitting q
    into (K, G): the repeat keeps the head axis intact, which is what lets
    GSPMD shard the O(S^2) score tensor over the mesh ``model`` axis (a
    (K,G) reshape of a sharded head axis defeats propagation and replicates
    the scores — measured 54 GiB/device on smollm train_4k before this).
    Softmax in fp32 for numerics; contractions stay in compute dtype so the
    MXU path (and cost analysis) reflect bf16 math.
    """
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    if K != H:
        reps = H // K
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k) / jnp.sqrt(dh).astype(q.dtype)
    scores = scores.astype(jnp.float32)
    if bias is not None:
        scores = scores + bias
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v)
    return out


def apply_attention(
    p: Params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    positions: Optional[jnp.ndarray] = None,
    kv_src: Optional[jnp.ndarray] = None,     # cross-attention source
    causal: Optional[bool] = None,
    window: int = 0,
) -> jnp.ndarray:
    """Full-sequence attention (train / prefill)."""
    dt = cfg.compute_dtype
    x = x.astype(dt)
    cross = kv_src is not None
    kv_src = x if kv_src is None else kv_src.astype(dt)
    causal = (cfg.causal and not cross) if causal is None else causal
    B, S = x.shape[0], x.shape[1]
    q, k, v = _project_qkv(p, x, kv_src, cfg)
    if cfg.pos_emb == "rope" and not cross:
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # "attn_seq" is the SP-fallback axis: mapped to the model axis only when
    # heads can't shard it (see dryrun._rules_for), so head-TP archs keep
    # collective-free attention and odd-head archs still shard the O(S^2)
    # scores over seq.
    from repro.distributed.sharding import shard_hint
    q = shard_hint(q, ("batch", "attn_seq", "heads", None))
    k = shard_hint(k, ("batch", "attn_seq", "kv_heads", None))
    v = shard_hint(v, ("batch", "attn_seq", "kv_heads", None))
    if cfg.use_flash and not cross and q.shape[1] == k.shape[1]:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal, window=window)
    elif (cfg.attn_impl == "chunked" and not cross
          and q.shape[1] == k.shape[1] and q.shape[1] > cfg.attn_block_q):
        out = _blockwise_sdpa(q, k, v, cfg, causal, window)
    else:
        bias = _mask_bias(q.shape[1], k.shape[1], causal, window)
        out = _sdpa(q, k, v, bias, cfg)
    out = out.reshape(B, S, cfg.q_dim)
    return out @ p["wo"].astype(dt)


def _blockwise_sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    cfg: ModelConfig, causal: bool, window: int) -> jnp.ndarray:
    """Blockwise (chunked) attention: scan over q blocks, O(S·bq) memory.

    The pure-XLA counterpart of the Pallas flash kernel: never materializes
    the (S × S) score tensor — each scan step computes one q-block's scores
    against all keys (bq × S), masks by absolute block position, softmaxes
    and contracts.  XLA reuses the step buffer across iterations, so the
    peak transient drops by S/bq (32× at prefill_32k with bq=1024).  Used
    when ``cfg.attn_impl == "chunked"``; the §Perf memory lever.
    """
    from repro.distributed.sharding import shard_hint
    B, S, H, dh = q.shape
    K = k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    bq = cfg.attn_block_q
    Sp = ((S + bq - 1) // bq) * bq
    if Sp != S:
        # pad query rows (their outputs are sliced off; keys keep length S)
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    nb = Sp // bq
    qb = jnp.moveaxis(q.reshape(B, nb, bq, H, dh), 1, 0)   # (nb,B,bq,H,dh)
    scale = jnp.sqrt(dh).astype(q.dtype)
    kpos = jnp.arange(S)[None, :]

    def body(_, inp):
        qi, i = inp
        # re-assert the SP sharding inside the scan (slicing the leading
        # block axis would otherwise leave the block replicated)
        qi = shard_hint(qi, ("batch", "attn_seq", "heads", None))
        s = jnp.einsum("bqhd,bshd->bhqs", qi, k) / scale
        s = s.astype(jnp.float32)
        qpos = i * bq + jnp.arange(bq)[:, None]
        ok = jnp.ones((bq, S), bool)
        if causal:
            ok = ok & (kpos <= qpos)
        if window > 0:
            ok = ok & (kpos > qpos - window)
        s = jnp.where(ok[None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqs,bshd->bqhd", w, v)
        return None, o

    _, ob = jax.lax.scan(body, None, (qb, jnp.arange(nb)))
    out = jnp.moveaxis(ob, 0, 1).reshape(B, Sp, H, dh)
    return out[:, :S]


# --- decode with KV cache -------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: Optional[int] = None,
                  dtype=None) -> Dict[str, jnp.ndarray]:
    L = cfg.n_layers if n_layers is None else n_layers
    dt = dtype or cfg.compute_dtype
    shape = (L, batch, max_len, cfg.n_kv_heads, cfg.dh)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def kv_cache_specs() -> Specs:
    return {"k": ("layers", "batch", "kv_seq", "kv_heads", None),
            "v": ("layers", "batch", "kv_seq", "kv_heads", None)}


def decode_attention(
    p: Params,
    x: jnp.ndarray,                 # (B, 1, D) current token activations
    layer_cache: Dict[str, jnp.ndarray],   # k/v (B, S_max, K, dh) this layer
    pos: jnp.ndarray,               # scalar int32: write/read position
    cfg: ModelConfig,
    *,
    window: int = 0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token decode: update cache at ``pos``, attend over prefix."""
    dt = cfg.compute_dtype
    x = x.astype(dt)
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(p, x, x, cfg)
    if cfg.pos_emb == "rope":
        pos_arr = jnp.full((B, 1), pos, jnp.int32)
        q = apply_rope(q, pos_arr, cfg.rope_theta)
        k_new = apply_rope(k_new, pos_arr, cfg.rope_theta)
    # cache write as an ELEMENTWISE select on the seq axis: unlike
    # dynamic-update-slice at a traced position, this partitions trivially
    # when kv_seq is sharded over the mesh (GSPMD was measured to all-gather
    # the whole cache for the d-u-s form: +7.5 GiB/dev on qwen2-7b decode).
    S = layer_cache["k"].shape[1]
    at_pos = (jnp.arange(S) == pos)[None, :, None, None]
    k = jnp.where(at_pos, k_new, layer_cache["k"])
    v = jnp.where(at_pos, v_new, layer_cache["v"])
    kpos = jnp.arange(S)
    ok = kpos <= pos
    if window > 0:
        ok = ok & (kpos > pos - window)
    bias = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)[None, :]
    out = _sdpa_grouped(q, k, v, bias, cfg)
    out = out.reshape(B, 1, cfg.q_dim) @ p["wo"].astype(dt)
    return out, {"k": k, "v": v}


def prefill_attention(
    p: Params,
    x: jnp.ndarray,                 # (B, S, D) the prompt's activations
    layer_cache: Dict[str, jnp.ndarray],   # k/v (B, S_max, K, dh) this layer
    cfg: ModelConfig,
    *,
    window: int = 0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Whole-prompt attention from position 0: causal over the S new keys,
    whose rope'd K/V are written to cache rows [0, S)."""
    dt = cfg.compute_dtype
    x = x.astype(dt)
    B, S = x.shape[0], x.shape[1]
    q, k, v = _project_qkv(p, x, x, cfg)
    if cfg.pos_emb == "rope":
        positions = jnp.arange(S)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # the write is at the static offset 0, so it is the same elementwise
    # select on the seq axis as decode_attention's and partitions as
    # trivially when kv_seq is sharded over the mesh
    S_max = layer_cache["k"].shape[1]
    fresh = (jnp.arange(S_max) < S)[None, :, None, None]
    rows = ((0, 0), (0, S_max - S), (0, 0), (0, 0))
    new = {n: jnp.where(fresh, jnp.pad(t.astype(layer_cache[n].dtype), rows),
                        layer_cache[n])
           for n, t in (("k", k), ("v", v))}
    out = _sdpa_grouped(q, k, v, _mask_bias(S, S, True, window), cfg)
    out = out.reshape(B, S, cfg.q_dim) @ p["wo"].astype(dt)
    return out, new


def _sdpa_grouped(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  bias: Optional[jnp.ndarray], cfg: ModelConfig
                  ) -> jnp.ndarray:
    """Decode-path attention in grouped (K, G) form — NO kv repetition.

    The repeat-based ``_sdpa`` is right for training (keeps the head axis
    intact for TP score sharding), but at decode the KV cache is SEQ-sharded
    and repeating K/V up to H heads makes GSPMD reconcile head-sharding vs
    seq-sharding by all-gathering the expanded cache (measured 64 GB/step on
    minitron-8b decode_32k).  Contracting against the grouped cache keeps
    every score/output computation local to the seq shards; only the tiny
    (B, 1, H, dh) query is replicated.
    """
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / jnp.sqrt(dh).astype(q.dtype)
    scores = scores.astype(jnp.float32)
    if bias is not None:
        scores = scores + bias
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return out.reshape(B, Sq, H, dh)
