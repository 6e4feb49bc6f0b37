"""Grouped-query attention: init, full-sequence apply, whole-prompt prefill
and single-token decode into a KV cache.

Supports: GQA (kv heads < q heads), optional QKV bias (Qwen2), per-head QK
RMS-norm (Qwen3), RoPE / learned / no positions, causal or bidirectional,
sliding-window masks (Hymba local layers), cross-attention (Whisper decoder),
and a Pallas flash-attention fast path (``cfg.use_flash``).  Multi-head
latent attention (DeepSeek-V2/V3, ``cfg.kv_lora_rank``) is at the end.

Shapes: activations (B, S, D); per-head tensors (B, S, H, dh).
KV cache for decode: dict(k=(B, S_max, Hkv, dh), v=..., pos scalar index);
a latent cache: dict(c_kv=(B, S_max, C), k_pe=(B, S_max, dr)).
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


# --- multi-head latent attention (MLA) ----------------------------------------
# K and V come from a latent c_kv = RMSNorm(x W_kv_a[:, :C]) of C values a
# token, expanded per head by W_kv_b into k_nope (dn) and v (dv); a shared
# rope'd key k_pe (dr) comes from the rest of W_kv_a.  Queries have no
# low-rank step: x W_q gives per head q_nope (dn) and q_pe (dr), and the
# scores are q_nope.k_nope + q_pe.k_pe over sqrt(dn + dr).  The cache holds
# only c_kv and k_pe, C + dr values a token a layer.

def init_mla(key, cfg: ModelConfig) -> Params:
    H, C = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {"wq": dense_init(ks[0], cfg.d_model, H * (dn + dr)),
            "wkv_a": dense_init(ks[1], cfg.d_model, C + dr),
            "kv_norm": ones((C,)),
            "wkv_b": dense_init(ks[2], C, H * (dn + dv)),
            "wo": dense_init(ks[3], H * dv, cfg.d_model)}


def mla_specs(cfg: ModelConfig) -> Specs:
    return {"wq": ("embed", "q_proj"), "wkv_a": ("embed", None),
            "kv_norm": (None,), "wkv_b": (None, "q_proj"),
            "wo": ("q_proj", "embed")}


def init_latent_cache(cfg: ModelConfig, batch: int, max_len: int,
                      n_layers: int, dtype=None) -> Dict[str, jnp.ndarray]:
    dt = dtype or cfg.compute_dtype
    return {"c_kv": jnp.zeros((n_layers, batch, max_len, cfg.kv_lora_rank), dt),
            "k_pe": jnp.zeros((n_layers, batch, max_len, cfg.qk_rope_head_dim),
                              dt)}


def latent_cache_specs() -> Specs:
    return {"c_kv": ("layers", "batch", "kv_seq", None),
            "k_pe": ("layers", "batch", "kv_seq", None)}


def _mla_project(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                 cfg: ModelConfig):
    """(q_nope, rope'd q_pe) per head, the normalised latent c_kv and the
    rope'd shared key k_pe, for the tokens of x at ``positions``."""
    dt = cfg.compute_dtype
    B, S = x.shape[0], x.shape[1]
    C, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = (x @ p["wq"].astype(dt)).reshape(B, S, cfg.n_heads, -1)
    q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    kv = x @ p["wkv_a"].astype(dt)
    c_kv = rms_norm_head(kv[..., :C], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(kv[:, :, None, C:], positions, cfg.rope_theta)[:, :, 0]
    return q[..., :dn], q_pe, c_kv, k_pe


def _mla_softmax(s_nope: jnp.ndarray, s_pe: jnp.ndarray, bias, cfg: ModelConfig
                 ) -> jnp.ndarray:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    scores = (s_nope + s_pe) * scale
    if bias is not None:
        scores = scores + bias
    return jax.nn.softmax(scores, axis=-1).astype(cfg.compute_dtype)


def _mla_expanded(p: Params, q_nope, q_pe, c_kv, k_pe, bias,
                  cfg: ModelConfig) -> jnp.ndarray:
    """Attention with K and V expanded per head from the latent."""
    dt, f32 = cfg.compute_dtype, jnp.float32
    B, Sq, H = q_nope.shape[:3]
    dn = cfg.qk_nope_head_dim
    kv = (c_kv @ p["wkv_b"].astype(dt)).reshape(B, c_kv.shape[1], H, -1)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    w = _mla_softmax(
        jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope, preferred_element_type=f32),
        jnp.einsum("bqhr,bsr->bhqs", q_pe, k_pe, preferred_element_type=f32),
        bias, cfg)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v).reshape(B, Sq, -1)
    return out @ p["wo"].astype(dt)


def _mla_absorbed(p: Params, q_nope, q_pe, c_kv, k_pe, bias,
                  cfg: ModelConfig) -> jnp.ndarray:
    """The same attention read from the latent alone: W_kv_b's key half is
    folded into the query and its value half into the output."""
    dt, f32 = cfg.compute_dtype, jnp.float32
    B, Sq, H = q_nope.shape[:3]
    dn = cfg.qk_nope_head_dim
    w_kv_b = p["wkv_b"].astype(dt).reshape(cfg.kv_lora_rank, H, -1)
    q_lat = jnp.einsum("bqhd,chd->bqhc", q_nope, w_kv_b[..., :dn])
    w = _mla_softmax(
        jnp.einsum("bqhc,bsc->bhqs", q_lat, c_kv, preferred_element_type=f32),
        jnp.einsum("bqhr,bsr->bhqs", q_pe, k_pe, preferred_element_type=f32),
        bias, cfg)
    o_lat = jnp.einsum("bhqs,bsc->bqhc", w, c_kv)
    out = jnp.einsum("bqhc,chd->bqhd", o_lat, w_kv_b[..., dn:])
    return out.reshape(B, Sq, -1) @ p["wo"].astype(dt)


def _mla_causal(p: Params, x: jnp.ndarray, cfg: ModelConfig):
    """Causal latent attention over x from position 0, expanded: (out,
    c_kv, k_pe)."""
    x = x.astype(cfg.compute_dtype)
    S = x.shape[1]
    q_nope, q_pe, c_kv, k_pe = _mla_project(p, x, jnp.arange(S)[None, :], cfg)
    out = _mla_expanded(p, q_nope, q_pe, c_kv, k_pe,
                        _mask_bias(S, S, True, 0), cfg)
    return out, c_kv, k_pe


def apply_mla(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Full-sequence causal latent attention (train)."""
    with jax.named_scope("mla"):
        return _mla_causal(p, x, cfg)[0]


def prefill_mla(p: Params, x: jnp.ndarray, layer_cache: Dict[str, jnp.ndarray],
                cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Whole-prompt latent attention from position 0, expanded; c_kv and
    k_pe written to cache rows [0, S) by an elementwise select, as
    ``prefill_attention`` writes K/V."""
    with jax.named_scope("mla"):
        out, c_kv, k_pe = _mla_causal(p, x, cfg)
        S, S_max = x.shape[1], layer_cache["c_kv"].shape[1]
        fresh = (jnp.arange(S_max) < S)[None, :, None]
        rows = ((0, 0), (0, S_max - S), (0, 0))
        new = {n: jnp.where(fresh, jnp.pad(t.astype(layer_cache[n].dtype), rows),
                            layer_cache[n])
               for n, t in (("c_kv", c_kv), ("k_pe", k_pe))}
    return out, new


def decode_mla(p: Params, x: jnp.ndarray, layer_cache: Dict[str, jnp.ndarray],
               pos: jnp.ndarray, cfg: ModelConfig
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token latent attention, absorbed: write c_kv and k_pe at ``pos``
    (the elementwise select of ``decode_attention``) and attend over the
    latent cache of positions <= pos."""
    x = x.astype(cfg.compute_dtype)
    B = x.shape[0]
    with jax.named_scope("mla"):
        q_nope, q_pe, c_new, k_new = _mla_project(
            p, x, jnp.full((B, 1), pos, jnp.int32), cfg)
        S = layer_cache["c_kv"].shape[1]
        at_pos = (jnp.arange(S) == pos)[None, :, None]
        new = {"c_kv": jnp.where(at_pos, c_new, layer_cache["c_kv"]),
               "k_pe": jnp.where(at_pos, k_new, layer_cache["k_pe"])}
        bias = jnp.where(jnp.arange(S) <= pos, 0.0,
                         NEG_INF).astype(jnp.float32)[None, :]
        out = _mla_absorbed(p, q_nope, q_pe, new["c_kv"], new["k_pe"], bias,
                            cfg)
    return out, new
