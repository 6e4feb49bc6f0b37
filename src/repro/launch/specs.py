"""ShapeDtypeStruct stand-ins for every model input — the dry-run contract.

``input_specs(cfg, shape)`` returns the exact batch pytree a train/serve step
consumes, as ShapeDtypeStructs (weak-type-correct, shardable, zero device
allocation).  ``state_specs`` / ``cache_specs`` do the same for the train
state and the decode cache via ``jax.eval_shape`` over the real constructors,
so dry-run shapes can never drift from what the runtime would build.

Also home to the MODEL_FLOPS accounting (6·N·D dense / 6·N_active·D MoE)
used by the §Roofline useful-flops ratio.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.shapes import ShapeSpec
from repro.distributed.sharding import is_logical_axes, specs_to_shardings
from repro.models.common import ModelConfig
from repro.serve import engine as serve_engine
from repro.train.loop import (TrainState, init_params, init_train_state,
                              model_param_specs)


def _sds(shape, dtype, mesh: Optional[Mesh], axes) -> jax.ShapeDtypeStruct:
    leaf = jax.ShapeDtypeStruct(shape, dtype)
    return leaf if mesh is None else attach(leaf, axes, mesh)


def input_specs(cfg: ModelConfig, shape: ShapeSpec,
                mesh: Optional[Mesh] = None) -> Dict[str, Any]:
    """Training/prefill batch stand-ins keyed by family."""
    B, S = shape.global_batch, shape.seq_len
    tok = lambda: _sds((B, S), jnp.int32, mesh, ("batch", "seq"))
    if cfg.family == "mlp":
        return {"features": _sds((B, cfg.mlp_widths[0]), jnp.float32, mesh,
                                 ("batch", None)),
                "click": _sds((B,), jnp.float32, mesh, ("batch",))}
    out = {"tokens": tok(), "labels": tok()}
    if cfg.family == "encdec":
        out["frames"] = _sds((B, cfg.encoder_seq, cfg.d_model), jnp.float32,
                             mesh, ("batch", "seq", "embed"))
    if cfg.family == "vlm":
        out["patches"] = _sds((B, cfg.visual_tokens, cfg.visual_width),
                              jnp.float32, mesh, ("batch", "seq", None))
    return out


def decode_input_specs(cfg: ModelConfig, shape: ShapeSpec,
                       mesh: Optional[Mesh] = None) -> Dict[str, Any]:
    B = shape.global_batch
    return {"tokens": _sds((B, 1), jnp.int32, mesh, ("batch", None)),
            "pos": _sds((), jnp.int32, mesh, ())}


# --- eval_shape-derived pytrees ---------------------------------------------------


def abstract_params(cfg: ModelConfig):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(lambda k: init_params(k, cfg), key)


def abstract_train_state(cfg: ModelConfig, optimizer) -> TrainState:
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(
        lambda k: init_train_state(k, cfg, optimizer), key)


def abstract_cache(cfg: ModelConfig, params_abs, shape: ShapeSpec):
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        frames = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model),
                                      jnp.float32)
        return jax.eval_shape(
            lambda p, f: serve_engine.init_cache(p, cfg, B, S, frames=f),
            params_abs, frames)
    return jax.eval_shape(
        lambda: serve_engine.init_cache(None, cfg, B, S))


# --- sharding attachment ----------------------------------------------------------

def attach(tree_abs, specs, mesh: Mesh):
    """Zip a ShapeDtypeStruct pytree with a logical-spec pytree.

    Mesh axes that don't divide a dimension are dropped per-dim (odd vocab
    sizes, 60-expert MoE, 9-head attention are the norm in the assigned
    configs; dropping to replication is the standard fallback).
    """
    return jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        tree_abs, specs_to_shardings(specs, tree_abs, mesh))


def _augment_data_axis(pspecs):
    """ZeRO-style: additionally shard the first free dim over "dp_shard".

    "dp_shard" is a logical alias the launcher maps to the data axis; dims
    that don't divide fall back to replication inside ``attach``.  Tensors
    with no free dim (MoE expert weights: experts × embed × expert_ffn)
    donate their "embed" dim — embed is replicated by the activation rules,
    so DP-sharding it on the *storage* side is always safe.
    """

    def one(axes):
        axes = tuple(axes)
        for i, a in enumerate(axes):
            if a is None:
                return axes[:i] + ("dp_shard",) + axes[i + 1:]
        for i, a in enumerate(axes):
            if a == "embed":
                return axes[:i] + ("dp_shard",) + axes[i + 1:]
        return axes

    return jax.tree.map(one, pspecs,
                        is_leaf=is_logical_axes)


def train_state_specs(cfg: ModelConfig, zero1: bool = True,
                      fsdp: bool = False, optimizer=None) -> TrainState:
    """Logical-axis pytree matching TrainState (params + optimizer state).

    ``optimizer`` (default AdamW) decides the state's fields: each one
    shaped like the params (AdamW mu/nu, SGD momentum) takes their specs,
    any other (step counters) is replicated.
    ``zero1`` (baseline default): optimizer moments additionally sharded
    over the DP axis — free memory, GSPMD turns the gradient all-reduce
    into reduce-scatter (+ all-gather of the final update).
    ``fsdp``: the parameters themselves also DP-sharded (ZeRO-3-style),
    needed for the biggest assigned archs on 16 GiB chips.
    """
    from repro.optim.optimizer import AdamW
    pspecs = model_param_specs(cfg)
    popt = _augment_data_axis(pspecs) if (zero1 or fsdp) else pspecs
    pmain = _augment_data_axis(pspecs) if fsdp else pspecs
    params_abs = abstract_params(cfg)
    opt_abs = jax.eval_shape((optimizer or AdamW()).init, params_abs)
    like_params = jax.tree.structure(params_abs)
    opt_specs = type(opt_abs)(*(
        popt if jax.tree.structure(field) == like_params
        else jax.tree.map(lambda _: (), field) for field in opt_abs))
    return TrainState(params=pmain, opt_state=opt_specs,
                      step=(), rng=(None,))


def cache_logical_specs(cfg: ModelConfig, cache_abs) -> Any:
    """Logical axes for the decode cache: rank-driven defaults.

    KV buffers (L,B,S,K,dh) or (B,S,K,dh) shard batch over DP and expose
    both "kv_seq" and "head_dim" axes; the serve rules map kv_seq -> model
    (SP-decode).  The cache write is an elementwise select at the decode
    position — a dynamic-update-slice on the sharded axis would make GSPMD
    all-gather the whole cache into temps (measured +7.5 GiB/dev on
    qwen2-7b decode_32k).

    Recurrent states (B,H,dk,dv)/(B,H,dk)/(B,D) -> batch (+ heads).
    """

    def axes_for(leaf):
        r = len(leaf.shape)
        if r == 5:
            return ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
        if r == 4:
            # could be (B,S,K,dh) kv or (B,H,dk,dv) state: kv if dim1 large
            if leaf.shape[1] > 64:
                return ("batch", "kv_seq", "kv_heads", "head_dim")
            return ("batch", "heads", None, None)
        if r == 3:
            return ("batch", "heads", None)
        if r == 2:
            return ("batch", None)
        return tuple([None] * r)

    return jax.tree.map(axes_for, cache_abs)


# --- MODEL_FLOPS accounting ---------------------------------------------------------

@functools.lru_cache(maxsize=None)
def param_counts(cfg: ModelConfig) -> Tuple[float, float]:
    """(total, active-per-token) parameter counts from abstract shapes.

    Active excludes the embedding gather but includes the LM head matmul;
    MoE expert tensors count at top_k/E (+ shared experts fully).
    Memoized on the (frozen, hashable) config: the ``jax.eval_shape``
    trace behind ``abstract_params`` runs once per model per process, not
    once per ``plan()``/``model_flops`` call.
    """
    params = abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    total = 0.0
    active = 0.0
    for path, leaf in flat:
        n = 1.0
        for d in leaf.shape:
            n *= d
        keys = "/".join(str(getattr(p, 'key', getattr(p, 'idx', p)))
                        for p in path)
        total += n
        if "embed" in keys and "lm_head" not in keys and "pos" not in keys:
            if cfg.tie_embeddings and not cfg.family == "mlp":
                active += n       # tied head matmul
            continue              # gather costs ~0 flops
        if "pos_embed" in keys or "dec_pos" in keys:
            continue
        if any(k in keys for k in ("w_gate", "w_up", "w_down")) and \
                "moe" in keys and "shared" not in keys:
            # the tensors hold E_pad = max(n_experts, pad_experts_to)
            # experts (init_moe pads for EP divisibility), so the active
            # fraction is top_k over the padded count actually allocated —
            # dividing by the true n_experts would inflate active FLOPs by
            # E_pad/E (padding experts never receive routing mass)
            active += n * cfg.moe_top_k / max(cfg.n_experts,
                                              cfg.pad_experts_to, 1)
            continue
        active += n
    return total, active


@functools.lru_cache(maxsize=None)
def expert_param_counts(cfg: ModelConfig) -> Tuple[float, float]:
    """(total, active) parameters of the *routed* expert tensors only.

    The slice of :func:`param_counts` that an expert-parallel axis shards:
    routed ``w_gate``/``w_up``/``w_down`` stacks at their padded
    ``E_pad = max(n_experts, pad_experts_to)`` allocation, excluding the
    router and shared experts (those replicate over ep).  Non-MoE configs
    return ``(0.0, 0.0)``.  Same memoization contract as
    :func:`param_counts`.
    """
    if cfg.n_experts <= 0:
        return 0.0, 0.0
    params = abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    total = 0.0
    for path, leaf in flat:
        n = 1.0
        for d in leaf.shape:
            n *= d
        keys = "/".join(str(getattr(p, 'key', getattr(p, 'idx', p)))
                        for p in path)
        if any(k in keys for k in ("w_gate", "w_up", "w_down")) and \
                "moe" in keys and "shared" not in keys:
            total += n
    active = total * cfg.moe_top_k / max(cfg.n_experts,
                                         cfg.pad_experts_to, 1)
    return total, active


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """6·N_active·tokens for train; 2·N_active·tokens for serve decode."""
    _, active = param_counts(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active * tokens
    return 2.0 * active * shape.global_batch  # decode: one token per seq
