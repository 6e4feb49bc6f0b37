"""Train-step construction: loss, grad, optimizer update — family-aware.

``build_train_step(cfg, optimizer)`` returns a pure function
``train_step(state, batch) -> (state, metrics)`` suitable for ``jax.jit``
with in/out shardings.  Batches are dicts of arrays (see
``repro.launch.specs.input_specs`` for the exact keys per family).

Gradient sync is implicit in the SPMD formulation: the loss is a global mean
over the batch axis, so ∂loss/∂params materializes as reduce-scatter /
all-reduce over the DP mesh axes in the lowered HLO — exactly the traffic the
paper's B_N term accounts for.  Optional hooks:

  * microbatching (gradient accumulation over ``n_micro`` scan steps),
  * gradient compression (error-feedback int8, ``repro.optim.compression``)
    applied at the accumulation boundary,
  * MoE aux-loss folding.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec as encdec_mod
from repro.models import mlp_dlrm as mlp_mod
from repro.models import transformer as lm_mod
from repro.models import vlm as vlm_mod
from repro.models.common import ModelConfig, softmax_cross_entropy
from repro.obs import trace


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray
    rng: jnp.ndarray


def make_loss_fn(cfg: ModelConfig) -> Callable:
    """Loss over one (micro)batch, returns (loss, metrics-dict)."""

    def lm_loss(params, batch):
        logits, aux = lm_mod.forward(params, batch["tokens"], cfg)
        ce = softmax_cross_entropy(logits, batch["labels"])
        loss = ce + cfg.router_aux_weight * aux
        return loss, {"ce": ce, "aux": aux}

    def encdec_loss(params, batch):
        logits, aux = encdec_mod.forward(params, batch["tokens"],
                                         batch["frames"], cfg)
        ce = softmax_cross_entropy(logits, batch["labels"])
        return ce, {"ce": ce, "aux": aux}

    def vlm_loss(params, batch):
        logits, aux = vlm_mod.forward(params, batch["tokens"],
                                      batch["patches"], cfg)
        nv = cfg.visual_tokens
        ce = softmax_cross_entropy(logits[:, nv:], batch["labels"])
        loss = ce + cfg.router_aux_weight * aux
        return loss, {"ce": ce, "aux": aux}

    def mlp_loss(params, batch):
        loss = mlp_mod.loss_fn(params, batch["features"], batch["click"], cfg)
        return loss, {"ce": loss, "aux": jnp.float32(0.0)}

    return {"encdec": encdec_loss, "vlm": vlm_loss,
            "mlp": mlp_loss}.get(cfg.family, lm_loss)


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    n_micro: int = 1                  # gradient-accumulation microbatches
    compression: Optional[Any] = None  # repro.optim.compression.Compressor


def build_train_step(cfg: ModelConfig, optimizer,
                     # shared default instance is safe: the dataclass is
                     # frozen, so no caller can mutate it for everyone
                     ts_cfg: TrainStepConfig = TrainStepConfig()):
    loss_fn = make_loss_fn(cfg)

    def forward(params, batch):
        # the scope names the forward ops in the HLO's metadata; their
        # gradients carry transpose(jvp(train.forward))
        with jax.named_scope("train.forward"):
            return loss_fn(params, batch)

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            forward, has_aux=True)(params, batch)
        return loss, metrics, grads

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray]
                   ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        params = state.params
        if ts_cfg.n_micro > 1:
            def micro(carry, mb):
                acc, loss_acc = carry
                loss, _, g = grads_of(params, mb)
                acc = jax.tree.map(lambda a, b: a + b, acc, g)
                return (acc, loss_acc + loss), None

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            mbs = jax.tree.map(
                lambda x: x.reshape(ts_cfg.n_micro,
                                    x.shape[0] // ts_cfg.n_micro,
                                    *x.shape[1:]), batch)
            (grads, loss), _ = jax.lax.scan(
                micro, (zero, jnp.float32(0.0)), mbs)
            grads = jax.tree.map(lambda g: g / ts_cfg.n_micro, grads)
            loss = loss / ts_cfg.n_micro
            metrics = {"ce": loss, "aux": jnp.float32(0.0)}
        else:
            loss, metrics, grads = grads_of(params, batch)

        if ts_cfg.compression is not None:
            grads = ts_cfg.compression.round_trip(grads)

        from repro.optim.optimizer import apply_updates, global_norm
        with jax.named_scope("train.optimizer"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  params)
            params = apply_updates(params, updates)
        metrics = dict(metrics, loss=loss, grad_norm=global_norm(grads),
                       step=state.step)
        new_state = TrainState(params=params, opt_state=opt_state,
                               step=state.step + 1, rng=state.rng)
        return new_state, metrics

    return train_step


def init_params(key, cfg: ModelConfig):
    """The model's parameters alone (serving needs no optimizer state)."""
    if cfg.family == "encdec":
        return encdec_mod.init_encdec(key, cfg)
    if cfg.family == "vlm":
        return vlm_mod.init_vlm(key, cfg)
    if cfg.family == "mlp":
        return mlp_mod.init_mlp(key, cfg)
    return lm_mod.init_lm(key, cfg)


def init_train_state(key, cfg: ModelConfig, optimizer) -> TrainState:
    with trace.span("train.init_state", arch=cfg.name, family=cfg.family):
        params = init_params(key, cfg)
        return TrainState(params=params, opt_state=optimizer.init(params),
                          step=jnp.zeros((), jnp.int32), rng=key)


def model_param_specs(cfg: ModelConfig):
    if cfg.family == "encdec":
        return encdec_mod.encdec_specs(cfg)
    if cfg.family == "vlm":
        return vlm_mod.vlm_specs(cfg)
    if cfg.family == "mlp":
        return mlp_mod.mlp_specs(cfg)
    return lm_mod.lm_specs(cfg)
