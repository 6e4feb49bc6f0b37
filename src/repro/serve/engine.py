"""Serving: prefill + batched single-token decode (``serve_step``).

``build_serve_step(cfg)`` returns the one-token decode function the
``decode_*`` / ``long_*`` dry-run cells lower: given the params, the KV
cache / recurrent state for a context of ``seq_len`` tokens, the current
token batch and position, produce logits + the updated cache.  Greedy
sampling helper included for the runnable demos.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec as encdec_mod
from repro.models import transformer as lm_mod
from repro.models import vlm as vlm_mod
from repro.models.common import ModelConfig
from repro.obs import trace
from repro.obs.metrics import REGISTRY
from repro.measure.timers import block_until_ready


def build_serve_step(cfg: ModelConfig) -> Callable:
    if cfg.family == "encdec":
        def serve_step(params, tokens, cache, pos):
            logits, cache = encdec_mod.decode_step(params, tokens, cache,
                                                   pos, cfg)
            return logits, cache
    elif cfg.family == "vlm":
        def serve_step(params, tokens, cache, pos):
            return vlm_mod.decode_step(params, tokens, cache, pos, cfg)
    else:
        def serve_step(params, tokens, cache, pos):
            return lm_mod.decode_step(params, tokens, cache, pos, cfg)
    return serve_step


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int,
               frames: jnp.ndarray | None = None):
    if cfg.family == "encdec":
        assert frames is not None
        return encdec_mod.init_encdec_cache(params, frames, batch, max_len, cfg)
    if cfg.family == "vlm":
        return vlm_mod.init_cache(cfg, batch, max_len)
    return lm_mod.init_cache(cfg, batch, max_len)


def greedy_generate(params, cfg: ModelConfig, prompt: jnp.ndarray,
                    steps: int, max_len: int,
                    frames: jnp.ndarray | None = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Prefill token-by-token then greedy-decode ``steps`` tokens.

    Returns the tokens (B, S + steps) and the decode logits
    (B, S + steps - 1, V), whose column t was computed at position t.
    """
    B, S = prompt.shape
    serve_step = jax.jit(build_serve_step(cfg))
    cache = init_cache(params, cfg, B, max_len, frames=frames)
    tok = prompt[:, :1]
    out = [tok]
    seen = []
    step_hist = REGISTRY.histogram("serve.step_seconds")
    with trace.span("serve.generate", arch=cfg.name, batch=B,
                    prompt_len=S, steps=steps):
        for t in range(S + steps - 1):
            # per-token decode latency: block inside the timed region so
            # async dispatch is charged for the work, not the dispatch
            with step_hist.time():
                logits, cache = serve_step(params, tok, cache, jnp.int32(t))
                block_until_ready(logits)
            seen.append(logits[:, -1])
            if t + 1 < S:
                tok = prompt[:, t + 1:t + 2]
            else:
                tok = jnp.argmax(logits[:, -1:], axis=-1).astype(prompt.dtype)
            out.append(tok)
    return jnp.concatenate(out, axis=1), jnp.stack(seen, axis=1)
