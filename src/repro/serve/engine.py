"""Serving: prefill + batched single-token decode (``serve_step``).

``build_serve_step(cfg)`` returns the one-token decode function the
``decode_*`` / ``long_*`` dry-run cells lower: given the params, the KV
cache / recurrent state for a context of ``seq_len`` tokens, the current
token batch and position, produce logits + the updated cache.  Greedy
sampling helper included for the runnable demos.
"""
from __future__ import annotations

import itertools
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
        decode = encdec_mod.decode_step
    elif cfg.family == "vlm":
        decode = vlm_mod.decode_step
    else:
        decode = lm_mod.decode_step

    def serve_step(params, tokens, cache, pos):
        # under jax.jit this body runs only while JAX traces it, so each
        # span marks one retrace and lasts as long as the Python trace
        with trace.span("serve.trace_step"):
            return decode(params, tokens, cache, pos, cfg)
    return serve_step


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int,
               frames: jnp.ndarray | None = None):
    if cfg.family == "encdec":
        assert frames is not None
        return encdec_mod.init_encdec_cache(params, frames, batch, max_len, cfg)
    if cfg.family == "vlm":
        return vlm_mod.init_cache(cfg, batch, max_len)
    return lm_mod.init_cache(cfg, batch, max_len)


#: numbers the ``serve.generate`` spans of this process
_CALLS = itertools.count(1)

#: the dense model's whole-prompt prefill, one trace per config and shape
_prefill = jax.jit(lm_mod.prefill, static_argnames="cfg")


def greedy_generate(params, cfg: ModelConfig, prompt: jnp.ndarray,
                    steps: int, max_len: int,
                    frames: jnp.ndarray | None = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Prefill the prompt, then greedy-decode ``steps`` tokens.

    A dense model prefills the whole prompt in one call
    (``transformer.prefill``); the other families carry recurrent state,
    route tokens or decode through steps of their own, and prefill token
    by token.  Returns the tokens (B, S + steps) and the logits
    (B, S + steps - 1, V), whose column t was computed at position t.

    Spans (``repro.obs.trace``): ``serve.generate`` over the whole call;
    ``serve.prefill`` over positions 0..S-1, ending when the first new
    token's logits are ready; ``serve.decode`` over the rest.  In each,
    one ``serve.step`` per single-token decode call (dispatch and sync,
    what the ``serve.step_seconds`` histogram times) holding a
    ``serve.sync``; a batched prefill is one ``serve.prefill_step``
    holding a ``serve.sync``, timed by ``serve.prefill_seconds``.
    """
    B, S = prompt.shape
    with trace.span("serve.generate", call=next(_CALLS), batch=B,
                    prompt_len=S, steps=steps):
        serve_step = jax.jit(build_serve_step(cfg))
        cache = init_cache(params, cfg, B, max_len, frames=frames)
        out = [prompt[:, :1]]
        seen = []
        step_hist = REGISTRY.histogram("serve.step_seconds")

        def advance(t, cache):
            # per-token decode latency: block inside the timed region so
            # async dispatch is charged for the work, not the dispatch
            with trace.span("serve.step"), step_hist.time():
                logits, cache = serve_step(params, out[-1], cache,
                                           jnp.int32(t))
                with trace.span("serve.sync"):
                    block_until_ready(logits)
            seen.append(logits[:, -1:])
            if t + 1 < S:
                out.append(prompt[:, t + 1:t + 2])
            else:
                out.append(jnp.argmax(logits[:, -1:], axis=-1)
                           .astype(prompt.dtype))
            return cache

        positions = steps + prompt.shape[1] - 1
        with trace.span("serve.prefill"):
            if cfg.family == "dense":
                with trace.span("serve.prefill_step"), REGISTRY.histogram(
                        "serve.prefill_seconds").time():
                    logits, cache = _prefill(params, prompt, cache, cfg=cfg)
                    with trace.span("serve.sync"):
                        block_until_ready(logits)
                seen.append(logits[:, :positions])
                out = [prompt]
                if steps:
                    out.append(jnp.argmax(logits[:, -1:], axis=-1)
                               .astype(prompt.dtype))
            else:
                for t in range(min(S, positions)):
                    cache = advance(t, cache)
        with trace.span("serve.decode"):
            for t in range(S, positions):
                cache = advance(t, cache)
        return jnp.concatenate(out, axis=1), jnp.concatenate(seen, axis=1)
