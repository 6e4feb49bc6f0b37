"""Serving launcher: batched greedy decoding against the KV-cache engine.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --reduced --batch 4 --prompt-len 8 --new-tokens 32
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, get_reduced
from repro.distributed.sharding import gqa_safe_rules, use_sharding
from repro.launch.mesh import make_mesh
from repro.models.common import ModelConfig
from repro.serve.engine import greedy_generate
from repro.train.loop import init_params


class ServeRun(NamedTuple):
    cfg: ModelConfig
    params: Any
    prompt: jnp.ndarray      # (B, prompt_len)
    tokens: jnp.ndarray      # (B, prompt_len + new_tokens)
    logits: jnp.ndarray      # (B, prompt_len + new_tokens - 1, V)
    seconds: float           # wall time of the generation, compile included


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run(args: argparse.Namespace) -> ServeRun:
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.reduced:
        cfg = cfg.replace(compute_dtype=jnp.float32)
    dims = tuple(int(d) for d in args.mesh.split("x"))
    mesh = make_mesh(dims, ("data", "model"))

    with use_sharding(mesh, gqa_safe_rules(cfg.n_kv_heads, mesh)):
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
        prompt = jax.random.randint(
            jax.random.PRNGKey(args.seed + 1),
            (args.batch, args.prompt_len), 0, cfg.vocab_size)
        t0 = time.perf_counter()
        tokens, logits = greedy_generate(
            params, cfg, prompt, steps=args.new_tokens,
            max_len=args.prompt_len + args.new_tokens)
        seconds = time.perf_counter() - t0
    return ServeRun(cfg, params, prompt, tokens, logits, seconds)


def main(argv=None) -> int:
    use_compile_cache()
    args = parse_args(argv)
    out = run(args)
    tok_s = args.batch * args.new_tokens / out.seconds
    print(f"{args.arch}: batch={args.batch} +{args.new_tokens} tokens "
          f"in {out.seconds:.2f}s ({tok_s:.0f} tok/s)")
    print("first sequence:", out.tokens[0].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
