"""Serving demo: batched greedy decoding with the KV-cache / recurrent-state
engines, across three architecture families (dense KV cache, xLSTM constant
state, Hymba sliding-window hybrid).

    PYTHONPATH=src python examples/serve_demo.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.serve.engine import greedy_generate
from repro.train.loop import init_params


def demo(arch: str, steps: int = 24):
    cfg = get_reduced(arch).replace(compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                                cfg.vocab_size)
    t0 = time.perf_counter()
    out, _ = greedy_generate(params, cfg, prompt, steps=steps,
                             max_len=8 + steps)
    dt = time.perf_counter() - t0
    n_new = out.shape[1] - prompt.shape[1]
    print(f"{arch:<18} family={cfg.family:<7} batch=4  "
          f"+{n_new} tokens in {dt:.2f}s "
          f"({4 * n_new / dt:.0f} tok/s on 1 CPU core)")
    assert out.shape == (4, 8 + steps)
    return out


if __name__ == "__main__":
    for arch in ("smollm-135m", "xlstm-125m", "hymba-1.5b"):
        demo(arch)
    print("OK")
