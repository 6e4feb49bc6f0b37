"""Serving cells, closed loop: ``clients`` clients each wait for their
reply before sending the next request, so every round is one
``serve/engine.greedy_generate`` call over a batch of ``clients`` prompts
of ``prompt_len`` tokens drawn from the seed, generating ``new_tokens``.

A request's latency runs from its batch entering ``greedy_generate`` to
the call's return.  Set-up makes the weights on the device in one jitted
call and serves one round at the cell's shapes, which compiles what the
window will run.  After the window a sample of the requests served, drawn
from the seed, is checked against the reference's full forward pass.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, manifest


def _prompts(key, r, traffic, vocab):
    return jax.random.randint(jax.random.fold_in(key, r),
                              (traffic["clients"], traffic["prompt_len"]),
                              0, vocab, jnp.int32)


def setup(run: Dict[str, Any]) -> Dict[str, Any]:
    times = run["times"]
    t = time.perf_counter()
    from repro.obs.metrics import REGISTRY
    from repro.serve.engine import greedy_generate
    from repro.train.loop import init_params
    times["import_program"] = time.perf_counter() - t

    config, traffic = run["config"], run["traffic"]
    cfg = common.model_config(config)
    keys = common.keys(run["seed"])
    t = time.perf_counter()
    params = jax.jit(lambda k: init_params(k, cfg))(keys["weights"])
    jax.block_until_ready(params)
    times["weights"] = time.perf_counter() - t

    prompts = jax.jit(lambda k, r: _prompts(k, r, traffic, cfg.vocab_size))
    ctx: Dict[str, Any] = {"run": run, "cfg": cfg, "params": params,
                           "prompts": prompts, "key": keys["inputs"],
                           "generate": greedy_generate,
                           "hist": REGISTRY.histogram("serve.step_seconds"),
                           "served": [], "round": 0}
    t = time.perf_counter()
    serve_round(ctx, time.perf_counter)
    times["warmup_round"] = time.perf_counter() - t
    return ctx


def serve_round(ctx: Dict[str, Any], clock=time.perf_counter, span=None):
    traffic = ctx["run"]["traffic"]
    P, G = traffic["prompt_len"], traffic["new_tokens"]
    span = span or common.spans(False)
    with span("bench.input"):
        prompt = ctx["prompts"](ctx["key"], ctx["round"])
    t = clock()
    with span("bench.generate"):
        tokens, logits = ctx["generate"](ctx["params"], ctx["cfg"], prompt,
                                         G, P + G)
        tokens.block_until_ready()
    latency = clock() - t
    del logits
    ctx["served"].append(tokens)
    ctx["round"] += 1
    return latency


def window(ctx: Dict[str, Any], seconds: float, span) -> Dict[str, Any]:
    traffic = ctx["run"]["traffic"]
    C, G = traffic["clients"], traffic["new_tokens"]
    hist = ctx["hist"]
    h0 = (hist.count, hist.snapshot()["sum"])
    first = ctx["round"]
    latencies: List[float] = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with span("bench.window"):
        while time.perf_counter() < deadline:
            lat = serve_round(ctx, time.perf_counter, span)
            latencies += [lat] * C
    t1 = time.perf_counter()
    h1 = (hist.count, hist.snapshot()["sum"])
    ctx["window_rounds"] = (first, ctx["round"])
    return {"seconds": t1 - t0, "requests": len(latencies),
            "tokens": len(latencies) * G, "latencies": latencies,
            "rounds": len(latencies) // C,
            "serve_steps": h1[0] - h0[0], "serve_step_seconds": h1[1] - h0[1],
            "t0": t0, "t1": t1}


def release(ctx: Dict[str, Any]) -> None:
    for k in ("params", "generate"):
        ctx.pop(k, None)


def sample(ctx: Dict[str, Any]) -> np.ndarray:
    """A sample, drawn from the seed, of the requests the window served:
    their prompts and served tokens, (n, prompt_len + new_tokens)."""
    traffic = ctx["run"]["traffic"]
    lo, hi = ctx["window_rounds"]
    rows = [(r, c) for r in range(lo, hi) for c in range(traffic["clients"])]
    rng = np.random.default_rng(ctx["run"]["seed"])
    pick = rng.choice(len(rows), size=min(traffic["check_requests"],
                                          len(rows)), replace=False)
    served = [np.asarray(t) for t in ctx["served"]]
    return np.stack([served[rows[i][0]][rows[i][1]] for i in sorted(pick)])


def widest_gap(logits: np.ndarray, seqs: np.ndarray, prompt_len: int,
               choice: np.ndarray | None = None) -> float:
    """The widest gap by which a served token's reference logit lies below
    the reference's best at its position.  ``choice`` picks other tokens
    to judge in place of the served ones (the control's)."""
    G = seqs.shape[1] - prompt_len
    at = logits[:, prompt_len - 1:prompt_len - 1 + G]          # (n, G, V)
    tok = seqs[:, prompt_len:] if choice is None else choice
    chosen = np.take_along_axis(at, tok[..., None], axis=-1)[..., 0]
    return float(np.max(at.max(-1) - chosen))


def reference_logits(run: Dict[str, Any], seqs: np.ndarray,
                     matmul: str = "f32") -> np.ndarray:
    ref = manifest.load_reference(run["config"]["name"])
    params = jax.jit(lambda k: ref.init_params(k, run["config"]["model"]))(
        common.keys(run["seed"])["weights"])
    fwd = jax.jit(lambda p, s: ref.logits(p, s, run["config"]["model"],
                                          matmul))
    block = run["traffic"]["check_block"]
    return np.concatenate([np.asarray(fwd(params, seqs[i:i + block]))
                           for i in range(0, len(seqs), block)])


def check(ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    run = ctx["run"]
    seqs = sample(ctx)
    ctx["served"] = []
    logits = reference_logits(run, seqs)
    return {"logit_gap": {"value": widest_gap(
        logits, seqs, run["traffic"]["prompt_len"]),
        "tokens": int(seqs.shape[0] * run["traffic"]["new_tokens"])}}
