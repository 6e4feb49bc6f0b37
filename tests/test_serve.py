"""Serving: decode-vs-forward equivalence per family + generation smoke."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import encdec as encdec_mod
from repro.models import transformer as lm_mod
from repro.serve.engine import build_serve_step, greedy_generate, init_cache
from repro.train.loop import init_params

KEY = jax.random.PRNGKey(7)

# archs whose decode must match teacher-forced forward exactly (capacity
# drops make MoE equality only approximate — tested separately)
EXACT = ["qwen2.5-3b", "smollm-135m", "minitron-8b", "qwen2-7b",
         "xlstm-125m", "hymba-1.5b"]


def _params(cfg):
    return init_params(KEY, cfg)


@pytest.mark.slow
@pytest.mark.parametrize("arch", EXACT)
def test_decode_matches_forward(arch):
    cfg = get_reduced(arch).replace(compute_dtype=jnp.float32)
    params = _params(cfg)
    toks = jax.random.randint(KEY, (2, 12), 0, cfg.vocab_size)
    full, _ = lm_mod.forward(params, toks, cfg)
    serve = build_serve_step(cfg)
    cache = init_cache(params, cfg, 2, 12)
    outs = []
    for t in range(12):
        lg, cache = serve(params, toks[:, t:t + 1], cache, jnp.int32(t))
        outs.append(lg[:, 0])
    np.testing.assert_allclose(np.stack(outs, 1), np.asarray(full),
                               atol=2e-4, rtol=1e-3)


@pytest.mark.slow
def test_whisper_decode_matches_forward():
    cfg = get_reduced("whisper-tiny").replace(compute_dtype=jnp.float32)
    params = _params(cfg)
    toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    frames = jax.random.normal(KEY, (2, cfg.encoder_seq, cfg.d_model))
    full, _ = encdec_mod.forward(params, toks, frames, cfg)
    serve = build_serve_step(cfg)
    cache = init_cache(params, cfg, 2, 8, frames=frames)
    outs = []
    for t in range(8):
        lg, cache = serve(params, toks[:, t:t + 1], cache, jnp.int32(t))
        outs.append(lg[:, 0])
    np.testing.assert_allclose(np.stack(outs, 1), np.asarray(full),
                               atol=2e-4, rtol=1e-3)


@pytest.mark.slow
def test_moe_decode_matches_forward_without_drops():
    cfg = get_reduced("qwen2-moe-a2.7b").replace(
        compute_dtype=jnp.float32, capacity_factor=16.0)
    params = _params(cfg)
    toks = jax.random.randint(KEY, (2, 6), 0, cfg.vocab_size)
    full, _ = lm_mod.forward(params, toks, cfg)
    serve = build_serve_step(cfg)
    cache = init_cache(params, cfg, 2, 6)
    outs = []
    for t in range(6):
        lg, cache = serve(params, toks[:, t:t + 1], cache, jnp.int32(t))
        outs.append(lg[:, 0])
    np.testing.assert_allclose(np.stack(outs, 1), np.asarray(full),
                               atol=2e-4, rtol=1e-3)


@pytest.mark.slow
def test_greedy_generate_is_deterministic_and_extends():
    cfg = get_reduced("smollm-135m").replace(compute_dtype=jnp.float32)
    params = _params(cfg)
    prompt = jax.random.randint(KEY, (2, 5), 0, cfg.vocab_size)
    out1, logits1 = greedy_generate(params, cfg, prompt, steps=4, max_len=16)
    out2, logits2 = greedy_generate(params, cfg, prompt, steps=4, max_len=16)
    assert out1.shape == (2, 9)
    assert logits1.shape == (2, 8, cfg.vocab_size)
    np.testing.assert_array_equal(out1, out2)
    np.testing.assert_array_equal(logits1, logits2)
    np.testing.assert_array_equal(out1[:, :5], prompt)
    # each new token is the argmax of the logits at the position before it
    np.testing.assert_array_equal(out1[:, 5:], np.argmax(logits1[:, 4:], -1))


def test_sliding_window_cache_is_bounded():
    """Hymba local layers must hold only O(window) KV regardless of max_len."""
    cfg = get_reduced("hymba-1.5b").replace(compute_dtype=jnp.float32)
    cache = init_cache(None, cfg, 1, 4096)
    for i in range(cfg.n_layers):
        row = cache[f"layer{i}"]
        if i in cfg.global_attn_layers:
            assert row["k"].shape[1] == 4096
        else:
            assert row["k"].shape[1] == cfg.sliding_window


def test_greedy_generate_spans_under_the_profiler(tmp_path):
    import glob
    from jax.profiler import ProfileData
    cfg = get_reduced("smollm-135m").replace(compute_dtype=jnp.float32)
    params = _params(cfg)
    S, G = 5, 4
    prompt = jax.random.randint(KEY, (2, S), 0, cfg.vocab_size)
    jax.profiler.start_trace(str(tmp_path))
    try:
        greedy_generate(params, cfg, prompt, G, S + G)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name.split("#", 1)[0], e.start_ns, e.end_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events if e.name.startswith("serve.")]

    def inside(outer, name):
        return [s for s in spans if s[0] == name and outer[1] <= s[1]
                and s[2] <= outer[2]]

    gen, = [s for s in spans if s[0] == "serve.generate"]
    prefill, = inside(gen, "serve.prefill")
    decode, = inside(gen, "serve.decode")
    assert prefill[2] <= decode[1]
    # a dense model prefills the whole prompt in one call
    batched, = inside(prefill, "serve.prefill_step")
    assert len(inside(batched, "serve.sync")) == 1
    assert not inside(prefill, "serve.step")
    assert len(inside(decode, "serve.step")) == G - 1
    steps = inside(gen, "serve.step")
    assert len(steps) == G - 1
    assert all(len(inside(step, "serve.sync")) == 1 for step in steps)


def _with_qkv_bias(params, cfg):
    """Non-zero q/k/v biases, so a prefill that dropped them would show."""
    if not cfg.qkv_bias:
        return params
    attn = dict(params["blocks"]["attn"])
    for i, b in enumerate(("bq", "bk", "bv")):
        attn[b] = 0.1 * jax.random.normal(jax.random.fold_in(KEY, i),
                                          attn[b].shape)
    return {**params, "blocks": {**params["blocks"], "attn": attn}}


def _token_loop(params, cfg, prompt, steps, max_len):
    """Greedy generation fed one position at a time through
    ``build_serve_step``: (tokens, logits (B, S + steps - 1, V), cache)."""
    serve = jax.jit(build_serve_step(cfg))
    cache = init_cache(params, cfg, prompt.shape[0], max_len)
    S = prompt.shape[1]
    tokens, logits = [prompt[:, :1]], []
    for t in range(S + steps - 1):
        lg, cache = serve(params, tokens[-1], cache, jnp.int32(t))
        logits.append(lg[:, 0])
        tokens.append(prompt[:, t + 1:t + 2] if t + 1 < S else
                      jnp.argmax(lg, axis=-1).astype(prompt.dtype))
    return jnp.concatenate(tokens, 1), jnp.stack(logits, 1), cache


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen2.5-3b"])
def test_prefill_matches_the_decode_steps(arch):
    cfg = get_reduced(arch).replace(compute_dtype=jnp.float32)
    params = _with_qkv_bias(_params(cfg), cfg)
    S, max_len = 6, 10
    prompt = jax.random.randint(KEY, (2, S), 0, cfg.vocab_size)
    _, want, want_cache = _token_loop(params, cfg, prompt, 1, max_len)
    got, cache = jax.jit(lm_mod.prefill, static_argnames="cfg")(
        params, prompt, init_cache(params, cfg, 2, max_len), cfg=cfg)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-3)
    for n in ("k", "v"):
        assert cache[n].shape == want_cache[n].shape
        assert cache[n].dtype == want_cache[n].dtype
        np.testing.assert_allclose(cache[n][:, :, :S], want_cache[n][:, :, :S],
                                   atol=2e-4, rtol=1e-3)
        assert not np.any(np.asarray(cache[n][:, :, S:]))


def test_greedy_generate_matches_the_token_loop():
    cfg = get_reduced("smollm-135m").replace(compute_dtype=jnp.float32)
    params = _params(cfg)
    S, G = 5, 4
    prompt = jax.random.randint(KEY, (2, S), 0, cfg.vocab_size)
    tokens, logits = greedy_generate(params, cfg, prompt, G, S + G)
    want_tokens, want_logits, _ = _token_loop(params, cfg, prompt, G, S + G)
    np.testing.assert_array_equal(tokens, want_tokens)
    np.testing.assert_allclose(logits, want_logits, atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("arch,prefills", [
    ("smollm-135m", 1), ("hymba-1.5b", 0), ("xlstm-125m", 0),
    ("qwen2-moe-a2.7b", 0)])
def test_only_a_dense_model_takes_the_batched_prefill(arch, prefills):
    from repro.obs.metrics import REGISTRY
    cfg = get_reduced(arch).replace(compute_dtype=jnp.float32)
    params = _params(cfg)
    prompt = jax.random.randint(KEY, (2, 3), 0, cfg.vocab_size)
    hist = REGISTRY.histogram("serve.prefill_seconds")
    for _ in range(2):
        before = hist.count
        greedy_generate(params, cfg, prompt, 2, 5)
        assert hist.count - before == prefills
