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
    assert len(inside(prefill, "serve.step")) == S
    assert len(inside(decode, "serve.step")) == G - 1
    steps = inside(gen, "serve.step")
    assert len(steps) == S + G - 1
    assert all(len(inside(step, "serve.sync")) == 1 for step in steps)
