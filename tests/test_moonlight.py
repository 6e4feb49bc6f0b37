"""Moonlight-16B-A3B (DeepSeek-V3 architecture) against its plain float32
reference, ``bench/configs/moonlight-16b-a3b.ref.py``, at a small size on
the CPU with seeded weights: the serve path (one batched prefill, then
absorbed decode through the latent cache), both forms of latent
attention, the dropless expert layer on a held share of the experts, and
the shares summing to the uncut layer; and the benchmark cell's check
(``bench/drive_serve_routed.py``) on a small run.

Tolerances: in float32 the program and the reference differ only in the
order of their sums (absorbed against expanded attention, grouped against
per-expert GEMMs); 1e-4 on logits of magnitude about 4 is some 15 times
the largest difference measured (6.3e-6) and some 8,000 times below what
bfloat16 compute gives (0.81), which the last test requires to fail.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, manifest
from bench import run as bench_run
from repro.configs import get_config, get_reduced
from repro.launch import specs as sp
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import transformer as lm
from repro.obs.metrics import REGISTRY
from repro.serve import engine

ref = manifest.load_reference("moonlight-16b-a3b")
LOGIT_TOL = 1e-4
LAYER_TOL = 1e-5

#: the benchmark configuration's model at a small size: one dense layer and
#: two expert layers, 16 experts of which 4 held, top-4, chunks of 16 tokens
SMALL = dict(json.loads((manifest.BENCH_DIR / "configs" /
                         "moonlight-16b-a3b.json").read_text())["model"],
             n_layers=3, d_model=64, n_heads=4, head_dim=16, n_kv_heads=4,
             d_ff=128, vocab_size=256, kv_lora_rank=32, qk_rope_head_dim=8,
             qk_nope_head_dim=16, v_head_dim=16, n_experts=16,
             experts_held=4, moe_top_k=4, moe_d_ff=24, moe_group_tokens=16,
             compute_dtype="float32")


def _cfg(**over):
    m = dict(SMALL, **over)
    return m, common.model_config({"arch": "moonlight-16b-a3b", "model": m})


def _served(m, cfg, seed=7, batch=4, prompt_len=12, new=6):
    key = jax.random.PRNGKey(seed)
    params = lm.init_lm(key, cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt_len), 0, cfg.vocab_size)
    tokens, logits = engine.greedy_generate(params, cfg, prompt, new,
                                            prompt_len + new)
    want = ref.logits(ref.init_params(key, m), tokens, m)[:, :-1]
    return np.asarray(logits, np.float32), np.asarray(want)


def test_program_and_reference_draw_the_same_weights():
    m, cfg = _cfg()
    key = jax.random.PRNGKey(3)
    got, want = lm.init_lm(key, cfg), ref.init_params(key, m)
    assert set(got) == set(want)
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), got, want)
    assert all(jax.tree.leaves(same))


@pytest.mark.parametrize("seed", [7, 2147483651])
def test_prefill_then_latent_decode_match_the_full_forward(seed):
    m, cfg = _cfg()
    prefills = REGISTRY.histogram("serve.prefill_seconds").count
    got, want = _served(m, cfg, seed)
    assert REGISTRY.histogram("serve.prefill_seconds").count == prefills + 1
    assert np.abs(got - want).max() < LOGIT_TOL


def test_absorbed_decode_matches_expanded_prefill():
    """Position 8 decoded from the latent cache of an 8-token prefill, as
    the 9-token prefill computes it expanded; the cache rows alike."""
    _, cfg = _cfg()
    p = attn_mod.init_mla(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    empty = {k: v[0] for k, v in attn_mod.init_latent_cache(
        cfg, 2, 12, 1).items()}
    full, full_cache = attn_mod.prefill_mla(p, x, empty, cfg)
    _, cache = attn_mod.prefill_mla(p, x[:, :8], empty, cfg)
    last, cache = attn_mod.decode_mla(p, x[:, 8:], cache, jnp.int32(8), cfg)
    np.testing.assert_allclose(last[:, 0], full[:, 8], atol=LAYER_TOL)
    for name in ("c_kv", "k_pe"):
        np.testing.assert_allclose(cache[name], full_cache[name],
                                   atol=LAYER_TOL)
        assert not np.any(np.asarray(cache[name][:, 9:]))


def _layer(m, cfg, key=jax.random.PRNGKey(5)):
    """The program's expert layer and the reference's (the same draw)."""
    block = ref._block(key, m, True)["moe"]
    return block, jax.random.normal(jax.random.PRNGKey(6), (3, 16, cfg.d_model))


def _ref_layer(m, p, x):
    mm = ref.MATMULS["f32"]
    flat = x.reshape(-1, x.shape[-1])
    return (ref.routed(mm, p, flat, m)
            + ref._swiglu(mm, flat, p["shared"])).reshape(x.shape)


@pytest.mark.parametrize("favoured", [(0, 1, 2, 3), (0, 9)])
def test_dropless_layer_matches_the_reference_on_uneven_routing(favoured):
    """A correction bias of +10 sends every token to the favoured experts:
    with (0..3), each held expert gets all 48 tokens; with (0, 9), expert 0
    gets all of them and the rest spread."""
    m, cfg = _cfg()
    p, x = _layer(m, cfg)
    p["bias"] = p["bias"].at[jnp.array(favoured)].add(10.0)
    out = moe_mod.apply_dropless(p, x, cfg)
    np.testing.assert_allclose(out, _ref_layer(m, p, x), atol=LAYER_TOL)
    _, idx = moe_mod.route_sigmoid(x.reshape(-1, cfg.d_model), p, cfg)
    held = int(np.sum(np.asarray(idx) < cfg.n_held))
    if favoured == (0, 1, 2, 3):
        assert held == x.shape[0] * x.shape[1] * cfg.moe_top_k


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips holding experts 0-3, 4-7, 8-11 and 12-15: their routed
    parts, plus the shared experts counted once, are the uncut layer."""
    m_all, cfg_all = _cfg(experts_held=16)
    key = jax.random.PRNGKey(11)
    whole = moe_mod.init_moe(key, cfg_all)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 20, cfg_all.d_model))
    total = ffn = None
    for first in range(0, 16, 4):
        _, cfg = _cfg(first_expert=first)
        share = moe_mod.init_moe(key, cfg)
        for name in ("w_gate", "w_up", "w_down"):
            np.testing.assert_array_equal(share[name],
                                          whole[name][first:first + 4])
        part = moe_mod.apply_held(share, x, cfg)
        total = part if total is None else total + part
        ffn = moe_mod.ffn_mod.apply_ffn(share["shared"], x, cfg)
    np.testing.assert_allclose(total + ffn, _ref_layer(m_all, whole, x),
                               atol=LAYER_TOL)
    np.testing.assert_allclose(
        total + ffn, moe_mod.apply_dropless(whole, x, cfg_all),
        atol=LAYER_TOL)


def test_bfloat16_compute_fails_the_logit_tolerance():
    m, cfg = _cfg(compute_dtype="bfloat16")
    got, want = _served(m, cfg)
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


def test_moonlight_prefills_in_one_call_and_gshard_moe_does_not():
    assert lm.prefills(get_reduced("moonlight-16b-a3b"))
    assert lm.prefills(get_reduced("smollm-135m"))
    assert not lm.prefills(get_reduced("qwen2-moe-a2.7b"))
    with pytest.raises(ValueError):
        cfg = get_reduced("qwen2-moe-a2.7b")
        lm.prefill(None, jnp.zeros((1, 4), jnp.int32), None, cfg)


def test_latent_cache_is_576_values_a_token_a_layer():
    cfg = get_config("moonlight-16b-a3b")
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 2, 8))
    assert cache["dense_blocks"]["c_kv"].shape == (1, 2, 8, 512)
    assert cache["blocks"]["k_pe"].shape == (26, 2, 8, 64)


def test_full_config_has_the_published_parameter_count():
    """15.96B parameters (the published 16B), 2.9B of them active (A3B)."""
    total, active = sp.param_counts(get_config("moonlight-16b-a3b"))
    assert 15.5e9 < total < 16.5e9
    assert active < total


def _small_run(seed):
    """The moonlight cell at a size a test run holds: 4 layers of width
    128, 16 experts of which 4 held, 16 clients of 32-token prompts."""
    run = bench_run.plan("moonlight-serve-b128-p512-g64", seed)
    run["config"]["model"].update(
        n_layers=4, d_model=128, n_heads=4, head_dim=32, n_kv_heads=4,
        d_ff=256, vocab_size=2048, kv_lora_rank=64, qk_rope_head_dim=16,
        qk_nope_head_dim=32, v_head_dim=32, n_experts=16, experts_held=4,
        moe_top_k=4, moe_d_ff=64, moe_group_tokens=256)
    run["traffic"].update(clients=16, prompt_len=32, new_tokens=16,
                          check_requests=16, check_block=8)
    run.update(devices=jax.devices()[:1], times={}, trace=False)
    return run


def _correct(checks, limits):
    return all(c["ok"] for c in bench_run.verdict(checks, limits).values())


def test_a_sound_moonlight_run_is_correct():
    run = _small_run(2147483649)
    res = bench_run.measure(run, 0.3, False)
    assert set(res["checks"]) == {"logit_gap", "logit_gap_p95",
                                  "wide_gap_share"}
    assert _correct(res["checks"], run["config"]["limits"])


@pytest.mark.parametrize("seed", [2147483651, 4294967311])
def test_the_8bit_control_fails_the_routed_check(seed):
    """Read at this size: the program's 95th-percentile gap 0.0 on both
    seeds, the control's 0.54 and 0.55; the widest gaps 0.59 and 0.46
    against 1.30 and 1.54, so the widest alone would not tell them apart
    on a run whose rarest expert flip is larger; gaps over 0.5 on 0.78%
    and 0% of the program's tokens, 6.3% and 5.9% of the control's."""
    run = _small_run(seed)
    got = manifest.load_driver("serve_routed").control(run)
    limits = run["config"]["limits"]
    assert _correct(got["program"], limits)
    assert not _correct(got["fp8"], limits)


def test_a_wrong_first_token_fails_the_routed_check():
    """Every other request's first new token replaced by the reference's
    worst choice: one judged token in 32 is wrong (as one in 64 would be
    at the cell's 64 new tokens, were it every request's), too few for
    the 95th percentile, and the share of wide gaps fails."""
    run = _small_run(2147483659)
    d = manifest.load_driver("serve_routed")
    ctx = d.setup(run)
    first = ctx["round"]
    d.serve_round(ctx)
    ctx["window_rounds"] = (first, ctx["round"])
    d.release(ctx)
    seqs = d.sample(ctx)
    P = run["traffic"]["prompt_len"]
    ref = d._serve.reference_logits(run, seqs)
    choice = seqs[:, P:].copy()
    choice[::2, 0] = np.argmin(ref[::2, P - 1], axis=-1)
    limits = run["config"]["limits"]
    assert _correct(d.numbers(d.gaps(ref, seqs, P)), limits)
    wrong = d.numbers(d.gaps(ref, seqs, P, choice))
    ok = bench_run.verdict(wrong, limits)
    assert ok["logit_gap_p95"]["ok"] and not ok["wide_gap_share"]["ok"]
