"""The moonlight cell's pieces: its counts of latent attention and of the
dropless expert layer against a hand count, the readers of the named
scopes ``mla`` and ``moe`` on a small synthetic trace, and the manifest's
rule that each (config, traffic) pair is given once.  The cell's check
(``drive_serve_routed``) is run at a small size in tests/test_moonlight.py."""
import collections
import types

import pytest

from bench import flops, flops_mla_moe, manifest

MS = 1e6
M = manifest.load_manifest()

#: a tiny latent-attention MoE model: hidden 4, 2 heads, latent 3, rope 2,
#: nope 2, value 2; one dense layer (FFN 6) then one expert layer of 8
#: routed experts (2 held, top-2, width 5) and 1 shared expert; vocabulary 10
TINY = {"d_model": 4, "n_heads": 2, "kv_lora_rank": 3, "qk_rope_head_dim": 2,
        "qk_nope_head_dim": 2, "v_head_dim": 2, "n_layers": 2,
        "first_dense_layers": 1, "n_experts": 8, "experts_held": 2,
        "moe_top_k": 2, "moe_d_ff": 5, "n_shared_experts": 1, "d_ff": 6,
        "vocab_size": 10, "compute_dtype": "bfloat16"}


def test_every_config_and_traffic_pair_is_given_once():
    pairs = collections.Counter((c["config"], c["traffic"])
                                for c in M["workloads"])
    assert max(pairs.values()) == 1, pairs
    four = [c["name"] for c in M["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(M["workloads"]) // 2)


def test_mla_decode_counts_by_hand():
    # a token, a layer: x W_q 4*2*4, x W_kv_a 4*5, absorption 2*2*3, scores
    # 2*live*5, values 2*live*3, W_uv 2*3*2, W_o 2*2*4: 92 + 16*live
    # multiply-adds; weights 32 + 20 + 3 + 24 + 16 = 95.  2 layers, 3 tokens
    got = flops_mla_moe.mla_decode(TINY, batch=3, pos=4)
    assert got["flops"] == 2 * 3 * 2 * (92 + 16 * 5)
    assert got["bytes"] == 2 * 2 * (95 + 3 * 5 * 5)
    assert flops_mla_moe.mla_weights(TINY) == 95


def test_moe_decode_counts_by_hand():
    # 6 tokens: router 6*4*8; 6*2*2/8 = 3 routed pairs of 3*4*5; shared
    # 6 tokens of 3*4*5: 192 + 180 + 360 multiply-adds.  Bytes: router and
    # bias at 4 (4*8 + 8), experts 2 held + 1 shared of 3*4*5 at 2
    got = flops_mla_moe.moe_decode(TINY, tokens=6)
    assert got["flops"] == 2 * (192 + 180 + 360)
    assert got["bytes"] == 4 * 40 + 2 * 3 * 60


def test_active_params_by_hand():
    # attention 2 layers of 95; dense FFN 3*4*6 = 72; the expert layer's
    # router 4*8, shared 3*4*5 and 2*2/8 of a held expert of 3*4*5; head
    # 4*10
    assert flops_mla_moe.active_params(TINY) == 190 + 72 + 32 + 60 + 30 + 40


def test_decode_step_is_its_layers_plus_dense_ffn_and_head():
    # the dense FFN and the head: 72 + 40 weights, 2 FLOPs each a token
    a = flops_mla_moe.mla_decode(TINY, 3, 4)
    e = flops_mla_moe.moe_decode(TINY, 3)
    got = flops_mla_moe.decode_step(TINY, batch=3, pos=4)
    assert got["flops"] == a["flops"] + e["flops"] + 2 * 3 * 112
    assert got["bytes"] == a["bytes"] + e["bytes"] + 2 * 112


def test_decode_positions_follow_the_batched_prefill():
    assert list(flops_mla_moe.decode_positions(
        {"prompt_len": 512, "new_tokens": 64})) == list(range(512, 575))


def _varint(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields):
    out = b""
    for number, v in fields:
        if isinstance(v, int):
            out += _varint(number << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(number << 3 | 2) + _varint(len(v)) + v
    return out


def _hlo(names):
    insts = [(2, _msg((1, n), (2, "fusion"), (7, _msg((1, "mul"),
                                                       (2, op)))))
             for n, op in names.items()]
    return _msg((1, _msg((1, "jit_serve_step"),
                         (3, _msg((1, "main"), *insts)))))


def _traced():
    """Two ``serve_step`` runs of 10 ms: in each, 2 ms of ``mla`` ops,
    3 ms of the expert layer (2 ms of ``moe`` ops and 1 ms of the
    compiler's grouped-GEMM kernel) and 1 ms of the head; a prefill run
    beside them whose ops count for nothing."""
    ops, mods = [], []
    for t0 in (0, 20):
        mods.append(("jit_serve_step(9)", t0 * MS, (t0 + 10) * MS))
        ops += [("fusion.1", t0 * MS, (t0 + 2) * MS),
                ("fusion.2", (t0 + 2) * MS, (t0 + 4) * MS),
                ("ragged-dot-none", (t0 + 4) * MS, (t0 + 5) * MS),
                ("fusion.3", (t0 + 5) * MS, (t0 + 6) * MS)]
    mods.append(("jit_prefill(3)", 40 * MS, 50 * MS))
    ops.append(("fusion.2", 40 * MS, 49 * MS))
    trace = {"host": [("bench.window", 0, 60 * MS)],
             "devices": [{"name": "/device:TPU:0", "modules": mods,
                          "ops": ops}]}
    body = "jit(serve_step)/while/body"
    pt = {"spans": [], "window": (0.0, 60 * MS),
          "hlo": {"jit_serve_step(9)": _hlo({
              "fusion.1": f"{body}/mla/dot_general",
              "fusion.2": f"{body}/moe/gather",
              "ragged-dot-none": "ragged-dot-none",
              "fusion.3": "jit(serve_step)/dot_general"})}}
    return {"trace": trace, "progtrace": pt}


def _run(model, traffic):
    dev = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    return {"n_chips": 1, "devices": [dev], "config": {"model": model},
            "traffic": traffic}


def _read(name, result, run=None):
    return manifest.load_metric(name).read(run, result)


def test_moe_ms_is_the_moe_scope_per_serve_step_run():
    assert _read("serve_moe_ms", _traced()) == pytest.approx(3.0)
    # the grouped GEMM's kernel carries no scope of its own
    assert flops_mla_moe.scope_ms_per_step(_traced(), "moe") == \
        pytest.approx(2.0)
    assert _read("serve_mla_roofline", _traced(), _run(TINY, {
        "clients": 6, "prompt_len": 3, "new_tokens": 3})) is not None


def test_rooflines_are_least_time_over_their_scopes_time():
    traffic = {"clients": 6, "prompt_len": 3, "new_tokens": 3}
    run = _run(TINY, traffic)
    peak = flops.peak_for("TPU v5 lite")
    least = [flops.least_seconds(s["flops"], s["bytes"], peak) for s in (
        flops_mla_moe.mla_decode(TINY, 6, pos) for pos in (3, 4))]
    assert _read("serve_mla_roofline", _traced(), run) == pytest.approx(
        100 * sum(least) / 2 / 2e-3)
    s = flops_mla_moe.moe_decode(TINY, 6)
    assert _read("serve_moe_roofline", _traced(), run) == pytest.approx(
        100 * flops.least_seconds(s["flops"], s["bytes"], peak) / 3e-3)


def test_step_roofline_is_the_whole_step_over_a_serve_step_run():
    run = _run(TINY, {"clients": 6, "prompt_len": 3, "new_tokens": 3})
    peak = flops.peak_for("TPU v5 lite")
    least = [flops.least_seconds(s["flops"], s["bytes"], peak) for s in (
        flops_mla_moe.decode_step(TINY, 6, pos) for pos in (3, 4))]
    assert _read("serve_step_roofline.mla_moe", _traced(), run) == \
        pytest.approx(100 * sum(least) / 2 / 10e-3)


def test_mfu_counts_every_position_a_round_computes():
    # 60 tokens in 2 s, 3 new a request: 20 requests of 3 + 3 - 1 positions
    run = _run(TINY, {"clients": 6, "prompt_len": 3, "new_tokens": 3})
    res = {"window": {"tokens": 60, "seconds": 2.0}}
    peak = flops.peak_for("TPU v5 lite")["bf16_flops"]
    assert _read("serve_mfu.mla_moe", res, run) == pytest.approx(
        100 * 50 * 2 * flops_mla_moe.active_params(TINY) / peak)


@pytest.mark.parametrize("name", ["serve_step_roofline.mla_moe",
                                  "serve_mfu.mla_moe"])
def test_a_model_without_latent_attention_reads_nothing(name):
    dense = {k: v for k, v in TINY.items() if k != "kv_lora_rank"}
    res = dict(_traced(), window={"tokens": 60, "seconds": 2.0})
    assert _read(name, res, _run(dense, {
        "clients": 6, "prompt_len": 3, "new_tokens": 3})) is None


@pytest.mark.parametrize("name", ["serve_moe_ms", "serve_mla_roofline",
                                  "serve_moe_roofline"])
def test_a_program_without_the_scopes_reads_nothing(name):
    run = _run(TINY, {"clients": 6, "prompt_len": 3, "new_tokens": 3})
    res = _traced()
    res["progtrace"]["hlo"] = {"jit_serve_step(9)": _hlo(
        {"fusion.1": "jit(serve_step)/dot_general"})}
    assert _read(name, res, run) is None
    assert _read(name, {"trace": None}, run) is None

