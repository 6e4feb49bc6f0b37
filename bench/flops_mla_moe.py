"""Operations and bytes of latent attention (MLA), of the dropless
expert layer and of a whole decode step of a model built of them, from
their shapes; the parameters a token multiplies; and the device time of a
named scope per decode step.

Counts are of the algorithm, as in ``flops``: a multiply-add is two
operations; weights are read once at the compute dtype (the router's at
float32, as it runs); the cache rows a step needs are read once.  ``m``
is a configuration's ``model`` section.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from bench import devtrace, progtrace, readers
from bench.flops import DTYPE_BYTES


def mla_weights(m: Dict[str, Any]) -> float:
    """Parameters of one latent attention layer: W_q, W_kv_a, the latent's
    norm, W_kv_b and W_o."""
    D, H, C = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return float(D * H * (dn + dr) + D * (C + dr) + C + C * H * (dn + dv)
                 + H * dv * D)


def mla_decode(m: Dict[str, Any], batch: int, pos: int) -> Dict[str, float]:
    """One absorbed decode step of every layer's latent attention, for
    ``batch`` tokens at position ``pos`` (0-based).

    FLOPs a token a layer: the projections x W_q and x W_kv_a, the
    absorption q_nope W_uk, scores over the ``pos + 1`` live latent rows
    (C + dr wide), values from the latent (C), the latent output through
    W_uv, and W_o.  Bytes: the layer's weights and its live cache rows
    (c_kv and k_pe) of every sequence.
    """
    D, H, C, L = m["d_model"], m["n_heads"], m["kv_lora_rank"], m["n_layers"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    live = pos + 1
    per_token = 2.0 * (D * H * (dn + dr) + D * (C + dr) + H * dn * C
                       + H * live * (C + dr) + H * live * C + H * C * dv
                       + H * dv * D)
    wb = DTYPE_BYTES[m["compute_dtype"]]
    cache = batch * live * (C + dr) * wb
    return {"flops": L * batch * per_token,
            "bytes": L * (mla_weights(m) * wb + cache)}


def moe_decode(m: Dict[str, Any], tokens: int) -> Dict[str, float]:
    """One decode step of every dropless expert layer for ``tokens``
    tokens.

    FLOPs a layer: the router over all ``n_experts`` for every token; the
    three GEMMs of an expert for each (token, expert) pair on the held
    experts, ``tokens * moe_top_k * experts_held / n_experts`` of them as
    routing spreads evenly; the shared experts' for every token.  Bytes: the
    router (float32) and its bias, the held experts and the shared ones.
    """
    D, E, F = m["d_model"], m["n_experts"], m["moe_d_ff"]
    held = m.get("experts_held") or E
    shared = F * m["n_shared_experts"]
    layers = m["n_layers"] - m.get("first_dense_layers", 0)
    pairs = tokens * m["moe_top_k"] * held / E
    flops = 2.0 * (tokens * D * E + 3 * D * F * pairs + 3 * D * shared * tokens)
    wb = DTYPE_BYTES[m["compute_dtype"]]
    nbytes = 4.0 * (D * E + E) + wb * 3.0 * D * (held * F + shared)
    return {"flops": layers * flops, "bytes": layers * nbytes}


def active_params(m: Dict[str, Any]) -> float:
    """Parameters a token multiplies on this chip, as routing spreads
    evenly: every layer's latent attention, the leading dense layers'
    FFN, each expert layer's router, shared experts and its
    ``moe_top_k * experts_held / n_experts`` held experts, and the untied
    head.  The embedding is a lookup and counts nothing."""
    D, E, F = m["d_model"], m["n_experts"], m["moe_d_ff"]
    dense = m.get("first_dense_layers", 0)
    held = m.get("experts_held") or E
    expert = (D * E + 3 * D * F * m["n_shared_experts"]
              + 3 * D * F * m["moe_top_k"] * held / E)
    return (m["n_layers"] * mla_weights(m) + dense * 3 * D * m["d_ff"]
            + (m["n_layers"] - dense) * expert + D * m["vocab_size"])


def decode_step(m: Dict[str, Any], batch: int, pos: int) -> Dict[str, float]:
    """One whole decode step of ``batch`` tokens at position ``pos``:
    ``mla_decode`` and ``moe_decode``, plus the leading dense layers' FFN
    and the untied head, two FLOPs a weight a token and each weight read
    once.  The embedding rows read and the logits written (under 1% of
    the bytes at the cell's shapes) are left out."""
    D, V = m["d_model"], m["vocab_size"]
    rest = m.get("first_dense_layers", 0) * 3 * D * m["d_ff"] + D * V
    a, e = mla_decode(m, batch, pos), moe_decode(m, batch)
    wb = DTYPE_BYTES[m["compute_dtype"]]
    return {"flops": a["flops"] + e["flops"] + 2.0 * batch * rest,
            "bytes": a["bytes"] + e["bytes"] + wb * rest}


def decode_positions(traffic: Dict[str, Any]) -> range:
    """The positions a round's decode steps run at: after the batched
    prefill of ``prompt_len`` tokens, one step for each new token but the
    first."""
    P = traffic["prompt_len"]
    return range(P, P + traffic["new_tokens"] - 1)


#: op_names the TPU compiler gives the kernels it builds for a grouped GEMM
#: (``jax.lax.ragged_dot``): they carry no scope, and only the expert
#: layer has them
GROUPED_GEMM = ("ragged-dot",)


def scope_ms_per_step(result: Dict[str, Any], scope: str,
                      unscoped: tuple = ()) -> Optional[float]:
    """Device time of the ops under the named scope ``scope`` per run of
    the ``serve_step`` program in the traced window, mean over devices, in
    ms; ops whose op_name starts with one of ``unscoped`` count as the
    scope's.  None for an untraced run or a program without the scope."""
    pt = progtrace.of(result)
    per_dev = readers.step_runs(result, match="serve_step")
    if pt is None or per_dev is None:
        return None
    ms = []
    for dev, runs in per_dev:
        hlo = pt["hlo"].get(runs[0][0])
        names = progtrace.op_names(hlo) if hlo else {}
        names = {n: f"{scope}/{op}" if unscoped and op.startswith(unscoped)
                 else op for n, op in names.items()}
        ms.append(progtrace.scope_ms_per_run(devtrace.ops_within(dev, runs),
                                             names, scope, len(runs)))
    return None if None in ms else sum(ms) / len(ms)
