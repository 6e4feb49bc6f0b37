"""Operations and bytes of the benchmarked computations, from their shapes.

Counts are of the algorithm, whatever implements it: a multiply-add is two
operations, and work that the algorithm does not need (the gradient of the
input features) is not counted.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def mlp_train_flops(batch: int, widths: Sequence[int]) -> float:
    """One training step of the MLP tower with a one-logit head.

    Forward and weight-gradient GEMMs of every layer and of the head, and
    activation-gradient GEMMs of every layer but the first, whose input is
    data: 2·B·d_in·d_out operations each.
    """
    dims = [(widths[i - 1] if i else widths[0], w)
            for i, w in enumerate(widths)] + [(widths[-1], 1)]
    gemm = [2.0 * batch * d_in * d_out for d_in, d_out in dims]
    return 2.0 * sum(gemm) + sum(gemm[1:])


def lm_param_counts(m: Dict[str, Any]) -> Dict[str, float]:
    """Parameters of a dense GQA + SwiGLU decoder with tied embeddings."""
    d, f, L = m["d_model"], m["d_ff"], m["n_layers"]
    dh = m.get("head_dim") or d // m["n_heads"]
    q, kv = m["n_heads"] * dh, m["n_kv_heads"] * dh
    per_layer_matmul = d * q + 2 * d * kv + q * d + 3 * d * f
    embed = m["vocab_size"] * d
    norms = (2 * L + 1) * d
    return {"matmul": float(L * per_layer_matmul), "embed": float(embed),
            "norms": float(norms),
            "total": float(L * per_layer_matmul + embed + norms)}


def lm_decode_step(m: Dict[str, Any], batch: int, pos: int) -> Dict[str, float]:
    """One decode step of ``batch`` tokens at position ``pos`` (0-based).

    FLOPs: the layer GEMMs and the tied head, 2 per weight per token, and
    attention over the ``pos + 1`` live positions (scores and values).
    Bytes: every weight read once at the compute dtype, and the live
    keys and values of the cache read once.
    """
    d, L = m["d_model"], m["n_layers"]
    dh = m.get("head_dim") or d // m["n_heads"]
    n = lm_param_counts(m)
    live = pos + 1
    flops = (2.0 * batch * (n["matmul"] + n["embed"])
             + 4.0 * batch * L * m["n_heads"] * dh * live)
    wb = DTYPE_BYTES[m["compute_dtype"]]
    kv_bytes = 2.0 * L * batch * live * m["n_kv_heads"] * dh * wb
    return {"flops": flops, "bytes": n["total"] * wb + kv_bytes}


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def peak_for(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind`` (``peaks.json``);
    a kind not in the table is an error."""
    import json
    from pathlib import Path
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]
