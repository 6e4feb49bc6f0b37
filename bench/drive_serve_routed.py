"""Serving cells of a model that chooses its experts by score: the closed
loop of ``drive_serve`` (same rounds, window and spans), judged by the
95th percentile of the served tokens' gaps and by the share of wide gaps.

A served token's gap is how far its reference logit lies below the
reference's best at its position (``drive_serve.widest_gap`` takes the
largest).  In bfloat16, a token whose expert choice in some layer lies
near a tie can take another expert than the float32 reference takes, and
such a flip moves that token's logits by up to about 1.5, while the other
tokens move by some 0.08 (PERF.md section 2).  Over a thousand tokens the
widest gap then reads the rarest flip, not the precision: it is reported
as ``logit_gap`` and not compared.  ``logit_gap_p95`` reads the
precision: computing in 8-bit floats moves most tokens and fails it.
``wide_gap_share``, the share of judged tokens whose gap exceeds ``WIDE``,
reads a fault too rare for the percentile but too gross for a flip, such
as a wrong first token in every request (one token in ``new_tokens``).
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from bench import manifest

_serve = manifest.load_driver("serve")
setup, serve_round, window = _serve.setup, _serve.serve_round, _serve.window
release, sample = _serve.release, _serve.sample

PERCENTILE = 95
#: a gap no bfloat16 rounding gives outside an expert flip
WIDE = 0.5


def gaps(logits: np.ndarray, seqs: np.ndarray, prompt_len: int,
         choice: np.ndarray | None = None) -> np.ndarray:
    """Each served token's gap, (n, new_tokens); ``choice`` picks other
    tokens to judge in place of the served ones (the control's)."""
    G = seqs.shape[1] - prompt_len
    at = logits[:, prompt_len - 1:prompt_len - 1 + G]
    tok = seqs[:, prompt_len:] if choice is None else choice
    return at.max(-1) - np.take_along_axis(at, tok[..., None], axis=-1)[..., 0]


def numbers(g: np.ndarray) -> Dict[str, Dict[str, Any]]:
    return {"logit_gap": {"value": float(g.max()), "tokens": int(g.size)},
            "logit_gap_p95": {"value": float(np.percentile(g, PERCENTILE)),
                              "tokens": int(g.size)},
            "wide_gap_share": {"value": float(np.mean(g > WIDE)),
                               "tokens": int(g.size)}}


def check(ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    run = ctx["run"]
    seqs = sample(ctx)
    ctx["served"] = []
    logits = _serve.reference_logits(run, seqs)
    return numbers(gaps(logits, seqs, run["traffic"]["prompt_len"]))


def control(run: Dict[str, Any]) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """The readings a limit is set from, as ``bench/control.py`` takes them
    for ``drive_serve``: one round at the cell's load, then the program's
    served tokens and the 8-bit control's choices, each against the
    float32 reference."""
    ctx = setup(run)
    first = ctx["round"]
    serve_round(ctx)
    ctx["window_rounds"] = (first, ctx["round"])
    release(ctx)
    seqs = sample(ctx)
    P = run["traffic"]["prompt_len"]
    ref = _serve.reference_logits(run, seqs)
    low = _serve.reference_logits(run, seqs, "fp8")
    G = seqs.shape[1] - P
    choice = np.argmax(low[:, P - 1:P - 1 + G], axis=-1)
    return {"program": numbers(gaps(ref, seqs, P)),
            "fp8": numbers(gaps(ref, seqs, P, choice))}
