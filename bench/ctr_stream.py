"""A copy of the data pipeline's click stream (``SyntheticCTRStream``), so
that the reference of a host-fed cell makes its batches without the code
under test."""
from __future__ import annotations

from typing import Dict

import numpy as np


def batch(seed: int, step: int, global_batch: int, d_in: int
          ) -> Dict[str, np.ndarray]:
    w = (np.random.default_rng(seed).standard_normal(d_in) / np.sqrt(d_in)
         ).astype(np.float32)
    rng = np.random.default_rng((seed * 1_000_003 + step) * 4096)
    x = rng.standard_normal((global_batch, d_in)).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-4.0 * x @ w))
    y = (rng.random(global_batch) < p).astype(np.float32)
    return {"features": x, "click": y}
