"""Architecture registry: the 10 assigned archs, then the configurations
added for the benchmark since, then the paper's case study (last)."""
from typing import Callable, Dict, List, Tuple

from repro.configs import (dlrm_mlp, hymba_1_5b, internvl2_26b, minitron_8b,
                           moonlight_16b_a3b, qwen2_5_3b, qwen2_7b,
                           qwen2_moe_a2_7b, qwen3_moe_30b_a3b, smollm_135m,
                           whisper_tiny, xlstm_125m)
from repro.configs.shapes import SHAPES, ShapeSpec, applicable, cells
from repro.models.config import ModelConfig

_ASSIGNED = [whisper_tiny, qwen2_5_3b, minitron_8b, smollm_135m, qwen2_7b,
             qwen2_moe_a2_7b, qwen3_moe_30b_a3b, xlstm_125m, internvl2_26b,
             hymba_1_5b]
_MODULES = _ASSIGNED + [moonlight_16b_a3b, dlrm_mlp]

REGISTRY: Dict[str, "module"] = {m.ARCH: m for m in _MODULES}

#: the 10 assigned architectures (dlrm-mlp is the paper's own, extra)
ASSIGNED: Tuple[str, ...] = tuple(m.ARCH for m in _ASSIGNED)


def get_config(arch: str) -> ModelConfig:
    return REGISTRY[arch].config()


def get_reduced(arch: str) -> ModelConfig:
    return REGISTRY[arch].reduced()


def list_archs() -> List[str]:
    return list(REGISTRY)
