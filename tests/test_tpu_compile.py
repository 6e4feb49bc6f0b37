"""The main path's kernels and train steps compile for a TPU v5e.

Compile-only: the topology is described (``v5e:2x2``), no chip is attached
and nothing runs, so these say that the chip's compiler accepts the
programs at full width, not that they are right or fast.  The topology is
described inside a fixture, never while a module is imported: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.core.hardware import TPU_V5E
from repro.core.hlo_analysis import analyze_compiled
from repro.distributed.sharding import gqa_safe_rules, use_sharding
from repro.kernels.blocked_matmul import blocked_matmul
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.launch.specs import abstract_train_state
from repro.launch.train import jit_train_step
from repro.optim.optimizer import AdamW
from repro.train.loop import TrainStepConfig

DLRM_BATCH = 8192


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_blocked_matmul_compiles(one_chip):
    n = 4096
    a = _sds((n, n), jnp.bfloat16, one_chip)
    bias = _sds((n,), jnp.bfloat16, one_chip)
    fn = functools.partial(blocked_matmul, act="relu", interpret=False)
    compiled = jax.jit(fn).lower(a, a, bias).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    B, H, K, S, dh = 1, 9, 3, 2048, 64          # smollm-135m's heads
    q = _sds((B, H, S, dh), jnp.bfloat16, one_chip)
    kv = _sds((B, K, S, dh), jnp.bfloat16, one_chip)
    fn = functools.partial(flash_attention_bhsd, causal=True, interpret=False)
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_dlrm_step(devices):
    """The train launcher's dlrm-mlp step, on a ("data",) mesh of
    ``devices`` (model axis 1)."""
    cfg = get_config("dlrm-mlp")
    opt = AdamW()
    mesh = Mesh(np.array(devices).reshape(len(devices), 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    batch = {"features": jax.ShapeDtypeStruct(
                 (DLRM_BATCH, cfg.mlp_widths[0]), jnp.float32),
             "click": jax.ShapeDtypeStruct((DLRM_BATCH,), jnp.float32)}
    with use_sharding(mesh, gqa_safe_rules(cfg.n_kv_heads, mesh)):
        step, state_sh = jit_train_step(cfg, opt, TrainStepConfig(), mesh,
                                        batch)
        state = jax.tree.map(lambda a, s: _sds(a.shape, a.dtype, s),
                             abstract_train_state(cfg, opt), state_sh)
        return step.lower(state, batch).compile()


def test_dlrm_train_step_fits_one_chip(topo):
    compiled = _compile_dlrm_step(topo.devices[:1])
    peak = analyze_compiled(compiled, 1).peak_memory_per_device
    assert 0 < peak < TPU_V5E.hbm_capacity_bytes


def test_dlrm_train_step_all_reduces_on_four_chips(topo):
    compiled = _compile_dlrm_step(topo.devices[:4])
    assert "all-reduce" in compiled.as_text()
