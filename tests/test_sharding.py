"""Logical-axis sharding rules: dedupe, divisibility fallback, GQA rules."""
import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import (DEFAULT_RULES, _drop_nondividing,
                                        gqa_safe_rules, logical_spec,
                                        shard_hint, use_sharding)
from repro.launch.mesh import make_host_mesh, make_mesh


def test_logical_spec_basic():
    rules = dict(DEFAULT_RULES)
    spec = logical_spec(("batch", "seq", "embed"), rules)
    assert spec == P(("pod", "data"), None, None)


def test_logical_spec_dedupes_mesh_axis():
    rules = dict(DEFAULT_RULES, seq="model")
    spec = logical_spec(("batch", "seq", "vocab"), rules)
    assert spec == P(("pod", "data"), "model", None)   # vocab dropped


def test_drop_nondividing():
    mesh = AbstractMesh((2, 2), ("data", "model"))
    spec = _drop_nondividing(P("data", "model"), (10, 7), mesh)
    assert spec == P("data", None)    # 7 % 2 != 0


def test_gqa_safe_rules():
    mesh = AbstractMesh((1, 4), ("data", "model"))
    rules = gqa_safe_rules(2, mesh)       # 2 kv heads % 4 != 0
    assert rules["kv_proj"] is None
    rules = gqa_safe_rules(4, mesh)
    assert rules["kv_proj"] == "model"


def test_shard_hint_identity_without_binding():
    x = jnp.ones((4, 4))
    assert shard_hint(x, ("batch", "embed")) is x


def test_shard_hint_inside_binding_single_device():
    mesh = make_host_mesh()
    with use_sharding(mesh):
        y = jax.jit(lambda x: shard_hint(x * 2, ("batch", "embed")))(
            jnp.ones((4, 4)))
    assert float(y[0, 0]) == 2.0


def test_use_sharding_filters_missing_axes():
    mesh = make_mesh((1, 1), ("data", "model"))   # no "pod" axis
    with use_sharding(mesh) as rules:
        assert rules["batch"] == ("data",)


def test_train_state_specs_zero1_adds_dp_shard():
    from repro.configs import get_reduced
    from repro.launch.specs import train_state_specs
    cfg = get_reduced("smollm-135m")
    specs = train_state_specs(cfg, zero1=True, fsdp=False)
    # params untouched, moments augmented
    flat_p = jax.tree_util.tree_leaves(
        specs.params, is_leaf=lambda x: isinstance(x, tuple))
    flat_m = jax.tree_util.tree_leaves(
        specs.opt_state.mu, is_leaf=lambda x: isinstance(x, tuple))
    assert not any("dp_shard" in t for t in flat_p if isinstance(t, tuple))
    assert any("dp_shard" in t for t in flat_m if isinstance(t, tuple))


def test_train_state_specs_follow_the_optimizer_state():
    from repro.configs import get_reduced
    from repro.launch.specs import abstract_train_state, train_state_specs
    from repro.optim.optimizer import SGD
    cfg = get_reduced("smollm-135m")
    specs = train_state_specs(cfg, zero1=False, optimizer=SGD())
    assert specs.opt_state.step == ()
    assert specs.opt_state.momentum == specs.params
    # one spec at each array of the state SGD builds
    jax.tree.map(lambda a, s: None, abstract_train_state(cfg, SGD()), specs)
