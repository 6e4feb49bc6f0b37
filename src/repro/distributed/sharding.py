"""Logical-axis sharding: rules mapping model axes -> mesh axes.

Models annotate params (``*_specs`` pytrees of logical-axis tuples) and
activations (``shard_hint``) with *logical* names; this module binds them to
mesh axes at launch time.  Outside an active binding, ``shard_hint`` is the
identity, so all model code runs unmodified on a single CPU device (smoke
tests) and under any mesh (dry-run / production).

Default rules (the baseline sharding scheme recorded in EXPERIMENTS.md):

  batch   -> ("pod", "data")   DP over pods and the data axis
  q_proj / kv_proj / heads / ffn / experts / vocab -> "model"   TP / EP
  embed   -> None (replicated activations dim)
  seq     -> None (SP variants map it to "model" for long-context shapes)
  layers / kv_seq -> None

GQA note: ``kv_proj`` maps to "model" only when n_kv_heads divides the mesh
axis; otherwise the launcher drops it to None (kv heads replicated), the
standard GQA TP fallback.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisName = Union[str, Tuple[str, ...], None]
Rules = Mapping[str, AxisName]

DEFAULT_RULES: Dict[str, AxisName] = {
    "batch": ("pod", "data"),
    "seq": None,
    "attn_seq": None,   # SP fallback for attention internals
    "embed": None,
    "q_proj": "model",
    "kv_proj": "model",
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "experts": "model",
    "expert_ffn": None,     # swapped with "experts" when E % model_size != 0
    "vocab": "model",
    "layers": None,
    "kv_seq": None,
    "head_dim": None,     # decode-cache dh sharding (serve rules map it to model)
    "dp_shard": ("pod", "data"),   # ZeRO/FSDP param & moment sharding
}

_state = threading.local()


def _active() -> Optional[Tuple[Mesh, Rules]]:
    return getattr(_state, "binding", None)


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: Optional[Rules] = None):
    """Bind a mesh + logical rules; nests with the jax mesh context."""
    rules = dict(DEFAULT_RULES, **(rules or {}))
    # drop rule entries naming axes the mesh doesn't have (single-pod mesh
    # has no "pod" axis)
    def _filter(axis: AxisName) -> AxisName:
        names = set(mesh.axis_names)
        if isinstance(axis, tuple):
            kept = tuple(a for a in axis if a in names)
            return kept if kept else None
        return axis if (axis is None or axis in names) else None

    rules = {k: _filter(v) for k, v in rules.items()}
    prev = _active()
    _state.binding = (mesh, rules)
    try:
        with mesh:
            yield rules
    finally:
        _state.binding = prev


def logical_spec(axes: Sequence[Optional[str]],
                 rules: Optional[Rules] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec.

    A mesh axis may appear at most once in a spec; when two logical axes
    map to the same mesh axis (e.g. seq and vocab both -> "model" under
    sequence parallelism), the first keeps it and later ones drop to None.
    """
    binding = _active()
    if rules is None:
        if binding is None:
            return P()
        rules = binding[1]
    used: set = set()
    out = []
    for a in axes:
        m = rules.get(a) if a is not None else None
        names = m if isinstance(m, tuple) else (m,) if m else ()
        if any(n in used for n in names):
            out.append(None)
            continue
        used.update(names)
        out.append(m)
    return P(*out)


def _drop_nondividing(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Replace spec entries whose mesh extent doesn't divide the dim size."""
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        ext = 1
        for n in names:
            ext *= mesh.shape[n]
        out.append(entry if dim % ext == 0 else None)
    return P(*out)


def shard_hint(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint against the active binding (identity if none).

    Axes whose mesh extent doesn't divide the dimension are dropped
    (replicated) rather than erroring — odd vocab sizes (51865, 32001, …)
    and head counts are the norm in the assigned configs.
    """
    binding = _active()
    if binding is None:
        return x
    mesh, rules = binding
    spec = _drop_nondividing(logical_spec(axes, rules), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def is_logical_axes(x: Any) -> bool:
    """A spec leaf: a tuple of logical axis names (``None`` = replicated).

    Named tuples of specs (``TrainState``, ``AdamWState``) are containers,
    not leaves.
    """
    return isinstance(x, tuple) and all(
        isinstance(a, (str, type(None))) for a in x)


def specs_to_shardings(specs: Any, like: Any, mesh: Mesh,
                       rules: Optional[Rules] = None) -> Any:
    """NamedShardings for the arrays of ``like`` from their logical axes.

    ``like`` holds arrays or ShapeDtypeStructs, ``specs`` a logical-axis
    tuple at each of their places.  ``rules`` default to the active
    binding's.  As in ``shard_hint``, a mesh axis that doesn't divide a
    dimension is dropped (that dimension replicated): odd vocab sizes, 60
    experts and batches the data axis doesn't divide are the norm.
    """

    def to_sharding(leaf, axes):
        spec = _drop_nondividing(logical_spec(axes, rules), leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree.map(to_sharding, like, specs)


def validate_divisibility(shapes: Any, shardings: Any) -> None:
    """Raise early (with a useful message) when a dim doesn't divide."""
    flat_sh, _ = jax.tree_util.tree_flatten(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    flat_shape, _ = jax.tree_util.tree_flatten(shapes)
    for arr, sh in zip(flat_shape, flat_sh):
        shape = getattr(arr, "shape", None)
        if shape is None or not isinstance(sh, NamedSharding):
            continue
        mesh = sh.mesh
        for dim, spec in zip(shape, sh.spec):
            if spec is None:
                continue
            names = spec if isinstance(spec, tuple) else (spec,)
            size = 1
            for n in names:
                size *= mesh.shape[n]
            if dim % size:
                raise ValueError(
                    f"dim {dim} not divisible by mesh extent {size} "
                    f"({names}) for shape {shape}")


def gqa_safe_rules(n_kv_heads: int, mesh: Mesh,
                   base: Optional[Rules] = None) -> Dict[str, AxisName]:
    """Drop kv_proj/kv_heads TP when kv heads don't divide the model axis."""
    rules = dict(DEFAULT_RULES, **(base or {}))
    model_size = mesh.shape.get("model", 1) if hasattr(mesh, "shape") else 1
    if n_kv_heads % max(model_size, 1):
        rules["kv_proj"] = None
        rules["kv_heads"] = None
    return rules
