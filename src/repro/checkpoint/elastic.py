"""Elastic scaling: reshard a checkpoint onto a different mesh.

Scenario: a 16×16 pod loses a row (hardware failure) and the job must
restart on 12×16, or scale from 1 to 2 pods.  Because checkpoints store
*full logical* arrays (see checkpointer.py), resharding is pure metadata:
build the new mesh, derive NamedShardings from the same logical-axis specs
under the new axis sizes (divisibility fallbacks recomputed), and
device_put at restore.

Also provides batch-schedule remapping: with the same global batch and a
different host count, each surviving host's shard of the batch changes —
``repro.data.pipeline`` batches are pure functions of (seed, step, host_id),
so the remap is just constructing new DataConfigs.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

from jax.sharding import Mesh

from repro.data.pipeline import DataConfig
from repro.distributed.sharding import specs_to_shardings, use_sharding


def restore_on_mesh(checkpointer, like: Any, specs: Any, mesh: Mesh,
                    rules=None, step: Optional[int] = None) -> Tuple[Any, int]:
    """Restore a checkpoint saved on any topology onto ``mesh``."""
    with use_sharding(mesh, rules):
        shardings = specs_to_shardings(specs, like, mesh)
        return checkpointer.restore(like, step=step, shardings=shardings)


def remap_data_configs(old: DataConfig, new_n_hosts: int) -> list[DataConfig]:
    """Recompute per-host data configs after an elastic resize."""
    if old.global_batch % new_n_hosts:
        raise ValueError(
            f"global batch {old.global_batch} must divide new host count "
            f"{new_n_hosts}")
    import dataclasses
    return [dataclasses.replace(old, n_hosts=new_n_hosts, host_id=h)
            for h in range(new_n_hosts)]
