"""Elastic scaling: re-plan the mesh and re-place checkpointed state.

Checkpoints store unsharded arrays (checkpoint.manager), so scaling is:
  1. build the new mesh (fewer/more hosts),
  2. recompute param/optimizer shardings for it (runtime.sharding rules
     are mesh-shape agnostic),
  3. device_put the restored tree onto the new shardings,
  4. rescale per-host batch so the global batch is preserved.

The step-indexed data pipeline guarantees the token stream is identical
across the rescale.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh

from .sharding import param_shardings


def replan_mesh(old_mesh: Mesh, lost_hosts: int, hosts_per_ring: int = 1
                ) -> Tuple[int, ...]:
    """Shrink the data axis by the lost hosts, keeping the model axis (TP
    topology is fixed by the model); returns the new mesh shape."""
    shape = dict(zip(old_mesh.axis_names, old_mesh.devices.shape))
    if "data" not in shape:
        raise ValueError("mesh has no data axis to shrink")
    new_data = shape["data"] - lost_hosts * hosts_per_ring
    if new_data < 1:
        raise ValueError("cannot shrink below one data shard")
    shape["data"] = new_data
    return tuple(shape[a] for a in old_mesh.axis_names)


def reshard_state(state, new_mesh: Mesh):
    """Place a (restored, host-resident) state pytree onto a new mesh."""
    params = state["params"] if isinstance(state, dict) and "params" in state \
        else state
    shardings = param_shardings(params, new_mesh)
    if isinstance(state, dict) and "params" in state:
        out = dict(state)
        out["params"] = jax.tree.map(jax.device_put, state["params"],
                                     shardings)
        return out
    return jax.tree.map(jax.device_put, state, shardings)


def rescale_batch(global_batch: int, old_hosts: int, new_hosts: int) -> int:
    """Per-host batch after a rescale (global batch preserved; pad the
    final microbatch when not divisible)."""
    per = global_batch // new_hosts
    if per * new_hosts != global_batch:
        per += 1
    return per
