"""Production mesh builders.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is the
outermost (DCN) dimension so hierarchical collectives keep the slow hops
few and large.

Functions, not module constants: importing this module never touches jax
device state (the dry-run force-sets the host device count first).
"""
from __future__ import annotations

from jax.sharding import Mesh

from repro.runtime.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over host devices (tests / subprocess scaling runs)."""
    return make_mesh((data, model), ("data", "model"))
