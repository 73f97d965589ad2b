"""ABFT-protected dense layer: every weight GEMM in the framework routes
through here, so the paper's workflow covers the model's dominant FLOPs.

Call sites name themselves (`apply_dense(..., name="wq")`) inside the
layer's `path_scope`: when an ambient plan context is active (a
ProtectedModel run), the PlanEntry at the joined param-tree path supplies
the offline policy config + precomputed weight checksums, and the ambient
execution mode (detect_only / correct) decides what the call returns -
layers never thread a ProtectConfig for the planned path."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import (DEFAULT_CONFIG, FaultReport, ProtectConfig,
                        ambient_mode, protect_site, protected_matmul,
                        resolve_entry)

F32 = jnp.float32


def init_dense(key: jax.Array, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.bfloat16, scale: Optional[float] = None):
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out), F32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def apply_dense(params, x: jnp.ndarray,
                cfg: Optional[ProtectConfig] = DEFAULT_CONFIG,
                wck=None, entry=None, name: str = "w"
                ) -> Tuple[jnp.ndarray, FaultReport]:
    """y = x @ W (+ b), protected when cfg.enabled. x: (..., d_in).

    Resolution order: explicit `entry` (a core.plan.PlanEntry), then the
    ambient plan context's entry at the current path + `name`, then the
    legacy cfg/wck per-call path. Under an ambient "detect_only" mode the
    second return is a DetectEvidence carry instead of a FaultReport."""
    w = params["w"]
    b = params.get("b")
    if entry is None:
        entry = resolve_entry(name)
    if entry is not None or ambient_mode() is not None:
        # planned path: the entry's offline cfg rules; without an entry
        # the threaded cfg is the fallback (None -> unprotected) and the
        # carry still speaks the ambient mode's type (DetectEvidence in
        # detect passes)
        inputs = (x, w) if b is None else (x, w, b)
        y, rep = protect_site(name, inputs, entry=entry, cfg=cfg)
        return y.astype(x.dtype), rep
    # unprotected too: the protected op's own arithmetic (the disabled
    # branch of protected_matmul), so protection changes no value
    y, rep = protected_matmul(x, w, wck=wck, bias=b, cfg=cfg)
    return y.astype(x.dtype), rep
