"""Mixture-of-experts block with sort-based capacity dispatch and ABFT on
both the router GEMM and the expert-batched GEMMs.

Expert GEMMs are protected with *per-expert* checksums via
protected_grouped_matmul - the exact analogue of the paper's grouped
convolution (SS5.2): expert groups never mix, so per-group invariants are
exact. The top-k router decision itself is discrete (no linear invariant);
its GEMM is protected and the decision is covered by step-level recompute
(DESIGN.md SSArch-applicability).

Dispatch: flatten (token, k) assignments, argsort by expert id, give each
expert a contiguous capacity-C buffer (dropped tokens fall straight
through the residual), run the three expert GEMMs batched over E, and
scatter-add weighted outputs back. All shapes static => pjit/shard_map
friendly; experts shard over the 'model' axis, capacity rows over 'data'.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import (FaultReport, OpSpec, ProtectConfig, ambient_mode,
                        merge_verdicts, path_scope, protect_site,
                        protected_grouped_matmul, resolve_entry)
from .linear import apply_dense, init_dense
from .norms import activate

_GROUPED = OpSpec("grouped_matmul")


def _grouped(name: str, h, w, abft):
    """One expert-batched GEMM through the unified plan path: the ambient
    PlanEntry (policy per path; per-group checksums stay runtime-derived,
    SS5.2 exact-group invariants) or the threaded abft config."""
    entry = resolve_entry(name)
    if entry is not None or ambient_mode() is not None:
        return protect_site(name, (h, w), entry=entry, op=_GROUPED,
                            cfg=abft)
    return protected_grouped_matmul(h, w, cfg=abft)

F32 = jnp.float32


def init_moe(key, cfg, dtype=jnp.bfloat16) -> Dict:
    d, ff, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.num_experts
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    scale = d ** -0.5
    p = {
        "router": init_dense(kr, d, e, dtype=jnp.float32),  # router in fp32
        "gate": (jax.random.normal(kg, (e, d, ff), F32) * scale).astype(dtype),
        "up": (jax.random.normal(ku, (e, d, ff), F32) * scale).astype(dtype),
        "down": (jax.random.normal(kd, (e, ff, d), F32) * ff ** -0.5
                 ).astype(dtype),
    }
    if cfg.n_shared_experts:
        from .ffn import init_ffn
        p["shared"] = init_ffn(ks, d, (cfg.moe_d_ff or cfg.d_ff)
                               * cfg.n_shared_experts, dtype=dtype)
    return p


def apply_moe(params: Dict, x: jnp.ndarray, cfg,
              abft: ProtectConfig) -> Tuple[jnp.ndarray, FaultReport, jnp.ndarray]:
    """x: (B, S, d) -> (y, report, aux_loss)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)

    logits, rep = apply_dense(params["router"], xt.astype(F32), abft,
                              name="router")
    probs = jax.nn.softmax(logits.astype(F32), axis=-1)            # (T, E)
    top_w, top_e = jax.lax.top_k(probs, k)                         # (T, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=F32), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * mean_probs)

    cap = int(max(1, round(cfg.capacity_factor * t * k / e)))

    flat_e = top_e.reshape(-1)                                     # (T*k,)
    order = jnp.argsort(flat_e)                                    # stable
    sorted_e = flat_e[order]
    group_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(t * k, dtype=jnp.int32) - group_start
    valid = pos < cap
    slot = jnp.where(valid, sorted_e * cap + pos, e * cap)         # drop -> OOB
    token_of = order // k

    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(xt[token_of])
    h = buf[:e * cap].reshape(e, cap, d)
    # pin the expert-parallel layout: experts over 'model', capacity rows
    # over the data axes. Without this GSPMD materialises the dispatch
    # scatter as a full-buffer all-reduce per layer (SSPerf cell 2).
    from repro.runtime.sharding import maybe_constrain
    h = maybe_constrain(h, "model", "data", None)

    g, r1 = _grouped("gate", h, params["gate"], abft)
    u, r2 = _grouped("up", h, params["up"], abft)
    act = activate(g, cfg.act) * u
    y, r3 = _grouped("down", act, params["down"], abft)
    for r in (r1, r2, r3):
        rep = merge_verdicts(rep, r)

    yb = jnp.concatenate([y.reshape(e * cap, d),
                          jnp.zeros((1, d), y.dtype)], axis=0)
    w_assign = top_w.reshape(-1)[order]                            # (T*k,)
    contrib = yb[slot] * jnp.where(valid, w_assign, 0.0)[:, None].astype(y.dtype)
    out = jnp.zeros((t, d), F32).at[token_of].add(contrib.astype(F32))
    from repro.runtime.sharding import maybe_constrain
    out = maybe_constrain(out, "data", None)

    if "shared" in params:
        from .ffn import apply_ffn
        with path_scope("shared"):
            ys, rs = apply_ffn(params["shared"], xt, abft, cfg.act)
        out = out + ys.astype(F32)
        rep = merge_verdicts(rep, rs)

    return out.astype(x.dtype).reshape(b, s, d), rep, aux
