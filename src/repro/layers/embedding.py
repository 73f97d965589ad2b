"""Token embeddings and LM heads.

Embedding lookup is a gather (no weight-stationary linear invariant - it
is one-hot @ W but the one-hot side is data; noted in DESIGN.md); the LM
head GEMM *is* protected, through the unified protect_op path: the plan
entry at "embed/head" (untied) or "embed/table" (tied, via the
plan.W_VIEWS "tied_head" derivation, so the head checksums are encoded
offline from the embedding table leaf). MusicGen-style multi-codebook
I/O: K embedding tables summed on input, K protected heads on output
(the EnCodec frontend is a stub per the assignment - tokens arrive
precomputed).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import (FaultReport, ProtectConfig, ambient_mode,
                        path_scope, protect_site, protected_matmul,
                        resolve_entry)
from .linear import apply_dense, init_dense

F32 = jnp.float32


def init_embedding(key, cfg, dtype=jnp.bfloat16) -> Dict:
    v, d = cfg.vocab_size, cfg.d_model
    nc = max(cfg.num_codebooks, 1)
    keys = jax.random.split(key, nc + 1)
    p = {"table": (jax.random.normal(keys[0], (nc, v, d), F32)
                   * d ** -0.5).astype(dtype)}
    if not cfg.tie_embeddings:
        p["head"] = init_dense(keys[1], d, nc * v, dtype=dtype)
    return p


def embed(params: Dict, tokens: jnp.ndarray, cfg) -> jnp.ndarray:
    """tokens: (B, S) or (B, S, K) for multi-codebook archs."""
    table = params["table"]
    if cfg.num_codebooks:
        # tokens (B,S,K), table (K,V,d): sum the K codebook embeddings
        per_cb = jax.vmap(lambda t, tk: t[tk], in_axes=(0, 2), out_axes=2)(
            table, tokens)                          # (B, S, K, d)
        return per_cb.sum(axis=2)
    return table[0][tokens]


def logits_head(params: Dict, x: jnp.ndarray, cfg,
                abft: ProtectConfig = None
                ) -> Tuple[jnp.ndarray, FaultReport]:
    """x: (B, S, d) -> (B, S, V) or (B, S, K, V)."""
    b, s, d = x.shape
    v = cfg.vocab_size
    nc = max(cfg.num_codebooks, 1)
    with path_scope("embed"):
        if cfg.tie_embeddings:
            w = params["table"].reshape(nc * v, d).T       # (d, nc*V)
            entry = resolve_entry("table")
            if (entry is not None or ambient_mode() is not None
                    or (abft is not None and abft.enabled)):
                y, rep = protect_site("table", (x, w), entry=entry,
                                      cfg=abft)
            else:
                # the protected op's arithmetic (see apply_dense)
                y, rep = protected_matmul(x, w, cfg=None)
        else:
            y, rep = apply_dense(params["head"], x, abft, name="head")
    y = y.astype(F32)
    if cfg.num_codebooks:
        return y.reshape(b, s, nc, v), rep
    return y.reshape(b, s, v), rep
