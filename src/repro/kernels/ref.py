"""Pure-jnp oracles for the Pallas kernels (per-kernel allclose targets)."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.core.types import PRECISION, op_operand, op_operand_dtype

F32 = jnp.float32


def _op_dot(d: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """The protected GEMM's arithmetic (core.protected.op_matmul)."""
    dt = op_operand_dtype(d.dtype)
    return jnp.dot(d.astype(dt), w.astype(dt), preferred_element_type=F32,
                   precision=PRECISION)


def abft_matmul_ref(d: jnp.ndarray, w: jnp.ndarray, bm: int,
                    out_dtype=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for kernels.abft_matmul: fp32-accumulated matmul + the same
    tile partials (computed from the fp32 product, as the kernel does)."""
    out_dtype = out_dtype or d.dtype
    acc = _op_dot(d, w)
    return acc.astype(out_dtype), checksum_reduce_ref(acc, bm)


def checksum_reduce_ref(o: jnp.ndarray, bm: int) -> jnp.ndarray:
    """Oracle for kernels.checksum_reduce: (N/bm, 3, M) rows colsum,
    locally row-weighted colsum, column sum of squares per row tile."""
    n, m = o.shape
    tiled = o.astype(F32).reshape(n // bm, bm, m)
    wcolsum = jnp.einsum("tbm,b->tm", tiled, jnp.arange(bm, dtype=F32),
                         precision=PRECISION)
    return jnp.stack([tiled.sum(axis=1), wcolsum,
                      (tiled * tiled).sum(axis=1)], axis=1)


def conv2d_ref(d: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
               padding="VALID", groups: int = 1) -> jnp.ndarray:
    """Independent oracle for checksums.conv2d: im2col (static strided
    slices) + fp32 matmul, never touching the conv primitive - so campaign
    trials that compare against it exercise a genuinely different lowering.

    d: (N, Ch, H, W), w: (M, Ch/G, R, R) -> (N, M, E, E'), NCHW like conv2d.
    """
    n, ch, h, wd = d.shape
    m, chg, r, _ = w.shape
    if padding == "SAME":
        # XLA's SAME is asymmetric: low side gets the floor of the total
        def _same(size):
            out = -(-size // stride)
            total = max((out - 1) * stride + r - size, 0)
            return total // 2, total - total // 2
        pads = (_same(h), _same(wd))
    elif padding == "VALID":
        pads = ((0, 0), (0, 0))
    else:
        pads = ((int(padding),) * 2,) * 2
    if any(p for lohi in pads for p in lohi):
        d = jnp.pad(d, ((0, 0), (0, 0), *pads))
        h, wd = h + sum(pads[0]), wd + sum(pads[1])
    e1 = (h - r) // stride + 1
    e2 = (wd - r) // stride + 1
    cols = [d[:, :, dy:dy + e1 * stride:stride, dx:dx + e2 * stride:stride]
            for dy in range(r) for dx in range(r)]
    # (N, Ch, R*R, E1, E2) -> (N, G, Ch/G * R*R, E1*E2)
    pat = op_operand(jnp.stack(cols, axis=2))
    pat = pat.reshape(n, groups, chg * r * r, e1 * e2)
    wm = op_operand(w).reshape(groups, m // groups, chg * r * r)
    o = jnp.einsum("ngkp,gmk->ngmp", pat, wm, precision=PRECISION)
    return o.reshape(n, m, e1, e2).astype(d.dtype)


def chunk_sums_ref(o: jnp.ndarray, rb: int, cb: int):
    """Oracle for ops.chunk_sums_from_partials: the (s5, s6, s7, sumsq)
    per-chunk values computed directly from O."""
    n, m = o.shape
    nb, mb = n // rb, m // cb
    o4 = o.astype(F32).reshape(nb, rb, mb, cb)
    s5 = jnp.einsum("arbc->ab", o4)
    s6 = jnp.einsum("arbc,r->ab", o4, jnp.arange(rb, dtype=F32),
                    precision=PRECISION)
    s7 = jnp.einsum("arbc,c->ab", o4, jnp.arange(cb, dtype=F32),
                    precision=PRECISION)
    sumsq = jnp.einsum("arbc,arbc->ab", o4, o4, precision=PRECISION)
    return s5, s6, s7, sumsq


def chunk_checksums_ref(d: jnp.ndarray, w: jnp.ndarray, rb: int, cb: int):
    """Exact per-chunk (c5, c6, c7, absdot) of the raw product D @ W,
    straight from the definition (locally index-weighted, fp32): the
    checksum predictions abft_matmul_detect compares against."""
    d32, w32 = op_operand(d), op_operand(w)
    o = _op_dot(d, w)
    c5, c6, c7, _ = chunk_sums_ref(o, rb, cb)
    ad = jnp.dot(jnp.abs(d32), jnp.abs(w32), precision=PRECISION)
    n, m = o.shape
    absdot = ad.reshape(n // rb, rb, m // cb, cb).sum(axis=(1, 3))
    return c5, c6, c7, absdot


def abft_matmul_detect_ref(d: jnp.ndarray, w: jnp.ndarray, c5, c6, c7,
                           absdot, rb: int, cb: int, tau_a: float,
                           tau_b: float, weighted: bool = True,
                           out_dtype=None):
    """Oracle for kernels.abft_matmul_detect: (o, flag, score) per
    (rb x cb) chunk with the kernel's threshold model."""
    out_dtype = out_dtype or d.dtype
    acc = _op_dot(d, w)
    s5, s6, s7, sumsq = chunk_sums_ref(acc, rb, cb)
    tau5 = tau_a * jnp.sqrt(jnp.maximum(sumsq, 0.0)) + tau_b * absdot + 1e-30
    pairs = [(c5, s5, tau5)]
    if weighted:
        pairs += [(c6, s6, tau5 * float(max(rb - 1, 1))),
                  (c7, s7, tau5 * float(max(cb - 1, 1)))]
    flag = jnp.zeros(c5.shape, bool)
    score = jnp.zeros(c5.shape, F32)
    for c, s, t in pairs:
        bad = ~(jnp.isfinite(c) & jnp.isfinite(s))
        flag = flag | bad | (jnp.abs(c - s) > t)
        score = jnp.maximum(score, jnp.where(bad, jnp.inf, jnp.abs(c - s) / t))
    return acc.astype(out_dtype), flag.astype(jnp.int32), score


def conv_detect_sums_ref(o: jnp.ndarray):
    """Oracle for kernels.ops.conv_detect_sums: per-payload (s5, s6, s7)
    of O[N,M,E,E] and its scalar sum of squares."""
    n, m = o.shape[:2]
    o3 = o.astype(F32).reshape(n, m, -1)
    s5 = o3.sum(axis=(0, 1))
    s6 = jnp.einsum("nmp,n->p", o3, jnp.arange(n, dtype=F32),
                    precision=PRECISION)
    s7 = jnp.einsum("nmp,m->p", o3, jnp.arange(m, dtype=F32),
                    precision=PRECISION)
    return s5, s6, s7, jnp.sum(o3 * o3)
