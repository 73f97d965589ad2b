"""Input/output checksum encodings (paper Eq. 5/6), for matmul and conv.

Matmul block view: O[N,M] = D[N,K] @ W[K,M]. Rows of D are the fmap blocks,
columns of W are the kernel blocks, and (x) degenerates to a dot product -
every identity of the paper holds verbatim with per-block payload P=1.

Conv view (paper's native form): D[N,Ch,H,H], W[M,Ch,R,R], O[N,M,E,E];
blocks are the 3D substructures and the payload is the E*E output map.

All checksums are carried in fp32 regardless of the operand dtype. They
encode the operand values the op multiplies (`types.op_operand`), and
every product here runs at `types.PRECISION`.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .types import (PRECISION, OutputChecksums, OutputSums, op_operand,
                    op_operand_dtype)

F32 = jnp.float32


def _iota(n: int) -> jnp.ndarray:
    return jnp.arange(n, dtype=F32)


def _mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a @ b at the protected path's precision."""
    return jnp.matmul(a, b, precision=PRECISION)


# --------------------------------------------------------------------------
# matmul path
# --------------------------------------------------------------------------

def encode_d_matmul(d: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """C_d1, C_d2 of D[N,K] (fp32). One pass over D; XLA fuses both sums."""
    d32 = op_operand(d)
    cd1 = jnp.sum(d32, axis=0)
    cd2 = _mm(_iota(d.shape[0]), d32)
    return cd1, cd2


def encode_w_matmul(w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """C_w1, C_w2 of W[K,M] (fp32). Precomputable for weight-stationary ops."""
    w32 = op_operand(w)
    cw1 = jnp.sum(w32, axis=1)
    cw2 = _mm(w32, _iota(w.shape[1]))
    return cw1, cw2


def output_sums_matmul(o: jnp.ndarray) -> OutputSums:
    """All seven summations + sumsq of O[N,M] in fp32 (single logical pass;
    XLA fuses the reductions). Payload axis P=1 is appended."""
    n, m = o.shape
    o32 = o.astype(F32)
    wn = _iota(n)
    wm = _iota(m)
    s1 = jnp.sum(o32, axis=0)          # (M,)
    s2 = jnp.sum(o32, axis=1)          # (N,)
    s3 = _mm(wn, o32)                  # (M,)
    s4 = _mm(o32, wm)                  # (N,)
    s5 = jnp.sum(s1)
    s6 = _mm(wn, s2)                   # sum_n n * rowsum
    s7 = _mm(s1, wm)
    sumsq = jnp.sum(o32 * o32)
    return OutputSums(s1[:, None], s2[:, None], s3[:, None], s4[:, None],
                      s5[None], s6[None], s7[None], sumsq)


def output_checksums_matmul(
    d: jnp.ndarray, w: jnp.ndarray,
    cd1: jnp.ndarray, cd2: jnp.ndarray,
    cw1: jnp.ndarray, cw2: jnp.ndarray,
    need_rowcol: bool = True,
) -> OutputChecksums:
    """C_o1..C_o7. The scalar triple is O(K); c1..c4 are single GEMVs."""
    c5 = _mm(cd1, cw1)[None]
    c6 = _mm(cd2, cw1)[None]
    c7 = _mm(cd1, cw2)[None]
    if need_rowcol:
        w32 = op_operand(w)
        d32 = op_operand(d)
        c1 = _mm(cd1, w32)[:, None]
        c2 = _mm(d32, cw1)[:, None]
        c3 = _mm(cd2, w32)[:, None]
        c4 = _mm(d32, cw2)[:, None]
    else:
        c1 = c2 = c3 = c4 = None
    return OutputChecksums(c1, c2, c3, c4, c5, c6, c7)


def absdot_matmul(cd1: jnp.ndarray, cw1: jnp.ndarray) -> jnp.ndarray:
    """|C_d1| . |C_w1| - checksum-side magnitude for the threshold model."""
    return _mm(jnp.abs(cd1), jnp.abs(cw1))


# --------------------------------------------------------------------------
# conv path (NCHW). dn = lax.conv dimension numbers for NCHW/OIHW.
# --------------------------------------------------------------------------

_DN = ("NCHW", "OIHW", "NCHW")


def _window_pads(hw: Tuple[int, int], rs: Tuple[int, int], stride: int,
                 padding) -> Tuple[Tuple[int, int], ...]:
    if isinstance(padding, str):
        return tuple(jax.lax.padtype_to_pads(hw, rs, (stride, stride),
                                             padding))
    return tuple((int(lo), int(hi)) for lo, hi in padding)


def checksum_conv(x: jnp.ndarray, f: jnp.ndarray, stride: int = 1,
                  padding="VALID", groups: int = 1) -> jnp.ndarray:
    """conv(x[B,C,H,W], f[F,C/G,R,S]) -> (B, F, E1, E2) in f32, for the
    checksum side, where B or F is a handful of checksum blocks.

    Runs as im2col (static slices) + ONE contraction at PRECISION
    instead of the conv primitive: XLA's TPU emitter for an f32 HIGHEST
    conv with a tiny batch or feature dimension takes seconds to minutes
    per conv to compile (it can fail and retry), while the equivalent dot
    is routine. The patches are R*S times the image side - checksum-sized
    for the c1/c3/c5-c7 convs, the fmap for c2/c4, which only the
    correction branch computes.

    At stride s > 1 the taps are unit-stride windows of the s*s phases
    `xp[..., p::s, q::s]` (`_phases`), so the patches are the same values
    in the same order as numpy-style strided taps, built without a gather.
    `xp[..., i::s, j::s]` itself lowers to one `gather` per tap (plus the
    iotas and concatenates of its indices): on a TPU v5e those gathers
    made the check of resnet18's 7x7 stride-2 stem take about half of the
    protected step.
    """
    with jax.named_scope("checksum_conv"):
        return _checksum_conv(x, f, stride, padding, groups)


def _checksum_conv(x, f, stride, padding, groups):
    b, c, h, w = x.shape
    nf, cg, r, s_ = f.shape
    (pt, pb), (pl, pr) = _window_pads((h, w), (r, s_), stride, padding)
    xp = jnp.pad(x.astype(F32), ((0, 0), (0, 0), (pt, pb), (pl, pr)))
    e1 = (h + pt + pb - r) // stride + 1
    e2 = (w + pl + pr - s_) // stride + 1
    phases = _phases(xp, stride, min(stride, r), min(stride, s_))
    cols = [jax.lax.slice(phases[i % stride, j % stride],
                          (0, 0, i // stride, j // stride),
                          (b, c, i // stride + e1, j // stride + e2))
            for i in range(r) for j in range(s_)]
    # (B, C, R*S, E1, E2) -> (B, G, C/G*R*S, P): (c, i, j)-major, as f's
    pat = jnp.stack(cols, axis=2).reshape(b, groups, cg * r * s_, e1 * e2)
    fm = f.astype(F32).reshape(groups, nf // groups, cg * r * s_)
    out = jnp.einsum("bgkp,gfk->bgfp", pat, fm, precision=PRECISION)
    return out.reshape(b, nf, e1, e2)


def _phases(xp, s: int, n_rows: int, n_cols: int):
    """{(p, q): xp[:, :, p::s, q::s]} for p < n_rows, q < n_cols.

    The column stride runs as a 0/1 selection matmul at PRECISION, exact
    for finite values: each output is one input times 1 plus zeros (a
    non-finite input spreads along its row, which detection flags anyway).
    The row stride is a strided slice. On a TPU v5e a slice strided along
    the minor (lane) axis is slow: resnet18's stem check built from such
    slices took 1.8 ms, against 0.09 ms this way; a row stride is cheap."""
    if s == 1:
        return {(0, 0): xp}
    b, c, hp, wp = xp.shape
    out = {}
    for q in range(n_cols):
        wq = (wp - 1 - q) // s + 1
        sel = (jnp.arange(wp)[:, None] == q + s * jnp.arange(wq)).astype(F32)
        xq = jnp.einsum("bchw,wv->bchv", xp, sel, precision=PRECISION)
        for p in range(n_rows):
            out[p, q] = jax.lax.slice(xq, (0, 0, p, 0), (b, c, hp, wq),
                                      (1, 1, s, 1))
    return out


def conv2d(d: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
           padding="VALID", groups: int = 1) -> jnp.ndarray:
    """The unprotected convolution (paper Eq. 1 without bias), on the
    operands of `types.op_operand_dtype`. XLA is free to choose its
    implementation - the checksums sit above it."""
    dt = op_operand_dtype(d.dtype)
    return jax.lax.conv_general_dilated(
        d.astype(dt), w.astype(dt), window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=_DN, feature_group_count=groups,
        precision=PRECISION, preferred_element_type=F32).astype(d.dtype)


def encode_d_conv(d: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """C_d1, C_d2 over the batch axis of D[N,Ch,H,W].

    Computed as ONE (2,N)@(N,Ch*H*W) GEMM with a constant weight matrix
    [ones; iota] instead of a reduce + a tensordot: on CPU the BLAS path
    is ~7x faster than XLA's strided axis-0 reductions, and on TPU both
    sums ride one MXU pass over D. Values differ from the naive
    reductions only by fp32 reassociation (ulps), which the detection
    thresholds already price in."""
    n = d.shape[0]
    enc = jnp.stack([jnp.ones((n,), F32), _iota(n)])
    cd = _mm(enc, op_operand(d).reshape(n, -1)).reshape(2, *d.shape[1:])
    return cd[0], cd[1]


def encode_w_conv(w: jnp.ndarray, groups: int = 1
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """C_w1, C_w2 over the output-channel axis of W[M,Ch,R,R].

    For grouped convolution (paper SS5.2) the checksums are computed per
    group and concatenated along the channel axis so the result convolves
    with the full-channel fmap blocks.
    """
    w32 = op_operand(w)
    m = w.shape[0]
    if groups == 1:
        cw1 = jnp.sum(w32, axis=0)
        cw2 = jnp.tensordot(_iota(m), w32, axes=1, precision=PRECISION)
        return cw1, cw2
    mg = m // groups
    wg = w32.reshape(groups, mg, *w32.shape[1:])       # (G, M/G, Ch/G, R, R)
    weights = _iota(m).reshape(groups, mg)
    cw1 = jnp.concatenate(list(jnp.sum(wg, axis=1)), axis=0)   # (Ch, R, R)
    cw2 = jnp.concatenate(
        list(jnp.einsum("gm,gmchw->gchw", weights, wg,
                        precision=PRECISION)), axis=0)
    return cw1, cw2


def detect_sums(o: jnp.ndarray, *, use_kernel: bool = False,
                interpret: Optional[bool] = None,
                tiles: Optional[Tuple[int, int]] = None,
                exact_order: bool = False,
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The CoC-D detection summations of O[N,M,E,E]: (s5, s6, s7, sumsq),
    each per payload position p (sumsq scalar), in ONE pass over O.

    This is the error-free hot path: `output_sums_conv` additionally
    materialises the full-resolution s1-s4 summations that only the
    correction rungs read, so calling it for detection pays several extra
    O(|O|) outputs per protected op.

    The default formulation is a single (3,N*M)@(N*M,P) GEMM with a
    constant weight matrix [1; n; m] plus a BLAS sdot for the sum of
    squares - on CPU this is ~2.5x faster than staged axis reductions
    (XLA's CPU reductions are not BLAS-grade), and the values differ from
    `output_sums_conv` only by fp32 reassociation at the ulp level, far
    inside the detection thresholds. `exact_order=True` instead reduces
    in `output_sums_conv`'s exact order (sum over n, then m) and is
    bitwise-identical to it on fp32 inputs - the differential-parity
    contract the tests pin down.

    `use_kernel=True` routes the pass through the Pallas single-pass
    reduction on the flattened (N*M, E*E) view (the same partials the
    fused matmul epilogue emits); `interpret` is then required.
    """
    if use_kernel and not exact_order:  # exact_order pins jnp's reduction order
        from repro.kernels import ops as kops  # lazy: keeps pallas off import
        if interpret is None:
            raise ValueError("detect_sums(use_kernel=True) needs interpret=")
        return kops.conv_detect_sums(o, interpret=interpret, tiles=tiles)
    n, m, e1, e2 = o.shape
    p = e1 * e2
    if exact_order:
        o32 = o.astype(F32).reshape(n, m, p)
        s1 = jnp.sum(o32, axis=0)                       # (M, P) intermediate
        s2 = jnp.sum(o32, axis=1)                       # (N, P) intermediate
        s5 = jnp.sum(s1, axis=0)                        # (P,)
        s6 = _mm(_iota(n), s2)                          # (P,)
        s7 = _mm(_iota(m), s1)                          # (P,)
        sumsq = jnp.sum(o32 * o32)
        return s5, s6, s7, sumsq
    o2 = o.astype(F32).reshape(n * m, p)
    enc = jnp.stack([jnp.ones((n * m,), F32),
                     jnp.repeat(_iota(n), m),
                     jnp.tile(_iota(m), n)])            # constant-folded
    s = _mm(enc, o2)
    flat = o2.reshape(-1)
    sumsq = jnp.vdot(flat, flat, precision=PRECISION)
    return s[0], s[1], s[2], sumsq


def detect_checksums_conv(
    cd1: jnp.ndarray, cd2: jnp.ndarray,
    cw1: jnp.ndarray, cw2: jnp.ndarray,
    stride: int = 1, padding="VALID",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(c5, c6, c7, absdot) for CoC-D in ONE batched `checksum_conv`.

    The three scalar-invariant checksum convs (cd1*cw1, cd2*cw1, cd1*cw2)
    and the |cd1|*|cw1| threshold conv share operands pairwise: stacking
    [cd1, cd2, |cd1|] as the batch and [cw1, cw2, |cw1|] as output channels
    computes all four (plus five unused pairings) in a single contraction.
    The wasted pairings cost 9 block-convs total - ~9/(N*M) of the
    protected op - while four separate checksum convs would be four
    dispatches, which at CNN layer sizes is dispatch-bound, not FLOP-bound.

    Grouped convs need no special case: cw1/cw2 already carry full
    channels, so the checksum convs are dense (the paper's SS5.2 identity).
    """
    dstk = jnp.stack([cd1.astype(F32), cd2.astype(F32),
                      jnp.abs(cd1).astype(F32)])
    wstk = jnp.stack([cw1.astype(F32), cw2.astype(F32),
                      jnp.abs(cw1).astype(F32)])
    out = checksum_conv(dstk, wstk, stride, padding)
    c5 = out[0, 0].reshape(-1)
    c6 = out[1, 0].reshape(-1)
    c7 = out[0, 1].reshape(-1)
    absdot = jnp.max(out[2, 2])
    return c5, c6, c7, absdot


def output_sums_conv(o: jnp.ndarray) -> OutputSums:
    """Summations of O[N,M,E,E], payload-flattened to (., P=E*E)."""
    n, m, e1, e2 = o.shape
    p = e1 * e2
    o32 = o.astype(F32).reshape(n, m, p)
    wn = _iota(n)
    wm = _iota(m)
    s1 = jnp.sum(o32, axis=0)                       # (M, P)
    s2 = jnp.sum(o32, axis=1)                       # (N, P)
    s3 = jnp.tensordot(wn, o32, axes=1, precision=PRECISION)   # (M, P)
    s4 = jnp.einsum("nmp,m->np", o32, wm, precision=PRECISION)  # (N, P)
    s5 = jnp.sum(s1, axis=0)                        # (P,)
    s6 = _mm(wn, s2)                                # (P,)
    s7 = _mm(wm, s1)                                # (P,)
    sumsq = jnp.sum(o32 * o32)
    return OutputSums(s1, s2, s3, s4, s5, s6, s7, sumsq)


def output_checksums_conv(
    d: jnp.ndarray, w: jnp.ndarray,
    cd1: jnp.ndarray, cd2: jnp.ndarray,
    cw1: jnp.ndarray, cw2: jnp.ndarray,
    stride: int = 1, padding="VALID", groups: int = 1,
    need_rowcol: bool = True,
) -> OutputChecksums:
    """C_o1..C_o7 via `checksum_conv` of the checksum blocks.

    c1/c3 convolve the two fmap checksums with W, c2/c4 the fmap with the
    two kernel checksums, c5/c6/c7 the checksums with each other - all
    small next to the NM-block op. Grouped conv (paper SS5.2): cw1/cw2
    already have full Ch channels, so every conv but c1/c3 (with W
    itself) is *dense* - this is exactly the identity proved in the paper.
    """
    cv = partial(checksum_conv, stride=stride, padding=padding)
    cdd = jnp.stack([cd1, cd2])
    cww = jnp.stack([cw1, cw2])
    s = cv(cdd, cww)                                        # (2, 2, E, E)
    c5 = s[0, 0].reshape(-1)
    c6 = s[1, 0].reshape(-1)
    c7 = s[0, 1].reshape(-1)
    if need_rowcol:
        m, n = w.shape[0], d.shape[0]
        c13 = cv(cdd, op_operand(w), groups=groups)        # (2, M, E, E)
        c24 = cv(op_operand(d), cww)                        # (N, 2, E, E)
        c1 = c13[0].reshape(m, -1)
        c3 = c13[1].reshape(m, -1)
        c2 = c24[:, 0].reshape(n, -1)
        c4 = c24[:, 1].reshape(n, -1)
    else:
        c1 = c2 = c3 = c4 = None
    return OutputChecksums(c1, c2, c3, c4, c5, c6, c7)


# --------------------------------------------------------------------------
# weight locator sums (at-rest repair side information)
#
# The weight-side sibling of the output-side CoC locator: per col_chunk
# block of W, FOUR sums - plain and index-weighted, over both the row and
# the column axis of the block. Detection only needs one side (the
# persisted cw1/cw2); with both sides a single-row or single-column
# corruption inside a block is fully *localized* (which rows / which
# columns diverge) and the per-element damage is read straight off the
# first-order residuals, so the audit can repair in place instead of
# escalating to a checkpoint restore (arXiv:1910.14479's in-place story).
#
# Offline (concrete weights) the sums are carried in float64: residuals
# of f64 sums over f32/int8 data sit ~1e-13 relative, far below an f32
# half-ulp, so a repaired f32 leaf casts back bitwise-identical to the
# original (and integer leaves repair exactly). Under a trace (campaign
# trials) the sums fall back to f32 on device and repairs verify within
# tolerance instead of bitwise.
# --------------------------------------------------------------------------

class WeightLocators(NamedTuple):
    """Per-block 2D locator sums of one weight tensor.

    matmul W[K,M] with resolved block width `cb` (mb = M/cb blocks):
      r1/r2: (mb, K) per-block row sums (plain / column-index-weighted) -
             f64 duplicates of cw1/cw2; c1/c2: (mb, cb) per-block column
             sums (plain / row-index-weighted).
    conv W[M,Ch,R,R], flattened to one (M, J=Ch*R*R) block (`cb` = 0):
      r1/r2: (M,) per-filter sums (plain / j-weighted); c1/c2: (J,)
      per-position sums - f64 duplicates of the flattened cw1/cw2.
    Stacked scanned-stage entries carry a leading reps axis on all four.
    """
    r1: Any
    r2: Any
    c1: Any
    c2: Any
    cb: int


def weight_locators_matmul(w, col_chunk: int) -> WeightLocators:
    """Locator sums of W[K,M], chunked exactly like weight_checksums_matmul
    (same pick_chunk, so block b of the locators is block b of cw1/cw2)."""
    from .protected import pick_chunk  # lazy: protected imports this module
    k, m = int(w.shape[0]), int(w.shape[1])
    cb = pick_chunk(m, col_chunk)
    mb = m // cb
    if isinstance(w, jax.core.Tracer):
        w3 = w.astype(F32).reshape(k, mb, cb)
        r1 = jnp.einsum("kbc->bk", w3)
        r2 = jnp.einsum("kbc,c->bk", w3, jnp.arange(cb, dtype=F32),
                        precision=PRECISION)
        c1 = jnp.einsum("kbc->bc", w3)
        c2 = jnp.einsum("kbc,k->bc", w3, jnp.arange(k, dtype=F32),
                        precision=PRECISION)
        return WeightLocators(r1, r2, c1, c2, cb)
    w3 = np.asarray(w).astype(np.float64).reshape(k, mb, cb)
    r1 = np.einsum("kbc->bk", w3)
    r2 = np.einsum("kbc,c->bk", w3, np.arange(cb, dtype=np.float64))
    c1 = np.einsum("kbc->bc", w3)
    c2 = np.einsum("kbc,k->bc", w3, np.arange(k, dtype=np.float64))
    return WeightLocators(r1, r2, c1, c2, cb)


def weight_locators_conv(w) -> WeightLocators:
    """Locator sums of W[M,Ch,R,R] viewed as one (M, Ch*R*R) block.
    Group-agnostic: per-filter and per-position sums do not depend on the
    group structure, so one recipe serves dense and grouped convs."""
    m = int(w.shape[0])
    j = 1
    for s in w.shape[1:]:
        j *= int(s)
    if isinstance(w, jax.core.Tracer):
        wf = w.astype(F32).reshape(m, j)
        r1 = jnp.sum(wf, axis=1)
        r2 = _mm(wf, jnp.arange(j, dtype=F32))
        c1 = jnp.sum(wf, axis=0)
        c2 = _mm(jnp.arange(m, dtype=F32), wf)
        return WeightLocators(r1, r2, c1, c2, 0)
    wf = np.asarray(w).astype(np.float64).reshape(m, j)
    iota_j = np.arange(j, dtype=np.float64)
    iota_m = np.arange(m, dtype=np.float64)
    return WeightLocators(wf.sum(axis=1), wf @ iota_j,
                          wf.sum(axis=0), iota_m @ wf, 0)


def absdot_conv(cd1: jnp.ndarray, cw1: jnp.ndarray, stride: int = 1,
                padding="VALID") -> jnp.ndarray:
    """Checksum-magnitude scale for conv: |cd1| (x) |cw1| summed, one value
    per op (coarse upper bound is fine - it only guards the fp32 term).
    Uses the op's own stride/padding so the output is never empty."""
    return jnp.max(checksum_conv(jnp.abs(cd1)[None], jnp.abs(cw1)[None],
                                 stride, padding))
