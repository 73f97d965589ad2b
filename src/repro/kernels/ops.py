"""Wrappers around the Pallas kernels: tile selection that Mosaic accepts,
zero padding where no legal tile divides an axis, and the partial ->
chunk-sum plumbing used by repro.core.protected.

Mosaic takes a block whose last two dimensions are multiples of
(sublane, 128) - sublane 8 for 32-bit, 16 for 16-bit data - or equal to
the array's own. `_fit` picks, per axis, the whole axis when it fits the
target tile, else the largest aligned power-of-two tile that divides it,
else (up to `_FULL_CAP`) the whole axis again, else an aligned tile over
a zero-padded axis. Zero rows / columns / K-slices contribute nothing to
the product or to any summation partial, so outputs are sliced back
exactly. Every shape runs the kernel: there is no jnp fallback.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp

from repro.core.types import PRECISION, op_operand_dtype

from . import ref as _ref
from .abft_matmul import LANES
from .abft_matmul import abft_matmul as _abft_matmul_kernel
from .abft_matmul import abft_matmul_detect as _abft_matmul_detect_kernel
from .checksum_reduce import checksum_reduce as _checksum_reduce_kernel

F32 = jnp.float32
_FULL_CAP = 1024   # an unaligned axis up to this long runs as one block


def sublanes(*dtypes) -> int:
    """Row alignment of a block holding these dtypes (8 for 32-bit)."""
    return max(8 * 4 // max(jnp.dtype(d).itemsize, 1) for d in dtypes)


def _fit(n: int, target: int, unit: int) -> Tuple[int, int]:
    """(tile, padded extent) of one kernel axis of length n."""
    if n <= target:
        return n, n
    aligned = [unit]
    while aligned[-1] * 2 <= target:
        aligned.append(aligned[-1] * 2)
    for t in reversed(aligned):
        if n % t == 0:
            return t, n
    if n <= _FULL_CAP:
        return n, n
    t = min(reversed(aligned), key=lambda t: -(-n // t) * t)
    return t, _ceil_to(n, t)


def _ceil_to(n: int, t: int) -> int:
    return -(-n // t) * t


def _pad2(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    if x.shape == (rows, cols):
        return x
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


class Partials(NamedTuple):
    """Summation partials of an (n, m) output: sums is (n_tiles, 3, m)
    with rows colsum / locally row-weighted colsum / column sum of
    squares per bm-row tile (the last tile zero-padded when bm does not
    divide n)."""
    sums: jnp.ndarray
    n: int
    bm: int
    bn: int


def abft_matmul(d: jnp.ndarray, w: jnp.ndarray, *, interpret: bool,
                bm: int = 256, bn: int = 256, bk: int = 512,
                out_dtype=None) -> Tuple[jnp.ndarray, Partials]:
    """Fused GEMM + checksum epilogue at legal tiles near the (bm, bn,
    bk) targets; padded axes are sliced back."""
    n, k = d.shape
    m = w.shape[1]
    out_dtype = out_dtype or d.dtype
    tm, np_ = _fit(n, bm, sublanes(d.dtype, w.dtype, out_dtype))
    tk, kp = _fit(k, bk, LANES)
    tn, mp = _fit(m, bn, LANES)
    o, sums = _abft_matmul_kernel(
        _pad2(d, np_, kp), _pad2(w, kp, mp), bm=tm, bn=tn, bk=tk,
        interpret=interpret, operand_dtype=op_operand_dtype(d.dtype),
        out_dtype=out_dtype)
    return o[:n, :m], Partials(sums[:, :, :m], n, tm, tn)


def abft_matmul_detect(d: jnp.ndarray, w: jnp.ndarray, c5, c6, c7, absdot,
                       *, rb: int, cb: int, bk: int = 512, tau_a: float,
                       tau_b: float, weighted: bool = True,
                       interpret: bool, out_dtype=None):
    """Single-launch fused GEMM + CoC-D compare: detection chunk == kernel
    tile. Returns (o, flag (nb,mb) i32, score (nb,mb) f32) - or None when
    the (rb, cb) chunking is not a legal kernel tile (or the checksum grid
    does not match it), signalling the caller to take the partials route
    instead. c5/c6/c7/absdot are the per-chunk checksum predictions
    ((n//rb, m//cb), locally index-weighted, WITHOUT bias adjustments -
    the kernel accumulates the raw product)."""
    n, k = d.shape
    m = w.shape[1]
    out_dtype = out_dtype or d.dtype
    unit = sublanes(d.dtype, w.dtype, out_dtype)
    if (n % rb or m % cb or c5.shape != (n // rb, m // cb)
            or (rb != n and rb % unit) or (cb != m and cb % LANES)):
        return None
    tk, kp = _fit(k, bk, LANES)
    nb, mb = n // rb, m // cb
    cs = jnp.stack([c5, c6, c7, absdot], axis=1).astype(F32)  # (nb,4,mb)
    cs = jnp.repeat(cs, LANES, axis=2)
    o, verdict = _abft_matmul_detect_kernel(
        _pad2(d, n, kp), _pad2(w, kp, m), cs, bm=rb, bn=cb, bk=tk,
        tau_a=tau_a, tau_b=tau_b, weighted=weighted, interpret=interpret,
        operand_dtype=op_operand_dtype(d.dtype), out_dtype=out_dtype)
    verdict = verdict[:, :, ::LANES]                          # (nb, 2, mb)
    return o, verdict[:, 0].astype(jnp.int32), verdict[:, 1]


def checksum_reduce(o: jnp.ndarray, *, interpret: bool, bm: int = 512,
                    bn: int = 512) -> Partials:
    """Single-pass summation partials of O[N,M] (see Partials).
    Unaligned shapes are zero-padded into the kernel and the partials
    sliced back."""
    n, m = o.shape
    (tm, np_), (tn, mp) = _fit(n, bm, sublanes(o.dtype)), _fit(m, bn, LANES)
    sums = _checksum_reduce_kernel(_pad2(o, np_, mp), bm=tm, bn=tn,
                                   interpret=interpret)
    return Partials(sums[:, :, :m], n, tm, tn)


def _ein(spec: str, *ops) -> jnp.ndarray:
    return jnp.einsum(spec, *ops, precision=PRECISION)


def chunk_sums_from_partials(parts: Partials, rb: int, cb: int, o=None):
    """Finish the fused-epilogue partials into per-chunk (s5, s6, s7,
    sumsq).

    sums has full column resolution -> exact local-index m-weighting for
    s7; the tile-local row weighting plus each tile's row offset inside
    its chunk gives the n-weighting for s6. Cost is O(M*N/bm), negligible
    next to the GEMM.

    When the chunk is not a multiple of the kernel tile (or the last row
    tile is padded), the tile partials cannot be split at chunk
    boundaries - recombine at element resolution from `o` instead (one
    extra fused pass; only exotic chunk/tile pairings pay it). With no
    `o` to recombine from, misalignment is an error.
    """
    sums, n, bm, bn = parts
    m = sums.shape[2]
    aligned = (rb % bm == 0 and cb % bn == 0 and sums.shape[0] * bm == n
               and n % rb == 0 and m % cb == 0)
    if not aligned:
        if o is None:
            raise ValueError(
                f"chunk ({rb},{cb}) must be a multiple of the kernel tile "
                f"({bm},{bn}) to recombine from partials; pass o= to "
                "recombine at element resolution")
        return _ref.chunk_sums_ref(o, rb, cb)
    nb, mb, tpc = n // rb, m // cb, rb // bm
    cs, ws, sq = (sums[:, r].reshape(nb, tpc, mb, cb) for r in range(3))
    s5 = jnp.sum(cs, axis=(1, 3))
    s7 = _ein("atbc,c->ab", cs, jnp.arange(cb, dtype=F32))
    s6 = (_ein("atbc,t->ab", cs, jnp.arange(tpc, dtype=F32) * bm)
          + jnp.sum(ws, axis=(1, 3)))
    return s5, s6, s7, jnp.sum(sq, axis=(1, 3))


def conv_detect_sums(o4: jnp.ndarray, *, interpret: bool,
                     tiles: Optional[Tuple[int, int]] = None):
    """Pallas route for `repro.core.checksums.detect_sums`: one kernel pass
    over the flattened (N*M, E*E) view of O[N,M,E,E], finished to the
    per-payload detection sums (s5, s6, s7, sumsq).

    Row tiles must not straddle batch-block boundaries (each flattened row
    nm has weights n = nm//M for s6 and m = nm%M for s7, and the kernel's
    wcolsum partial carries only the *local* row weighting) - so M is
    padded with zero blocks (which contribute nothing) to a multiple of
    the sublane count, and the row tile divides it.
    """
    n, m, e1, e2 = o4.shape
    p = e1 * e2
    tm, tp = tiles or (256, 256)
    unit = sublanes(o4.dtype)
    mp = _ceil_to(m, unit)
    bm = unit
    while bm * 2 <= tm and mp % (bm * 2) == 0:
        bm *= 2
    bn, pp = _fit(p, tp, LANES)
    o3 = o4.reshape(n, m, p)
    if (mp, pp) != (m, p):
        o3 = jnp.pad(o3, ((0, 0), (0, mp - m), (0, pp - p)))
    sums = _checksum_reduce_kernel(o3.reshape(n * mp, pp), bm=bm, bn=bn,
                                   interpret=interpret)
    colsum, wcolsum, sq = sums[:, 0], sums[:, 1], sums[:, 2]
    base = jnp.arange(sums.shape[0]) * bm
    nw = (base // mp).astype(F32)             # n, constant per tile
    mbase = (base % mp).astype(F32)           # m of the tile's first row
    s5 = jnp.sum(colsum, axis=0)
    s6 = _ein("t,tp->p", nw, colsum)
    s7 = _ein("t,tp->p", mbase, colsum) + jnp.sum(wcolsum, axis=0)
    return s5[:p], s6[:p], s7[:p], jnp.sum(sq)
