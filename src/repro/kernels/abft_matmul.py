"""Fused ABFT matmul: O = D @ W with the output-summation encode folded
into the GEMM epilogue.

The paper's runtime model (Table 3/4) charges a beta-weighted *extra pass*
over O to encode the output summations (S_o). On TPU that pass is a second
HBM round-trip of the largest tensor in the op. Here the per-tile partial
sums are computed while the accumulator tile is still in VMEM and written
as one small lane-dense block per tile:

    sums : (N/bm, 3, M)   rows per row-tile: column sum, locally
                          row-index-weighted column sum, column sum of
                          squares -> S_o5/S_o6/S_o7 and the threshold scale

A negligible jnp reduction (repro.kernels.ops.chunk_sums_from_partials)
finishes them at any chunk granularity that is a multiple of the tile.
Full column resolution lets the wrapper apply the column-index weights of
s7 exactly; the locally row-weighted sum plus each tile's row offset gives
the row-index weights of s6.

Mosaic layout rules shape every block here: the last two dimensions of a
block are multiples of (8, 128) - (16, 128) for 16-bit operands - or equal
to the array's own. Per-tile results therefore leave the kernel as
(3, bn) / (2, 128) rows of 3-D arrays whose middle axis is exactly that
wide; ops.py picks tiles that satisfy the rule (padding where it must).

MXU alignment: tiles default to 256x256 output blocks; the fp32
accumulator lives in VMEM scratch. Tiles multiply the protected op's
operand values (core/types.op_operand_dtype, passed in as `operand_dtype`)
with exact products and f32 accumulation, the arithmetic the checksum
side encodes.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import PRECISION

F32 = jnp.float32
LANES = 128      # per-tile verdicts are broadcast across one lane row


def _dot(a, b, operand_dtype):
    """Tile product in the protected op's arithmetic (protected.op_matmul):
    operands rounded to `operand_dtype`, f32 accumulation. Mosaic takes a
    precision only for f32 operands; narrower ones multiply exactly."""
    f32 = jnp.dtype(operand_dtype) == F32
    return jnp.dot(a.astype(operand_dtype), b.astype(operand_dtype),
                   preferred_element_type=F32,
                   precision=PRECISION if f32 else None)


def _row_iota(shape) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0).astype(F32)


def _col_iota(shape) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1).astype(F32)


def tile_sums(tile: jnp.ndarray, sums_ref) -> None:
    """Store the (3, bn) partials of one f32 tile: column sum, locally
    row-index-weighted column sum, column sum of squares. Shared with
    checksum_reduce, so both kernels emit one partials layout."""
    sums_ref[0:1, :] = jnp.sum(tile, axis=0, keepdims=True)
    sums_ref[1:2, :] = jnp.sum(tile * _row_iota(tile.shape), axis=0,
                               keepdims=True)
    sums_ref[2:3, :] = jnp.sum(tile * tile, axis=0, keepdims=True)


def _params(n_parallel: int, reduce_axis: bool = True):
    sem = ("parallel",) * n_parallel + (("arbitrary",) if reduce_axis
                                        else ())
    return pltpu.CompilerParams(dimension_semantics=sem)


def _accumulate(d_ref, w_ref, acc_ref, operand_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(d_ref[...], w_ref[...], operand_dtype)


def _kernel(d_ref, w_ref, o_ref, sums_ref, acc_ref, *, k_steps: int,
            operand_dtype):
    _accumulate(d_ref, w_ref, acc_ref, operand_dtype)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        o_ref[...] = acc.astype(o_ref.dtype)
        # checksum epilogue: the tile is in VMEM - the extra HBM traffic
        # is 3*M*N/bm fp32 words instead of a full re-read of O
        tile_sums(acc, sums_ref)


def _gemm_specs(bm: int, bn: int, bk: int):
    return [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype", "operand_dtype"))
def abft_matmul(d: jnp.ndarray, w: jnp.ndarray, *, bm: int, bn: int,
                bk: int, interpret: bool, operand_dtype,
                out_dtype=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (O, sums (N/bm, 3, M)). Shapes must tile evenly and the
    tiles must be legal Mosaic blocks; ops.abft_matmul picks and pads."""
    n, k = d.shape
    k2, m = w.shape
    assert k == k2, (d.shape, w.shape)
    assert n % bm == 0 and m % bn == 0 and k % bk == 0, (
        f"abft_matmul needs tile-aligned shapes, got {(n, k, m)} with "
        f"tiles {(bm, bk, bn)}")
    out_dtype = out_dtype or d.dtype
    grid = (n // bm, m // bn, k // bk)
    o, sums = pl.pallas_call(
        functools.partial(_kernel, k_steps=grid[2],
                          operand_dtype=operand_dtype),
        grid=grid,
        in_specs=_gemm_specs(bm, bn, bk),
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((None, 3, bn), lambda i, j, kk: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, m), out_dtype),
            jax.ShapeDtypeStruct((grid[0], 3, m), F32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), F32)],
        compiler_params=_params(2),
        interpret=interpret,
    )(d, w)
    return o, sums


# --------------------------------------------------------------------------
# fused GEMM + in-epilogue threshold compare (single-launch detection)
# --------------------------------------------------------------------------

def _total(x: jnp.ndarray) -> jnp.ndarray:
    """Sum of a 2-D tile as a (1, 1) array (vector reductions only)."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _detect_kernel(d_ref, w_ref, cs_ref, o_ref, verdict_ref, acc_ref, *,
                   k_steps: int, tau_a: float, tau_b: float,
                   weighted: bool, operand_dtype):
    """abft_matmul's epilogue extended with the CoC-D compare itself: the
    per-tile scalar invariants (s5 and, when `weighted`, the locally
    index-weighted s6/s7) are reduced from the VMEM accumulator and
    compared against the checksum-side predictions while the tile is
    still resident - one flag (+ evidence score) per tile leaves the
    kernel instead of the summation partials.

    cs_ref is the tile's (4, LANES) block of checksum predictions: rows
    c5, c6, c7, absdot, each broadcast across the lanes. The verdict
    block is (2, LANES): row 0 the flag (0/1), row 1 the score. tau
    inlines thresholds.tau_scalar's affine form (tau_scalar_coeffs):
    tau5 = tau_a*sqrt(sumsq) + tau_b*absdot + 1e-30, with the weighted
    invariants amplified by the tile extents (tau_weighted). NaN/Inf on
    either side of a compare flags the tile (mismatch semantics)."""
    _accumulate(d_ref, w_ref, acc_ref, operand_dtype)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        o_ref[...] = acc.astype(o_ref.dtype)
        bm, bn = acc.shape
        cs = cs_ref[...]
        tau5 = (tau_a * jnp.sqrt(jnp.maximum(_total(acc * acc), 0.0))
                + tau_b * cs[3:4, :] + 1e-30)
        pairs = [(cs[0:1, :], _total(acc), tau5)]
        if weighted:
            pairs += [(cs[1:2, :], _total(acc * _row_iota(acc.shape)),
                       tau5 * float(max(bm - 1, 1))),
                      (cs[2:3, :], _total(acc * _col_iota(acc.shape)),
                       tau5 * float(max(bn - 1, 1)))]
        flag = jnp.zeros((1, LANES), jnp.bool_)
        score = jnp.zeros((1, LANES), F32)
        for c, s, t in pairs:
            bad = ~((jnp.abs(c) < jnp.inf) & (jnp.abs(s) < jnp.inf))
            gap = jnp.abs(c - s)
            flag = flag | bad | (gap > t)
            score = jnp.maximum(score, jnp.where(bad, jnp.inf, gap / t))
        verdict_ref[0:1, :] = flag.astype(F32)
        verdict_ref[1:2, :] = score


@functools.partial(jax.jit, static_argnames=(
    "bm", "bn", "bk", "tau_a", "tau_b", "weighted", "interpret",
    "out_dtype", "operand_dtype"))
def abft_matmul_detect(d: jnp.ndarray, w: jnp.ndarray, cs: jnp.ndarray, *,
                       bm: int, bn: int, bk: int, tau_a: float,
                       tau_b: float, weighted: bool, interpret: bool,
                       operand_dtype,
                       out_dtype=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """O = D @ W plus the in-epilogue CoC-D compare: ONE kernel launch
    returning (O, verdict (nb, 2, mb*LANES) f32).

    Detection chunk granularity IS the kernel tile here: `cs` holds the
    per-(bm x bn)-chunk checksum predictions (c5, c6, c7, absdot;
    locally index-weighted) as (nb, 4, mb*LANES), each value broadcast
    across its tile's lanes (ops.abft_matmul_detect packs and unpacks).
    tau_a/tau_b are the static affine threshold coefficients
    (thresholds.tau_scalar_coeffs)."""
    n, k = d.shape
    k2, m = w.shape
    assert k == k2, (d.shape, w.shape)
    assert n % bm == 0 and m % bn == 0 and k % bk == 0, (
        f"abft_matmul_detect needs tile-aligned shapes, got {(n, k, m)} "
        f"with tiles {(bm, bk, bn)}")
    nb, mb = n // bm, m // bn
    assert cs.shape == (nb, 4, mb * LANES), (cs.shape, (nb, 4, mb * LANES))
    out_dtype = out_dtype or d.dtype
    grid = (nb, mb, k // bk)
    kernel = functools.partial(_detect_kernel, k_steps=grid[2],
                               tau_a=tau_a, tau_b=tau_b, weighted=weighted,
                               operand_dtype=operand_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=_gemm_specs(bm, bn, bk) + [
            pl.BlockSpec((None, 4, LANES), lambda i, j, kk: (i, 0, j))],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((None, 2, LANES), lambda i, j, kk: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, m), out_dtype),
            jax.ShapeDtypeStruct((nb, 2, mb * LANES), F32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), F32)],
        compiler_params=_params(2),
        interpret=interpret,
    )(d, w, cs.astype(F32))
