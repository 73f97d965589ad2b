"""Single-pass output-summation encode for an existing O[N,M].

The conv/attention outputs of the paper's workflow need S_o sums even when
the producing op is not our fused GEMM (XLA conv, attention, an external
library - "any convolution implementation"). This kernel reads O exactly
once from HBM and emits the same partials as the fused epilogue
(abft_matmul.tile_sums): per row tile, the column sum, the locally
row-index-weighted column sum (wcolsum) and the column sum of squares.

wcolsum weights each row by its index *within the tile*; combined with the
tile's base row index it reconstructs any affine row weighting exactly:

    sum_r w(r) * O[r, :]  =  w(base) * colsum_tile + step * wcolsum_tile

for w(r) = w(base) + step * (r - base). That is what lets the conv detect
path recover both the n-weighted (s6) and m-weighted (s7) invariants from
the flattened (N*M, E*E) view without a second pass (kernels.ops
.conv_detect_sums).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .abft_matmul import _params, tile_sums

F32 = jnp.float32


def _kernel(o_ref, sums_ref):
    tile_sums(o_ref[...].astype(F32), sums_ref)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def checksum_reduce(o: jnp.ndarray, *, bm: int, bn: int,
                    interpret: bool) -> jnp.ndarray:
    """Returns sums (N/bm, 3, M): rows colsum, wcolsum, column sum of
    squares per row tile. Shapes must tile evenly into legal Mosaic
    blocks; ops.py picks the tiles."""
    n, m = o.shape
    assert n % bm == 0 and m % bn == 0, (o.shape, bm, bn)
    grid = (n // bm, m // bn)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((None, 3, bn), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 3, m), F32),
        compiler_params=_params(2, reduce_axis=False),
        interpret=interpret,
    )(o)
