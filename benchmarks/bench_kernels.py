"""Kernel-level benchmark: (a) XLA-fused detection cost on CPU (real
timings of matmul vs matmul+CoC-D), and (b) the *structural* HBM-traffic
accounting of the fused Pallas epilogue vs the paper's separate encode
pass (interpret-mode timings are meaningless, so the kernel's win is
reported in derived bytes - the quantity the TPU roofline uses)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import protect_matmul_output, protected_matmul
from .common import row, time_fn

SHAPES = [(4096, 1024, 4096), (8192, 2048, 2048)]


def run():
    print("# kernels: detection overhead (CPU) + fused-epilogue traffic")
    out = []
    for n, k, m in SHAPES:
        key = jax.random.PRNGKey(0)
        d = jax.random.normal(key, (n, k), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (k, m),
                              jnp.float32)
        f_plain = jax.jit(lambda d, w: d @ w)
        f_prot = jax.jit(lambda d, w: protected_matmul(d, w)[0])
        t0 = time_fn(f_plain, d, w, iters=3)
        t1 = time_fn(f_prot, d, w, iters=3)
        out.append(row(f"kernels/detect/{n}x{k}x{m}", t1 * 1e6,
                       f"overhead_pct={(t1-t0)/t0*100:.2f}"))
        # structural traffic: separate encode re-reads O (n*m*4B) +
        # re-reads D (n*k*4B); fused epilogue writes only the partials
        # (3 rows of m per 256-row tile)
        bm = 256
        sep = (n * m + n * k) * 4
        fused = 3 * m * (n // bm) * 4
        out.append(row(f"kernels/fused_traffic/{n}x{k}x{m}", 0.0,
                       f"separate_encode_bytes={sep};"
                       f"fused_partial_bytes={fused};"
                       f"reduction={sep/max(fused,1):.0f}x"))
    return out


if __name__ == "__main__":
    run()
