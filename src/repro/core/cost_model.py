"""Measured roofline cost model (the offline-calibration half of the
arithmetic-intensity ABFT decision layer, arXiv:2104.09455).

The paper's SS4.3 analytic model (policy.CostModel) prices schemes in
abstract alpha/beta units; which ABFT variant actually wins on a given
host is decided by each layer's arithmetic intensity *relative to that
host's ridge point* (peak_FLOPs / memory_bandwidth). This module
measures both peaks once per host (a GEMM FLOPs microbench + a STREAM
triad bandwidth microbench, cached as JSON keyed by host+backend) and
derives a `MeasuredCostModel` whose alpha/beta are real seconds, so
every consumer of the analytic model - `decide_rc_clc`, rung selection,
chunk sizing, kernel-profile pruning and the per-entry execution
membership - classifies shapes against this machine instead of the
hardcoded TPU v5e constants in benchmarks/roofline.py.

    peaks = measure_peaks()                    # cached after first call
    model = MeasuredCostModel.from_host()
    model.classify(OpShape(n=8, m=256, ch=96, r=5, h=27))
    # -> {"bound": "compute", "intensity": 38.2, "ridge": 11.4,
    #     "predicted_us": {"base": ..., "coc": ..., "rc": ..., ...}}

Refresh a stale calibration (host upgrade, backend change) with
`measure_peaks(refresh=True)` or by deleting the cache file
(`REPRO_ROOFLINE_CACHE` overrides its location).
"""
from __future__ import annotations

import dataclasses
import json
import os
import platform
import time
from typing import Dict, Optional

from .policy import CostModel, OpShape

CACHE_SCHEMA = "repro.roofline_cache/v1"
CACHE_ENV = "REPRO_ROOFLINE_CACHE"

BYTES_F32 = 4
# microbench sizes: big enough to sit above dispatch noise on a 2-core CI
# runner, small enough that first-call calibration stays ~1s
_GEMM_N = 512
_TRIAD_ELEMS = 1 << 22     # 16 MiB per operand array


@dataclasses.dataclass(frozen=True)
class HostPeaks:
    """One host's measured roofline corners (sustained, not datasheet)."""
    peak_flops: float     # FLOP/s sustained on an f32 GEMM
    hbm_bw: float         # bytes/s sustained on a triad stream
    backend: str          # jax.default_backend() at measurement time
    host: str             # platform.node() at measurement time
    source: str           # "measured" | "cache"

    @property
    def ridge(self) -> float:
        """Ridge-point arithmetic intensity (FLOPs per byte)."""
        return self.peak_flops / self.hbm_bw

    def doc(self) -> dict:
        return {"peak_flops": self.peak_flops, "hbm_bw": self.hbm_bw,
                "ridge": self.ridge, "backend": self.backend,
                "host": self.host, "source": self.source}


def default_cache_path(backend: Optional[str] = None) -> str:
    """Per-host calibration cache location (REPRO_ROOFLINE_CACHE wins)."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    if backend is None:
        import jax
        backend = jax.default_backend()
    host = platform.node() or "unknown"
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "repro",
                        f"roofline_{backend}_{host}.json")


def _time_best(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _bench_gemm_flops(n: int = _GEMM_N) -> float:
    """Sustained f32 GEMM FLOP/s in the protected op's arithmetic
    (protected.op_matmul): 2*n^3 FLOPs over the best of a few timed
    (n,n)@(n,n) products."""
    import jax
    import jax.numpy as jnp

    from .protected import op_matmul
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, n), jnp.float32)
    f = jax.jit(op_matmul)
    t = _time_best(f, a, b)
    return 2.0 * n ** 3 / max(t, 1e-9)


def _bench_triad_bw(elems: int = _TRIAD_ELEMS) -> float:
    """Sustained bytes/s on a STREAM-triad pass (y = 2x + z): three f32
    streams (two reads, one write) per element."""
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (elems,), jnp.float32)
    z = jax.random.normal(jax.random.fold_in(key, 1), (elems,), jnp.float32)
    f = jax.jit(lambda x, z: 2.0 * x + z)
    t = _time_best(f, x, z)
    return 3.0 * BYTES_F32 * elems / max(t, 1e-9)


def measure_peaks(cache_path: Optional[str] = None, refresh: bool = False
                  ) -> HostPeaks:
    """This host's (peak_flops, hbm_bw), measured once and cached as JSON.

    The first call on a host runs the two microbenches (~1s) and writes
    the cache; later calls (and other processes) load it, so plan builds
    are deterministic given the cache file. `refresh=True` re-measures
    and rewrites; a cache recorded under a different backend is treated
    as stale and re-measured too. A microbench that cannot run raises:
    made-up peaks would silently decide every plan verdict."""
    import jax
    backend = jax.default_backend()
    path = cache_path or default_cache_path(backend)
    if not refresh and os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
            if (doc.get("schema") == CACHE_SCHEMA
                    and doc.get("backend") == backend):
                return HostPeaks(float(doc["peak_flops"]),
                                 float(doc["hbm_bw"]),
                                 backend, doc.get("host", "?"), "cache")
        except (ValueError, KeyError, OSError):
            pass                       # unreadable cache: re-measure
    flops = _bench_gemm_flops()
    bw = _bench_triad_bw()
    host = platform.node() or "unknown"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"schema": CACHE_SCHEMA, "backend": backend,
                   "host": host, "peak_flops": flops, "hbm_bw": bw,
                   "gemm_n": _GEMM_N, "triad_elems": _TRIAD_ELEMS,
                   "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S")},
                  f, indent=2)
    return HostPeaks(flops, bw, backend, host, "measured")


# --------------------------------------------------------------------------
# the measured model
# --------------------------------------------------------------------------

def shape_flops(s: OpShape) -> float:
    """FLOPs of the raw op (2 per MAC): matmul r=h=1 gives 2*n*m*ch."""
    return 2.0 * s.n * s.m * s.ch * s.r ** 2 * s.h ** 2


def shape_bytes(s: OpShape) -> float:
    """Minimum f32 traffic: read D and W once, write O once."""
    return BYTES_F32 * (s.d_elems + s.w_elems + s.n * s.m * s.h ** 2)


@dataclasses.dataclass
class MeasuredCostModel(CostModel):
    """policy.CostModel with measured coefficients: alpha is this host's
    seconds per MAC (2 FLOPs), beta its seconds per f32 element moved, so
    `decide_rc_clc` and the Table-4 t_* terms price schemes in real
    seconds. Adds roofline classification (`classify`), the
    kernel-profile pruning window (`should_profile`) and bandwidth-bound
    detection chunk sizing (`detect_chunk`)."""
    peak_flops: float = dataclasses.field(kw_only=True)
    hbm_bw: float = dataclasses.field(kw_only=True)
    source: str = "given"
    # profile only shapes whose intensity/ridge ratio falls inside this
    # window: far-bandwidth-bound shapes never amortise a fused epilogue
    # and far-compute-bound shapes hide the detection pass entirely, so
    # timing either is wasted plan-build time
    profile_window: tuple = (0.25, 4.0)
    # target seconds of streamed detect traffic per chunk: keeps the
    # chunked detection pass bandwidth-bound (one chunk's checksum
    # reduction stays resident while the stream saturates)
    chunk_stream_s: float = 1e-4

    def __post_init__(self):
        self.alpha = 2.0 / self.peak_flops
        self.beta = BYTES_F32 / self.hbm_bw

    @classmethod
    def from_host(cls, cache_path: Optional[str] = None,
                  refresh: bool = False) -> "MeasuredCostModel":
        p = measure_peaks(cache_path=cache_path, refresh=refresh)
        return cls(peak_flops=p.peak_flops, hbm_bw=p.hbm_bw,
                   source=p.source)

    @property
    def ridge(self) -> float:
        return self.peak_flops / self.hbm_bw

    def intensity(self, s: OpShape) -> float:
        return shape_flops(s) / shape_bytes(s)

    def base_us(self, s: OpShape) -> float:
        """Roofline time of the raw op: max of the compute and memory
        terms, in microseconds."""
        return max(shape_flops(s) / self.peak_flops,
                   shape_bytes(s) / self.hbm_bw) * 1e6

    def classify(self, s: OpShape) -> Dict:
        """Roofline verdict for one op shape: which side of this host's
        ridge it falls on, plus the predicted cost of each scheme tier
        (base = the raw op; the others add the Table-4 scheme term)."""
        inten = self.intensity(s)
        base = self.base_us(s)
        return {
            "intensity": inten,
            "ridge": self.ridge,
            "bound": "compute" if inten >= self.ridge else "bandwidth",
            "predicted_us": {
                "base": base,
                "coc": base + self.t_coc(s) * 1e6,
                "rc": base + (self.t_coc(s) + self.t_rc(s)) * 1e6,
                "clc": base + (self.t_coc(s) + self.t_clc(s)) * 1e6,
                "fc": base + (self.t_coc(s) + self.t_fc(s)) * 1e6,
            },
        }

    def should_profile(self, s: OpShape) -> bool:
        """Prune the profile_kernels candidate set to shapes near the
        ridge - the only regime where the plain-vs-fused decision is
        actually close enough to need a measurement."""
        lo, hi = self.profile_window
        ratio = self.intensity(s) / self.ridge
        return lo <= ratio <= hi

    def detect_chunk(self, default: int,
                     lo: int = 256, hi: int = 4096) -> int:
        """Detection chunk edge sized so one (chunk x chunk) f32 tile
        streams in ~chunk_stream_s at this host's measured bandwidth -
        large enough to amortise per-chunk reduction setup, small enough
        that the chunked detect pass stays bandwidth-bound. Snapped to a
        power of two and clamped to [lo, hi]; deterministic given the
        calibration."""
        budget_elems = self.chunk_stream_s * self.hbm_bw / BYTES_F32
        edge = max(budget_elems, 1.0) ** 0.5
        snapped = 1 << max(int(edge).bit_length() - 1, 0)
        return int(min(max(snapped, lo), hi))

    def params_doc(self) -> dict:
        return {"alpha": self.alpha, "beta": self.beta,
                "peak_flops": self.peak_flops, "hbm_bw": self.hbm_bw,
                "ridge": self.ridge, "source": self.source,
                "profile_window": list(self.profile_window),
                "chunk_stream_s": self.chunk_stream_s}


def cost_model_doc(model: CostModel) -> dict:
    """Persistable description of any cost model: class name + its
    parameters (the satellite fix for plans that recorded only
    {alpha, beta} and could not state which policy produced them)."""
    doc = {"class": type(model).__name__,
           "alpha": model.alpha, "beta": model.beta}
    if hasattr(model, "params_doc"):
        doc["params"] = model.params_doc()
    else:
        doc["params"] = {"alpha": model.alpha, "beta": model.beta}
    return doc
