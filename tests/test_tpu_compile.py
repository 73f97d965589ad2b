"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e
(no chip attached): Mosaic's block-shape and memory rules at the real
widths the chip smoke runs, which interpret mode never checks.

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and every xdist
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

F32, BF16 = jnp.float32, jnp.bfloat16
GEMM = (512, 960, 2560)       # smollm-360m ffn up-projection, 512 rows
FC = (8, 512, 1000)           # resnet18 fc at batch 8


@pytest.fixture(scope="module")
def one_chip():
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev


@pytest.fixture
def tpu_arithmetic(monkeypatch):
    """The program picks its operand precision from the backend
    (types.op_operand_dtype); JAX here runs on the CPU, so steer that
    choice to what the chip runs."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one - keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem is not None and mem.temp_size_in_bytes >= 0
    return compiled


@pytest.mark.parametrize("shape,dtype", [(GEMM, F32), (GEMM, BF16),
                                         (FC, F32)])
def test_abft_matmul_compiles_for_v5e(one_chip, no_compile_cache,
                                      tpu_arithmetic, shape, dtype):
    n, k, m = shape
    _compile(lambda d, w: ops.abft_matmul(d, w, interpret=False),
             one_chip, ((n, k), dtype), ((k, m), dtype))


@pytest.mark.parametrize("dtype", [F32, BF16])
def test_abft_matmul_detect_compiles_for_v5e(one_chip, no_compile_cache,
                                             tpu_arithmetic, dtype):
    n, k, m = GEMM
    rb = cb = 256

    def detect(d, w, c):
        out = ops.abft_matmul_detect(d, w, c, c, c, c, rb=rb, cb=cb,
                                     tau_a=1e-3, tau_b=1e-6,
                                     interpret=False)
        assert out is not None
        return out

    _compile(detect, one_chip, ((n, k), dtype), ((k, m), dtype),
             ((n // rb, m // cb), F32))


@pytest.mark.parametrize("oshape", [(8, 64, 56, 56), (8, 512, 7, 7)])
def test_checksum_reduce_compiles_for_v5e(one_chip, no_compile_cache,
                                          tpu_arithmetic, oshape):
    """resnet18's first- and last-stage conv outputs at batch 8, through
    the flattened-view route the conv detect path takes."""
    _compile(lambda o: ops.conv_detect_sums(o, interpret=False), one_chip,
             (oshape, F32))
