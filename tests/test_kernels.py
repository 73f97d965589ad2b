"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs the pure-jnp
oracles in repro.kernels.ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [(64, 32, 48), (128, 128, 128), (256, 64, 512), (96, 160, 224),
          (512, 256, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]


PARTS = ["colsum", "wcolsum", "sqsum"]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_abft_matmul_vs_ref(shape, dtype):
    n, k, m = shape
    key = jax.random.PRNGKey(n * 7 + m)
    d = jax.random.normal(key, (n, k), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, m),
                          jnp.float32).astype(dtype)
    o, parts = ops.abft_matmul(d, w, interpret=True)
    o_ref, sums_ref = ref.abft_matmul_ref(d, w, parts.bm)
    # kernel accumulates over bk-sized K steps; the oracle in one dot -
    # fp32 reassociation noise only
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=1e-5, atol=1e-4 * k ** 0.5)
    for r, name in enumerate(PARTS):
        np.testing.assert_allclose(np.asarray(parts.sums[:, r]),
                                   np.asarray(sums_ref[:, r]), rtol=1e-5,
                                   atol=1e-3 * k ** 0.5 * (
                                       parts.bm if name == "wcolsum" else 1),
                                   err_msg=name)


@pytest.mark.parametrize("shape", [(64, 48), (512, 384), (128, 1024)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_checksum_reduce_vs_ref(shape, dtype):
    key = jax.random.PRNGKey(shape[0])
    o = jax.random.normal(key, shape, jnp.float32).astype(dtype)
    parts = ops.checksum_reduce(o, interpret=True)
    want = ref.checksum_reduce_ref(o, parts.bm)
    np.testing.assert_allclose(np.asarray(parts.sums[:, 0]),
                               np.asarray(want[:, 0]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(parts.sums[:, 2]),
                               np.asarray(want[:, 2]), rtol=1e-5)
    # weights up to bm-1 amplify magnitudes (and reassociation noise)
    wscale = float(np.max(np.abs(np.asarray(want[:, 1])))) + 1.0
    np.testing.assert_allclose(np.asarray(parts.sums[:, 1]),
                               np.asarray(want[:, 1]), atol=1e-6 * wscale)


@pytest.mark.parametrize("shape", [(1100, 300), (40, 1100), (1100, 1100)])
def test_checksum_reduce_padded_edges(shape):
    """Axes past the one-block cap that no aligned tile divides run the
    kernel on zero-padded operands and slice back - partial totals must
    match the element-resolution values."""
    key = jax.random.PRNGKey(sum(shape))
    o = jax.random.normal(key, shape, jnp.float32)
    parts = ops.checksum_reduce(o, interpret=True)
    n, m = shape
    assert parts.sums.shape == (-(-n // parts.bm), 3, m)
    assert parts.n == n
    assert n % parts.bm or m % parts.bn        # padding really happened
    # totals are exact regardless of tiling
    np.testing.assert_allclose(float(jnp.sum(parts.sums[:, 0])),
                               float(jnp.sum(o)), rtol=1e-5)
    np.testing.assert_allclose(float(jnp.sum(parts.sums[:, 2])),
                               float(jnp.sum(o * o)), rtol=1e-5)


@pytest.mark.parametrize("rb,cb", [(64, 128), (128, 256), (256, 128)])
def test_chunk_sums_from_partials(rb, cb):
    key = jax.random.PRNGKey(0)
    n, k, m = 256, 64, 512
    d = jax.random.normal(key, (n, k))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, m))
    o, parts = ops.abft_matmul(d, w, interpret=True, bm=min(64, rb),
                               bn=min(128, cb))
    s = ops.chunk_sums_from_partials(parts, rb, cb)
    sref = ref.chunk_sums_ref(jnp.asarray(o, jnp.float32), rb, cb)
    for a, b, name in zip(s, sref, ["s5", "s6", "s7", "sumsq"]):
        scale = float(jnp.max(jnp.abs(b))) + 1.0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * scale, err_msg=name)


def test_fused_protection_end_to_end():
    """protected_matmul with the fused kernel detects + corrects exactly
    like the unfused path."""
    import repro.core as core
    cfg = core.ProtectConfig(use_fused_kernel=True, kernel_interpret=True,
                             row_chunk=128, col_chunk=128)
    key = jax.random.PRNGKey(5)
    d = jax.random.normal(key, (256, 128))
    w = jax.random.normal(jax.random.fold_in(key, 1), (128, 256))
    o, rep = core.protected_matmul(d, w, cfg=cfg)
    assert int(rep.detected) == 0
    np.testing.assert_allclose(np.asarray(o), np.asarray(d @ w), atol=1e-4)


def test_unaligned_fallback():
    """Odd shapes run the kernel as whole-axis blocks without changing
    semantics."""
    key = jax.random.PRNGKey(9)
    d = jax.random.normal(key, (37, 19))
    w = jax.random.normal(jax.random.fold_in(key, 1), (19, 53))
    o, parts = ops.abft_matmul(d, w, interpret=True)
    assert (parts.bm, parts.bn) == (37, 53)
    np.testing.assert_allclose(np.asarray(o), np.asarray(d @ w), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("shape", [(1100, 24, 56), (40, 1100, 136)])
def test_abft_matmul_padded_edges(shape):
    """Axes that no legal tile divides still run the fused kernel via zero
    padding; O and the partial totals stay exact."""
    n, k, m = shape
    key = jax.random.PRNGKey(n + m)
    d = jax.random.normal(key, (n, k))
    w = jax.random.normal(jax.random.fold_in(key, 2), (k, m))
    o, parts = ops.abft_matmul(d, w, interpret=True, bm=32, bn=128, bk=128)
    np.testing.assert_allclose(np.asarray(o), np.asarray(d @ w), rtol=1e-5,
                               atol=1e-4)
    assert parts.sums.shape == (-(-n // parts.bm), 3, m) and parts.n == n
    np.testing.assert_allclose(float(jnp.sum(parts.sums[:, 0])),
                               float(jnp.sum(o)), rtol=1e-5)
    np.testing.assert_allclose(float(jnp.sum(parts.sums[:, 2])),
                               float(jnp.sum(jnp.square(d @ w))), rtol=1e-4)


def test_chunk_sums_fallback_from_o():
    """Chunks that are not tile multiples recombine from O at element
    resolution instead of raising."""
    key = jax.random.PRNGKey(3)
    n, k, m = 96, 32, 160
    d = jax.random.normal(key, (n, k))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, m))
    o, parts = ops.abft_matmul(d, w, interpret=True, bm=32, bn=32)
    # rb=48 is not a multiple of bm=32 -> needs the o= fallback
    with pytest.raises(ValueError):
        ops.chunk_sums_from_partials(parts, 48, 32)
    s = ops.chunk_sums_from_partials(parts, 48, 32, o=o)
    sref = ref.chunk_sums_ref(jnp.asarray(o, jnp.float32), 48, 32)
    for a, b, name in zip(s, sref, ["s5", "s6", "s7", "sumsq"]):
        scale = float(jnp.max(jnp.abs(b))) + 1.0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * scale, err_msg=name)


@pytest.mark.parametrize("oshape", [(8, 32, 8, 8), (4, 24, 15, 15)])
def test_conv_detect_sums_vs_jnp(oshape):
    """The Pallas route for the conv detection sums agrees with the fused
    jnp pass (including M/P padding on the flattened view)."""
    from repro.core import checksums as C
    key = jax.random.PRNGKey(oshape[1])
    o = jax.random.normal(key, oshape, jnp.float32)
    got = ops.conv_detect_sums(o, interpret=True, tiles=(8, 64))
    want = C.detect_sums(o)
    for a, b, name in zip(got, want, ["s5", "s6", "s7", "sumsq"]):
        scale = float(jnp.max(jnp.abs(jnp.atleast_1d(b)))) + 1.0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * scale, err_msg=name)


def _chunk_checksums_ref(d, w, rb, cb):
    """Exact per-chunk c5/c6/c7/absdot of the raw product, straight from
    the definition (locally index-weighted, fp32)."""
    n, k = d.shape
    m = w.shape[1]
    o = jnp.dot(d.astype(jnp.float32), w.astype(jnp.float32))
    nb, mb = n // rb, m // cb
    oc = o.reshape(nb, rb, mb, cb)
    c5 = oc.sum(axis=(1, 3))
    c6 = jnp.einsum("arbc,r->ab", oc, jnp.arange(rb, dtype=jnp.float32))
    c7 = jnp.einsum("arbc,c->ab", oc, jnp.arange(cb, dtype=jnp.float32))
    ad = jnp.dot(jnp.abs(d.astype(jnp.float32)),
                 jnp.abs(w.astype(jnp.float32)))
    absdot = ad.reshape(nb, rb, mb, cb).sum(axis=(1, 3))
    return c5, c6, c7, absdot


@pytest.mark.parametrize("dtype", DTYPES)
def test_abft_matmul_detect_clean_and_tampered(dtype):
    """The single-launch detect kernel: exact checksums -> every tile
    flag clear and output matches the dot; a corrupted checksum -> the
    owning tile (and only it) flags with score > 1."""
    from repro.core import thresholds as TH
    n, k, m = 32, 64, 256
    rb, cb = 16, 128
    key = jax.random.PRNGKey(5)
    d = jax.random.normal(key, (n, k), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, m),
                          jnp.float32).astype(dtype)
    c5, c6, c7, absdot = _chunk_checksums_ref(d, w, rb, cb)
    tau_a, tau_b = TH.tau_scalar_coeffs(k, dtype, 64.0)
    o, flag, score = ops.abft_matmul_detect(
        d, w, c5, c6, c7, absdot, rb=rb, cb=cb, tau_a=tau_a, tau_b=tau_b,
        interpret=True)
    assert flag.shape == (n // rb, m // cb)
    assert int(flag.sum()) == 0, np.asarray(score)
    np.testing.assert_allclose(
        np.asarray(o, np.float32),
        np.asarray(jnp.dot(d.astype(jnp.float32), w.astype(jnp.float32)),
                   np.float32).astype(np.asarray(o).dtype),
        rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-2)
    _, flag2, score2 = ops.abft_matmul_detect(
        d, w, c5.at[1, 0].add(5e3), c6, c7, absdot, rb=rb, cb=cb,
        tau_a=tau_a, tau_b=tau_b, interpret=True)
    assert int(flag2[1, 0]) == 1 and float(score2[1, 0]) > 1.0
    assert int(flag2.sum()) == 1


def test_abft_matmul_detect_refuses_misaligned_chunks():
    """Chunkings the kernel cannot launch as tiles signal the partials
    route with None instead of computing something wrong."""
    d = jnp.ones((32, 64))
    w = jnp.ones((64, 256))
    z = jnp.zeros((8, 2))
    # rb=4 is below the minimum (sublane) tile
    assert ops.abft_matmul_detect(d, w, z, z, z, z, rb=4, cb=128,
                                  tau_a=1.0, tau_b=1.0,
                                  interpret=True) is None
    # cb=64 is not a whole number of lane tiles
    z2 = jnp.zeros((2, 4))
    assert ops.abft_matmul_detect(d, w, z2, z2, z2, z2, rb=16, cb=64,
                                  tau_a=1.0, tau_b=1.0,
                                  interpret=True) is None
    # checksum grid does not match the (rb, cb) chunking
    assert ops.abft_matmul_detect(d, w, z, z, z, z, rb=16, cb=128,
                                  tau_a=1.0, tau_b=1.0,
                                  interpret=True) is None


@pytest.mark.parametrize("dtype", DTYPES)
def test_abft_matmul_detect_vs_ref(dtype):
    """Kernel verdicts equal the ref.py oracle's, tile by tile, for clean
    and for tampered checksum predictions."""
    from repro.core import thresholds as TH
    n, k, m = 64, 96, 256
    rb, cb = 32, 128
    key = jax.random.PRNGKey(21)
    d = jax.random.normal(key, (n, k), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, m),
                          jnp.float32).astype(dtype)
    cs = ref.chunk_checksums_ref(d, w, rb, cb)
    tau_a, tau_b = TH.tau_scalar_coeffs(k, dtype, 32.0)
    for c5 in (cs[0], cs[0].at[1, 1].add(1e3)):
        got = ops.abft_matmul_detect(d, w, c5, *cs[1:], rb=rb, cb=cb,
                                     tau_a=tau_a, tau_b=tau_b,
                                     interpret=True)
        want = ref.abft_matmul_detect_ref(d, w, c5, *cs[1:], rb, cb,
                                          tau_a, tau_b)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        # scores are |c - s| / tau: clean tiles carry rounding noise far
        # below 1 on both sides, so compare them in units of tau
        np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                                   rtol=1e-4, atol=1e-2)
        np.testing.assert_allclose(np.asarray(got[0], np.float32),
                                   np.asarray(want[0], np.float32),
                                   rtol=1e-5, atol=1e-4)


def test_conv_detect_sums_vs_ref():
    o = jax.random.normal(jax.random.PRNGKey(4), (4, 40, 7, 7))
    got = ops.conv_detect_sums(o, interpret=True)
    for a, b, name in zip(got, ref.conv_detect_sums_ref(o),
                          ["s5", "s6", "s7", "sumsq"]):
        scale = float(jnp.max(jnp.abs(jnp.atleast_1d(b)))) + 1.0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5 * scale, err_msg=name)


@pytest.mark.parametrize("entry", ["abft_matmul", "checksum_reduce",
                                   "conv_detect_sums"])
def test_kernel_entry_points_require_interpret(entry):
    """No kernel entry point defaults to interpret mode: a caller that
    forgets the flag fails instead of interpreting on the chip."""
    x = jnp.ones((8, 8, 4, 4)) if entry == "conv_detect_sums" \
        else jnp.ones((8, 128))
    args = (x, jnp.ones((128, 128))) if entry == "abft_matmul" else (x,)
    with pytest.raises(TypeError, match="interpret"):
        getattr(ops, entry)(*args)
