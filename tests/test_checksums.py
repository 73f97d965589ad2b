"""Checksum algebra (paper Eq. 4/5/6): property-based over random shapes,
dtypes and adversarial value distributions. Runs under hypothesis when
installed, else as a deterministic seed sweep (see hypcompat)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import HealthCheck, given, settings, st

from repro.core import checksums as C

SETTINGS = dict(max_examples=25, deadline=None,
                suppress_health_check=[HealthCheck.too_slow])


def rand(key, shape, dtype, scale=1.0):
    x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(dtype)


@given(
    n=st.integers(2, 33), k=st.integers(1, 40), m=st.integers(2, 37),
    seed=st.integers(0, 2**31 - 1),
    scale=st.sampled_from([1.0, 1e-3, 1e3]))
@settings(**SETTINGS)
def test_matmul_checksum_invariants(n, k, m, seed, scale):
    """C_o1..C_o7 computed from input checksums equal the corresponding
    output summations (fp32, rounding-level tolerance)."""
    key = jax.random.PRNGKey(seed)
    d = rand(key, (n, k), jnp.float32, scale)
    w = rand(jax.random.fold_in(key, 1), (k, m), jnp.float32, scale)
    o = d @ w
    cd1, cd2 = C.encode_d_matmul(d)
    cw1, cw2 = C.encode_w_matmul(w)
    cs = C.output_checksums_matmul(d, w, cd1, cd2, cw1, cw2)
    ss = C.output_sums_matmul(o)
    tol = 1e-4 * (np.abs(float(cs.c5[0])) + float(jnp.sum(jnp.abs(o))) + 1e-6)
    np.testing.assert_allclose(cs.c5, ss.s5, atol=tol)
    np.testing.assert_allclose(cs.c6, ss.s6, atol=tol * n)
    np.testing.assert_allclose(cs.c7, ss.s7, atol=tol * m)
    np.testing.assert_allclose(cs.c1[:, 0], ss.s1[:, 0], atol=tol)
    np.testing.assert_allclose(cs.c2[:, 0], ss.s2[:, 0], atol=tol)
    np.testing.assert_allclose(cs.c3[:, 0], ss.s3[:, 0], atol=tol * n)
    np.testing.assert_allclose(cs.c4[:, 0], ss.s4[:, 0], atol=tol * m)


@given(
    n=st.integers(1, 6), ch=st.integers(1, 5), m=st.integers(1, 7),
    h=st.integers(4, 12), r=st.sampled_from([1, 3]),
    stride=st.sampled_from([1, 2]), seed=st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_conv_checksum_invariants(n, ch, m, h, r, stride, seed):
    """The distributive property of (x) (paper Eq. 4) holds for the real
    convolution: checksum convs equal output summations."""
    key = jax.random.PRNGKey(seed)
    d = rand(key, (n, ch, h, h), jnp.float32)
    w = rand(jax.random.fold_in(key, 1), (m, ch, r, r), jnp.float32)
    o = C.conv2d(d, w, stride=stride)
    cd1, cd2 = C.encode_d_conv(d)
    cw1, cw2 = C.encode_w_conv(w)
    cs = C.output_checksums_conv(d, w, cd1, cd2, cw1, cw2, stride=stride)
    ss = C.output_sums_conv(o)
    scale = float(jnp.sum(jnp.abs(o))) + 1.0
    np.testing.assert_allclose(cs.c5, ss.s5, atol=1e-4 * scale)
    np.testing.assert_allclose(cs.c6, ss.s6, atol=1e-4 * scale * n)
    np.testing.assert_allclose(cs.c7, ss.s7, atol=1e-4 * scale * m)
    np.testing.assert_allclose(cs.c1, ss.s1, atol=1e-4 * scale)
    np.testing.assert_allclose(cs.c2, ss.s2, atol=1e-4 * scale)


@given(groups=st.sampled_from([1, 2, 4]),
                  seed=st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_grouped_conv_checksums(groups, seed):
    """Paper SS5.2: grouped-conv kernel checksums concatenate per group and
    the output invariants still hold."""
    key = jax.random.PRNGKey(seed)
    n, ch, m, h, r = 3, 8, 8, 6, 3
    d = rand(key, (n, ch, h, h), jnp.float32)
    w = rand(jax.random.fold_in(key, 1), (m, ch // groups, r, r),
             jnp.float32)
    o = C.conv2d(d, w, groups=groups)
    cd1, cd2 = C.encode_d_conv(d)
    cw1, cw2 = C.encode_w_conv(w, groups=groups)
    cs = C.output_checksums_conv(d, w, cd1, cd2, cw1, cw2, groups=groups)
    ss = C.output_sums_conv(o)
    scale = float(jnp.sum(jnp.abs(o))) + 1.0
    np.testing.assert_allclose(cs.c5, ss.s5, atol=1e-4 * scale)
    np.testing.assert_allclose(cs.c1, ss.s1, atol=1e-4 * scale)


def test_distributive_property():
    """Paper Eq. 4 directly: (D1+D2) (x) W == D1 (x) W + D2 (x) W."""
    key = jax.random.PRNGKey(0)
    d1 = rand(key, (1, 4, 8, 8), jnp.float32)
    d2 = rand(jax.random.fold_in(key, 1), (1, 4, 8, 8), jnp.float32)
    w = rand(jax.random.fold_in(key, 2), (5, 4, 3, 3), jnp.float32)
    lhs = C.conv2d(d1 + d2, w)
    rhs = C.conv2d(d1, w) + C.conv2d(d2, w)
    np.testing.assert_allclose(lhs, rhs, rtol=1e-4, atol=1e-4)


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_bf16_no_false_positive(seed):
    """Error-free detection must not fire in bf16 (threshold contract)."""
    from repro.core import protect_matmul_output
    key = jax.random.PRNGKey(seed)
    d = rand(key, (128, 64), jnp.bfloat16)
    w = rand(jax.random.fold_in(key, 1), (64, 96), jnp.bfloat16)
    o = jnp.dot(d, w, preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    _, rep = protect_matmul_output(d, w, o)
    assert int(rep.detected) == 0


# --------------------------------------------------------------------------
# checksum_conv's im2col: strided taps without gathers
# --------------------------------------------------------------------------

def _indexed_im2col_conv(x, f, stride, padding, groups):
    """The reference: checksum_conv with numpy-indexed taps
    (`xp[..., i::stride, j::stride]`), which lowers to gathers at
    stride > 1. checksum_conv must keep its values bitwise."""
    b, c, h, w = x.shape
    nf, cg, r, s = f.shape
    (pt, pb), (pl, pr) = C._window_pads((h, w), (r, s), stride, padding)
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, 0), (pt, pb), (pl, pr)))
    e1 = (h + pt + pb - r) // stride + 1
    e2 = (w + pl + pr - s) // stride + 1
    cols = [xp[:, :, i:i + stride * (e1 - 1) + 1:stride,
               j:j + stride * (e2 - 1) + 1:stride]
            for i in range(r) for j in range(s)]
    pat = jnp.stack(cols, axis=2).reshape(b, groups, cg * r * s, e1 * e2)
    fm = f.astype(jnp.float32).reshape(groups, nf // groups, cg * r * s)
    out = jnp.einsum("bgkp,gfk->bgfp", pat, fm, precision=C.PRECISION)
    return out.reshape(b, nf, e1, e2)


# (x shape, f shape, stride, padding): resnet18's conv0 (7x7/2, pad 3),
# its 3x3/2 downsampling convs (conv5/9/13), alexnet's 11x11/4 stem, and
# a stride-1 site (conv1-conv4).
SITES = {
    "conv0": ((3, 3, 224, 224), (3, 3, 7, 7), 2, ((3, 3), (3, 3))),
    "3x3s2": ((3, 64, 56, 56), (3, 64, 3, 3), 2, ((1, 1), (1, 1))),
    "11x11s4": ((3, 3, 224, 224), (3, 3, 11, 11), 4, ((2, 2), (2, 2))),
    "3x3s1": ((3, 64, 56, 56), (3, 64, 3, 3), 1, ((1, 1), (1, 1))),
}


@pytest.mark.parametrize("site", sorted(SITES))
def test_checksum_conv_lowers_to_no_gather(site):
    """No `gather` anywhere, and no slice strided along the minor (lane)
    axis: numpy-style strided indexing lowers to gathers, which a TPU ran
    ~200x slower than a pass over the patches, and lane-strided slices
    still ~20x slower than a stride-1 site's whole check."""
    from jaxpr_walk import eqns as walk_eqns
    xs, fs, stride, padding = SITES[site]
    jaxpr = jax.make_jaxpr(
        lambda x, f: C.checksum_conv(x, f, stride, padding))(
            jnp.zeros(xs, jnp.float32), jnp.zeros(fs, jnp.float32))
    eqs = walk_eqns(jaxpr.jaxpr)
    assert "gather" not in [e.primitive.name for e in eqs]
    slices = [e for e in eqs if e.primitive.name == "slice"]
    assert len(slices) >= fs[2] * fs[3]          # one per tap
    assert all((e.params["strides"] or (1,))[-1] == 1 for e in slices)


@pytest.mark.parametrize("xs,fs,stride,padding,groups", [
    ((2, 3, 9, 9), (4, 3, 3, 3), 1, "SAME", 1),
    ((2, 3, 10, 10), (4, 3, 3, 3), 2, "SAME", 1),
    ((2, 4, 11, 11), (3, 4, 2, 2), 2, "VALID", 1),
    ((2, 3, 35, 35), (4, 3, 11, 11), 4, "VALID", 1),
    ((2, 3, 32, 30), (5, 3, 5, 4), 4, ((2, 1), (0, 3)), 1),
    ((2, 8, 15, 15), (6, 4, 3, 3), 2, "SAME", 2),
    ((1, 8, 12, 12), (8, 2, 3, 3), 1, "VALID", 4),
    SITES["conv0"] + (1,),
    SITES["3x3s2"] + (1,),
])
def test_checksum_conv_matches_indexed_im2col_bitwise(xs, fs, stride,
                                                     padding, groups):
    """The phase-built patches give bitwise the values of the
    numpy-indexed im2col, and the conv primitive's within f32 rounding."""
    key = jax.random.PRNGKey(sum(xs) + stride)
    x = rand(key, xs, jnp.float32)
    f = rand(jax.random.fold_in(key, 1), fs, jnp.float32)
    got = jax.jit(lambda x, f: C.checksum_conv(x, f, stride, padding,
                                               groups))(x, f)
    ref = jax.jit(lambda x, f: _indexed_im2col_conv(x, f, stride, padding,
                                                    groups))(x, f)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    conv = jax.lax.conv_general_dilated(
        x, f, (stride, stride), padding, dimension_numbers=C._DN,
        feature_group_count=groups, precision=jax.lax.Precision.HIGHEST)
    scale = float(jnp.max(jnp.abs(conv)))
    np.testing.assert_allclose(got, conv, rtol=1e-5, atol=1e-5 * scale)


@pytest.mark.parametrize("xs,fs,padding,groups", [
    SITES["3x3s1"][:2] + (SITES["3x3s1"][3], 1),
    ((3, 512, 7, 7), (3, 512, 3, 3), ((1, 1), (1, 1)), 1),
    ((2, 8, 12, 12), (6, 4, 3, 3), "VALID", 2),
])
def test_stride1_checksum_conv_lowering_unchanged(xs, fs, padding, groups):
    """At stride 1 the lowered checksum conv is the numpy-indexed one's,
    op for op: stride-1 sites run the same program as before."""
    def lowered(impl):
        def site(x, f):
            return impl(x, f, 1, padding, groups)
        return jax.jit(site).lower(
            jax.ShapeDtypeStruct(xs, jnp.float32),
            jax.ShapeDtypeStruct(fs, jnp.float32)).as_text(debug_info=False)
    assert lowered(C.checksum_conv) == lowered(_indexed_im2col_conv)
