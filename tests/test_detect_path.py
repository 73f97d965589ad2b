"""Structural + differential guards for the single-pass detection hot path.

The error-free cost model of this repo is: every protected op is the
underlying op plus ONE fused O(|O|) detection pass. These tests pin that
down two ways:

* jaxpr structure - trace the error-free path and assert exactly one
  large conv / dot_general sits outside the `lax.cond` correction branch,
  and that none of the full-resolution s1-s4 / c1-c4 reductions leak out
  of it (a reintroduced per-checksum conv or weighted full-size reduction
  fails the op-count/shape assertions immediately);
* differential parity - the lean detection sums and checksums must agree
  with the full `output_sums_conv` / `output_checksums_conv` values
  (bitwise on fp32 for the sums: same reduction order, same arithmetic),
  and detection/correction verdicts through the new path must match a
  seeded injection sweep.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jaxpr_walk import eqns as walk_eqns

import repro.core as core
from repro.core import checksums as C
from repro.core import injection as inj
from repro.core import types as T
from repro.core.protected import protected_conv, protected_matmul
from repro.models import cnn

F32 = jnp.float32


# --------------------------------------------------------------------------
# jaxpr walking helpers
# --------------------------------------------------------------------------

def _outer_eqns(jaxpr):
    """Equations of `jaxpr` and of every inner jaxpr EXCEPT cond branches
    (the correction ladder); pjit/closed_call bodies are inlined."""
    return walk_eqns(jaxpr, skip=("cond",))


def _convs(eqns):
    """The convolutions among `eqns`: conv primitives, and the checksum
    convs, which run as one contraction each (checksums.checksum_conv)."""
    return [e for e in eqns
            if e.primitive.name == "conv_general_dilated"
            or (e.primitive.name == "dot_general"
                and "checksum_conv" in str(e.source_info.name_stack))]


def _size(var) -> int:
    sh = getattr(var.aval, "shape", ())
    out = 1
    for s in sh:
        out *= s
    return out


def _dot_flops(eqn) -> int:
    """Rough dot_general cost: output elements * contraction length."""
    dims = eqn.params["dimension_numbers"][0][0]
    k = 1
    for ax in dims:
        k *= eqn.invars[0].aval.shape[ax]
    return _size(eqn.outvars[0]) * k


# --------------------------------------------------------------------------
# structure: the error-free path is op + one fused pass
# --------------------------------------------------------------------------

N, CH, H = 8, 6, 16
M, R = 24, 3
K_MM, M_MM = 96, 64


def _conv_operands():
    key = jax.random.PRNGKey(0)
    d = jax.random.normal(key, (N, CH, H, H), F32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (M, CH, R, R), F32)
    b = jax.random.normal(jax.random.fold_in(key, 2), (M,), F32)
    return d, w, b


def _matmul_operands():
    key = jax.random.PRNGKey(1)
    d = jax.random.normal(key, (N, K_MM), F32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K_MM, M_MM), F32)
    return d, w


@pytest.mark.parametrize("detect_only", [True, False])
def test_conv_errorfree_path_structure(detect_only):
    d, w, b = _conv_operands()
    cfg = T.DEFAULT_CONFIG.replace(detect_only=detect_only)
    jaxpr = jax.make_jaxpr(
        lambda d, w, b: protected_conv(d, w, bias=b, cfg=cfg)[0])(d, w, b)
    eqns = _outer_eqns(jaxpr.jaxpr)
    convs = _convs(eqns)
    # exactly the protected op itself + ONE fused checksum conv; the old
    # path's separate c5/c6/c7/absdot convs (and the correction branch's
    # c1-c4 convs) would push this to 5+
    assert len(convs) == 2, [str(e) for e in convs]
    o_elems = N * M * (H - R + 1) ** 2
    # no s1-s4-style reductions in the detect path: every dot_general out
    # here is an O(P)-sized finishing step, never a full-resolution
    # (M,P)/(N,P) weighted summation
    for e in eqns:
        if e.primitive.name == "dot_general":
            assert _size(e.outvars[0]) < o_elems / 2, str(e)


@pytest.mark.parametrize("detect_only", [True, False])
def test_matmul_errorfree_path_structure(detect_only):
    d, w = _matmul_operands()
    cfg = T.DEFAULT_CONFIG.replace(detect_only=detect_only)
    jaxpr = jax.make_jaxpr(
        lambda d, w: protected_matmul(d, w, cfg=cfg)[0])(d, w)
    eqns = _outer_eqns(jaxpr.jaxpr)
    assert not _convs(eqns)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    main_flops = N * K_MM * M_MM
    heavy = [e for e in dots if _dot_flops(e) >= main_flops / 2]
    # the GEMM itself is the only heavy contraction outside the ladder
    # (c1-c4 GEMVs are K*M/N*K-sized and must stay inside the cond)
    assert len(heavy) == 1, [str(e) for e in heavy]


# --------------------------------------------------------------------------
# single-launch fused detection (GEMM + threshold compare in one kernel)
# --------------------------------------------------------------------------

def _outer_eqns_no_pallas(jaxpr):
    """_outer_eqns, but treating pallas_call bodies as opaque: the fused
    detect kernel's inner jaxpr legitimately holds the GEMM dot and the
    epilogue reductions, so recursing into it would count the very ops
    whose absence OUTSIDE the kernel these assertions pin."""
    return walk_eqns(jaxpr, skip=("cond",), opaque=("pallas_call",))


def test_fused_detect_only_is_single_launch():
    """With use_fused_kernel pinned, a detect-only matmul site lowers to
    exactly ONE Pallas launch: the GEMM and the threshold compare run in
    the same kernel, and the only contractions left outside are the
    O(K)-sized checksum encodes - no standalone detection dot, no second
    dispatch."""
    d, w = _matmul_operands()
    cfg = T.DEFAULT_CONFIG.replace(use_fused_kernel=True)
    jaxpr = jax.make_jaxpr(
        lambda d, w: protected_matmul(d, w, cfg=cfg,
                                      mode="detect_only"))(d, w)
    eqns = _outer_eqns_no_pallas(jaxpr.jaxpr)
    launches = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(launches) == 1, [str(e.primitive) for e in eqns]
    main_flops = N * K_MM * M_MM
    for e in eqns:
        if e.primitive.name == "dot_general":
            assert _dot_flops(e) < main_flops / 2, str(e)


def test_fused_detect_verdicts_match_unfused():
    """The single-launch verdict agrees with the unfused detect path:
    clean on clean weights, flagged on a post-encode corruption, same raw
    output either way."""
    from repro.core.protected import pick_chunk, weight_checksums_matmul
    d, w = _matmul_operands()
    cb = pick_chunk(M_MM, T.DEFAULT_CONFIG.col_chunk)
    wck = weight_checksums_matmul(w, cb)
    fused = T.DEFAULT_CONFIG.replace(use_fused_kernel=True)
    for tamper in (0.0, 60.0):
        wx = w.at[3, 5].add(tamper)
        o_f, ev_f = protected_matmul(d, wx, wck=wck, cfg=fused,
                                     mode="detect_only")
        o_p, ev_p = protected_matmul(d, wx, wck=wck,
                                     cfg=T.DEFAULT_CONFIG,
                                     mode="detect_only")
        assert isinstance(ev_f, T.DetectEvidence)
        want = 1 if tamper else 0
        assert int(ev_f.flag) == int(ev_p.flag) == want, tamper
        np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_p),
                                   rtol=1e-6, atol=1e-5)


def test_fused_detect_bias_site_keeps_partials_route():
    """Bias-carrying sites must NOT take the raw-vs-raw single-launch
    compare (the kernel accumulates the raw product; the checksum side
    would need bias adjustment) - they keep the partials route and still
    return a correct verdict."""
    d, w = _matmul_operands()
    b = jax.random.normal(jax.random.PRNGKey(7), (M_MM,), F32)
    cfg = T.DEFAULT_CONFIG.replace(use_fused_kernel=True)
    o, ev = protected_matmul(d, w, bias=b, cfg=cfg, mode="detect_only")
    assert int(ev.flag) == 0
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(jnp.dot(d, w) + b), rtol=1e-5,
        atol=1e-4)


def test_conv_correction_stays_in_cond():
    """The full config still traces the correction machinery - but only
    inside the cond: the whole program contains the c1-c4 convs, the
    outer slice does not."""
    d, w, b = _conv_operands()
    cfg = T.DEFAULT_CONFIG

    jaxpr = jax.make_jaxpr(
        lambda d, w, b: protected_conv(d, w, bias=b, cfg=cfg)[0])(d, w, b)
    total = len(_convs(walk_eqns(jaxpr.jaxpr)))
    outer = len(_convs(_outer_eqns(jaxpr.jaxpr)))
    assert outer == 2
    assert total > outer  # ladder rungs really are traced, behind the cond


# --------------------------------------------------------------------------
# differential parity: lean detection == full encode, bitwise on fp32
# --------------------------------------------------------------------------

@pytest.mark.parametrize("oshape", [(8, 24, 7, 7), (4, 12, 15, 15),
                                    (16, 8, 3, 3)])
def test_detect_sums_bitwise_parity(oshape):
    """Two parity contracts against the old full encode:

    * exact_order=True reduces in output_sums_conv's order and must be
      BITWISE identical on fp32 (same arithmetic, fewer outputs);
    * the default GEMM formulation reassociates (BLAS) and must stay at
      ulp level - far inside the detection thresholds.
    """
    o = jax.random.normal(jax.random.PRNGKey(oshape[1]), oshape, F32)
    full = C.output_sums_conv(o)
    staged = C.detect_sums(o, exact_order=True)
    for a, b, name in zip(staged, (full.s5, full.s6, full.s7, full.sumsq),
                          ("s5", "s6", "s7", "sq")):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{name}: exact-order detect_sums must be bitwise "
                    "equal to output_sums_conv on fp32")
    for jit in (False, True):
        fast = (jax.jit(C.detect_sums) if jit else C.detect_sums)(o)
        for a, b, name in zip(fast, (full.s5, full.s6, full.s7, full.sumsq),
                              ("s5", "s6", "s7", "sq")):
            scale = float(np.max(np.abs(np.asarray(b)))) + 1.0
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5 * scale,
                err_msg=f"{name} (gemm formulation, jit={jit})")


def test_detect_checksums_conv_parity():
    d, w, _ = _conv_operands()
    cd1, cd2 = C.encode_d_conv(d)
    cw1, cw2 = C.encode_w_conv(w)
    c5, c6, c7, absd = C.detect_checksums_conv(cd1, cd2, cw1, cw2)
    full = C.output_checksums_conv(d, w, cd1, cd2, cw1, cw2,
                                   need_rowcol=False)
    scale = float(jnp.max(jnp.abs(full.c5))) + 1.0
    for a, b, name in ((c5, full.c5, "c5"), (c6, full.c6, "c6"),
                       (c7, full.c7, "c7")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5 * scale, err_msg=name)
    np.testing.assert_allclose(float(absd), float(C.absdot_conv(cd1, cw1)),
                               rtol=1e-6)


def test_detection_correction_verdicts_unchanged():
    """Seeded injection sweep through the new hot path: every burst is
    detected and corrected, the clean arm stays silent (the statistical
    version of this runs in test_campaign.py over the same protect_op
    entry points)."""
    d, w, b = _conv_operands()
    o_clean = C.conv2d(d, w)
    o_clean = (o_clean.astype(F32) + b[None, :, None, None]).astype(F32)
    run = jax.jit(lambda d, w, b, o: protected_conv(d, w, bias=b, o=o))
    out, rep = run(d, w, b, o_clean)
    assert int(rep.detected) == 0 and int(rep.residual) == 0

    e = o_clean.shape[2]
    for seed in range(8):
        key = jax.random.PRNGKey(100 + seed)
        kn, km, kv = jax.random.split(key, 3)
        i = int(jax.random.randint(kn, (), 0, N))
        j = int(jax.random.randint(km, (), 0, M))
        bad = o_clean.at[i, j].add(
            jax.random.normal(kv, (e, e)) * 37.0 + 11.0)
        out, rep = run(d, w, b, bad)
        assert int(rep.detected) == 1, seed
        assert int(rep.residual) == 0, seed
        # scheme fixes restore to within eps * |corruption| (see
        # VERIFY_ROWCOL_SLACK discussion in core/protected.py)
        np.testing.assert_allclose(np.asarray(out), np.asarray(o_clean),
                                   atol=5e-2)


def test_detect_only_conv_reports_without_correcting():
    d, w, b = _conv_operands()
    cfg = T.DEFAULT_CONFIG.replace(detect_only=True)
    o_clean = C.conv2d(d, w)
    o_clean = (o_clean.astype(F32) + b[None, :, None, None]).astype(F32)
    bad = o_clean.at[0, 0, 0, 0].add(1e4)
    out, rep = jax.jit(
        lambda d, w, b, o: protected_conv(d, w, bias=b, cfg=cfg, o=o))(
            d, w, b, bad)
    assert int(rep.detected) == 1
    assert int(rep.residual) == 1          # surfaced, not fixed
    np.testing.assert_array_equal(np.asarray(out), np.asarray(bad))


def test_plan_pins_kernel_choice_and_roundtrips(tmp_path):
    """kernel_tiles/use_fused_kernel decisions survive save/load and stay
    hashable (jit-static)."""
    cfg = T.DEFAULT_CONFIG.replace(use_fused_kernel=True,
                                   kernel_tiles=(128, 128, 256))
    entry = core.matmul_entry("fc", jnp.ones((32, 48), F32), cfg)
    plan = core.ProtectionPlan(entries={"fc": entry})
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = core.ProtectionPlan.load(path)
    lcfg = loaded["fc"].cfg
    assert lcfg.use_fused_kernel is True
    assert lcfg.kernel_tiles == (128, 128, 256)
    assert isinstance(lcfg.kernel_tiles, tuple)
    hash(lcfg)


def test_kernel_interpret_auto_resolution():
    cfg = T.DEFAULT_CONFIG
    assert cfg.kernel_interpret is None
    # explicit override wins; auto matches the backend rule
    assert cfg.replace(kernel_interpret=False).resolve_interpret() is False
    assert cfg.replace(kernel_interpret=True).resolve_interpret() is True
    auto = cfg.resolve_interpret()
    assert auto == (jax.default_backend() != "tpu")


# --------------------------------------------------------------------------
# the detect-only/correct_op split (the deferred-correction building blocks)
# --------------------------------------------------------------------------

def test_detect_only_mode_returns_evidence_carry():
    """protect_op(mode="detect_only") returns the raw output plus a
    compact DetectEvidence for every op kind; correct_op then runs the
    full ladder on the flagged output."""
    d, w, b = _conv_operands()
    o_clean = C.conv2d(d, w)
    o_clean = (o_clean.astype(F32) + b[None, :, None, None]).astype(F32)
    op = core.OpSpec("conv")
    out, ev = core.protect_op(op, (d, w, b), o=o_clean, mode="detect_only")
    assert isinstance(ev, core.DetectEvidence)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(o_clean))
    assert int(ev.flag) == 0 and float(ev.score) < 1.0

    bad = o_clean.at[1, 2].add(1e4)
    out, ev = core.protect_op(op, (d, w, b), o=bad, mode="detect_only")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(bad))
    assert int(ev.flag) == 1 and float(ev.score) > 1.0

    fixed, rep = core.correct_op(op, (d, w, b), o=bad, detected=ev.flag > 0)
    assert int(rep.detected) == 1 and int(rep.residual) == 0
    np.testing.assert_allclose(np.asarray(fixed), np.asarray(o_clean),
                               atol=5e-2)

    # matmul and grouped_matmul speak the same carry
    dm, wm = _matmul_operands()
    _, ev_m = core.protect_op(core.OpSpec("matmul"), (dm, wm),
                              mode="detect_only")
    assert isinstance(ev_m, core.DetectEvidence) and int(ev_m.flag) == 0
    dg = jnp.stack([dm[:4], dm[4:8]])
    wg = jnp.stack([wm, wm])
    _, ev_g = core.protect_op(core.OpSpec("grouped_matmul"), (dg, wg),
                              mode="detect_only")
    assert isinstance(ev_g, core.DetectEvidence) and int(ev_g.flag) == 0


def test_detect_only_mode_traces_no_correction_machinery():
    """mode='detect_only' must not even trace the ladder: no cond, no
    c1-c4 checksum convs anywhere in the program."""
    d, w, b = _conv_operands()
    jaxpr = jax.make_jaxpr(
        lambda d, w, b: core.protect_op(core.OpSpec("conv"), (d, w, b),
                                        mode="detect_only")[0])(d, w, b)
    eqns = walk_eqns(jaxpr.jaxpr)
    assert not any(e.primitive.name == "cond" for e in eqns)
    convs = _convs(eqns)
    assert len(convs) == 2    # the op + ONE fused checksum conv, nothing else


# --------------------------------------------------------------------------
# deferred model-level correction (forward_cnn(..., correction="deferred"))
# --------------------------------------------------------------------------

SCALE_CNN, IMG_CNN = 0.12, 48


@pytest.fixture(scope="module")
def cnn_model():
    cfg = cnn.alexnet(SCALE_CNN)
    cfg = cfg.__class__(**{**cfg.__dict__, "img": IMG_CNN})
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, IMG_CNN, IMG_CNN))
    plan = core.build_plan(params, cfg, batch=2)
    return cfg, params, x, plan


def test_deferred_exactly_one_model_cond(cnn_model):
    """The deferred forward carries exactly ONE correction cond for the
    whole model (the per-layer path pays one per protected op) - the
    error-free-overhead contract of the deferred mode."""
    cfg, params, x, plan = cnn_model
    jaxpr = jax.make_jaxpr(
        lambda p, x: cnn.forward_cnn(p, x, cfg, plan=plan,
                                     correction="deferred")[0])(params, x)
    conds = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1, [str(e.primitive) for e in jaxpr.jaxpr.eqns]
    jaxpr_pl = jax.make_jaxpr(
        lambda p, x: cnn.forward_cnn(p, x, cfg, plan=plan)[0])(params, x)
    conds_pl = [e for e in jaxpr_pl.jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds_pl) == len(plan)       # one per conv + the fc GEMM


def test_deferred_clean_parity_bitwise(cnn_model):
    cfg, params, x, plan = cnn_model
    l_pl, r_pl = cnn.forward_cnn(params, x, cfg, plan=plan)
    l_df, r_df = jax.jit(
        lambda p, x: cnn.forward_cnn(p, x, cfg, plan=plan,
                                     correction="deferred"))(params, x)
    np.testing.assert_array_equal(np.asarray(l_pl), np.asarray(l_df))
    assert r_df.mode == "deferred" and r_pl.mode == "per_layer"
    assert set(r_df.by_layer) == set(r_pl.by_layer)
    assert int(r_df.detected) == 0 and int(r_df.residual) == 0


@pytest.mark.parametrize("fault", ["burst_row", "burst_col", "single_flip",
                                   "scattered"])
def test_deferred_injection_parity(cnn_model, fault):
    """Under the campaign's fault models the deferred path must reproduce
    the per-layer path's verdicts exactly, layer by layer, and its logits
    to correction precision.

    The corrective rerun IS the per-layer computation, but it compiles
    inside the single model-level cond branch while the per-layer ladder
    compiles in its own per-op branch: XLA fuses the identical correction
    arithmetic differently across the two contexts, so corrected values
    agree to fp32 reassociation noise (~1e-5 rel), not bit for bit - the
    bitwise contract holds on the error-free path, where no cond branch
    executes (test_deferred_clean_parity_bitwise and the campaign's
    control arm)."""
    cfg, params, x, plan = cnn_model
    layer = 2
    _, o_clean = cnn.conv_output_at(params, x, cfg, layer)
    model = inj.FAULT_MODELS[fault]
    n, m = o_clean.shape[0], o_clean.shape[1]
    spec = model.plan(jax.random.PRNGKey(layer + 31), n, m,
                      o_clean.shape[2] * o_clean.shape[3], 64)
    o_bad = inj.inject(o_clean, spec, model)
    l_pl, r_pl = cnn.forward_cnn(params, x, cfg, plan=plan,
                                 inject_layer=layer, inject_o={layer: o_bad})
    l_df, r_df = cnn.forward_cnn(params, x, cfg, plan=plan,
                                 inject_layer=layer, inject_o={layer: o_bad},
                                 correction="deferred")
    scale = float(np.max(np.abs(np.asarray(l_pl)))) + 1.0
    np.testing.assert_allclose(np.asarray(l_pl), np.asarray(l_df),
                               atol=1e-4 * scale)
    assert int(r_df.by_layer[f"conv{layer}"].detected) == 1
    for name in r_pl.by_layer:
        a, b = r_pl.by_layer[name], r_df.by_layer[name]
        assert int(a.detected) == int(b.detected), name
        assert int(a.corrected_by) == int(b.corrected_by), name
        assert int(a.residual) == int(b.residual), name


def test_deferred_rejects_unknown_mode(cnn_model):
    cfg, params, x, plan = cnn_model
    with pytest.raises(ValueError, match="correction mode"):
        cnn.forward_cnn(params, x, cfg, plan=plan, correction="bogus")
    with pytest.raises(ValueError, match="protect_op mode"):
        core.protect_op(core.OpSpec("matmul"),
                        (jnp.zeros((4, 4)), jnp.zeros((4, 4))),
                        mode="bogus")


# --------------------------------------------------------------------------
# mixed execution membership (roofline-guided plans)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def guided_cnn_model():
    """AlexNet under a synthetic calibration whose ridge point lands in
    the middle of the conv layers' intensity spread, so the guided plan
    genuinely mixes per_layer and deferred membership - host-independent,
    unlike MeasuredCostModel.from_host()."""
    from repro.core.cost_model import shape_bytes, shape_flops
    cfg = cnn.alexnet(SCALE_CNN)
    cfg = cfg.__class__(**{**cfg.__dict__, "img": IMG_CNN})
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, IMG_CNN, IMG_CNN))
    spec = core.protection_spec(cfg, batch=2)
    conv_int = sorted(shape_flops(s.shape) / shape_bytes(s.shape)
                      for s in spec.sites
                      if s.shape is not None and s.op.kind == "conv")
    assert conv_int[0] < conv_int[-1]
    ridge = (conv_int[0] + conv_int[-1]) / 2.0
    mcm = core.MeasuredCostModel(peak_flops=ridge * 1e9, hbm_bw=1e9)
    plan = core.build_plan(params, cfg, batch=2, cost_model=mcm)
    return cfg, params, x, plan


def test_mixed_plan_has_both_memberships(guided_cnn_model):
    cfg, params, x, plan = guided_cnn_model
    inline = [n for n in plan.names()
              if plan[n].execution == "per_layer"]
    deferred = [n for n in plan.names()
                if plan[n].execution != "per_layer"]
    assert inline and deferred
    # membership matches the recorded roofline verdicts
    for n in plan.names():
        want = ("per_layer"
                if plan.meta["roofline"][n]["bound"] == "compute"
                else "deferred")
        assert plan[n].execution == want, n


def test_mixed_clean_path_bitwise_identical_to_unprotected(
        guided_cnn_model):
    """On the clean path the mixed deferred forward must be
    bitwise-identical to the unprotected forward: inline members' ladders
    sit inside untaken conds and deferred members never rerun."""
    cfg, params, x, plan = guided_cnn_model
    off = cfg.__class__(**{**cfg.__dict__, "abft": False})
    l_off = jax.jit(lambda p, x: cnn.forward_cnn(p, x, off)[0])(params, x)
    l_mix, rep = jax.jit(
        lambda p, x: cnn.forward_cnn(p, x, cfg, plan=plan,
                                     correction="deferred"))(params, x)
    np.testing.assert_array_equal(np.asarray(l_off), np.asarray(l_mix))
    assert int(rep.detected) == 0 and int(rep.residual) == 0
    assert set(rep.by_layer) == set(plan.names())


def test_mixed_cond_count_is_inline_plus_one(guided_cnn_model):
    """The mixed forward carries one top-level cond per inline member
    (their immediate ladders) plus exactly ONE model-level cond for the
    deferred members - the structural contract of mixed membership."""
    cfg, params, x, plan = guided_cnn_model
    n_inline = sum(1 for n in plan.names()
                   if plan[n].execution == "per_layer")
    jaxpr = jax.make_jaxpr(
        lambda p, x: cnn.forward_cnn(p, x, cfg, plan=plan,
                                     correction="deferred")[0])(params, x)
    conds = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == n_inline + 1


@pytest.mark.parametrize("membership", ["per_layer", "deferred"])
def test_mixed_injection_corrects_in_both_memberships(
        guided_cnn_model, membership):
    """A fault at an inline conv corrects through its immediate ladder; a
    fault at a deferred conv corrects through the model-level rerun -
    both report detected=1, residual=0 and leave every other layer
    clean."""
    cfg, params, x, plan = guided_cnn_model
    convs = [n for n in plan.names() if n.startswith("conv")]
    names = [n for n in convs if (plan[n].execution == "per_layer")
             == (membership == "per_layer")]
    assert names, f"fixture produced no {membership} conv"
    layer = int(names[0][len("conv"):])
    _, o_clean = cnn.conv_output_at(params, x, cfg, layer)
    model = inj.FAULT_MODELS["burst_row"]
    spec = model.plan(jax.random.PRNGKey(layer + 7), o_clean.shape[0],
                      o_clean.shape[1],
                      o_clean.shape[2] * o_clean.shape[3], 64)
    o_bad = inj.inject(o_clean, spec, model)
    l_mix, rep = cnn.forward_cnn(params, x, cfg, plan=plan,
                                 inject_layer=layer, inject_o={layer: o_bad},
                                 correction="deferred")
    assert int(rep.by_layer[f"conv{layer}"].detected) == 1
    assert int(rep.by_layer[f"conv{layer}"].corrected_by) > 0
    assert int(rep.residual) == 0
    for n in rep.by_layer:
        if n != f"conv{layer}":
            assert int(rep.by_layer[n].detected) == 0, n
    # corrected logits track the clean forward to correction precision
    l_clean, _ = cnn.forward_cnn(params, x, cfg, plan=plan)
    scale = float(np.max(np.abs(np.asarray(l_clean)))) + 1.0
    np.testing.assert_allclose(np.asarray(l_mix), np.asarray(l_clean),
                               atol=1e-4 * scale)
