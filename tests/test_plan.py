"""ProtectionPlan tests: offline build -> serialize -> load round-trip
(checksums bitwise-equal to a fresh encode), stale-plan rejection, the
unified protect_op's parity with the per-call API, per-layer ModelReport
semantics, and the forward_cnn residual-shape contract."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as core
from repro.core import injection as inj
from repro.models import cnn

SCALE = 0.12
IMG = 48


def _model(name="alexnet", batch=2):
    cfg = cnn.CNN_REGISTRY[name](SCALE)
    cfg = cfg.__class__(**{**cfg.__dict__, "img": IMG})
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 3, IMG, IMG))
    return cfg, params, x


# --------------------------------------------------------------------------
# build / structure
# --------------------------------------------------------------------------

def test_build_plan_structure_and_policy():
    cfg, params, _ = _model()
    plan = core.build_plan(params, cfg, batch=2)
    assert plan.names() == tuple(f"conv{i}" for i in range(len(cfg.convs))
                                 ) + ("fc",)
    for i in range(len(cfg.convs)):
        e = plan[f"conv{i}"]
        assert e.op.kind == "conv"
        assert e.wck is not None
        assert e.w_shape == tuple(params[f"conv{i}"]["w"].shape)
        assert e.cfg.fc_enabled  # correction of last resort always on
    assert plan["fc"].op.kind == "matmul"
    # the legacy shim returns exactly the plan's conv configs
    pol = cnn.layer_policies(cfg, 2)
    assert [p.rc_enabled for p in pol] == \
        [plan[f"conv{i}"].cfg.rc_enabled for i in range(len(cfg.convs))]
    assert [p.clc_enabled for p in pol] == \
        [plan[f"conv{i}"].cfg.clc_enabled for i in range(len(cfg.convs))]


def test_plan_forward_matches_legacy_path():
    cfg, params, x = _model()
    plan = core.build_plan(params, cfg, batch=2)
    logits_legacy, rep_legacy = cnn.forward_cnn(params, x, cfg)
    logits_plan, rep_plan = cnn.forward_cnn(params, x, cfg, plan=plan)
    np.testing.assert_array_equal(np.asarray(logits_legacy),
                                  np.asarray(logits_plan))
    assert int(rep_plan.detected) == 0
    assert set(rep_plan.by_layer) == set(plan.names())


# --------------------------------------------------------------------------
# serialization round-trip + staleness
# --------------------------------------------------------------------------

def test_plan_roundtrip_checksums_bitwise_equal(tmp_path):
    cfg, params, _ = _model()
    plan = core.build_plan(params, cfg, batch=2)
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = core.ProtectionPlan.load(path)
    loaded.validate(params)

    assert loaded.names() == plan.names()
    for name in plan.names():
        e, l = plan[name], loaded[name]
        assert l.op == e.op
        assert l.cfg == e.cfg
        assert l.w_shape == e.w_shape and l.w_dtype == e.w_dtype
        # loaded checksums must be bitwise-equal to a *fresh* encode
        if e.op.kind == "conv":
            f1, f2 = core.checksums.encode_w_conv(params[name]["w"])
        else:
            fresh = core.weight_checksums_matmul(params[name]["w"],
                                                 e.cfg.col_chunk)
            assert l.wck.col_chunk == fresh.col_chunk
            f1, f2 = fresh.cw1, fresh.cw2
        np.testing.assert_array_equal(np.asarray(l.wck[0]), np.asarray(f1))
        np.testing.assert_array_equal(np.asarray(l.wck[1]), np.asarray(f2))


def test_guided_plan_roundtrip_execution_and_roofline(tmp_path):
    """Roofline-guided plans persist their per-entry execution membership
    and the meta.roofline / meta.cost_model decision record exactly
    through JSON - a loaded plan replays the same mixed-membership
    forward the builder decided."""
    import json
    cfg, params, _ = _model()
    mcm = core.MeasuredCostModel(peak_flops=2e11, hbm_bw=2e10)
    plan = core.build_plan(params, cfg, batch=2, cost_model=mcm)
    assert plan.meta["cost_model"]["class"] == "MeasuredCostModel"
    roof = plan.meta["roofline"]
    assert set(roof) == set(plan.names())
    for name in plan.names():
        e = plan[name]
        assert e.execution in ("per_layer", "deferred")
        assert roof[name]["execution"] == e.execution
        assert roof[name]["bound"] in ("compute", "bandwidth")
        assert roof[name]["intensity"] > 0

    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = core.ProtectionPlan.load(path)
    loaded.validate(params)
    assert loaded.meta["roofline"] == plan.meta["roofline"]
    assert loaded.meta["cost_model"] == plan.meta["cost_model"]
    for name in plan.names():
        assert loaded[name].execution == plan[name].execution

    # legacy plans (written before the execution field existed) load with
    # execution=None, which means all-deferred - unchanged semantics
    # (rewrite the json in place so the npz sidecar still pairs up)
    with open(path) as f:
        doc = json.load(f)
    for e in doc["entries"].values():
        e.pop("execution", None)
    with open(path, "w") as f:
        json.dump(doc, f)
    legacy = core.ProtectionPlan.load(path)
    assert all(legacy[n].execution is None for n in legacy.names())


def test_default_plan_has_no_roofline_meta():
    """The analytic default keeps old behaviour: no execution membership,
    no meta.roofline - only the cost-model provenance record is new."""
    cfg, params, _ = _model()
    plan = core.build_plan(params, cfg, batch=2)
    assert "roofline" not in plan.meta
    assert plan.meta["cost_model"]["class"] == "CostModel"
    assert all(plan[n].execution is None for n in plan.names())


def test_stale_plan_rejected(tmp_path):
    cfg, params, _ = _model()
    plan = core.build_plan(params, cfg, batch=2)
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = core.ProtectionPlan.load(path)

    # shape change (re-architected layer)
    bad = dict(params)
    bad["conv1"] = {"w": params["conv1"]["w"][:, :, :3, :3],
                    "b": params["conv1"]["b"]}
    with pytest.raises(core.PlanStaleError, match="conv1.*shape"):
        loaded.validate(bad)

    # dtype change (re-quantised model)
    bad = dict(params)
    bad["conv0"] = {"w": params["conv0"]["w"].astype(jnp.bfloat16),
                    "b": params["conv0"]["b"]}
    with pytest.raises(core.PlanStaleError, match="conv0.*dtype"):
        loaded.validate(bad)

    # missing layer
    bad = {k: v for k, v in params.items() if k != "fc"}
    with pytest.raises(core.PlanStaleError, match="fc.*not found"):
        loaded.validate(bad)

    # same-shape retrain (content fingerprint: shape/dtype checks pass
    # but the stale checksums would fire detection on clean data)
    bad = dict(params)
    bad["conv2"] = {"w": params["conv2"]["w"] + 0.1,
                    "b": params["conv2"]["b"]}
    with pytest.raises(core.PlanStaleError, match="conv2.*content"):
        loaded.validate(bad)

    # trace-time check on the op itself
    with pytest.raises(core.PlanStaleError, match="conv0"):
        core.protect_op(loaded["conv0"].op,
                        (jnp.zeros((1, 3, 8, 8)), jnp.zeros((4, 3, 3, 3))),
                        entry=loaded["conv0"])


def test_plan_schema_guard(tmp_path):
    path = str(tmp_path / "plan.json")
    (tmp_path / "plan.json").write_text('{"schema": "bogus/v0"}')
    (tmp_path / "plan.npz").write_bytes(b"")
    with pytest.raises(ValueError, match="schema"):
        core.ProtectionPlan.load(path)


# --------------------------------------------------------------------------
# the unified op
# --------------------------------------------------------------------------

def test_protect_op_matmul_parity():
    key = jax.random.PRNGKey(3)
    d = jax.random.normal(key, (64, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 48))
    entry = core.matmul_entry("m", w)
    o_new, rep_new = core.protect_op(entry.op, (d, w), entry=entry)
    o_old, rep_old = core.protected_matmul(d, w)
    np.testing.assert_array_equal(np.asarray(o_new), np.asarray(o_old))
    assert int(rep_new.detected) == int(rep_old.detected) == 0


def test_protect_op_conv_injection_corrected():
    key = jax.random.PRNGKey(4)
    d = jax.random.normal(key, (4, 3, 10, 10))
    w = jax.random.normal(jax.random.fold_in(key, 1), (8, 3, 3, 3))
    o_ref = core.checksums.conv2d(d, w)
    p = inj.plan(jax.random.PRNGKey(5), 4, 8, max_elems=16, axis=0)
    o_bad = inj.inject_conv(o_ref, p)
    entry = core.conv_entry("c", w)
    fixed, rep = core.protect_op(entry.op, (d, w), entry=entry, o=o_bad)
    assert int(rep.detected) == 1
    assert int(rep.residual) == 0
    np.testing.assert_allclose(np.asarray(fixed), np.asarray(o_ref),
                               rtol=2e-2, atol=2e-2)


def test_protect_op_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown op kind"):
        core.OpSpec("dft")


def test_protect_op_grouped_rejects_unsupported_inputs():
    d = jnp.zeros((2, 4, 3))
    w = jnp.zeros((2, 3, 5))
    op = core.OpSpec("grouped_matmul")
    with pytest.raises(NotImplementedError, match="grouped_matmul"):
        core.protect_op(op, (d, w), o=jnp.zeros((2, 4, 5)))
    with pytest.raises(NotImplementedError, match="grouped_matmul"):
        core.protect_op(op, (d, w, jnp.zeros((5,))))


def test_apply_dense_routes_through_plan_entry():
    from repro.layers.linear import apply_dense, init_dense
    key = jax.random.PRNGKey(7)
    p = init_dense(key, 16, 24, dtype=jnp.float32)
    entry = core.matmul_entry("dense", p["w"])
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16))
    y_plan, rep = apply_dense(p, x, entry=entry)
    y_legacy, _ = apply_dense(p, x)
    np.testing.assert_array_equal(np.asarray(y_plan), np.asarray(y_legacy))
    assert int(rep.detected) == 0
    # stale entries are rejected at trace time
    stale = core.matmul_entry("dense", p["w"][:8])
    with pytest.raises(core.PlanStaleError):
        apply_dense(p, x, entry=stale)


def test_protect_op_disabled_config_leaves_output_untouched():
    """A disabled entry must be a no-op for every op kind, including the
    precomputed-output matmul path."""
    key = jax.random.PRNGKey(6)
    d = jax.random.normal(key, (16, 8))
    w = jax.random.normal(jax.random.fold_in(key, 1), (8, 12))
    o_bad = (d @ w).at[0, 0].add(1e6)   # blatant corruption
    off = core.DEFAULT_CONFIG.replace(enabled=False)
    out, rep = core.protect_op(core.OpSpec("matmul"), (d, w), cfg=off,
                               o=o_bad)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(o_bad))
    assert int(rep.detected) == 0


def test_plan_forward_injection_attributed_to_layer():
    """Per-layer attribution: the injected conv layer's entry carries the
    verdict; other layers stay clean (paper's L-epoch protocol)."""
    cfg, params, x = _model()
    plan = core.build_plan(params, cfg, batch=2)
    layer = 2
    _, o_clean = cnn.conv_output_at(params, x, cfg, layer)
    p = inj.plan(jax.random.PRNGKey(11), o_clean.shape[0], o_clean.shape[1],
                 max_elems=64)
    o_bad = inj.inject_conv(o_clean, p)
    clean_logits, _ = cnn.forward_cnn(params, x, cfg, plan=plan)
    logits, rep = cnn.forward_cnn(params, x, cfg, plan=plan,
                                  inject_layer=layer,
                                  inject_o={layer: o_bad})
    assert int(rep.by_layer[f"conv{layer}"].detected) == 1
    assert int(rep.by_layer[f"conv{layer}"].residual) == 0
    for name in rep.by_layer:
        if name != f"conv{layer}":
            assert int(rep.by_layer[name].detected) == 0, name
    np.testing.assert_allclose(np.asarray(logits), np.asarray(clean_logits),
                               rtol=1e-3, atol=1e-3)


def test_deferred_report_keeps_detect_scores():
    """The deferred report keeps each site's detect-pass score: far below
    1 on clean traffic, above 1 at an injected site, and 0 for a site
    that ran its own ladder inline (it has no detect-pass score)."""
    cfg, params, x = _model()
    plan = core.build_plan(params, cfg, batch=2)
    layer = 2
    _, o_clean = cnn.conv_output_at(params, x, cfg, layer)
    p = inj.plan(jax.random.PRNGKey(11), o_clean.shape[0], o_clean.shape[1],
                 max_elems=64)
    o_bad = inj.inject_conv(o_clean, p)
    _, rep = cnn.forward_cnn(params, x, cfg, plan=plan,
                             correction="deferred")
    assert list(rep.scores) == list(rep.by_layer)
    assert all(0 <= float(s) < 1 for s in rep.scores.values()), rep.scores
    _, rep = cnn.forward_cnn(params, x, cfg, plan=plan, inject_layer=layer,
                             inject_o={layer: o_bad}, correction="deferred")
    assert float(rep.scores[f"conv{layer}"]) > 1
    assert all(float(s) < 1 for n, s in rep.scores.items()
               if n != f"conv{layer}")
    inline = dataclasses.replace(plan, entries={
        **plan.entries,
        "conv0": dataclasses.replace(plan.entries["conv0"],
                                     execution="per_layer")})
    _, rep = cnn.forward_cnn(params, x, cfg, plan=inline,
                             correction="deferred")
    assert float(rep.scores["conv0"]) == 0.0
    assert float(rep.scores["conv1"]) > 0.0


# --------------------------------------------------------------------------
# ModelReport semantics
# --------------------------------------------------------------------------

def test_model_report_merge_and_views():
    z = jnp.zeros((), jnp.int32)
    one = jnp.ones((), jnp.int32)
    clean = core.FaultReport(z, z, z)
    hit = core.FaultReport(one, jnp.int32(core.RC), z)
    a = core.ModelReport({"conv0": clean}).add("conv1", hit)
    assert int(a.detected) == 1
    assert int(a.corrected_by) == core.RC
    assert a.summary()["conv1"]["corrected_by"] == "rc"
    b = core.ModelReport({"conv0": hit})
    m = a.merge(b)
    assert int(m["conv0"].detected) == 1          # merged elementwise
    assert int(m["conv1"].corrected_by) == core.RC
    hist = m.scheme_histogram()
    assert set(hist) == set(core.SCHEME_NAMES.values())  # stable columns
    assert hist["rc"] == 2
    # nested adds flatten with a path prefix
    nested = core.ModelReport({"blk": clean}).add("ffn", a)
    assert "ffn/conv1" in nested.by_layer
    # scalar normalisation helper
    assert int(core.as_fault_report(a).detected) == 1
    assert int(core.as_fault_report(hit).detected) == 1


def test_model_report_is_pytree():
    rep = core.ModelReport({"a": core.FaultReport.clean()})
    leaves, tree = jax.tree_util.tree_flatten(rep)
    assert len(leaves) == 3  # one FaultReport = 3 scalar leaves
    rebuilt = jax.tree_util.tree_unflatten(tree, leaves)
    assert rebuilt.by_layer.keys() == rep.by_layer.keys()


def test_model_report_scores_ride_the_pytree():
    """Scores are leaves beside the verdicts: they cross a jit boundary,
    and add/merge keep them (nested under the prefix, max on a clash)."""
    clean = core.FaultReport.clean()
    rep = core.ModelReport({"a": clean}, mode="deferred",
                           scores={"a": jnp.float32(0.25)})
    leaves, tree = jax.tree_util.tree_flatten(rep)
    assert len(leaves) == 4
    rebuilt = jax.jit(lambda r: r)(rep)
    assert rebuilt.mode == "deferred"
    assert float(rebuilt.scores["a"]) == 0.25
    other = core.ModelReport({"a": clean}, scores={"a": jnp.float32(2.0)})
    assert float(rep.merge(other).scores["a"]) == 2.0
    nested = core.ModelReport({"b": clean}).add("blk", rep)
    assert float(nested.scores["blk/a"]) == 0.25
    assert core.ModelReport({"a": clean}).scores == {}


# --------------------------------------------------------------------------
# residual contract
# --------------------------------------------------------------------------

def test_residual_shape_mismatch_raises_at_trace_time():
    cfg = cnn.CNNConfig("bad", (
        cnn.ConvSpec(8, 3, 1, 1),
        cnn.ConvSpec(8, 3, 2, 1, residual_from=0)), img=16)
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, 3, 16, 16))
    with pytest.raises(ValueError, match=r"conv layer 1.*layer 0"):
        cnn.forward_cnn(params, x, cfg)


def test_resnet18_residuals_are_shape_valid():
    """The config only declares identity shortcuts where shapes match, so
    the strict forward traces cleanly."""
    cfg = cnn.resnet18(SCALE)
    cfg = cfg.__class__(**{**cfg.__dict__, "img": 32})
    assert any(s.residual_from >= 0 for s in cfg.convs)
    assert all(s.stride == 1 for s in cfg.convs if s.residual_from >= 0)
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, 3, 32, 32))
    logits, rep = cnn.forward_cnn(params, x, cfg)
    assert logits.shape == (1, cfg.num_classes)


# --------------------------------------------------------------------------
# profile-guided kernel selection (transformer sites + fairness)
# --------------------------------------------------------------------------

def test_transformer_spec_sites_carry_opshapes():
    """Every plain-matmul transformer site gets a real OpShape (rows =
    batch*seq), so profile_kernels has something to measure; grouped MoE
    expert GEMMs stay shapeless (vmapped - no single kernel launch to
    profile)."""
    import repro.configs as C
    from repro.core.plan import protection_spec
    cfg = C.reduced(C.get("smollm-360m"))
    spec = protection_spec(cfg, batch=2, seq=16)
    mm = [s for s in spec.sites if s.op.kind == "matmul"]
    assert mm and all(s.shape is not None for s in mm)
    assert all(s.shape.n == 32 for s in mm)
    wq = next(s for s in spec.sites if s.path.endswith("attn/wq"))
    assert wq.shape.ch == cfg.d_model
    assert wq.shape.m == cfg.num_heads * cfg.head_dim
    head = next(s for s in spec.sites if s.path.startswith("embed/"))
    assert head.shape is not None and head.shape.m >= cfg.vocab_size


def test_build_plan_profiles_transformer_gemms():
    """build_plan(profile_kernels=True) on a transformer config records a
    kernel profile for every GEMM site (stages included) and pins a
    coherent config: fused entries get kernel tiles with chunking snapped
    to them; unfused entries carry no tiles."""
    import repro.configs as C
    from repro.models import transformer as M
    cfg = C.reduced(C.get("smollm-360m"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    plan = core.build_plan(params, cfg, batch=2, seq=16,
                           profile_kernels=True)
    kp = plan.meta["kernel_profile"]
    assert any(p.startswith("stages/") for p in kp)
    assert "embed/head" in kp or "embed/table" in kp
    for path, doc in kp.items():
        e = plan.entries[path]
        assert e.cfg.use_fused_kernel == doc["use_fused"]
        if doc["use_fused"]:
            assert e.cfg.kernel_tiles is not None
            assert e.cfg.row_chunk == e.cfg.kernel_tiles[0]
            assert e.cfg.col_chunk == e.cfg.kernel_tiles[1]


def test_force_fused_matmul_pins_and_runs():
    """force_fused_matmul flips every enabled plain-matmul entry to the
    fused kernel; the protected forward still matches the unprotected one
    (detection only, no arithmetic change beyond kernel reassociation)."""
    import repro.configs as C
    from repro.core.plan import force_fused_matmul
    from repro.models import transformer as M
    cfg = C.reduced(C.get("smollm-360m"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size, jnp.int32)
    plan = force_fused_matmul(core.build_plan(params, cfg, batch=2,
                                              seq=16))
    assert all(e.cfg.use_fused_kernel for e in plan.entries.values()
               if e.op.kind == "matmul" and e.cfg.enabled)
    pm = core.ProtectedModel(M.train_apply(cfg), plan)
    off = cfg.replace(abft=False)
    ref = M.forward_train(params, tokens, off)[0]
    (lo, _), rep = jax.jit(lambda p, t: pm(p, t,
                                           correction="deferred"))(params,
                                                                   tokens)
    assert int(rep.detected) == 0
    np.testing.assert_allclose(np.asarray(lo, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-3, atol=1e-2)


def test_matmul_profile_fairness_same_outputs():
    """Regression for the profiling bias: both timed programs must finish
    at the SAME five outputs (o, s5, s6, s7, sumsq) - the fused side used
    to stop at the kernel launch, never paying the partials-finishing
    reduction the production path runs."""
    from repro.core.policy import matmul_profile_programs
    n, k, m = 32, 64, 96
    f_plain, f_fused = matmul_profile_programs(n, k, m, tiles=(16, 16, 32),
                                               interpret=True)
    key = jax.random.PRNGKey(11)
    d = jax.random.normal(key, (n, k))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, m))
    outs_p = f_plain(d, w)
    outs_f = f_fused(d, w)
    assert len(outs_p) == len(outs_f) == 5
    for a, b, name in zip(outs_p, outs_f,
                          ["o", "s5", "s6", "s7", "sumsq"]):
        scale = float(jnp.max(jnp.abs(a))) + 1.0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * scale, err_msg=name)


def test_plan_records_operand_precision_and_refuses_another():
    """A plan's weight checksums encode the operand values of the backend
    it was built on (types.op_operand_dtype); entering it where f32 ops
    multiply other operands is a PlanStaleError, not clean-traffic
    false positives."""
    plan = core.build_plan(None, cnn.alexnet(0.12), batch=2)
    assert plan.meta["f32_operands"] == str(
        core.types.op_operand_dtype(jnp.float32))
    with core.plan_scope(plan):
        pass
    other = core.ProtectionPlan(entries=dict(plan.entries),
                                meta={**plan.meta, "f32_operands": "int8"})
    with pytest.raises(core.PlanStaleError, match="operands"):
        with core.plan_scope(other):
            pass
