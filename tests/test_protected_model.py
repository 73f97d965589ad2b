"""The model-agnostic protection surface (ProtectedModel) on the
transformer family: offline plan round-trip for attention/ffn/moe
entries, DetectEvidence through the lax.scan stage carry, the deferred
one-cond jaxpr contract, clean-path bitwise parity with the unprotected
forward, per-entry calibrated thresholds, and the StepRunner plan-trusted
weight audit on transformer param trees.

The CNN-side twins of these contracts live in tests/test_detect_path.py
and tests/test_plan.py; forward_cnn is now a shim over the same
ProtectedModel code, so the two families are pinned to one workflow.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jaxpr_walk import eqns as walk_eqns

import repro.core as core
from repro.configs.base import ModelConfig
from repro.models import transformer as M
from repro.runtime.ft import (FTPolicy, StepRunner, WeightDivergenceError,
                              audit_weights_against_plan)

F32 = jnp.float32


def _tiny_cfg(**kw):
    base = dict(
        name="tiny", family="dense", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=96,
        vocab_size=128, stage_pattern=("attn_full", "ffn"),
        tie_embeddings=False, dtype="bfloat16")
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def moe_model():
    """attn + ffn + moe in one scanned stage: the three GEMM families the
    plan walk must key (matmul, grouped_matmul, head)."""
    # d_ff deep enough that its calibrated tau_factor sits above the
    # floor (the attn GEMMs' K = d_model clips to TAU_FLOOR)
    cfg = _tiny_cfg(name="tiny_moe", family="moe",
                    stage_pattern=("attn_full", "ffn", "moe"),
                    d_ff=1536, num_experts=4, top_k=2, moe_d_ff=48)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size, jnp.int32)
    plan = core.build_plan(params, cfg, batch=2)
    return cfg, params, tokens, plan


@pytest.fixture(scope="module")
def tied_model():
    cfg = _tiny_cfg(name="tiny_tied", tie_embeddings=True)
    params = M.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                                cfg.vocab_size, jnp.int32)
    plan = core.build_plan(params, cfg, batch=2)
    return cfg, params, tokens, plan


# --------------------------------------------------------------------------
# plan structure + round-trip
# --------------------------------------------------------------------------

def test_transformer_plan_walks_stable_paths(moe_model):
    cfg, params, _, plan = moe_model
    names = plan.names()
    assert "stages/b0_attn_full/attn/wq" in names
    assert "stages/b1_ffn/ffn/down" in names
    assert "stages/b2_moe/moe/router" in names
    assert "stages/b2_moe/moe/gate" in names
    assert "embed/head" in names
    # scanned-stage entries are stacked over the repeats axis, with
    # offline checksums encoded per repeat slice
    wq = plan["stages/b0_attn_full/attn/wq"]
    assert wq.stack == 1
    assert wq.w_shape[0] == cfg.stages()[1]          # leading reps axis
    assert wq.wck is not None
    assert wq.wck.cw1.shape[0] == cfg.stages()[1]
    # expert GEMMs keep per-group runtime checksums (SS5.2): policy-only
    assert plan["stages/b2_moe/moe/gate"].op.kind == "grouped_matmul"
    assert plan["stages/b2_moe/moe/gate"].wck is None
    plan.validate(params)


def test_transformer_plan_roundtrip_bitwise(moe_model, tmp_path):
    """Save/load reproduces every attention/ffn/moe entry bitwise: the
    stacked checksums, configs, stack counts and view tags."""
    cfg, params, _, plan = moe_model
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = core.ProtectionPlan.load(path)
    loaded.validate(params)
    assert loaded.names() == plan.names()
    for name in plan.names():
        e, l = plan[name], loaded[name]
        assert l.op == e.op and l.cfg == e.cfg, name
        assert l.stack == e.stack and l.w_view == e.w_view, name
        assert l.w_shape == e.w_shape and l.w_dtype == e.w_dtype, name
        if e.wck is None:
            assert l.wck is None, name
            continue
        np.testing.assert_array_equal(np.asarray(l.wck[0]),
                                      np.asarray(e.wck[0]), err_msg=name)
        np.testing.assert_array_equal(np.asarray(l.wck[1]),
                                      np.asarray(e.wck[1]), err_msg=name)


def test_tied_head_entry_uses_view(tied_model, tmp_path):
    """Tied embeddings: the head entry is keyed under the table leaf with
    the 'tied_head' view, so offline checksums cover the derived GEMM
    weight and the audit can re-derive them from the table."""
    cfg, params, _, plan = tied_model
    e = plan["embed/table"]
    assert e.w_view == "tied_head"
    d, = (cfg.d_model,)
    assert e.w_shape == (d, cfg.vocab_size)
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = core.ProtectionPlan.load(path)
    assert loaded["embed/table"].w_view == "tied_head"
    loaded.validate(params)
    # a retrained table is caught through the view
    bad = jax.tree_util.tree_map(lambda x: x, params)
    bad["embed"]["table"] = bad["embed"]["table"] + jnp.asarray(
        0.1, bad["embed"]["table"].dtype)
    with pytest.raises(core.PlanStaleError):
        loaded.validate(bad)


def test_per_entry_tau_factor_calibrated_and_roundtrips(moe_model,
                                                        tmp_path):
    """Satellite: per-layer tau_factor - shallow-contraction layers get a
    tighter factor than deep ones, and the values survive plan JSON."""
    cfg, params, _, plan = moe_model
    shallow = plan["stages/b0_attn_full/attn/wq"].cfg.tau_factor  # K=d=64
    deep = plan["stages/b1_ffn/ffn/down"].cfg.tau_factor          # K=d_ff
    assert shallow < deep
    assert shallow == core.calibrate_tau_factor(cfg.d_model)
    assert deep == core.calibrate_tau_factor(cfg.d_ff)
    assert core.plan.TAU_FLOOR <= shallow <= core.plan.TAU_CAP
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = core.ProtectionPlan.load(path)
    for name in plan.names():
        assert loaded[name].cfg.tau_factor == plan[name].cfg.tau_factor
    # opting out restores the global default everywhere
    flat = core.build_plan(params, cfg, batch=2, calibrate_tau=False)
    assert all(e.cfg.tau_factor == core.plan.TAU_DEFAULT
               for e in flat.entries.values())


# --------------------------------------------------------------------------
# the unified forward: clean parity + deferred jaxpr
# --------------------------------------------------------------------------

def test_clean_path_bitwise_identical_to_unprotected(moe_model):
    """A planned ProtectedModel forward (both correction modes) returns
    logits bitwise-identical to the fully unprotected forward: protection
    is detection + a never-taken branch, never arithmetic."""
    cfg, params, tokens, plan = moe_model
    off = cfg.replace(abft=False)
    logits_off, _, _ = M.forward_train(params, tokens, off)
    pm = core.ProtectedModel(M.train_apply(cfg), plan)
    (logits_pl, _), rep_pl = pm(params, tokens)
    (logits_df, _), rep_df = jax.jit(
        lambda p, t: pm(p, t, correction="deferred"))(params, tokens)
    np.testing.assert_array_equal(np.asarray(logits_off),
                                  np.asarray(logits_pl))
    np.testing.assert_array_equal(np.asarray(logits_off),
                                  np.asarray(logits_df))
    assert rep_df.mode == "deferred"
    assert int(rep_df.detected) == 0 and int(rep_df.residual) == 0
    assert int(rep_pl.detected) == 0
    assert set(rep_df.by_layer) == set(rep_pl.by_layer)


def test_deferred_transformer_exactly_one_model_cond(moe_model):
    """The deferred transformer jaxpr carries exactly ONE top-level
    correction cond: the detect-only pass traces no ladder anywhere (the
    scan body stays cond-free), and the corrective rerun lives inside the
    single model-level branch - the same contract test_detect_path.py
    pins for the CNN."""
    cfg, params, tokens, plan = moe_model
    pm = core.ProtectedModel(M.train_apply(cfg), plan)
    jaxpr = jax.make_jaxpr(
        lambda p, t: pm(p, t, correction="deferred")[0][0])(params, tokens)
    conds = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1, [str(e.primitive) for e in jaxpr.jaxpr.eqns]

    # and the detect pass's scan body really is ladder-free: no cond
    # inside any scan equation at the top level
    def scan_conds(jx):
        n = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                body = eqn.params["jaxpr"]
                n += len([e for e in body.jaxpr.eqns
                          if e.primitive.name == "cond"])
        return n

    assert scan_conds(jaxpr.jaxpr) == 0


def test_deferred_detects_stage_and_head_faults(moe_model):
    """Post-encode weight corruption (the stale-plan regime) is detected
    and attributed to the right report section - through the scan carry
    for stage weights, at the exact head path for the LM head."""
    cfg, params, tokens, plan = moe_model
    pm = core.ProtectedModel(M.train_apply(cfg), plan)
    bad = jax.tree_util.tree_map(lambda x: x, params)
    w = bad["stages"]["b0_attn_full"]["attn"]["wq"]["w"]
    bad["stages"]["b0_attn_full"]["attn"]["wq"]["w"] = w.at[0, 3, 5].add(
        jnp.asarray(80.0, w.dtype))
    _, rep = pm(bad, tokens, correction="deferred")
    assert int(rep.by_layer["stages"].detected) == 1
    assert int(rep.by_layer["embed/head"].detected) == 0

    bad2 = jax.tree_util.tree_map(lambda x: x, params)
    h = bad2["embed"]["head"]["w"]
    bad2["embed"]["head"]["w"] = h.at[3, 7].add(jnp.asarray(90.0, h.dtype))
    _, rep2 = pm(bad2, tokens, correction="deferred")
    assert int(rep2.by_layer["embed/head"].detected) == 1
    assert int(rep2.by_layer["stages"].detected) == 0


def test_detect_pass_carries_evidence_through_scan(moe_model):
    """Under an ambient detect_only scope the raw forward's stage carry
    is a DetectEvidence (compact flag+score), not a FaultReport."""
    cfg, params, tokens, plan = moe_model
    with core.plan_scope(plan, mode="detect_only"):
        (_, _), rep = M.train_apply(cfg)(params, tokens)
    assert isinstance(rep.by_layer["stages"], core.DetectEvidence)
    assert isinstance(rep.by_layer["embed/head"], core.DetectEvidence)
    assert int(rep.merged().flag) == 0


# --------------------------------------------------------------------------
# serving runtime: plan-trusted weight audit on transformer trees
# --------------------------------------------------------------------------

def test_audit_transformer_weights_against_plan(moe_model, tmp_path):
    cfg, params, _, plan = moe_model
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = core.ProtectionPlan.load(path)
    ok, bad = audit_weights_against_plan(params, loaded)
    assert ok, bad
    # stacked stage entry (checksum-resolution catch)
    corrupt = jax.tree_util.tree_map(lambda x: x, params)
    w = corrupt["stages"]["b1_ffn"]["ffn"]["gate"]["w"]
    corrupt["stages"]["b1_ffn"]["ffn"]["gate"]["w"] = w.at[1, 0, 0].add(
        jnp.asarray(3.0, w.dtype))
    ok, bad = audit_weights_against_plan(corrupt, loaded)
    assert not ok and any("b1_ffn" in b for b in bad)
    # grouped (policy-only) entry falls back to the fingerprint
    corrupt = jax.tree_util.tree_map(lambda x: x, params)
    g = corrupt["stages"]["b2_moe"]["moe"]["gate"]
    corrupt["stages"]["b2_moe"]["moe"]["gate"] = g.at[0, 1, 0, 0].add(
        jnp.asarray(4.0, g.dtype))
    ok, bad = audit_weights_against_plan(corrupt, loaded)
    assert not ok and any("b2_moe" in b for b in bad)


def test_step_runner_audits_transformer_plan(moe_model, tmp_path):
    """StepRunner(plan=transformer_plan) polices the serving RowHammer
    regime on LLM weights exactly as on CNN weights: pre-start corruption
    is caught on step 0 and climbs the ladder - a single flipped element
    of a stacked scanned-stage weight repairs in place from the loaded
    plan's locator sums, multi-slice damage restores from checkpoint, and
    no restore path means refusing to serve."""
    cfg, params, _, plan = moe_model
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = core.ProtectionPlan.load(path)
    corrupt = jax.tree_util.tree_map(lambda x: x, params)
    w = corrupt["stages"]["b0_attn_full"]["attn"]["wk"]["w"]
    corrupt["stages"]["b0_attn_full"]["attn"]["wk"]["w"] = \
        w.at[0, 0, 0].add(jnp.asarray(7.0, w.dtype))

    def step_fn(state, batch):
        return state, {"loss": 0.0,
                       "report": core.FaultReport.clean()}

    runner = StepRunner(step_fn, FTPolicy(audit_weights_every=1),
                        restore_fn=lambda: {"params": params}, plan=loaded)
    state, _ = runner.run({"params": corrupt}, {})
    assert runner.stats["weight_repairs"] == 1
    assert runner.stats["weight_restores"] == 0
    assert runner.stats["weight_audits"] == 2    # fail + post-repair audit
    np.testing.assert_array_equal(
        np.asarray(state["params"]["stages"]["b0_attn_full"]["attn"]["wk"]
                   ["w"]), np.asarray(w))

    # damage in two repeat slices sits beyond the single-block contract
    multi = jax.tree_util.tree_map(lambda x: x, params)
    multi["stages"]["b0_attn_full"]["attn"]["wk"]["w"] = \
        w.at[0, 0, 0].add(jnp.asarray(7.0, w.dtype)) \
         .at[1, 1, 1].add(jnp.asarray(5.0, w.dtype))
    runner = StepRunner(step_fn, FTPolicy(audit_weights_every=1),
                        restore_fn=lambda: {"params": params}, plan=loaded)
    runner.run({"params": multi}, {})
    assert runner.stats["weight_restores"] == 1

    runner2 = StepRunner(step_fn, FTPolicy(audit_weights_every=1),
                         plan=loaded)
    with pytest.raises(WeightDivergenceError):
        runner2.run({"params": multi}, {})


# --------------------------------------------------------------------------
# ambient context unit behaviour
# --------------------------------------------------------------------------

def test_plan_scope_resolution_and_modes():
    key = jax.random.PRNGKey(5)
    w = jax.random.normal(key, (32, 48), F32)
    d = jax.random.normal(jax.random.fold_in(key, 1), (8, 32), F32)
    entry = core.matmul_entry("blk/ffn/up", w)
    plan = core.ProtectionPlan(entries={"blk/ffn/up": entry})
    assert core.resolve_entry("anything") is None     # no scope active
    with core.plan_scope(plan):
        assert core.ambient_mode() is None
        with core.path_scope("blk", "ffn"):
            assert core.current_path("up") == "blk/ffn/up"
            assert core.resolve_entry("up") is entry
            assert core.resolve_entry("down") is None
        assert core.resolve_entry("up") is None       # prefix popped
    with core.plan_scope(plan, mode="detect_only"), \
            core.path_scope("blk", "ffn"):
        out, ev = core.protect_site("up", (d, w))
        assert isinstance(ev, core.DetectEvidence)
        assert int(ev.flag) == 0
    with pytest.raises(ValueError, match="plan_scope mode"):
        with core.plan_scope(plan, mode="bogus"):
            pass


def test_merge_verdicts_rejects_mixed_kinds():
    with pytest.raises(TypeError, match="mix"):
        core.merge_verdicts(core.DetectEvidence.clean(),
                            core.FaultReport.clean())
    ev = core.merge_verdicts(
        core.DetectEvidence(jnp.int32(1), jnp.float32(3.0)),
        core.DetectEvidence.clean())
    assert int(ev.flag) == 1 and float(ev.score) == 3.0
    assert isinstance(core.clean_report("detect_only"),
                      core.DetectEvidence)
    assert isinstance(core.clean_report(None), core.FaultReport)


def test_fused_pinned_scan_body_one_launch_per_gemm():
    """With force_fused_matmul pinned, the detect-only scan body launches
    exactly ONE Pallas kernel per protected stage GEMM (attn wq/wk/wv/wo
    + ffn gate/up/down = 7) and keeps no standalone detection dot: every
    dot_general left outside the kernels (attention scores, rope, the
    O(K) checksum encodes) is small next to the protected GEMMs."""
    from repro.core.plan import force_fused_matmul
    cfg = _tiny_cfg(name="tiny_fused")
    params = M.init_params(jax.random.PRNGKey(4), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0,
                                cfg.vocab_size, jnp.int32)
    plan = force_fused_matmul(core.build_plan(params, cfg, batch=2, seq=8))
    with core.plan_scope(plan, mode="detect_only"):
        jaxpr = jax.make_jaxpr(
            lambda p, t: M.train_apply(cfg)(p, t)[0][0])(params, tokens)


    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    body = walk_eqns(scans[0].params["jaxpr"].jaxpr,
                     opaque=("pallas_call",))
    launches = [e for e in body if e.primitive.name == "pallas_call"]
    assert len(launches) == 7, len(launches)
    # rows=16, smallest protected GEMM K=64, M=32
    min_gemm_flops = 16 * 64 * 32
    for e in body:
        if e.primitive.name == "dot_general":
            dims = e.params["dimension_numbers"][0][0]
            k = 1
            for ax in dims:
                k *= e.invars[0].aval.shape[ax]
            out_sz = 1
            for s in e.outvars[0].aval.shape:
                out_sz *= s
            assert out_sz * k < min_gemm_flops / 2, str(e)
