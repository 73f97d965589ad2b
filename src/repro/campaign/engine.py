"""Vectorized fault-injection campaign engine.

One campaign cell = (layer kind, scheme config, fault model). A cell runs
`trials` independent trials as a single jitted `vmap` over PRNG keys: each
trial draws fresh operands, computes the unfaulted reference through the
pure-jnp oracles in repro.kernels.ref, injects a planned fault into the
protected op's output, runs the full multischeme workflow, and scores the
result against the oracle (the differential part: the protected path and
the reference path use different lowerings, so the campaign doubles as a
randomized correctness harness for the kernels).

All fault models share one FaultSpec structure, so the per-(layer, scheme)
program `lax.switch`es over model ids - the engine compiles ONCE per
(layer, scheme) and reuses the executable for every fault arm including
the error-free control. Under vmap the workflow's lax.conds batch into
selects, i.e. every trial pays the worst-case ladder cost; that is the
price of running thousands of trials in one XLA program instead of a
Python loop, and it is still orders of magnitude faster on CPU.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import injection as inj
from repro.core import (ProtectionPlan, conv_entry, correct_op, matmul_entry,
                        path_scope, plan_scope, protect_op, protect_site,
                        resolve_entry)
from repro.core import types as T
from repro.core import weight_repair as WR
from repro.kernels import ref

from .report import CampaignResult, CellResult, summarize_cell

F32 = jnp.float32

# Scheme-ladder configurations, keyed like the paper's Fig. 10 variants.
SCHEME_CONFIGS: Dict[str, T.ProtectConfig] = {
    # the full multischeme workflow (CoC -> RC -> ClC -> FC -> recompute)
    "full": T.DEFAULT_CONFIG,
    # RC/ClC disabled (paper Fig. 10b): CoC then FC then recompute
    "no_rcclc": T.DEFAULT_CONFIG.replace(rc_enabled=False,
                                         clc_enabled=False),
    # CoC only: anything CoC can't fix falls through to recompute
    "coc": T.DEFAULT_CONFIG.replace(rc_enabled=False, clc_enabled=False,
                                    fc_enabled=False),
    # detection-only (CoC-D, the serving mode): no in-graph correction
    "detect": T.DEFAULT_CONFIG.replace(detect_only=True),
    # deferred correction: the op runs detect-only (DetectEvidence carry)
    # and ONE cond invokes correct_op when flagged - the per-op twin of
    # forward_cnn(..., correction="deferred"). Ladder config = full.
    "deferred": T.DEFAULT_CONFIG,
}


@dataclasses.dataclass(frozen=True)
class MatmulCase:
    """O[N,M] = D[N,K] @ W[K,M]; normalised block form has P=1."""
    n: int = 64
    k: int = 32
    m: int = 48

    kind = "matmul"

    @property
    def block_shape(self) -> Tuple[int, int, int]:
        return self.n, self.m, 1


@dataclasses.dataclass(frozen=True)
class ConvCase:
    """O[N,M,E,E] = D[N,Ch,H,H] (x) W[M,Ch,R,R]; P = E*E."""
    n: int = 6
    ch: int = 4
    m: int = 8
    h: int = 10
    r: int = 3
    stride: int = 1

    kind = "conv"

    @property
    def e(self) -> int:
        return (self.h - self.r) // self.stride + 1

    @property
    def block_shape(self) -> Tuple[int, int, int]:
        return self.n, self.m, self.e * self.e


@dataclasses.dataclass(frozen=True)
class TransformerGemmCase:
    """A transformer-block GEMM (d_model -> d_ff shape) protected through
    the ambient plan-context path (plan_scope + by-path entry resolution,
    the route every ProtectedModel layer takes) instead of an explicit
    entry argument - so the campaign's statistical detection/correction
    gates cover the unified resolution code, not just protect_op."""
    n: int = 48     # tokens (B*S of a decode-ish microbatch)
    k: int = 64     # d_model
    m: int = 96     # d_ff

    kind = "transformer_gemm"

    @property
    def block_shape(self) -> Tuple[int, int, int]:
        return self.n, self.m, 1


LAYER_CASES = {"matmul": MatmulCase(), "conv": ConvCase(),
               "transformer_gemm": TransformerGemmCase()}

# Differential-oracle tolerance: corrected output must match the reference
# to within TOL_REL * (max|O_ref| + 1) - the same envelope the scheme tests
# use for checksum-corrected values in fp32.
TOL_REL = 2e-2


class TrialOutcome(NamedTuple):
    """Per-trial scores (batched across the vmap)."""
    detected: jnp.ndarray      # i32
    corrected_by: jnp.ndarray  # i32 scheme enum
    residual: jnp.ndarray      # i32
    corrected: jnp.ndarray     # i32: 1 if output matches the oracle
    max_err: jnp.ndarray       # f32 max |out - oracle|


def _ordered_models() -> List[inj.FaultModel]:
    models = sorted(inj.FAULT_MODELS.values(), key=lambda fm: fm.model_id)
    assert [fm.model_id for fm in models] == list(range(len(models)))
    return models


def _score(out, rep: T.FaultReport, o_ref) -> TrialOutcome:
    scale = jnp.max(jnp.abs(o_ref)) + 1.0
    err = jnp.max(jnp.abs(out.astype(F32) - o_ref.astype(F32)))
    return TrialOutcome(rep.detected, rep.corrected_by, rep.residual,
                        (err <= TOL_REL * scale).astype(jnp.int32), err)


def _weight_correctable_ids(models: List[inj.FaultModel]) -> List[int]:
    return [fm.model_id for fm in models
            if fm.target == "weight" and fm.correctable]


def _weight_repair_outcome(entry, w_run, o_ref, o_fix_fn) -> TrialOutcome:
    """Score the audit ladder's in-place repair rung for one trial: solve
    the corrupted weights against the entry's locator sums on device
    (core.weight_repair, f32 path), recompute the output from the
    repaired weights through the same reference oracle, and report the
    verdict in TrialOutcome terms - detected = locator residuals fired,
    corrected_by = W_REPAIR, residual = the ladder would have escalated
    to a checkpoint restore (so run.check's zero-residual gate IS the
    zero-restores gate for this arm)."""
    tol = WR.locator_tol(entry.wlc, WR.REPAIR_RTOL, xp=jnp)
    if entry.op.kind == "conv":
        w_fix, verdict = WR.repair_conv_weight(w_run, entry.wlc, tol)
    else:
        w_fix, verdict = WR.repair_matmul_weight(w_run, entry.wlc, tol)
    o_fix = o_fix_fn(w_fix)
    scale = jnp.max(jnp.abs(o_ref)) + 1.0
    err = jnp.max(jnp.abs(o_fix.astype(F32) - o_ref.astype(F32)))
    repaired = verdict == WR.REPAIRED
    return TrialOutcome(
        (verdict != WR.CLEAN).astype(jnp.int32),
        jnp.where(repaired, T.W_REPAIR, T.NONE).astype(jnp.int32),
        (verdict == WR.ESCALATE).astype(jnp.int32),
        (repaired & (err <= TOL_REL * scale)).astype(jnp.int32),
        err)


def _merge_weight_repair(models: List[inj.FaultModel], model_id,
                         base: TrialOutcome, rep: TrialOutcome
                         ) -> TrialOutcome:
    """Trials of weight-correctable fault arms are scored by the repair
    path; every other arm keeps the protected-op score. The id list is
    static, so one compiled program per (layer, scheme) still serves the
    whole fault registry."""
    ids = jnp.asarray(_weight_correctable_ids(models), jnp.int32)
    is_wrep = jnp.any(model_id == ids)
    return TrialOutcome(*(jnp.where(is_wrep, r, b)
                          for b, r in zip(base, rep)))


def _switch_inject(models: List[inj.FaultModel], block_shape, max_elems: int,
                   target: str = "output"):
    """(key, model_id, X) -> corrupted X, dispatching plan+apply over the
    registry with lax.switch so one compiled program serves every fault
    arm. Models whose `target` differs are identity branches, so the same
    switch structure serves the output-corruption stage (X = O, dims =
    O's block form) and the post-encode weight-corruption stage (X = W,
    dims = W's block form). X may be the matmul or conv layout; the
    normalised-form round-trip is inj.inject's."""
    n, m, p = block_shape

    def injectf(key, model_id, x):
        branches = []
        for fm in models:
            if fm.target == target:
                branches.append(
                    lambda k, x_, fm=fm: inj.inject(
                        x_, fm.plan(k, n, m, p, max_elems), fm))
            else:
                branches.append(lambda k, x_: x_)
        return jax.lax.switch(model_id, branches, key, x)

    return injectf


def _deferred_protect(entry, d, w, o_bad):
    """The per-op deferred workflow: detect-only pass, then ONE cond that
    runs the full correction ladder only when the evidence flagged - the
    campaign-grade twin of the model-level deferred forward. Verdicts and
    corrected outputs must match the per-layer 'full' scheme bit for bit
    (the cond branch is the per-layer computation)."""
    out_d, ev = protect_op(entry.op, (d, w), entry=entry, o=o_bad,
                           mode="detect_only")

    def _correct(_):
        # the branch trusts the carried flag; it is constant-true here
        # (the outer cond already gated on it), so the ladder's own gate
        # folds away instead of tracing a redundant nested cond
        o_c, rep = correct_op(entry.op, (d, w), entry=entry, o=o_bad,
                              detected=jnp.ones((), jnp.bool_))
        return o_c, rep.corrected_by, rep.residual

    def _skip(_):
        z = jnp.zeros((), jnp.int32)
        return out_d, z, z

    out, by, resid = jax.lax.cond(ev.flag > 0, _correct, _skip, None)
    return out, T.FaultReport(ev.flag, by, resid)


def _matmul_trial(case: MatmulCase, cfg: T.ProtectConfig, max_elems: int,
                  models: List[inj.FaultModel], deferred: bool = False):
    inject_o = _switch_inject(models, case.block_shape, max_elems)
    inject_w = _switch_inject(models, (case.k, case.m, 1), max_elems,
                              target="weight")

    def trial(key, model_id):
        kd, kw, kf = jax.random.split(key, 3)
        d = jax.random.normal(kd, (case.n, case.k), F32)
        w = jax.random.normal(kw, (case.k, case.m), F32)
        o_ref, _ = ref.abft_matmul_ref(d, w, bm=case.n)
        # the ProtectionPlan path: weight checksums encoded once per trial
        # weight draw (the offline step), then handed to the unified op.
        # Weight-target models corrupt W *after* this encode (stale-plan
        # regime): the runtime output comes from the corrupted weights
        # while the entry still carries the clean-plan checksums.
        entry = matmul_entry("cell", w, cfg)
        w_run = inject_w(kf, model_id, w)
        o_run, _ = ref.abft_matmul_ref(d, w_run, bm=case.n)
        o_bad = inject_o(kf, model_id, o_run)
        if deferred:
            out, rep = _deferred_protect(entry, d, w_run, o_bad)
        else:
            out, rep = protect_op(entry.op, (d, w_run), entry=entry, o=o_bad)
        outcome = _score(out, rep, o_ref)
        if _weight_correctable_ids(models):
            wrep = _weight_repair_outcome(
                entry, w_run, o_ref,
                lambda wf: ref.abft_matmul_ref(d, wf, bm=case.n)[0])
            outcome = _merge_weight_repair(models, model_id, outcome, wrep)
        return outcome

    return trial


def _transformer_gemm_trial(case: TransformerGemmCase, cfg: T.ProtectConfig,
                            max_elems: int, models: List[inj.FaultModel],
                            deferred: bool = False):
    """Like _matmul_trial, but the entry reaches the op the way a
    ProtectedModel layer gets it: a per-trial one-entry ProtectionPlan
    entered via plan_scope, the call site resolving "blk/ffn/gate" from
    nested path scopes."""
    inject_o = _switch_inject(models, case.block_shape, max_elems)
    inject_w = _switch_inject(models, (case.k, case.m, 1), max_elems,
                              target="weight")

    def trial(key, model_id):
        kd, kw, kf = jax.random.split(key, 3)
        d = jax.random.normal(kd, (case.n, case.k), F32)
        w = jax.random.normal(kw, (case.k, case.m), F32)
        o_ref, _ = ref.abft_matmul_ref(d, w, bm=case.n)
        plan = ProtectionPlan(entries={
            "blk/ffn/gate": matmul_entry("blk/ffn/gate", w, cfg)})
        w_run = inject_w(kf, model_id, w)
        o_run, _ = ref.abft_matmul_ref(d, w_run, bm=case.n)
        o_bad = inject_o(kf, model_id, o_run)
        with plan_scope(plan), path_scope("blk", "ffn"):
            entry = resolve_entry("gate")
            if entry is None:   # would silently run unprotected
                raise RuntimeError("ambient plan resolution failed")
            if deferred:
                out, rep = _deferred_protect(entry, d, w_run, o_bad)
            else:
                out, rep = protect_site("gate", (d, w_run), entry=entry,
                                        o=o_bad)
            outcome = _score(out, rep, o_ref)
            if _weight_correctable_ids(models):
                wrep = _weight_repair_outcome(
                    entry, w_run, o_ref,
                    lambda wf: ref.abft_matmul_ref(d, wf, bm=case.n)[0])
                outcome = _merge_weight_repair(models, model_id, outcome,
                                               wrep)
        return outcome

    return trial


def _conv_trial(case: ConvCase, cfg: T.ProtectConfig, max_elems: int,
                models: List[inj.FaultModel], deferred: bool = False):
    inject_o = _switch_inject(models, case.block_shape, max_elems)
    inject_w = _switch_inject(models, (case.m, case.ch, case.r * case.r),
                              max_elems, target="weight")

    def trial(key, model_id):
        kd, kw, kf = jax.random.split(key, 3)
        d = jax.random.normal(kd, (case.n, case.ch, case.h, case.h), F32)
        w = jax.random.normal(kw, (case.m, case.ch, case.r, case.r), F32)
        o_ref = ref.conv2d_ref(d, w, stride=case.stride)
        entry = conv_entry("cell", w, cfg, stride=case.stride)
        w_run = inject_w(kf, model_id, w)
        o_run = ref.conv2d_ref(d, w_run, stride=case.stride)
        o_bad = inject_o(kf, model_id, o_run)
        if deferred:
            out, rep = _deferred_protect(entry, d, w_run, o_bad)
        else:
            out, rep = protect_op(entry.op, (d, w_run), entry=entry, o=o_bad)
        outcome = _score(out, rep, o_ref)
        if _weight_correctable_ids(models):
            wrep = _weight_repair_outcome(
                entry, w_run, o_ref,
                lambda wf: ref.conv2d_ref(d, wf, stride=case.stride))
            outcome = _merge_weight_repair(models, model_id, outcome, wrep)
        return outcome

    return trial


class CampaignEngine:
    """Builds, caches and runs the jitted per-(layer, scheme) programs."""

    def __init__(self, cases: Optional[Dict[str, object]] = None,
                 max_elems: int = 100, batch: int = 4096):
        self.cases = dict(cases or LAYER_CASES)
        self.max_elems = max_elems
        self.batch = batch
        self._models = _ordered_models()
        self._runners: Dict[Tuple[str, str], object] = {}
        self._compiled: Dict[Tuple[str, str, int], object] = {}

    def _runner(self, layer: str, scheme: str):
        cache_key = (layer, scheme)
        if cache_key not in self._runners:
            case = self.cases[layer]
            cfg = SCHEME_CONFIGS[scheme]
            build = {"matmul": _matmul_trial, "conv": _conv_trial,
                     "transformer_gemm": _transformer_gemm_trial}[case.kind]
            trial = build(case, cfg, self.max_elems, self._models,
                          deferred=scheme == "deferred")
            self._runners[cache_key] = jax.jit(
                jax.vmap(trial, in_axes=(0, None)))
        return self._runners[cache_key]

    def run_cell(self, layer: str, scheme: str, fault: str, trials: int,
                 seed: int = 0) -> CellResult:
        """Run one (layer, scheme, fault) cell: `trials` vmapped trials,
        sliced into batches to bound working-set memory."""
        if trials < 1:
            raise ValueError(f"trials must be >= 1, got {trials}")
        if fault not in inj.FAULT_MODELS:
            raise ValueError(f"unknown fault model {fault!r} "
                             f"(have {sorted(inj.FAULT_MODELS)})")
        runner = self._runner(layer, scheme)
        if inj.FAULT_MODELS[fault].model_id >= len(self._models):
            # lax.switch clamps out-of-range ids - running a model that was
            # registered after this engine was built would silently execute
            # the wrong branch, so refuse instead
            raise ValueError(
                f"fault model {fault!r} was registered after this engine "
                "was built; construct a fresh CampaignEngine")
        model_id = jnp.int32(inj.FAULT_MODELS[fault].model_id)
        keys = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(seed),
                               inj.FAULT_MODELS[fault].model_id), trials)
        slices = [(lo, min(lo + self.batch, trials))
                  for lo in range(0, trials, self.batch)]
        # AOT-compile each distinct batch shape up front and execute the
        # compiled objects, so wall_seconds (and the CSV us_per_call
        # derived from it) measures trials, not whichever arm happened to
        # trigger the one-time jit (the executables are cached per runner)
        for size in {hi - lo for lo, hi in slices}:
            cache_key = (layer, scheme, size)
            if cache_key not in self._compiled:
                self._compiled[cache_key] = runner.lower(
                    keys[:size], model_id).compile()
        t0 = time.perf_counter()
        chunks = []
        for lo, hi in slices:
            out = self._compiled[(layer, scheme, hi - lo)](
                keys[lo:hi], model_id)
            jax.block_until_ready(out)
            chunks.append(out)
        wall = time.perf_counter() - t0
        merged = TrialOutcome(*(jnp.concatenate(f) for f in zip(*chunks)))
        return summarize_cell(layer, scheme, fault, merged.detected,
                              merged.corrected_by, merged.residual,
                              merged.corrected, merged.max_err,
                              wall_seconds=wall)

    def run(self, layers: Iterable[str], schemes: Iterable[str],
            faults: Optional[Iterable[str]] = None, trials: int = 1000,
            seed: int = 0, include_control: bool = True,
            progress=None) -> CampaignResult:
        """The full campaign grid. `faults=None` means every registered
        model; the error-free control arm rides along unless disabled."""
        fault_list = list(faults) if faults is not None else \
            inj.fault_model_names()
        if include_control and inj.CONTROL_MODEL not in fault_list:
            fault_list = [inj.CONTROL_MODEL] + fault_list
        cells = []
        for layer in layers:
            for scheme in schemes:
                for fault in fault_list:
                    cell = self.run_cell(layer, scheme, fault, trials, seed)
                    cells.append(cell)
                    if progress is not None:
                        progress(cell)
        meta = {"trials": trials, "seed": seed, "max_elems": self.max_elems,
                "jax_version": jax.__version__,
                "wall_seconds": sum(c.wall_seconds for c in cells)}
        return CampaignResult(cells=cells, meta=meta)


def run_campaign(layers=("matmul", "conv"), schemes=("full",), faults=None,
                 trials: int = 1000, seed: int = 0, max_elems: int = 100,
                 progress=None) -> CampaignResult:
    """One-shot convenience wrapper around CampaignEngine."""
    eng = CampaignEngine(max_elems=max_elems)
    return eng.run(layers, schemes, faults, trials=trials, seed=seed,
                   progress=progress)
