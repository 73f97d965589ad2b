"""Multischeme workflow engine (paper SS4.3, Fig. 7).

Detection (CoC-D) runs on every protected op; the correction ladder
CoC -> RC -> ClC -> FC -> recompute runs inside a `lax.cond` branch so the
error-free path pays nothing beyond detection. Every rung re-verifies the
corrected output against *fresh* checksums before accepting (the paper's
"invoke the next-level scheme on failure").

The ladder is assembled from static config (layerwise RC/ClC enablement is
a compile-time choice, matching the paper's per-layer offline decision), so
disabled rungs are not even traced.
"""
from __future__ import annotations

from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp

from . import types as T

# A rung: (o, trusted) -> (o_fixed, ok). Verification is applied by the
# engine.
Rung = Tuple[int, Callable[[jnp.ndarray, Any],
                           Tuple[jnp.ndarray, jnp.ndarray]]]


def run_ladder(
    o: jnp.ndarray,
    detected: jnp.ndarray,
    rungs: List[Rung],
    verify_fn: Callable[[jnp.ndarray, Any], jnp.ndarray],
    recompute_fn: Callable[[], jnp.ndarray],
    trusted_fn: Callable[[], Any],
) -> Tuple[jnp.ndarray, T.FaultReport]:
    """Escalate through `rungs` until one verifies; fall back to recompute.

    trusted_fn() derives the checksum sets once, at the top of the
    correction branch; every rung and every verification reads them
    instead of re-encoding (they depend on D and W only, not on the
    candidate output). verify_fn(o, trusted) must re-derive the output
    summations of `o` and compare against the trusted checksums -
    returning a scalar bool.
    """

    def _clean(o):
        z = jnp.zeros((), jnp.int32)
        return o, z, z

    def _correct(o):
        with jax.named_scope("correct"):
            return _ladder(o)

    def _ladder(o):
        by = jnp.zeros((), jnp.int32)
        trusted = trusted_fn()

        for enum_val, fn in rungs:
            # apply rung only while uncorrected; lax.cond keeps the rung's
            # cost out of the path once a lower rung succeeded.
            def _attempt(args, fn=fn, enum_val=enum_val):
                o, by = args
                fixed, ok = fn(o, trusted)
                ok = ok & verify_fn(fixed, trusted)
                o = jnp.where(ok, fixed, o)
                by = jnp.where(ok, jnp.int32(enum_val), by)
                return o, by

            def _skip(args):
                return args

            o, by = jax.lax.cond(by == 0, _attempt, _skip, (o, by))

        # last resort: full recompute (paper SS4.1.1 for multi-fault cases)
        def _recompute(args):
            o, by = args
            fresh = recompute_fn()
            return fresh, jnp.int32(T.RECOMPUTE)

        o, by = jax.lax.cond(by == 0, _recompute, _skip, (o, by))
        residual = jnp.where(verify_fn(o, trusted), 0, 1).astype(jnp.int32)
        return o, by, residual

    o, by, residual = jax.lax.cond(detected, _correct, _clean, o)
    report = T.FaultReport(detected.astype(jnp.int32), by, residual)
    return o, report


class ProtectedModel:
    """The model-agnostic protection session: one surface for every model
    family (paper SS4.3's offline-per-layer, model-shape-independent
    workflow, lifted to the API).

        plan = build_plan(params, arch_cfg)        # offline, either family
        pm = ProtectedModel(apply_fn, plan)
        out, report = pm(params, x)                          # per-layer
        out, report = pm(params, x, correction="deferred")   # one cond

    `apply_fn(params, *args, **kwargs) -> (out, report)` is any forward
    whose protected call sites resolve their PlanEntry from the ambient
    plan context (layers.linear.apply_dense and friends do; protect_site
    is the raw spelling). The report must be a ModelReport (or a single
    scalar carry) of FaultReports - or of DetectEvidence when the ambient
    mode is "detect_only", which is how the deferred workflow's detect
    pass surfaces its compact per-path carries (a lax.scan model carries
    them through its stage carry).

    `correction="deferred"` runs apply_fn detect-only and executes ONE
    model-level lax.cond that reruns it with full correction only when
    any site flagged - the same jaxpr shape for a CNN layer walk and a
    scanned transformer. In the corrective rerun, sites whose exact path
    produced a detect-pass carry trust that flag (no re-detection); sites
    whose evidence merged into a coarser carry (inside a scan) re-derive
    their own gate.
    """

    def __init__(self, apply_fn: Callable, plan=None):
        from .plan import ProtectionPlan  # circular-import-free at call time
        if plan is not None and not isinstance(plan, ProtectionPlan):
            raise TypeError("ProtectedModel expects a ProtectionPlan "
                            f"(or None); got {type(plan).__name__}")
        self.apply_fn = apply_fn
        self.plan = plan

    @staticmethod
    def _layer_map(rep, what: str):
        if isinstance(rep, T.ModelReport):
            return dict(rep.by_layer)
        if isinstance(rep, (T.FaultReport, T.DetectEvidence)):
            return {"model": rep}
        raise TypeError(f"ProtectedModel: apply_fn's {what} report must be "
                        "a ModelReport, FaultReport or DetectEvidence; got "
                        f"{type(rep).__name__}")

    def __call__(self, params, *args, correction: str = "per_layer",
                 with_detect_out: bool = False, **kwargs):
        from .plan import plan_scope
        if correction not in ("per_layer", "deferred"):
            raise ValueError(f"ProtectedModel: unknown correction mode "
                             f"{correction!r} (have 'per_layer', "
                             "'deferred')")
        if with_detect_out and correction != "deferred":
            raise ValueError("ProtectedModel: with_detect_out requires "
                             "correction='deferred' (there is no separate "
                             "detect pass in per-layer mode)")
        if correction == "per_layer":
            with plan_scope(self.plan):
                return self.apply_fn(params, *args, **kwargs)

        # ---- deferred: detect-only pass + ONE model-level cond ----------
        with plan_scope(self.plan, mode="detect_only"):
            out_d, ev = self.apply_fn(params, *args, **kwargs)
        evmap = self._layer_map(ev, "detect-only")
        # mixed execution membership: sites whose plan entry is marked
        # execution="per_layer" ran their immediate in-graph ladder during
        # the detect pass and carry a full FaultReport - they are already
        # corrected in out_d and stay out of the model-level cond. Every
        # other carry must be DetectEvidence.
        inline: dict = {}
        for n, e in evmap.items():
            if isinstance(e, T.DetectEvidence):
                continue
            entry = self.plan.get(n) if self.plan is not None else None
            if (isinstance(e, T.FaultReport) and entry is not None
                    and entry.execution == "per_layer"):
                inline[n] = e
                continue
            raise TypeError(
                "ProtectedModel deferred mode: the detect-only pass "
                f"returned a non-DetectEvidence carry for {n!r} whose "
                "plan entry is not marked execution='per_layer'; some "
                "protected op bypassed the ambient execution mode "
                "(e.g. a direct protected_matmul call) - route it through "
                "protect_site / apply_dense so the ladder is not traced "
                "on the hot path")
        names = list(evmap)
        if not names:
            rep0 = T.ModelReport({}, mode="deferred")
            return ((out_d, rep0, out_d) if with_detect_out
                    else (out_d, rep0))
        flags = jnp.stack([evmap[n].detected if n in inline
                           else evmap[n].flag for n in names])
        # clean-branch verdict vectors: inline members keep the ladder
        # verdicts they already earned; deferred members are zeros
        z = jnp.zeros((), jnp.int32)
        base_by = jnp.stack([evmap[n].corrected_by if n in inline else z
                             for n in names])
        base_resid = jnp.stack([evmap[n].residual if n in inline else z
                                for n in names])
        deferred_flags = [flags[i] for i, n in enumerate(names)
                          if n not in inline]

        def _corrective():
            # the rerun trusts the detect-pass flags at every path that
            # carried one (the ladder re-verifies against fresh checksums
            # anyway); scan-merged paths re-detect inside the branch, and
            # inline members rerun their (deterministic) immediate ladder
            carried = {n: flags[i] > 0 for i, n in enumerate(names)}
            with jax.named_scope("correct"), plan_scope(
                    self.plan, mode="correct", detected=carried):
                out_c, rep = self.apply_fn(params, *args, **kwargs)
            repmap = {n: T.as_fault_report(r) for n, r in
                      self._layer_map(rep, "corrective").items()}
            if set(repmap) != set(names):
                raise ValueError(
                    "ProtectedModel: the corrective rerun reported layers "
                    f"{sorted(repmap)} but the detect pass carried "
                    f"{sorted(names)}; apply_fn must be "
                    "mode-deterministic")
            by = jnp.stack([repmap[n].corrected_by for n in names])
            resid = jnp.stack([repmap[n].residual for n in names])
            return out_c, by, resid

        if deferred_flags:
            any_flag = jnp.max(jnp.stack(deferred_flags)) > 0
            out, by, resid = run_deferred(any_flag, out_d, _corrective,
                                          len(names), base_by=base_by,
                                          base_resid=base_resid)
        else:
            # every member is per_layer: out_d is already fully corrected
            # and there is nothing for a model-level cond to gate
            out, by, resid = out_d, base_by, base_resid
        # each deferred member's detect-pass score; an inline member ran
        # its own ladder and carries none
        zf = jnp.zeros((), jnp.float32)
        rep = T.ModelReport(
            {n: T.FaultReport(flags[i], by[i], resid[i])
             for i, n in enumerate(names)}, mode="deferred",
            scores={n: zf if n in inline else evmap[n].score
                    for n in names})
        # out_d is the detect pass's raw output: equal to `out` on the
        # clean path (the cond returns it untouched), the *faulty* values
        # on a corrective rerun - so out vs out_d localizes which rows a
        # correction actually changed (serving uses this per slot).
        return (out, rep, out_d) if with_detect_out else (out, rep)


def run_deferred(any_flag, clean_out, correct_fn: Callable, n_layers: int,
                 base_by=None, base_resid=None):
    """The multischeme workflow lifted to model granularity (the paper's
    Fig. 7 fuse-then-defer discipline, in-graph): the forward ran every
    op detect-only, and ONE model-level cond reruns the protected forward
    with full correction only when any layer flagged - the in-graph twin
    of runtime.ft's step-recompute pattern.

    `clean_out` is the detect-only pass's output pytree; `correct_fn()`
    must return (out, by, resid) where by/resid are (n_layers,) i32
    vectors of per-layer scheme enums / residual flags. The error-free
    path therefore carries exactly one cond instead of one per layer -
    the per-layer cond carry (~0.1 ms/layer on CPU) that dominates
    reduced-scale error-free overhead.

    `base_by`/`base_resid` are the no-rerun branch's verdict vectors
    (default zeros): under mixed execution membership, per_layer members
    already corrected inside the detect pass, so their ladder verdicts
    ride through the clean branch instead of being zeroed."""

    def _clean(_):
        z = jnp.zeros((n_layers,), jnp.int32)
        return (clean_out,
                z if base_by is None else base_by,
                z if base_resid is None else base_resid)

    def _correct(_):
        return correct_fn()

    return jax.lax.cond(any_flag, _correct, _clean, None)
