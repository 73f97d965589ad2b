"""Offline-compiled, model-level protection plans (the ProtectionPlan API).

The paper's runtime model (Table 4) assumes kernel/weight checksums are
encoded **once, offline** and that RC/ClC enablement is a **per-layer
offline decision**. This module makes that the shape of the interface
instead of a convention every call site re-implements:

    # offline (once per model / deployment)
    plan = build_plan(params, arch_cfg, cost_model=None, batch=8)
    plan.save("plan.json")                      # JSON + sibling .npz

    # online (every inference)
    plan = ProtectionPlan.load("plan.json")
    plan.validate(params)                       # stale plans fail loudly
    logits, report = forward_cnn(params, x, arch_cfg, plan=plan)

A plan maps param-tree paths to `PlanEntry`s, each holding the op geometry
(`OpSpec`), the SS4.3 policy decision (a static `ProtectConfig`) and the
precomputed weight checksums ("kernel checksums can be precalculated
before the application"). `protect_op` is the single runtime entry point
that subsumes protected_matmul / protected_conv / protected_grouped_matmul
behind one op-spec.

Plans close over jit: configs are static python, checksums become
compile-time constants - exactly the offline-encode semantics the paper's
overhead accounting assumes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
from typing import Any, Dict, Iterator, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import checksums as C
from .policy import (CostModel, OpShape, decide_rc_clc,
                     profile_conv_detect_kernel, profile_matmul_kernel)
from .protected import (WeightChecksums, op_matmul, pick_chunk,
                        protect_matmul_output,
                        protected_conv, protected_grouped_matmul,
                        protected_matmul, weight_checksums_matmul)
from .types import (DEFAULT_CONFIG, DetectEvidence, FaultReport,
                    ProtectConfig, op_operand_dtype, op_output)

PLAN_SCHEMA = "repro.plan/v1"

OP_KINDS = ("matmul", "conv", "grouped_matmul")


class PlanStaleError(ValueError):
    """A plan's recorded weight shapes/dtypes no longer match the params
    (retrained, re-quantised or re-architected model): its precomputed
    checksums would silently verify the wrong invariants, so using it is
    an error, not a fallback."""


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """Static geometry of one protected op (hashable: jit-safe)."""
    kind: str = "matmul"       # one of OP_KINDS
    stride: int = 1            # conv only
    pad: int = 0               # conv only: symmetric spatial padding
    groups: int = 1            # conv only

    def __post_init__(self):
        if self.kind not in OP_KINDS:
            raise ValueError(f"unknown op kind {self.kind!r} "
                             f"(have {OP_KINDS})")

    @property
    def padding(self):
        return [(self.pad, self.pad)] * 2


# Named weight views: how a plan entry's GEMM weight is derived from the
# param-tree leaf it is keyed under. The only non-identity view today is
# the tied-embeddings LM head, whose (d, nc*V) weight is the transposed
# flattened embedding table - the view lets build_plan precompute head
# checksums offline and lets the at-rest audit re-derive them from the
# table leaf without a second copy of the weights in the plan.
W_VIEWS = {
    "tied_head": lambda w: w.reshape(-1, w.shape[-1]).T,
}

# Inverse views: write a repaired GEMM weight back to the param-tree leaf
# it was derived from (runtime.ft's in-place repair rung). Each inverse
# takes (viewed_weight, leaf_shape) and must satisfy
# apply_w_view(apply_w_view_inv(v, view, leaf.shape), view) == v.
W_VIEWS_INV = {
    "tied_head": lambda v, shape: v.T.reshape(shape),
}


def apply_w_view(w, view: Optional[str]):
    """Resolve a param leaf to the GEMM weight an entry was encoded from."""
    if view is None:
        return w
    if view not in W_VIEWS:
        raise ValueError(f"unknown weight view {view!r} "
                         f"(have {sorted(W_VIEWS)})")
    return W_VIEWS[view](w)


def apply_w_view_inv(v, view: Optional[str], leaf_shape):
    """Invert a weight view: map an entry's (repaired) GEMM weight back
    onto the param leaf of shape `leaf_shape` it is derived from."""
    if view is None:
        return v
    if view not in W_VIEWS_INV:
        raise ValueError(f"weight view {view!r} has no inverse "
                         f"(have {sorted(W_VIEWS_INV)})")
    return W_VIEWS_INV[view](v, tuple(leaf_shape))


@dataclasses.dataclass
class PlanEntry:
    """One op's offline decisions: policy config + precomputed weight
    checksums + the weight identity they were encoded from."""
    name: str
    op: OpSpec
    cfg: ProtectConfig
    wck: Any = None                 # WeightChecksums | (cw1, cw2) | None
    # per-block 2D locator sums (checksums.WeightLocators): the repair
    # side information the at-rest audit ladder solves single-block
    # corruption from. Persisted in float64 alongside wck; None on
    # policy-only / grouped entries (audit falls back to detect+restore).
    wlc: Any = None
    w_shape: Optional[Tuple[int, ...]] = None
    w_dtype: Optional[str] = None
    # host-side fp32 content fingerprint (signed weight sum, plus the
    # abs-sum as its noise scale), set by build_plan on concrete params:
    # catches same-shape retrains that shape/dtype checks cannot. None
    # when the entry was built inside a trace (campaign trials) or
    # without params.
    w_sum: Optional[float] = None
    w_asum: Optional[float] = None
    # Number of leading STACK axes on the recorded weight (1 for the
    # scanned transformer stages, whose params carry a leading repeats
    # axis; the op inside the scan sees one slice). Checksums of stacked
    # entries are encoded per slice with a matching leading axis.
    stack: int = 0
    # Named derivation of the GEMM weight from the param leaf (W_VIEWS).
    w_view: Optional[str] = None
    # Deferred-workflow membership of this site ("per_layer" | "deferred" |
    # None). Under ProtectedModel(correction="deferred"), sites marked
    # "per_layer" keep their immediate in-graph correction ladder while
    # the rest ride the detect-only carry into the single model-level
    # cond - the roofline compiler marks expensive compute-bound sites
    # per_layer (their detection cost is hidden under the op, and an
    # immediate fix avoids rerunning them in the corrective branch).
    # None means "deferred" (the pre-roofline behaviour, so old plan
    # files load unchanged). Only direct-path sites may be per_layer:
    # sites inside a lax.scan merge their carries into the stage carry,
    # which cannot mix FaultReports with DetectEvidence.
    execution: Optional[str] = None

    def check_weight(self, w) -> None:
        """Trace-time staleness check against the weight actually used.
        Stacked entries accept either the full stacked weight or one
        per-repeat slice (what the op inside a lax.scan body sees)."""
        if self.w_shape is not None:
            want = tuple(self.w_shape)
            ok = (tuple(w.shape) == want
                  or (self.stack and tuple(w.shape) == want[self.stack:]))
            if not ok:
                raise PlanStaleError(
                    f"plan entry {self.name!r} was built for weight shape "
                    f"{want} but got {tuple(w.shape)}; rebuild "
                    "the plan with build_plan()")
        if self.w_dtype is not None and str(w.dtype) != self.w_dtype:
            raise PlanStaleError(
                f"plan entry {self.name!r} was built for dtype "
                f"{self.w_dtype} but got {w.dtype}; rebuild the plan "
                "with build_plan()")


# --------------------------------------------------------------------------
# entry builders (the offline encode step)
# --------------------------------------------------------------------------

def matmul_entry(name: str, w=None, cfg: ProtectConfig = DEFAULT_CONFIG
                 ) -> PlanEntry:
    """Entry for O = D @ W[K,M]; w=None builds a policy-only entry."""
    if w is None:
        return PlanEntry(name, OpSpec("matmul"), cfg)
    return PlanEntry(name, OpSpec("matmul"), cfg,
                     wck=weight_checksums_matmul(w, cfg.col_chunk),
                     wlc=C.weight_locators_matmul(w, cfg.col_chunk),
                     w_shape=tuple(w.shape), w_dtype=str(w.dtype))


def conv_entry(name: str, w=None, cfg: ProtectConfig = DEFAULT_CONFIG,
               stride: int = 1, pad: int = 0, groups: int = 1) -> PlanEntry:
    """Entry for O = D (x) W[M,Ch,R,R]; w=None builds a policy-only entry."""
    op = OpSpec("conv", stride=stride, pad=pad, groups=groups)
    if w is None:
        return PlanEntry(name, op, cfg)
    return PlanEntry(name, op, cfg, wck=C.encode_w_conv(w, groups=groups),
                     wlc=C.weight_locators_conv(w),
                     w_shape=tuple(w.shape), w_dtype=str(w.dtype))


def grouped_matmul_entry(name: str, w=None,
                         cfg: ProtectConfig = DEFAULT_CONFIG) -> PlanEntry:
    """Entry for expert-batched O[g] = D[g] @ W[g] (per-group checksums are
    derived from runtime operands inside the vmapped op).

    A concrete (E, K, M) expert stack additionally gets per-expert block
    checksums + locator sums (the stacked matmul encoders, one slice per
    expert), so the at-rest audit ladder covers expert weights at full
    block resolution and its in-place repair rung can solve single-block
    corruption - instead of silently degrading to the w_sum fingerprint.
    Scanned MoE stacks (4D leaves) and traced weights stay
    fingerprint-only, as before."""
    e = PlanEntry(name, OpSpec("grouped_matmul"), cfg)
    if w is not None:
        e.w_shape, e.w_dtype = tuple(w.shape), str(w.dtype)
        if w.ndim == 3 and not isinstance(w, jax.core.Tracer):
            # same-module helpers, defined below (resolved at call time)
            e.wck = stacked_weight_checksums_matmul(w, cfg.col_chunk)
            e.wlc = stacked_weight_locators_matmul(w, cfg.col_chunk)
    return e


# --------------------------------------------------------------------------
# the unified protected-op entry point
# --------------------------------------------------------------------------

PROTECT_MODES = (None, "detect_only", "correct")


def protect_op(op: OpSpec, inputs, entry: Optional[PlanEntry] = None,
               cfg: Optional[ProtectConfig] = None, o=None,
               mode: Optional[str] = None, detected=None,
               ) -> Tuple[jnp.ndarray, FaultReport]:
    """Run one protected op through the multischeme workflow.

    inputs is (d, w) or (d, w, bias). `entry` supplies the offline policy
    config and precomputed weight checksums (and is staleness-checked at
    trace time); without an entry, `cfg` (default DEFAULT_CONFIG) applies
    and weight checksums are derived per call. `o` injects an
    already-computed output (tests / fused kernels / fault campaigns).

    `mode` splits execution for the deferred-correction workflow:
    * None - cfg-driven (the per-layer default: detection + in-graph
      ladder, or CoC-D serving under cfg.detect_only);
    * "detect_only" - run CoC-D only and return (raw_out,
      DetectEvidence): the compact per-op flag/evidence carry; the
      correction ladder is not even traced;
    * "correct" - force the full s1-s4/row-col ladder even under a
      detect_only config (use `correct_op`, the public spelling).
    `detected` (correct mode) overrides the ladder's gate with an
    externally carried flag.
    """
    if mode not in PROTECT_MODES:
        raise ValueError(f"unknown protect_op mode {mode!r} "
                         f"(have {PROTECT_MODES})")
    d, w = inputs[0], inputs[1]
    bias = inputs[2] if len(inputs) > 2 else None
    if entry is not None:
        if entry.op != op:
            # a mismatched pair would unpack wrong-geometry checksums and
            # verify the wrong invariants instead of failing clearly
            raise ValueError(
                f"protect_op: op spec {op} does not match entry "
                f"{entry.name!r}'s op {entry.op}")
        entry.check_weight(w)
        use_cfg = entry.cfg if cfg is None else cfg
        wck = entry.wck
    else:
        use_cfg = DEFAULT_CONFIG if cfg is None else cfg
        wck = None

    if op.kind == "matmul":
        if o is not None:
            if use_cfg is None or not use_cfg.enabled:
                return o, (DetectEvidence.clean() if mode == "detect_only"
                           else FaultReport.clean())
            return protect_matmul_output(d, w, o, wck=wck, bias=bias,
                                         cfg=use_cfg, mode=mode,
                                         detected=detected)
        return protected_matmul(d, w, wck=wck, bias=bias, cfg=use_cfg,
                                mode=mode, detected=detected)
    if op.kind == "conv":
        return protected_conv(d, w, bias=bias, stride=op.stride,
                              padding=op.padding, groups=op.groups,
                              wck=wck, cfg=use_cfg, o=o, mode=mode,
                              detected=detected)
    if op.kind == "grouped_matmul":
        if o is not None or bias is not None:
            # silently dropping either would report clean verdicts on
            # operands the op never saw
            raise NotImplementedError(
                "protect_op: grouped_matmul does not support `o` injection "
                "or bias")
        if detected is not None:
            raise NotImplementedError(
                "protect_op: grouped_matmul does not support an external "
                "`detected` gate (per-group gates would need a vector)")
        return protected_grouped_matmul(d, w, wck=wck, cfg=use_cfg,
                                        mode=mode)
    raise ValueError(f"unknown op kind {op.kind!r}")


def correct_op(op: OpSpec, inputs, entry: Optional[PlanEntry] = None,
               cfg: Optional[ProtectConfig] = None, o=None, detected=None,
               ) -> Tuple[jnp.ndarray, FaultReport]:
    """The reusable correction entry point: run the full multischeme
    ladder (all s1-s4/row-col/verify work) on one op, regardless of any
    detect_only serving config. This is the second half of the deferred
    workflow - `protect_op(..., mode="detect_only")` produced the carry,
    and a driver (the model-level cond in models.cnn, or a serving loop)
    invokes correct_op only when something flagged. `detected` gates the
    in-graph ladder from the carried flag instead of re-deriving it."""
    return protect_op(op, inputs, entry=entry, cfg=cfg, o=o, mode="correct",
                      detected=detected)


# --------------------------------------------------------------------------
# the ambient plan context (how layers resolve their PlanEntry by path)
# --------------------------------------------------------------------------
#
# A ProtectedModel run executes the model's apply_fn under a plan scope:
# every GEMM call site names itself ("wq", "gate", ...) inside nested path
# scopes ("stages/b0_attn_full/attn"), and protect_site joins the two to
# resolve the offline PlanEntry - the same param-tree path build_plan keyed
# it under. The context also carries the execution mode of the deferred
# workflow (detect_only / correct) and, in the corrective rerun, the
# carried per-path CoC-D flags, so layers never thread a ProtectConfig or
# a mode argument through the model family again.
#
# The context is trace-time state (like jax config flags): scopes are
# entered inside the traced function, so a jitted forward captures one
# consistent context per trace.

@dataclasses.dataclass
class _PlanContext:
    plan: Optional["ProtectionPlan"]
    mode: Optional[str] = None                     # PROTECT_MODES
    detected: Optional[Mapping[str, Any]] = None   # path -> carried flag
    prefix: Tuple[str, ...] = ()
    overrides: Dict[str, PlanEntry] = dataclasses.field(default_factory=dict)


_CTX: List[_PlanContext] = []


def _current() -> Optional[_PlanContext]:
    return _CTX[-1] if _CTX else None


@contextlib.contextmanager
def plan_scope(plan: Optional["ProtectionPlan"] = None, *,
               mode: Optional[str] = None,
               detected: Optional[Mapping[str, Any]] = None
               ) -> Iterator[_PlanContext]:
    """Enter a fresh ambient protection context (path prefix resets to the
    param-tree root). `mode`/`detected` as in protect_op."""
    if mode not in PROTECT_MODES:
        raise ValueError(f"unknown plan_scope mode {mode!r} "
                         f"(have {PROTECT_MODES})")
    if plan is not None:
        plan.check_backend()
    ctx = _PlanContext(plan=plan, mode=mode, detected=detected)
    _CTX.append(ctx)
    try:
        yield ctx
    finally:
        _CTX.pop()


@contextlib.contextmanager
def path_scope(*segments: str) -> Iterator[None]:
    """Append param-tree path segments to the ambient prefix (no-op when
    no plan scope is active, so layers can always declare their paths)."""
    ctx = _current()
    if ctx is None:
        yield
        return
    saved = ctx.prefix
    ctx.prefix = saved + tuple(segments)
    try:
        yield
    finally:
        ctx.prefix = saved


@contextlib.contextmanager
def entry_overrides(mapping: Dict[str, PlanEntry]) -> Iterator[None]:
    """Temporarily override resolved entries by absolute path - the
    lax.scan body uses this to swap a stacked entry for its per-repeat
    slice (checksums threaded through the scan's xs)."""
    ctx = _current()
    if ctx is None:
        yield
        return
    saved = dict(ctx.overrides)
    ctx.overrides.update(mapping)
    try:
        yield
    finally:
        ctx.overrides = saved


def current_path(name: str = "") -> str:
    ctx = _current()
    parts = (ctx.prefix if ctx is not None else ()) + ((name,) if name else ())
    return "/".join(parts)


def ambient_mode() -> Optional[str]:
    ctx = _current()
    return ctx.mode if ctx is not None else None


def ambient_plan() -> Optional["ProtectionPlan"]:
    ctx = _current()
    return ctx.plan if ctx is not None else None


def resolve_entry(name: str) -> Optional[PlanEntry]:
    """PlanEntry for `name` under the ambient path prefix (None when no
    scope/plan is active or the plan has no entry at that path)."""
    ctx = _current()
    if ctx is None:
        return None
    path = current_path(name)
    if path in ctx.overrides:
        return ctx.overrides[path]
    if ctx.plan is None:
        return None
    return ctx.plan.get(path)


def _carried_flag(path: str):
    ctx = _current()
    if ctx is None or ctx.detected is None:
        return None
    return ctx.detected.get(path)


def protect_site(name: str, inputs, *, entry: Optional[PlanEntry] = None,
                 cfg: Optional[ProtectConfig] = None, o=None,
                 op: Optional[OpSpec] = None):
    """The uniform protected call site: protect_op with the ambient
    context's entry resolution, execution mode, and carried detect flags.

    `entry` (explicit) beats ambient resolution. When an entry applies,
    its offline cfg rules; `cfg` is ONLY the fallback for sites without
    an entry - and `cfg=None` there means unprotected (a planned-path
    site the plan chose not to cover must not silently pick up the
    default full config). `op` defaults to the entry's OpSpec, else a
    plain matmul. In the deferred corrective rerun, sites whose exact
    path carries a detect-pass flag trust it (the ladder skips
    re-detection); sites inside a scan (whose evidence merged into the
    stage carry) re-derive their own flag.

    Everything the site traces sits under a named scope of its path, so
    compiled HLO and profiler traces attribute each kernel to its site,
    and under that to one phase scope: `op` (the op and its bias add),
    `encode` (the input checksums), `detect` (CoC-D), `correct` (the
    ladder; it also wraps the deferred workflow's whole rerun) or
    `inject` (a fault hook's planted output).
    """
    with jax.named_scope(current_path(name)):
        return _protect_site(name, inputs, entry, cfg, o, op)


def _protect_site(name, inputs, entry, cfg, o, op):
    if entry is None:
        entry = resolve_entry(name)
    if entry is not None:
        use_cfg = None                     # entry.cfg rules
    else:
        use_cfg = cfg if cfg is not None \
            else DEFAULT_CONFIG.replace(enabled=False)
    mode = ambient_mode()
    if (mode == "detect_only" and entry is not None
            and entry.execution == "per_layer" and not entry.stack):
        # mixed deferred membership: a per_layer site keeps its immediate
        # in-graph ladder even inside the deferred workflow's detect pass
        # (it returns a FaultReport carry; ProtectedModel folds it into
        # the model report without routing it through the model cond).
        # Stacked sites never qualify - their carries merge through the
        # scan, which cannot mix report types.
        mode = None
    detected = _carried_flag(current_path(name)) if mode == "correct" \
        else None
    if op is None:
        op = entry.op if entry is not None else OpSpec("matmul")
    if op.kind == "grouped_matmul":
        # per-group gates would need a vector; grouped sites re-detect
        detected = None
    if o is None and op.kind == "matmul":
        # serving-drill seam: an ambient fault hook at this exact path
        # (injection.fault_scope) corrupts the raw output and routes it
        # through the ordinary `o=` injection path, so a jitted forward
        # carries a campaign-identical fault at one named site
        from .injection import site_fault
        hook = site_fault(current_path(name))
        if hook is not None:
            d, w = inputs[0], inputs[1]
            lead, k, m = d.shape[:-1], d.shape[-1], w.shape[-1]
            d2 = d.reshape(-1, k)
            # same spelling as protected_matmul's raw product, so rows the
            # hook leaves alone stay bitwise identical to the clean path
            with jax.named_scope("op"):
                o2 = op_output(op_matmul(d2, w), d.dtype)
                if len(inputs) > 2:
                    o2 = op_output(o2.astype(jnp.float32)
                                   + inputs[2].astype(jnp.float32), o2.dtype)
            with jax.named_scope("inject"):
                o2 = hook(o2.reshape(*lead, m))
            out, rep = protect_op(op, (d2,) + tuple(inputs[1:]),
                                  entry=entry, cfg=use_cfg,
                                  o=o2.reshape(-1, m), mode=mode,
                                  detected=detected)
            return out.reshape(*lead, m), rep
    return protect_op(op, inputs, entry=entry, cfg=use_cfg, o=o, mode=mode,
                      detected=detected)


# --------------------------------------------------------------------------
# the plan
# --------------------------------------------------------------------------

def weight_leaf(params, name: str):
    """Resolve an entry name ('conv3', 'fc', 'block/ffn/gate') to its
    weight leaf in a nested param dict (shared by plan.validate and the
    runtime.ft plan-trusted weight audit)."""
    node = params
    for part in name.split("/"):
        if not isinstance(node, dict) or part not in node:
            raise KeyError(name)
        node = node[part]
    if isinstance(node, dict):
        if "w" not in node:
            raise KeyError(name)
        node = node["w"]
    return node


@dataclasses.dataclass
class ProtectionPlan:
    """Per-model protection plan: ordered {param path -> PlanEntry}."""
    entries: Dict[str, PlanEntry] = dataclasses.field(default_factory=dict)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __getitem__(self, name: str) -> PlanEntry:
        return self.entries[name]

    def __contains__(self, name: str) -> bool:
        return name in self.entries

    def __len__(self) -> int:
        return len(self.entries)

    def get(self, name: str, default=None) -> Optional[PlanEntry]:
        return self.entries.get(name, default)

    def names(self) -> Tuple[str, ...]:
        return tuple(self.entries)

    def summary(self) -> Dict[str, dict]:
        """Host-side table of the offline decisions."""
        return {name: {"kind": e.op.kind,
                       "enabled": e.cfg.enabled,
                       "rc": e.cfg.rc_enabled, "clc": e.cfg.clc_enabled,
                       "fc": e.cfg.fc_enabled,
                       "precomputed_checksums": e.wck is not None}
                for name, e in self.entries.items()}

    # -- staleness ---------------------------------------------------------
    def check_backend(self) -> None:
        """Raise PlanStaleError when this backend multiplies f32 operands
        at another precision than the one the plan's weight checksums
        encode (types.op_operand_dtype): a plan built on the CPU used on
        a TPU would flag clean traffic at every f32 site. Plans that
        predate the record were built for f32 operands."""
        built = self.meta.get("f32_operands", "float32")
        here = str(op_operand_dtype(jnp.float32))
        if built != here:
            raise PlanStaleError(
                f"plan's weight checksums encode {built} operands for f32 "
                f"weights, but this backend multiplies {here}; rebuild the "
                "plan with build_plan() on this backend")

    def validate(self, params, rtol: float = 1e-5) -> None:
        """Raise PlanStaleError unless every entry's recorded weight
        shape/dtype AND content fingerprint match `params` (missing
        layers count as stale). The fingerprint (fp32 weight sum, same
        audit style as runtime.ft.weight_checksums) catches same-shape
        retrains whose stale checksums would silently fire detection on
        clean data; rtol absorbs cross-backend reduction-order noise."""
        problems = []
        for name, e in self.entries.items():
            try:
                w = apply_w_view(weight_leaf(params, name), e.w_view)
            except KeyError:
                problems.append(f"{name}: not found in params")
                continue
            if e.w_shape is not None and tuple(w.shape) != tuple(e.w_shape):
                problems.append(f"{name}: shape {tuple(e.w_shape)} in plan "
                                f"vs {tuple(w.shape)} in params")
                continue
            if e.w_dtype is not None and str(w.dtype) != e.w_dtype:
                problems.append(f"{name}: dtype {e.w_dtype} in plan vs "
                                f"{w.dtype} in params")
                continue
            if e.w_sum is not None:
                w32 = w.astype(jnp.float32)
                got = float(jnp.sum(w32))
                got_abs = float(jnp.sum(jnp.abs(w32)))
                # tolerance scales with sum|w|, not the signed sum: for
                # zero-mean weights the signed sum cancels to ~0 while
                # reduction-order noise scales with the element magnitudes.
                # `is None`, not falsy: a recorded w_asum of 0.0 (all-zero
                # leaf) is a legitimate scale, not a missing one.
                scale = rtol * ((abs(e.w_sum) if e.w_asum is None
                                 else e.w_asum) + 1.0)
                drift = abs(got - e.w_sum)
                if e.w_asum is not None:
                    drift = max(drift, abs(got_abs - e.w_asum))
                if drift > scale:
                    problems.append(
                        f"{name}: weight content changed (fingerprint "
                        f"{e.w_sum:.6g} in plan vs {got:.6g} in params - "
                        "same-shape retrain?)")
        if problems:
            raise PlanStaleError(
                "stale ProtectionPlan (rebuild with build_plan): "
                + "; ".join(problems))

    # -- serialization (JSON structure + npz checksum payload) -------------
    @staticmethod
    def _paths(path: str) -> Tuple[str, str]:
        base = path[:-5] if str(path).endswith(".json") else str(path)
        return base + ".json", base + ".npz"

    def save(self, path: str) -> None:
        """Write `<base>.json` (structure) + `<base>.npz` (checksums)."""
        json_path, npz_path = self._paths(path)
        arrays: Dict[str, np.ndarray] = {}
        entries_doc = {}
        for name, e in self.entries.items():
            doc = {"op": dataclasses.asdict(e.op),
                   "cfg": dataclasses.asdict(e.cfg),
                   "w_shape": list(e.w_shape) if e.w_shape else None,
                   "w_dtype": e.w_dtype, "w_sum": e.w_sum,
                   "w_asum": e.w_asum, "stack": e.stack,
                   "w_view": e.w_view, "execution": e.execution,
                   "wck": None, "wlc": None}
            if isinstance(e.wck, WeightChecksums):
                doc["wck"] = {"kind": "matmul",
                              "col_chunk": int(e.wck.col_chunk)}
                arrays[f"{name}/cw1"] = np.asarray(e.wck.cw1)
                arrays[f"{name}/cw2"] = np.asarray(e.wck.cw2)
            elif e.wck is not None:
                cw1, cw2 = e.wck
                doc["wck"] = {"kind": "conv"}
                arrays[f"{name}/cw1"] = np.asarray(cw1)
                arrays[f"{name}/cw2"] = np.asarray(cw2)
            if e.wlc is not None:
                # locator sums persist in float64: the host repair path's
                # bitwise-restoration guarantee rests on this precision
                doc["wlc"] = {"cb": int(e.wlc.cb)}
                for fld in ("r1", "r2", "c1", "c2"):
                    arrays[f"{name}/wl_{fld}"] = np.asarray(
                        getattr(e.wlc, fld), dtype=np.float64)
            entries_doc[name] = doc
        with open(json_path, "w") as f:
            json.dump({"schema": PLAN_SCHEMA, "meta": self.meta,
                       "entries": entries_doc}, f, indent=2)
        np.savez(npz_path, **arrays)

    @classmethod
    def load(cls, path: str) -> "ProtectionPlan":
        json_path, npz_path = cls._paths(path)
        with open(json_path) as f:
            raw = json.load(f)
        if raw.get("schema") != PLAN_SCHEMA:
            raise ValueError(f"unknown plan schema {raw.get('schema')!r} "
                             f"(want {PLAN_SCHEMA})")
        payload = np.load(npz_path)
        entries: Dict[str, PlanEntry] = {}
        for name, doc in raw["entries"].items():
            wck = None
            if doc["wck"] is not None:
                cw1 = jnp.asarray(payload[f"{name}/cw1"])
                cw2 = jnp.asarray(payload[f"{name}/cw2"])
                if doc["wck"]["kind"] == "matmul":
                    wck = WeightChecksums(cw1, cw2, doc["wck"]["col_chunk"])
                else:
                    wck = (cw1, cw2)
            wlc = None
            if doc.get("wlc") is not None:
                # kept as host numpy float64 (jnp.asarray would downcast
                # to f32 under the default x64-disabled config and void
                # the bitwise-repair contract)
                wlc = C.WeightLocators(
                    payload[f"{name}/wl_r1"], payload[f"{name}/wl_r2"],
                    payload[f"{name}/wl_c1"], payload[f"{name}/wl_c2"],
                    int(doc["wlc"]["cb"]))
            entries[name] = PlanEntry(
                name, OpSpec(**doc["op"]), ProtectConfig(**doc["cfg"]),
                wck=wck, wlc=wlc,
                w_shape=tuple(doc["w_shape"]) if doc["w_shape"] else None,
                w_dtype=doc["w_dtype"], w_sum=doc.get("w_sum"),
                w_asum=doc.get("w_asum"), stack=doc.get("stack", 0),
                w_view=doc.get("w_view"), execution=doc.get("execution"))
        return cls(entries=entries, meta=raw.get("meta", {}))

    # -- sharding ----------------------------------------------------------
    def shard(self, mesh, cfg=None) -> "ProtectionPlan":
        """Place every entry's weight checksums on `mesh` with the same
        runtime/sharding.py rules as the weights they encode (the checksum
        of a column-sharded weight is row-sharded, and vice versa), so a
        protected forward under the mesh contracts checksums against
        already-colocated weight shards. Returns a new plan; `self` is
        untouched. `cfg` enables the head-divisibility guard for attention
        projections (same rule as param_shardings)."""
        from repro.runtime.sharding import checksum_shardings
        shardings = checksum_shardings(self, mesh, cfg=cfg)
        entries: Dict[str, PlanEntry] = {}
        for name, e in self.entries.items():
            if e.wck is not None and name in shardings:
                s1, s2 = shardings[name]
                if isinstance(e.wck, WeightChecksums):
                    wck = WeightChecksums(jax.device_put(e.wck.cw1, s1),
                                          jax.device_put(e.wck.cw2, s2),
                                          e.wck.col_chunk)
                else:
                    cw1, cw2 = e.wck
                    wck = (jax.device_put(cw1, s1), jax.device_put(cw2, s2))
                e = dataclasses.replace(e, wck=wck)
            entries[name] = e
        meta = dict(self.meta)
        meta["mesh"] = {str(k): int(v) for k, v in mesh.shape.items()}
        return ProtectionPlan(entries=entries, meta=meta)


# --------------------------------------------------------------------------
# the protection spec (the model-agnostic middle layer)
# --------------------------------------------------------------------------

TAU_DEFAULT = 32.0
TAU_FLOOR, TAU_CAP = 12.0, 64.0
_TAU_REF_K = 1024  # contraction depth at which the calibrated factor
                   # equals the historical global default


def calibrate_tau_factor(k_dim: int) -> float:
    """Per-layer detection safety factor from the layer's contraction
    depth (the ROADMAP's per-layer-thresholds item).

    The thresholds.py noise model already scales with sqrt(K); the safety
    *factor* absorbs what the model does not capture - the tail risk of
    the accumulation-order random walk, which also grows with the number
    of accumulated terms. Shallow layers therefore get a tighter factor
    (more sensitive detection) and deep ones a looser one, clipped so the
    tightest setting still sits ~48x above the subthreshold negative
    control's delta (injection.SUBTHRESHOLD_REL) and the loosest never
    exceeds 2x the historical global default."""
    import math
    f = TAU_DEFAULT * math.sqrt(max(int(k_dim), 1) / _TAU_REF_K)
    return round(min(TAU_CAP, max(TAU_FLOOR, f)), 3)


@dataclasses.dataclass(frozen=True)
class OpSite:
    """One protectable GEMM/conv in a model, identified by its stable
    param-tree path - the unit the offline compiler decides about."""
    path: str
    op: OpSpec
    k_dim: int                       # contraction depth (tau calibration)
    shape: Optional[OpShape] = None  # conv geometry (SS4.3 policy/profile)
    stack: int = 0                   # leading stack axes on the leaf
    w_view: Optional[str] = None     # W_VIEWS derivation of the GEMM weight
    optional: bool = True            # skip silently when params lack it


@dataclasses.dataclass
class ProtectionSpec:
    """Model-agnostic protection spec: the ordered op sites plus the base
    ProtectConfig they start from. Derived from a CNNConfig or a
    transformer ModelConfig by `protection_spec`; `build_plan` compiles it
    against concrete params."""
    sites: List[OpSite]
    base: ProtectConfig = DEFAULT_CONFIG
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _attn_kind(kind: str) -> bool:
    return kind.startswith("attn")


def _block_sites(prefix: str, kind: str, cfg, stack: int,
                 rows: int) -> List[OpSite]:
    """GEMM sites of one transformer block, keyed by the exact param-tree
    paths models.transformer.init_params creates.

    `rows` is the planned batch*seq row count: together with each site's
    (k_dim, out_dim) it gives every plain-matmul site a real OpShape, so
    build_plan's profile-guided calibration covers transformer GEMMs the
    same way it covers convs. grouped_matmul sites stay shapeless (their
    per-expert geometry is runtime routing-dependent)."""
    d, hd = cfg.d_model, cfg.head_dim
    mm = OpSpec("matmul")

    def site(rel, k_dim, op=mm, m=0):
        shape = OpShape(n=rows, m=m, ch=k_dim) \
            if m and op.kind == "matmul" else None
        return OpSite(f"{prefix}/{rel}", op, k_dim, shape=shape,
                      stack=stack)

    if _attn_kind(kind):
        q, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
        return [site("attn/wq", d, m=q), site("attn/wk", d, m=kv),
                site("attn/wv", d, m=kv), site("attn/wo", q, m=d)]
    if kind == "ffn":
        return [site("ffn/gate", d, m=cfg.d_ff), site("ffn/up", d,
                                                      m=cfg.d_ff),
                site("ffn/down", cfg.d_ff, m=d)]
    if kind == "moe":
        ff = cfg.moe_d_ff or cfg.d_ff
        g = OpSpec("grouped_matmul")
        sites = [site("moe/router", d, m=cfg.num_experts),
                 site("moe/gate", d, g), site("moe/up", d, g),
                 site("moe/down", ff, g)]
        if cfg.n_shared_experts:
            sh = ff * cfg.n_shared_experts
            sites += [site("moe/shared/gate", d, m=sh),
                      site("moe/shared/up", d, m=sh),
                      site("moe/shared/down", sh, m=d)]
        return sites
    if kind == "ssm":
        di = cfg.ssm_expand * d
        n_st = cfg.ssm_state
        heads = di // cfg.ssm_head_dim
        return [site("ssm/in_proj", d, m=2 * di + 2 * n_st + heads),
                site("ssm/out_proj", di, m=d)]
    if kind == "rec":
        w = cfg.lru_width or d
        return [site("rec/in_x", d, m=w), site("rec/in_gate", d, m=w),
                site("rec/gate_a", w, m=w), site("rec/gate_i", w, m=w),
                site("rec/out", w, m=d)]
    raise ValueError(f"unknown block kind {kind!r}")


def _cnn_spec(arch_cfg, batch: int) -> ProtectionSpec:
    base = (DEFAULT_CONFIG if getattr(arch_cfg, "abft", True)
            else DEFAULT_CONFIG.replace(enabled=False))
    sites: List[OpSite] = []
    img, ch = arch_cfg.img, arch_cfg.in_ch
    for i, spec in enumerate(arch_cfg.convs):
        e = (img + 2 * spec.pad - spec.kernel) // spec.stride + 1
        out = arch_cfg.scaled(spec.out_ch)
        sites.append(OpSite(
            f"conv{i}", OpSpec("conv", stride=spec.stride, pad=spec.pad),
            k_dim=ch * spec.kernel ** 2,
            shape=OpShape(n=batch, m=out, ch=ch, r=spec.kernel, h=e),
            optional=False))
        img = e // spec.pool if spec.pool else e
        ch = out
    sites.append(OpSite("fc", OpSpec("matmul"), k_dim=ch,
                        shape=OpShape(n=batch,
                                      m=getattr(arch_cfg, "num_classes",
                                                1000), ch=ch)))
    meta = {"arch": getattr(arch_cfg, "name", "?"), "family": "cnn",
            "batch": batch, "img": arch_cfg.img, "in_ch": arch_cfg.in_ch}
    return ProtectionSpec(sites=sites, base=base, meta=meta)


DEFAULT_PLAN_SEQ = 128


def _transformer_spec(cfg, batch: int, seq: int) -> ProtectionSpec:
    base = ProtectConfig(enabled=cfg.abft,
                         row_chunk=cfg.abft_row_chunk,
                         col_chunk=cfg.abft_col_chunk,
                         detect_only=cfg.abft_detect_only)
    pattern, reps, rem = cfg.stages()
    rows = batch * max(seq, 1)
    sites: List[OpSite] = []
    for i, kind in enumerate(cfg.prefix_pattern):
        sites += _block_sites(f"prefix/b{i}_{kind}", kind, cfg, stack=0,
                              rows=rows)
    if reps:
        for i, kind in enumerate(pattern):
            sites += _block_sites(f"stages/b{i}_{kind}", kind, cfg,
                                  stack=1, rows=rows)
    for i, kind in enumerate(rem):
        sites += _block_sites(f"rem/b{i}_{kind}", kind, cfg, stack=0,
                              rows=rows)
    head_m = cfg.vocab_size * max(cfg.num_codebooks, 1)
    head_shape = OpShape(n=rows, m=head_m, ch=cfg.d_model)
    if cfg.tie_embeddings:
        sites.append(OpSite("embed/table", OpSpec("matmul"),
                            k_dim=cfg.d_model, shape=head_shape,
                            w_view="tied_head", optional=False))
    else:
        sites.append(OpSite("embed/head", OpSpec("matmul"),
                            k_dim=cfg.d_model, shape=head_shape,
                            optional=False))
    meta = {"arch": getattr(cfg, "name", "?"), "batch": batch, "seq": seq,
            "family": getattr(cfg, "family", "?"),
            "stage_repeats": reps}
    return ProtectionSpec(sites=sites, base=base, meta=meta)


def protection_spec(arch_cfg, batch: int = 8,
                    seq: int = DEFAULT_PLAN_SEQ) -> ProtectionSpec:
    """Derive the model-agnostic ProtectionSpec from an architecture
    config: a models.cnn.CNNConfig (`.convs` walk) or a transformer
    configs.base.ModelConfig (`.stages()` walk over the param tree's
    stable block paths). `seq` is the planned sequence length for
    transformer specs (rows = batch*seq feed the per-site OpShapes; CNN
    specs ignore it). The spec is what build_plan actually compiles -
    per arXiv:2104.09455, variant selection is a per-layer-shape decision
    independent of the model family."""
    if isinstance(arch_cfg, ProtectionSpec):
        return arch_cfg
    if hasattr(arch_cfg, "convs"):
        return _cnn_spec(arch_cfg, batch)
    if hasattr(arch_cfg, "stages"):
        return _transformer_spec(arch_cfg, batch, seq)
    raise TypeError(
        "protection_spec expects a CNNConfig (.convs), a transformer "
        f"ModelConfig (.stages) or a ProtectionSpec; got "
        f"{type(arch_cfg).__name__}")


# --------------------------------------------------------------------------
# the offline compiler
# --------------------------------------------------------------------------

def _fingerprint(entry: PlanEntry, w) -> None:
    """Record the host-side content fingerprint on a concrete weight."""
    if w is not None:
        w32 = w.astype(jnp.float32)
        entry.w_sum = float(jnp.sum(w32))
        entry.w_asum = float(jnp.sum(jnp.abs(w32)))


def stacked_weight_checksums_matmul(w, col_chunk: int) -> WeightChecksums:
    """Offline checksums of a stacked (reps, K, M) weight: one encode per
    repeat slice (vmapped), stored with a matching leading reps axis so
    the scan can thread per-repeat checksums through its xs. The at-rest
    audit (runtime.ft) re-encodes through this same function, so the
    offline and audit recipes cannot drift."""
    cw1, cw2 = jax.vmap(
        lambda ww: tuple(weight_checksums_matmul(ww, col_chunk))[:2])(w)
    return WeightChecksums(cw1, cw2,
                           pick_chunk(w.shape[-1], col_chunk))


def stacked_weight_locators_matmul(w, col_chunk: int) -> "C.WeightLocators":
    """Offline locator sums of a stacked (reps, K, M) weight: one encode
    per repeat slice, stored with a matching leading reps axis (the
    locator sibling of stacked_weight_checksums_matmul). Concrete weights
    encode per slice in float64 on the host; traced weights vmap the f32
    device encoder."""
    cb = pick_chunk(int(w.shape[-1]), col_chunk)
    if isinstance(w, jax.core.Tracer):
        r1, r2, c1, c2 = jax.vmap(
            lambda ww: tuple(C.weight_locators_matmul(ww, col_chunk))[:4])(w)
        return C.WeightLocators(r1, r2, c1, c2, cb)
    per = [C.weight_locators_matmul(w[i], col_chunk)
           for i in range(int(w.shape[0]))]
    return C.WeightLocators(np.stack([p.r1 for p in per]),
                            np.stack([p.r2 for p in per]),
                            np.stack([p.c1 for p in per]),
                            np.stack([p.c2 for p in per]), cb)


def _site_entry(site: OpSite, w, cfg: ProtectConfig) -> PlanEntry:
    """Compile one OpSite against its (possibly absent) weight leaf."""
    if site.op.kind == "conv":
        e = conv_entry(site.path, w, cfg, stride=site.op.stride,
                       pad=site.op.pad, groups=site.op.groups)
    elif site.op.kind == "grouped_matmul":
        e = grouped_matmul_entry(site.path, w, cfg)
    elif w is None:
        e = PlanEntry(site.path, site.op, cfg)
    elif site.stack:
        e = PlanEntry(site.path, site.op, cfg,
                      wck=stacked_weight_checksums_matmul(w, cfg.col_chunk),
                      wlc=stacked_weight_locators_matmul(w, cfg.col_chunk),
                      w_shape=tuple(w.shape), w_dtype=str(w.dtype))
    else:
        e = matmul_entry(site.path, w, cfg)
    e.stack = site.stack
    e.w_view = site.w_view
    _fingerprint(e, w)
    return e


def build_plan(params, arch_cfg, cost_model: Optional[CostModel] = None,
               batch: int = 8, seq: int = DEFAULT_PLAN_SEQ,
               profile_kernels: bool = False,
               calibrate_tau: bool = True) -> ProtectionPlan:
    """Compile a model-level protection plan (the offline phase).

    `arch_cfg` may be a CNNConfig, a transformer ModelConfig, or an
    already-derived ProtectionSpec - `protection_spec` walks either model
    family to the same site list, so one compiler serves both. Per site it
    decides RC/ClC from the SS4.3 cost model (conv sites), calibrates the
    per-layer detection threshold factor from the contraction depth
    (`calibrate_tau_factor`; persisted in each entry's cfg), and - when
    `params` is given - precomputes the weight checksums keyed by
    param-tree path (scanned-stage sites are encoded per repeat slice,
    stored stacked). `params=None` builds a policy-only plan (no
    checksums; the legacy layer_policies shim uses this).

    `profile_kernels=True` runs the measured calibration pass
    (policy.profile_*_kernel): per layer shape it times the plain XLA op
    + fused jnp detection against the Pallas fused-epilogue route and pins
    the winner (`use_fused_kernel` + `kernel_tiles`) into the entry's
    config - the profile-guided step the arithmetic-intensity ABFT work
    argues for. The timings land in `meta["kernel_profile"]`. Transformer
    GEMM sites profile too (their OpShapes come from batch*`seq` rows);
    when a matmul profile picks the fused kernel, the entry's chunking is
    snapped to the kernel tiles so detect-only sites lower to the
    single-launch fused detect path (chunk == tile). Profiling is
    memoized per distinct (n, k, m) / conv shape, so the dozens of
    identically-shaped per-block sites pay one timing each.

    A measured cost model (`cost_model=MeasuredCostModel.from_host()`,
    core.cost_model) upgrades every one of those decisions from the
    abstract alpha/beta units to this host's calibrated roofline:
    * RC/ClC enablement prices schemes in real seconds, and extends from
      conv sites to every shaped matmul site;
    * detection chunking is sized to keep the chunked detect pass
      bandwidth-bound (`detect_chunk`), instead of the global default;
    * the profile_kernels candidate set is pruned to shapes near the
      ridge point (`should_profile`) - far-from-ridge shapes skip the
      timing entirely and record a skip reason;
    * direct-path CNN sites get a per-entry `execution` membership:
      compute-bound sites keep their immediate in-graph ladder
      ("per_layer") while bandwidth-bound ones ride the deferred carry -
      ProtectedModel(correction="deferred") honors the mix;
    * every verdict persists in `meta["roofline"]` (intensity, bound,
      predicted scheme costs, measured kernel timings when profiled), so
      a loaded plan is auditable and re-derivable.
    """
    spec = protection_spec(arch_cfg, batch=batch, seq=seq)
    base = spec.base
    measured = hasattr(cost_model, "classify")     # MeasuredCostModel
    # mixed execution membership only applies to direct-path model walks
    # (the CNN family): scanned/stacked transformer sites merge their
    # carries through the scan, which cannot mix report types
    direct_family = spec.meta.get("family") == "cnn"
    entries: Dict[str, PlanEntry] = {}
    kprof: Dict[str, dict] = {}
    roofline: Dict[str, dict] = {}
    prof_cache: Dict[tuple, object] = {}
    for site in spec.sites:
        w = None
        if params is not None:
            try:
                w = apply_w_view(weight_leaf(params, site.path), site.w_view)
            except KeyError:
                if site.optional:
                    continue
                raise KeyError(
                    f"build_plan: params have no leaf at {site.path!r} "
                    "(spec/params mismatch)")
        cfg = base
        if calibrate_tau and cfg.enabled:
            cfg = cfg.replace(tau_factor=calibrate_tau_factor(site.k_dim))
        if site.op.kind == "conv" and site.shape is not None:
            rc, clc = decide_rc_clc(site.shape, cost_model)
            cfg = cfg.replace(rc_enabled=rc, clc_enabled=clc)
        cls = None
        execution = None
        if measured and site.shape is not None:
            cls = cost_model.classify(site.shape)
            if site.op.kind == "matmul":
                # rung selection in real seconds for GEMM sites too (the
                # analytic default only ever decided conv sites)
                rc, clc = decide_rc_clc(site.shape, cost_model)
                cfg = cfg.replace(rc_enabled=rc, clc_enabled=clc)
            chunk = cost_model.detect_chunk(cfg.col_chunk)
            cfg = cfg.replace(row_chunk=chunk, col_chunk=chunk)
            if direct_family and not site.stack:
                execution = ("per_layer" if cls["bound"] == "compute"
                             else "deferred")
        if profile_kernels and cfg.enabled and site.shape is not None:
            s = site.shape
            if measured and not cost_model.should_profile(s):
                kprof[site.path] = {
                    "use_fused": False, "tiles": None, "plain_us": None,
                    "fused_us": None,
                    "skipped": "roofline prune: intensity "
                               f"{cls['intensity']:.2f} outside the "
                               "profile window around ridge "
                               f"{cls['ridge']:.2f}"}
                entries[site.path] = _compile_entry(site, w, cfg, execution)
                if cls is not None:
                    roofline[site.path] = _roofline_doc(cls, execution,
                                                        kprof.get(site.path))
                continue
            if site.op.kind == "conv":
                ckey = ("conv", s.n, s.m, s.h)
                prof = prof_cache.get(ckey)
                if prof is None:
                    prof = profile_conv_detect_kernel((s.n, s.m, s.h, s.h))
                    prof_cache[ckey] = prof
            else:
                m = w.shape[-1] if w is not None else s.m
                ckey = ("mm", s.n, s.ch, m)
                prof = prof_cache.get(ckey)
                if prof is None:
                    prof = profile_matmul_kernel(s.n, s.ch, m)
                    prof_cache[ckey] = prof
            cfg = cfg.replace(use_fused_kernel=prof.use_fused,
                              kernel_tiles=prof.tiles)
            if (prof.use_fused and prof.tiles
                    and site.op.kind == "matmul"):
                # snap chunking to the kernel tiles so detect-only
                # lowers to the single-launch fused detect kernel
                cfg = cfg.replace(row_chunk=prof.tiles[0],
                                  col_chunk=prof.tiles[1])
            kprof[site.path] = prof.doc()
        entries[site.path] = _compile_entry(site, w, cfg, execution)
        if cls is not None:
            roofline[site.path] = _roofline_doc(cls, execution,
                                                kprof.get(site.path))
    model = cost_model or CostModel()
    meta = dict(spec.meta)
    meta["f32_operands"] = str(op_operand_dtype(jnp.float32))
    from .cost_model import cost_model_doc
    meta["cost_model"] = cost_model_doc(model)
    if measured:
        meta["roofline"] = roofline
    if profile_kernels:
        meta["kernel_profile"] = kprof
        if not kprof and entries:
            # only shapeless sites (grouped/moe experts) in this spec -
            # say so instead of letting the caller believe the
            # calibration pass ran
            logging.getLogger("repro.plan").warning(
                "build_plan(profile_kernels=True): no profilable sites "
                "in this spec (every site lacks an OpShape); plan built "
                "without kernel pinning")
    return ProtectionPlan(entries=entries, meta=meta)


def _compile_entry(site: OpSite, w, cfg: ProtectConfig,
                   execution: Optional[str]) -> PlanEntry:
    e = _site_entry(site, w, cfg)
    e.execution = execution
    return e


def _roofline_doc(cls: dict, execution: Optional[str],
                  prof_doc: Optional[dict]) -> dict:
    """One site's persisted roofline verdict: the classification inputs,
    the membership decision it produced, and - when the site was profiled
    - the measured plain/fused timings next to the prediction."""
    doc = {"intensity": cls["intensity"], "ridge": cls["ridge"],
           "bound": cls["bound"], "predicted_us": dict(cls["predicted_us"]),
           "execution": execution}
    if prof_doc is not None:
        doc["measured_us"] = {"plain": prof_doc.get("plain_us"),
                              "fused": prof_doc.get("fused_us")}
        if prof_doc.get("skipped"):
            doc["profile_skipped"] = prof_doc["skipped"]
    return doc


def force_fused_matmul(plan: ProtectionPlan,
                       tiles: Optional[Tuple[int, int, int]] = None
                       ) -> ProtectionPlan:
    """Pin the fused Pallas kernel on every plain-matmul entry regardless
    of what profiling measured - the benchmark hook for pricing the fused
    transformer column on hosts where interpret-mode timings would never
    pick it. The runtime launches the detect kernel with tiles equal to
    the entry's (row_chunk, col_chunk), so chunk==tile holds by
    construction; `tiles` only overrides the K tile / non-detect path."""
    entries = {}
    for path, e in plan.entries.items():
        if e.op.kind == "matmul" and e.cfg.enabled:
            cfg = e.cfg.replace(use_fused_kernel=True,
                                kernel_tiles=tiles or e.cfg.kernel_tiles)
            e = dataclasses.replace(e, cfg=cfg)
        entries[path] = e
    return ProtectionPlan(entries=entries, meta=dict(plan.meta))
