"""Shared types for the ABFT core.

Scheme enum values follow the escalation order of the paper's multischeme
workflow (Fig. 7): CoC-D detects; CoC -> RC/ClC -> FC correct; full
recompute is the last resort.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The arithmetic of the protected path, stated once. The protected op
# multiplies `op_operand_dtype` operands - on a TPU, f32 operands go to the
# MXU as bf16, which is what XLA's default precision does there, made
# explicit - with exact products and f32 accumulation. The checksum side
# encodes those same operand values (`op_operand`) and runs every product
# at PRECISION (HIGHEST: f32-accurate), so an op and its checksum differ by
# f32 accumulation order only and the eps_f32 noise model of
# core/thresholds.py holds unchanged. Left implicit, the op's ~2^-8 operand
# rounding would sit far above tau and flag clean traffic at every layer.
PRECISION = jax.lax.Precision.HIGHEST



def op_operand_dtype(dtype):
    """The dtype the protected op multiplies for operands of `dtype`."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32 and jax.default_backend() == "tpu":
        return jnp.dtype(jnp.bfloat16)
    return dtype


def round_to(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """`x` rounded to the precision of float `dtype`, kept in f32.

    A `reduce_precision`, not an astype round trip: under XLA's default
    excess precision a fused f32 -> bf16 -> f32 pair may be computed
    without rounding (seen on a v5e: the deferred resnet18's fc checksum
    encoded unrounded activations and flagged clean traffic), while
    reduce_precision always rounds."""
    x32 = x.astype(jnp.float32)
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize >= 4:
        return x32
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x32, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def op_operand(x: jnp.ndarray) -> jnp.ndarray:
    """The values the protected op multiplies for operand `x`, in f32 -
    what every checksum encode starts from."""
    return round_to(x, op_operand_dtype(x.dtype))


def op_output(o32: jnp.ndarray, dtype) -> jnp.ndarray:
    """The op's f32 product as a `dtype` output whose rounding no fusion
    can skip: the protected program, whose checksums also read the
    output, and the unprotected one then see the same values."""
    return round_to(o32, dtype).astype(dtype)


# corrected_by enum (kept as plain ints so they live inside jit).
NONE = 0          # no fault detected
COC = 1           # corrected by checksum-of-checksums
RC = 2            # corrected by row checksum scheme
CLC = 3           # corrected by column checksum scheme
FC = 4            # corrected by full checksum scheme
CHECKSUM_REFRESH = 5  # detection was caused by a corrupted checksum; output clean
RECOMPUTE = 6     # recomputed the whole operation
W_REPAIR = 7      # at-rest weight corruption repaired in place from the
                  # plan's locator sums (the audit ladder's first rung)

SCHEME_NAMES = {
    NONE: "none", COC: "coc", RC: "rc", CLC: "clc", FC: "fc",
    CHECKSUM_REFRESH: "checksum_refresh", RECOMPUTE: "recompute",
    W_REPAIR: "w_repair",
}


class FaultReport(NamedTuple):
    """Verdict of one protected op. All fields are scalar jnp arrays so the
    report can cross a jit boundary and be aggregated across layers."""
    detected: jnp.ndarray      # i32: 1 if CoC-D flagged the op
    corrected_by: jnp.ndarray  # i32: scheme enum that resolved it
    residual: jnp.ndarray      # i32: 1 if inconsistency survived all schemes

    @staticmethod
    def clean() -> "FaultReport":
        z = jnp.zeros((), jnp.int32)
        return FaultReport(z, z, z)

    @staticmethod
    def merge(a: "FaultReport", b: "FaultReport") -> "FaultReport":
        return FaultReport(
            jnp.maximum(a.detected, b.detected),
            jnp.maximum(a.corrected_by, b.corrected_by),
            jnp.maximum(a.residual, b.residual),
        )


class DetectEvidence(NamedTuple):
    """Compact CoC-D carry of one protected op in detect-only execution
    (the deferred-correction mode): just the flag and the strength of the
    evidence, so a whole model's worth of carries stays O(layers) scalars.

    `score` is max |C - S| / tau over the compared invariants (>1 means a
    mismatch, non-finite values score +inf) - enough for a driver to rank
    which layer screamed loudest without re-deriving any checksums."""
    flag: jnp.ndarray   # i32: 1 if CoC-D flagged the op
    score: jnp.ndarray  # f32: max residue-to-threshold ratio

    @staticmethod
    def clean() -> "DetectEvidence":
        return DetectEvidence(jnp.zeros((), jnp.int32),
                              jnp.zeros((), jnp.float32))

    @staticmethod
    def merge(a: "DetectEvidence", b: "DetectEvidence") -> "DetectEvidence":
        return DetectEvidence(jnp.maximum(a.flag, b.flag),
                              jnp.maximum(a.score, b.score))


def clean_report(mode: Optional[str] = None):
    """The identity element for verdict merging in a given protect mode:
    DetectEvidence under "detect_only", FaultReport otherwise. Lets layer
    walks (and the transformer scan carry) initialise one accumulator that
    works in every ProtectedModel execution mode."""
    return DetectEvidence.clean() if mode == "detect_only" \
        else FaultReport.clean()


def merge_verdicts(a, b):
    """Merge two per-op carries of the SAME kind: FaultReport with
    FaultReport (the per-layer/correct modes) or DetectEvidence with
    DetectEvidence (the detect-only pass of the deferred workflow).
    ModelReports are collapsed to their scalar view first, so call sites
    that used FaultReport.merge(a, r.merged()) keep one spelling."""
    if isinstance(a, ModelReport):
        a = a.merged()
    if isinstance(b, ModelReport):
        b = b.merged()
    if isinstance(a, DetectEvidence) or isinstance(b, DetectEvidence):
        if not (isinstance(a, DetectEvidence)
                and isinstance(b, DetectEvidence)):
            raise TypeError(
                "merge_verdicts: cannot mix DetectEvidence with "
                f"FaultReport ({type(a).__name__} vs {type(b).__name__}); "
                "a detect-only pass must stay detect-only end to end")
        return DetectEvidence.merge(a, b)
    return FaultReport.merge(a, b)


def scheme_histogram(corrected_by) -> dict:
    """Host-side histogram of a batched `corrected_by` field: scheme name ->
    count. The campaign engine and benchmarks aggregate per-trial
    FaultReports through this single definition so their tables agree.
    Every scheme appears (zero counts included) so campaign/bench tables
    keep a stable column set across runs."""
    arr = np.asarray(corrected_by).reshape(-1)
    return {name: int((arr == val).sum())
            for val, name in SCHEME_NAMES.items()}


@jax.tree_util.register_pytree_node_class
class ModelReport:
    """Per-layer fault verdicts of one model pass, as a pytree.

    Layer names are static metadata (they live in the treedef), the
    per-layer FaultReports are the leaves - so a ModelReport crosses jit
    boundaries, and `report.by_layer["conv3"]` works on concrete results.
    The merged-scalar view (`detected` / `corrected_by` / `residual`)
    matches the old single-FaultReport contract, so call sites that only
    want the model-level verdict keep working unchanged.

    `mode` records which correction regime produced the verdicts
    ("per_layer": every op ran its own lax.cond ladder; "deferred": the
    ops ran detect-only and ONE model-level cond reran the corrective
    forward). In deferred mode the per-layer `detected` flags are the
    detect-pass provenance - attribution survives even though correction
    happened at model granularity. Static metadata: lives in the treedef.

    `scores` holds, per layer, the detect pass's `DetectEvidence.score`
    (max |C - S| / tau; > 1 flags) where the deferred workflow has one,
    so a caller can read how close clean traffic runs to the threshold.
    """

    def __init__(self, by_layer: Optional[Mapping[str, FaultReport]] = None,
                 mode: str = "per_layer",
                 scores: Optional[Mapping[str, jnp.ndarray]] = None):
        self.by_layer: Dict[str, FaultReport] = dict(by_layer or {})
        self.mode = mode
        self.scores: Dict[str, jnp.ndarray] = dict(scores or {})

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        keys, skeys = tuple(self.by_layer), tuple(self.scores)
        return (tuple(self.by_layer[k] for k in keys)
                + tuple(self.scores[k] for k in skeys),
                (keys, self.mode, skeys))

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, mode, skeys = aux
        children = list(children)
        return cls(dict(zip(keys, children[:len(keys)])), mode=mode,
                   scores=dict(zip(skeys, children[len(keys):])))

    # -- construction ------------------------------------------------------
    def add(self, name: str, rep: "FaultReport | ModelReport") -> "ModelReport":
        """Functional append of one layer's verdict (sub-reports flatten in
        as 'name/sub')."""
        out, scores = dict(self.by_layer), dict(self.scores)
        if isinstance(rep, ModelReport):
            for sub, r in rep.by_layer.items():
                out[f"{name}/{sub}"] = r
            for sub, sc in rep.scores.items():
                scores[f"{name}/{sub}"] = sc
        else:
            out[name] = rep
        return ModelReport(out, mode=self.mode, scores=scores)

    def merge(self, other: "ModelReport") -> "ModelReport":
        """Union of layers; shared names merge elementwise."""
        out, scores = dict(self.by_layer), dict(self.scores)
        for name, r in other.by_layer.items():
            out[name] = FaultReport.merge(out[name], r) if name in out else r
        for name, sc in other.scores.items():
            scores[name] = (jnp.maximum(scores[name], sc) if name in scores
                            else sc)
        return ModelReport(out, mode=self.mode, scores=scores)

    # -- views -------------------------------------------------------------
    def __getitem__(self, name: str) -> FaultReport:
        return self.by_layer[name]

    def __len__(self) -> int:
        return len(self.by_layer)

    def layers(self) -> Tuple[str, ...]:
        return tuple(self.by_layer)

    def merged(self) -> FaultReport:
        """Model-level FaultReport (max over layers, the old contract).
        A report holding DetectEvidence leaves (the detect-only pass of
        the deferred workflow) merges to a scalar DetectEvidence."""
        if not self.by_layer:
            return FaultReport.clean()
        reps = list(self.by_layer.values())
        if isinstance(reps[0], DetectEvidence):
            return DetectEvidence(
                jnp.max(jnp.stack([r.flag for r in reps])),
                jnp.max(jnp.stack([r.score for r in reps])))
        return FaultReport(
            jnp.max(jnp.stack([r.detected for r in reps])),
            jnp.max(jnp.stack([r.corrected_by for r in reps])),
            jnp.max(jnp.stack([r.residual for r in reps])))

    @property
    def detected(self) -> jnp.ndarray:
        return self.merged().detected

    @property
    def corrected_by(self) -> jnp.ndarray:
        return self.merged().corrected_by

    @property
    def residual(self) -> jnp.ndarray:
        return self.merged().residual

    def scheme_histogram(self) -> dict:
        """Stable-column histogram of per-layer corrected_by values."""
        if not self.by_layer:
            return scheme_histogram(np.zeros((0,), np.int32))
        return scheme_histogram(
            np.concatenate([np.asarray(r.corrected_by).reshape(-1)
                            for r in self.by_layer.values()]))

    def summary(self) -> dict:
        """Host-side {layer: {detected, corrected_by, residual}} table."""
        return {name: {"detected": int(np.max(np.asarray(r.detected))),
                       "corrected_by": SCHEME_NAMES[
                           int(np.max(np.asarray(r.corrected_by)))],
                       "residual": int(np.max(np.asarray(r.residual)))}
                for name, r in self.by_layer.items()}

    def __repr__(self) -> str:
        return f"ModelReport({list(self.by_layer)}, mode={self.mode!r})"


def as_fault_report(rep) -> FaultReport:
    """Normalise FaultReport | ModelReport to the scalar FaultReport view
    (what scan carries and step verdicts consume)."""
    return rep.merged() if isinstance(rep, ModelReport) else rep


@dataclasses.dataclass(frozen=True)
class ProtectConfig:
    """Static configuration of a protected op (hashable: safe as a jit
    static argument)."""
    enabled: bool = True
    # Layerwise RC/ClC enablement (paper SS4.3). Decided offline by
    # repro.core.policy; static so disabled schemes cost nothing.
    rc_enabled: bool = True
    clc_enabled: bool = True
    fc_enabled: bool = True
    # Chunk sizes for the matmul path. Each (row_chunk x col_chunk) tile of O
    # carries independent checksums: bounds index-weight magnitude (locator
    # precision in low precision) and lets disjoint chunks correct
    # independent faults (the paper's "elements across blocks are
    # independent" argument, lifted to tiles).
    row_chunk: int = 1024
    col_chunk: int = 1024
    # Safety factor for detection thresholds (see thresholds.py).
    tau_factor: float = 32.0
    # Also compare the index-weighted invariants (s6/s7) during detection.
    # Free with the fused kernel; catches symmetric multi-fault patterns
    # that cancel in s5. Beyond-paper (paper's CoC-D uses C_o5 only).
    detect_weighted: bool = True
    # Protect the backward pass (paper SS5.3).
    protect_backward: bool = True
    # Detection-only (the paper's CoC-D stage): skip the in-graph
    # correction ladder and surface the verdict - the driver recomputes
    # the step (runtime.ft). Production serving mode: the rarely-taken
    # correction branches never enter the compiled program.
    detect_only: bool = False
    # Use the Pallas fused-epilogue kernel for O + summations. Set per
    # layer by build_plan's profile-guided calibration (policy.profile_*).
    use_fused_kernel: bool = False
    # Interpret mode for the Pallas kernel. None = auto: compile on TPU,
    # interpret everywhere else (the kernels are TPU-shaped; interpreting
    # them on CPU is for validation, not speed). True/False overrides.
    kernel_interpret: Optional[bool] = None
    # Pallas tile sizes (bm, bn, bk) pinned by the profile-guided plan;
    # None = the kernels' shape-derived defaults.
    kernel_tiles: Optional[Tuple[int, int, int]] = None

    def __post_init__(self):
        # JSON round-trips tuples as lists; normalise so the config stays
        # hashable (it is a jit static argument)
        if isinstance(self.kernel_tiles, list):
            object.__setattr__(self, "kernel_tiles", tuple(self.kernel_tiles))

    def replace(self, **kw) -> "ProtectConfig":
        return dataclasses.replace(self, **kw)

    def resolve_interpret(self) -> bool:
        """Concrete interpret flag: explicit override, else backend auto."""
        if self.kernel_interpret is not None:
            return self.kernel_interpret
        return default_kernel_interpret()


def default_kernel_interpret() -> bool:
    """Interpret Pallas kernels everywhere but TPU (where they compile)."""
    return jax.default_backend() != "tpu"


DEFAULT_CONFIG = ProtectConfig()


class OutputSums(NamedTuple):
    """The seven output summations of the paper (S_o1..S_o7) plus the
    sum-of-squares used by the threshold model.

    Normalised block form: O is (N, M, P); P is the per-block payload
    (1 for matmul; E*E for conv).
    """
    s1: jnp.ndarray  # (M, P)  sum_n O[n,m]
    s2: jnp.ndarray  # (N, P)  sum_m O[n,m]
    s3: jnp.ndarray  # (M, P)  sum_n n*O[n,m]
    s4: jnp.ndarray  # (N, P)  sum_m m*O[n,m]
    s5: jnp.ndarray  # (P,)    sum_nm O
    s6: jnp.ndarray  # (P,)    sum_nm n*O
    s7: jnp.ndarray  # (P,)    sum_nm m*O
    sumsq: jnp.ndarray  # ()   sum_nmp O^2 (threshold scale)


class OutputChecksums(NamedTuple):
    """Checksum-side predictions C_o1..C_o7 (paper Eq. 6), normalised.

    Note on naming: we fix the paper's SS3.6 index swap - here c_o6 is the
    n-weighted invariant (row locator) and c_o7 the m-weighted one (column
    locator), matching the correction formulas actually used in SS3.6.
    """
    c1: Optional[jnp.ndarray]  # (M, P) = C_d1 (x) W
    c2: Optional[jnp.ndarray]  # (N, P) = D (x) C_w1
    c3: Optional[jnp.ndarray]  # (M, P) = C_d2 (x) W
    c4: Optional[jnp.ndarray]  # (N, P) = D (x) C_w2
    c5: jnp.ndarray            # (P,)   = C_d1 (x) C_w1
    c6: jnp.ndarray            # (P,)   = C_d2 (x) C_w1   (n-weighted)
    c7: jnp.ndarray            # (P,)   = C_d1 (x) C_w2   (m-weighted)
