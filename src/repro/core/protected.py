"""Protected matmul / conv: the paper's ABFT wrapped around any
implementation of the underlying linear op.

Matmul protection is *chunked*: O[N,M] is tiled into (row_chunk x col_chunk)
regions, each carrying independent checksums (vmapped schemes). Chunking
bounds the index-weight magnitude (locator precision in low precision) and
lets disjoint chunks recover independent faults - the block-level
independence argument of the paper, lifted one level.

The error-free cost is: one pass over D (C_d1/C_d2 encode), the chunked
output summations (one pass over O, or free via the fused Pallas epilogue),
and the O(K)-sized checksum dots. This is the CoC-D detection stage of the
multischeme workflow; everything else lives behind a lax.cond.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import checksums as C
from . import schemes as S
from . import thresholds as TH
from . import types as T
from .types import PRECISION, op_operand, op_operand_dtype, op_output
from .workflow import run_ladder

F32 = jnp.float32

# Row/column-invariant slack for post-correction verification: a correct
# scheme fix restores elements only to within eps * |corruption| (the
# residues were computed against values up to 2^12 larger), so the verify
# taus get this extra headroom. Miscorrections leave residues ~0.25 * the
# corruption itself - six orders of magnitude above this slack - so the
# separation stays sharp.
VERIFY_ROWCOL_SLACK = 64.0


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _replicate_small(x: jnp.ndarray) -> jnp.ndarray:
    """Pin a small checksum/summation tensor to a fully-replicated layout.

    Under a device mesh, GSPMD's propagation through a stage scan and the
    deferred-correction cond can assign these reductions a partial-sum
    layout it then "involuntarily rematerializes" - double-counting one
    side of the invariant (observed as c == 2*s on CPU SPMD, a guaranteed
    false positive on clean traffic). The arrays are O(chunks * K);
    replicating them costs one tiny collective and keeps both sides of
    every comparison in a single layout. No-op only when no mesh is in
    scope (`jax.set_mesh`); any other error propagates, since a dropped
    constraint would turn into clean-traffic false positives.
    """
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*([None] * x.ndim)))


def op_matmul(d2: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """The protected GEMM itself: `op_operand_dtype` operands,
    f32-accumulated at PRECISION."""
    dt = op_operand_dtype(d2.dtype)
    return jnp.dot(d2.astype(dt), w.astype(dt), precision=PRECISION,
                   preferred_element_type=F32)


def _add_bias(o: jnp.ndarray, bias: Optional[jnp.ndarray]) -> jnp.ndarray:
    """O + bias over the last axis, added in f32 and rounded back."""
    if bias is None:
        return o
    return op_output(o.astype(F32) + bias.astype(F32), o.dtype)


def pick_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (n itself if n <= target)."""
    if n <= target:
        return max(n, 1)
    best = 1
    for d in range(1, int(math.isqrt(n)) + 1):
        if n % d == 0:
            if d <= target:
                best = max(best, d)
            q = n // d
            if q <= target:
                best = max(best, q)
    return best


# --------------------------------------------------------------------------
# shared multischeme scaffolding
#
# The matmul path (chunked, vmapped over tiles) and the conv path
# (normalised N x M block form) used to carry parallel copies of the
# detection comparison, the post-correction verification, the per-scheme
# threshold derivation and the rung-list assembly. Both now go through the
# four helpers below; only the geometry (how O is viewed as blocks and how
# thresholds broadcast over residues) stays path-specific.
# --------------------------------------------------------------------------

def _detect_invariants(c5, c6, c7, s5, s6, s7, tau5, rows: int, cols: int,
                       weighted: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """CoC-D: compare the scalar invariant (and optionally the two
    index-weighted ones) against their thresholds. rows/cols are the block
    extents that bound the index-weight noise amplification.

    Returns (flag, score): flag is the detection verdict, score is the
    max |C - S| / tau evidence ratio (>1 on a mismatch, +inf on
    non-finite values) - the compact carry the deferred-correction mode
    surfaces per layer. The comparisons are stacked into ONE mismatch +
    any so the error-free path pays a single fused compare instead of
    three compare/reduce/or chains (dispatch-bound at CNN layer sizes)."""
    if not weighted:
        c, s, t = c5, s5, jnp.broadcast_to(tau5, jnp.shape(c5))
    else:
        t5 = jnp.broadcast_to(tau5, jnp.shape(c5))
        c = jnp.stack([c5, c6, c7])
        s = jnp.stack([s5, s6, s7])
        t = jnp.stack([t5, TH.tau_weighted(t5, rows),
                       TH.tau_weighted(t5, cols)])
    c32, s32 = c.astype(F32), s.astype(F32)
    ratio = jnp.where(jnp.isfinite(c32) & jnp.isfinite(s32),
                      jnp.abs(c32 - s32) / t, jnp.inf)
    return jnp.any(TH.mismatch(c, s, t)), jnp.max(ratio)


def _verify_invariants(cs: T.OutputChecksums, ss: T.OutputSums, tau5,
                       t_elem, rows: int, cols: int) -> jnp.ndarray:
    """Post-correction acceptance: scalar + weighted + row/column
    invariants against *fresh* checksums.

    Scalar invariants alone can accept a miscorrection: for a multi-element
    burst, CoC's column locator is the delta-weighted mean of the corrupted
    columns, and when that mean happens to sit near an integer the
    single-point "fix" satisfies c5/c6/c7 while leaving every burst element
    wrong (found by the campaign's differential oracle, ~0.5% of row
    bursts). The row/column invariants are not fooled; checking them here
    costs only inside the correction branch. `t_elem` is tau5 broadcast
    against the per-row/column residues; a column residue sums `rows`
    elements (~1/cols of the block energy), hence the sqrt scalings."""
    ok = ~jnp.any(TH.mismatch(cs.c5, ss.s5, tau5))
    ok &= ~jnp.any(TH.mismatch(cs.c6, ss.s6, TH.tau_weighted(tau5, rows)))
    ok &= ~jnp.any(TH.mismatch(cs.c7, ss.s7, TH.tau_weighted(tau5, cols)))
    trc = VERIFY_ROWCOL_SLACK * t_elem
    ok &= ~jnp.any(TH.mismatch(cs.c1, ss.s1, trc / max(cols, 1) ** 0.5))
    ok &= ~jnp.any(TH.mismatch(cs.c2, ss.s2, trc / max(rows, 1) ** 0.5))
    return ok


def _scheme_taus(kind: str, t_scalar, t_elem, rows: int, cols: int) -> tuple:
    """Residue thresholds handed to a correction scheme. `t_scalar`
    compares per-block scalar invariants; `t_elem` is pre-broadcast against
    per-row/column residues (each column residue sums `rows` elements, i.e.
    ~1/cols of the block's energy, and symmetrically for rows)."""
    if kind == "scalar":
        return (t_scalar,)
    if kind == "col":
        return (t_elem / max(cols, 1) ** 0.5,)
    if kind == "row":
        return (t_elem / max(rows, 1) ** 0.5,)
    return (t_elem / max(cols, 1) ** 0.5, t_elem / max(rows, 1) ** 0.5)


def _ladder_rungs(cfg: T.ProtectConfig, run_scheme):
    """The multischeme escalation ladder (Fig. 7) from the layerwise
    policy; disabled rungs never enter the compiled program. The
    CHECKSUM_REFRESH rung is the Fig. 3 shortcut: fresh checksums inside
    the verifier decide whether O was clean all along."""
    rungs = [
        (T.CHECKSUM_REFRESH, lambda o, cs: (o, jnp.array(True))),
        (T.COC, lambda o, cs: run_scheme(S.coc_correct, o, "scalar", cs)),
    ]
    if cfg.rc_enabled:
        rungs.append((T.RC,
                      lambda o, cs: run_scheme(S.rc_correct, o, "col", cs)))
    if cfg.clc_enabled:
        rungs.append((T.CLC,
                      lambda o, cs: run_scheme(S.clc_correct, o, "row", cs)))
    if cfg.fc_enabled:
        rungs.append((T.FC,
                      lambda o, cs: run_scheme(S.fc_correct, o, "fc", cs)))
    return rungs


def _clean_result(o, mode: Optional[str]):
    """The disabled-protection verdict in whichever carry `mode` asks for."""
    if mode == "detect_only":
        return o, T.DetectEvidence.clean()
    return o, T.FaultReport.clean()


class WeightChecksums(NamedTuple):
    """Chunked kernel checksums of W[K,M] (precomputable; paper: 'kernel
    checksums can be precalculated before the application')."""
    cw1: jnp.ndarray  # (mb, K)  per-chunk sum over columns
    cw2: jnp.ndarray  # (mb, K)  per-chunk locally-index-weighted sum
    col_chunk: int


def weight_checksums_matmul(w: jnp.ndarray, col_chunk: int) -> WeightChecksums:
    k, m = w.shape
    cb = pick_chunk(m, col_chunk)
    mb = m // cb
    w32 = op_operand(w).reshape(k, mb, cb)
    cw1 = jnp.einsum("kbc->bk", w32)
    cw2 = jnp.einsum("kbc,c->bk", w32, jnp.arange(cb, dtype=F32),
                     precision=PRECISION)
    return WeightChecksums(cw1, cw2, cb)


class _ChunkedChecksums(NamedTuple):
    """Scalar (CoC) invariants per chunk-pair + encodes needed by rungs."""
    cd1: jnp.ndarray      # (nb, K)
    cd2: jnp.ndarray      # (nb, K)
    cw1: jnp.ndarray      # (mb, K)
    cw2: jnp.ndarray      # (mb, K)
    c5: jnp.ndarray       # (nb, mb)
    c6: jnp.ndarray       # (nb, mb)  n-weighted (local indices)
    c7: jnp.ndarray       # (nb, mb)  m-weighted (local indices)
    absdot: jnp.ndarray   # (nb, mb)  |cd1|.|cw1| threshold scale


def _encode_d_chunked(d2: jnp.ndarray, rb: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n, k = d2.shape
    nb = n // rb
    d32 = op_operand(d2).reshape(nb, rb, k)
    cd1 = jnp.sum(d32, axis=1)
    cd2 = jnp.einsum("brk,r->bk", d32, jnp.arange(rb, dtype=F32),
                     precision=PRECISION)
    return cd1, cd2


def _scalar_checksums(cd1, cd2, wck: WeightChecksums) -> _ChunkedChecksums:
    """c5/c6/c7 and the |.| threshold dot as ONE stacked (3nb,K)@(K,3mb)
    GEMM. The four dots share operands pairwise; stacking computes them in
    a single dispatch (the unused off-diagonal pairings roughly double the
    FLOPs of an O(K)-sized op - far cheaper than three extra XLA calls on
    the detect-only hot path)."""
    nb, mb = cd1.shape[0], wck.cw1.shape[0]
    cd1, cd2 = _replicate_small(cd1), _replicate_small(cd2)
    cw1, cw2 = _replicate_small(wck.cw1), _replicate_small(wck.cw2)
    lhs = jnp.concatenate([cd1, cd2, jnp.abs(cd1)], axis=0)
    rhs = jnp.concatenate([cw1, cw2, jnp.abs(cw1)], axis=0)
    out = _replicate_small(jnp.matmul(lhs, rhs.T, precision=PRECISION))
    c5 = out[:nb, :mb]
    c6 = out[nb:2 * nb, :mb]
    c7 = out[:nb, mb:2 * mb]
    absdot = out[2 * nb:, 2 * mb:]
    return _ChunkedChecksums(cd1, cd2, cw1, cw2, c5, c6, c7, absdot)


def _chunk_sums(o: jnp.ndarray, rb: int, cb: int):
    """Per-chunk s5/s6/s7 of O[N,M] as ONE constant-weight
    (nb*mb, rb*cb) @ (rb*cb, 3) GEMM, plus a fused per-chunk sumsq.

    Mirrors `checksums.detect_sums` on the conv path: each chunk's
    payload row is dotted with the constant [1; local-n; local-m]
    weightings in a single BLAS dispatch instead of four strided XLA
    einsum reductions (2-7x on CPU, where XLA reductions are not
    BLAS-grade; one MXU pass on TPU). Values differ from the einsum
    formulation only by fp32 reassociation at the ulp level, far inside
    the detection thresholds."""
    n, m = o.shape
    nb, mb = n // rb, m // cb
    x = (o.astype(F32).reshape(nb, rb, mb, cb).transpose(0, 2, 1, 3)
         .reshape(nb * mb, rb * cb))
    enc = jnp.stack([jnp.ones((rb * cb,), F32),
                     jnp.repeat(jnp.arange(rb, dtype=F32), cb),
                     jnp.tile(jnp.arange(cb, dtype=F32), rb)])
    s = _replicate_small(jnp.matmul(x, enc.T, precision=PRECISION))
    sumsq = _replicate_small(jnp.sum(x * x, axis=1))
    return (s[:, 0].reshape(nb, mb), s[:, 1].reshape(nb, mb),
            s[:, 2].reshape(nb, mb), sumsq.reshape(nb, mb))


class BiasAdjust(NamedTuple):
    """Checksum-side bias adjustments (paper Table 5, applied to C instead
    of S - algebraically identical, avoids touching the hot summations)."""
    b_chunk_sum: jnp.ndarray   # (mb,)   sum_c b per column chunk
    b_chunk_wsum: jnp.ndarray  # (mb,)   sum_c c*b per column chunk
    b_chunks: jnp.ndarray      # (mb, cb)


def _bias_adjust(bias: jnp.ndarray, cb: int) -> BiasAdjust:
    mb = bias.shape[0] // cb
    b = bias.astype(F32).reshape(mb, cb)
    return BiasAdjust(jnp.sum(b, axis=1),
                      jnp.matmul(b, jnp.arange(cb, dtype=F32),
                                 precision=PRECISION), b)


# --------------------------------------------------------------------------
# the protected matmul
# --------------------------------------------------------------------------

def protect_matmul_output(
    d2: jnp.ndarray,
    w: jnp.ndarray,
    o: jnp.ndarray,
    wck: Optional[WeightChecksums] = None,
    bias: Optional[jnp.ndarray] = None,
    cfg: T.ProtectConfig = T.DEFAULT_CONFIG,
    recompute_fn: Optional[Callable[[], jnp.ndarray]] = None,
    tamper_checksums: Optional[Callable] = None,
    precomputed_sums=None,
    mode: Optional[str] = None,
    detected=None,
) -> Tuple[jnp.ndarray, T.FaultReport]:
    """Run the multischeme workflow on an already-computed O = D @ W (+bias).

    `o` may have been produced by *any* implementation (XLA dot, the fused
    Pallas kernel, ...). `tamper_checksums` is a test hook that corrupts the
    checksum set after encoding (paper Fig. 3/5 scenarios).
    `precomputed_sums` threads the fused kernel's epilogue partials
    (s5, s6, s7, sumsq per chunk) so detection costs no extra pass over O;
    they are sums of the RAW product (pre-bias) and are compared against
    the unadjusted checksums (the bias adjustment cancels on both sides).

    `mode` selects the execution split of the deferred-correction story:
    None runs whatever `cfg` says (the per-layer default), "detect_only"
    stops after CoC-D and returns (o, DetectEvidence) - the ladder is not
    even traced - and "correct" forces the full ladder even under a
    detect_only config (what `correct_op` routes through). `detected`
    overrides the ladder's gate with an externally carried flag.
    """
    n, k = d2.shape
    m = w.shape[1]
    rb = pick_chunk(n, cfg.row_chunk)
    cb = wck.col_chunk if wck is not None else pick_chunk(m, cfg.col_chunk)
    nb, mb = n // rb, m // cb

    if recompute_fn is None:
        def recompute_fn():
            with jax.named_scope("op"):
                fresh = op_matmul(d2, w)
                if bias is not None:
                    fresh = fresh + bias.astype(F32)
                return fresh.astype(o.dtype)

    with jax.named_scope("encode"):
        if wck is None:
            wck = weight_checksums_matmul(w, cb)
        cd1, cd2 = _encode_d_chunked(d2, rb)
        cs = _scalar_checksums(cd1, cd2, wck)
        if tamper_checksums is not None:
            cs = tamper_checksums(cs)

    def _adjusted_scalars(cs):
        """c5/c6/c7 with the bias contribution added (Table 5)."""
        c5, c6, c7 = cs.c5, cs.c6, cs.c7
        if adj is not None:
            sum_n = rb * (rb - 1) / 2.0
            c5 = c5 + rb * adj.b_chunk_sum[None, :]
            c6 = c6 + sum_n * adj.b_chunk_sum[None, :]
            c7 = c7 + rb * adj.b_chunk_wsum[None, :]
        return c5, c6, c7

    with jax.named_scope("detect"):
        adj = _bias_adjust(bias, cb) if bias is not None else None
        if mode == "correct" and detected is not None:
            # the caller carries the CoC-D verdict (a DetectEvidence flag
            # from the detect-only pass): trust it and skip the O(|O|)
            # detection sums + compare entirely - the ladder re-derives
            # everything it verifies against, so nothing is lost, and the
            # deferred correction branch stays one detection pass per op
            # smaller
            detected = jnp.asarray(detected).astype(jnp.bool_).reshape(())
        else:
            if precomputed_sums is not None:
                # kernel partials are RAW-product sums (reduced before the
                # bias add), so compare them against the unadjusted
                # checksums: adding the analytic bias term to one side
                # only would false-flag every bias-carrying fused site,
                # and adding it to both sides cancels exactly
                s5, s6, s7, sumsq = precomputed_sums
                c5a, c6a, c7a = cs.c5, cs.c6, cs.c7
            else:
                s5, s6, s7, sumsq = _chunk_sums(o, rb, cb)
                c5a, c6a, c7a = _adjusted_scalars(cs)

            tau5 = TH.tau_scalar(sumsq, k, o.dtype, cfg.tau_factor,
                                 cs.absdot)
            flag, score = _detect_invariants(c5a, c6a, c7a, s5, s6, s7,
                                             tau5, rb, cb,
                                             cfg.detect_weighted)

            if mode == "detect_only":
                return o, T.DetectEvidence(flag.astype(jnp.int32), score)
            if cfg.detect_only and mode != "correct":
                det = flag.astype(jnp.int32)
                return o, T.FaultReport(det, jnp.zeros((), jnp.int32), det)
            detected = flag if detected is None else \
                jnp.asarray(detected).astype(jnp.bool_).reshape(())

    # ---------------- correction ladder (lax.cond branch) ----------------
    w32 = op_operand(w)
    d32 = op_operand(d2)

    def _chunk_view(o):
        # (nb, mb, rb, cb, P=1) chunk-major view for the vmapped schemes
        return (o.reshape(nb, rb, mb, cb).transpose(0, 2, 1, 3)
                [..., None])

    def _unchunk(oc):
        return oc[..., 0].transpose(0, 2, 1, 3).reshape(n, m)

    def _trusted():
        """The correction branch's checksum sets, derived once: the
        rungs' (detection-time) set with and without the row/column
        GEMVs, and freshly recomputed ones for verification."""
        cd1f, cd2f = _encode_d_chunked(d2, rb)
        csf = _scalar_checksums(cd1f, cd2f, wck)
        return {"fresh": csf,
                "verify": _chunk_cs_pytree(csf, need_rowcol=True),
                "scalar": _chunk_cs_pytree(cs, need_rowcol=False),
                "rowcol": _chunk_cs_pytree(cs, need_rowcol=True)}

    def _verify(o, trusted):
        csf, csp = trusted["fresh"], trusted["verify"]
        # one pass over O: the chunked view's sums carry the scalar
        # invariants too (unused s3/s4 are dead-code-eliminated by XLA)
        ssf = _chunk_ss(o)
        t5 = TH.tau_scalar(ssf.sumsq, k, o.dtype, cfg.tau_factor,
                           csf.absdot)
        return _verify_invariants(csp, ssf, t5[..., None],
                                  t5[..., None, None], rb, cb)

    def _rowcol_checksums(cs):
        """c1..c4 for the RC/ClC/FC rungs (the expensive GEMVs; only paid
        inside the correction branch)."""
        mm = partial(jnp.matmul, precision=PRECISION)
        c1 = mm(cs.cd1, w32).reshape(nb, 1, mb, cb).transpose(0, 2, 3, 1)
        c3 = mm(cs.cd2, w32).reshape(nb, 1, mb, cb).transpose(0, 2, 3, 1)
        # (nb, mb, rb, 1): D-chunk @ per-chunk weight checksums
        d3 = d32.reshape(nb, rb, k)
        c2 = jnp.einsum("brk,mk->bmr", d3, cs.cw1,
                        precision=PRECISION)[..., None]
        c4 = jnp.einsum("brk,mk->bmr", d3, cs.cw2,
                        precision=PRECISION)[..., None]
        if adj is not None:
            sum_n = rb * (rb - 1) / 2.0
            c1 = c1 + rb * adj.b_chunks[None, :, :, None]
            c3 = c3 + sum_n * adj.b_chunks[None, :, :, None]
            c2 = c2 + adj.b_chunk_sum[None, :, None, None]
            c4 = c4 + adj.b_chunk_wsum[None, :, None, None]
        return c1, c2, c3, c4

    def _chunk_cs_pytree(cs, need_rowcol: bool):
        c5a_, c6a_, c7a_ = _adjusted_scalars(cs)
        if need_rowcol:
            c1, c2, c3, c4 = _rowcol_checksums(cs)
        else:
            zc = jnp.zeros((nb, mb, cb, 1), F32)
            zr = jnp.zeros((nb, mb, rb, 1), F32)
            c1, c3 = zc, zc
            c2, c4 = zr, zr
        return T.OutputChecksums(c1, c2, c3, c4,
                                 c5a_[..., None], c6a_[..., None],
                                 c7a_[..., None])

    def _chunk_ss(o):
        oc = _chunk_view(o)                                   # (nb,mb,rb,cb,1)
        wn = jnp.arange(rb, dtype=F32)
        wm = jnp.arange(cb, dtype=F32)
        o32 = oc.astype(F32)
        s1 = jnp.sum(o32, axis=2)[..., 0][..., None]          # (nb,mb,cb,1)
        s2 = jnp.sum(o32, axis=3)[..., 0][..., None]          # (nb,mb,rb,1)
        ein = partial(jnp.einsum, precision=PRECISION)
        s3 = ein("abrcp,r->abcp", o32, wn)
        s4 = ein("abrcp,c->abrp", o32, wm)
        s5 = ein("abcp->abp", s1)
        s6 = ein("abrp,r->abp", s2, wn)
        s7 = ein("abcp,c->abp", s1, wm)
        sq = ein("abrcp,abrcp->ab", o32, o32)
        return T.OutputSums(s1, s2, s3, s4, s5, s6, s7, sq)

    vmap2 = lambda f: jax.vmap(jax.vmap(f))

    def _run_scheme(scheme_fn, o, tau_kind, trusted):
        oc = _chunk_view(o)
        cs_c = trusted["scalar" if tau_kind == "scalar" else "rowcol"]
        ss_c = _chunk_ss(o)
        t5 = TH.tau_scalar(ss_c.sumsq, k, o.dtype, cfg.tau_factor, cs.absdot)
        taus = _scheme_taus(tau_kind, t5[..., None], t5[..., None, None],
                            rb, cb)
        fixed, ok = vmap2(scheme_fn)(oc, cs_c, ss_c, *taus)
        return _unchunk(fixed), jnp.all(ok)

    rungs = _ladder_rungs(cfg, _run_scheme)
    return run_ladder(o, detected, rungs, _verify, recompute_fn, _trusted)


def protected_matmul(
    d: jnp.ndarray,
    w: jnp.ndarray,
    wck: Optional[WeightChecksums] = None,
    bias: Optional[jnp.ndarray] = None,
    cfg: T.ProtectConfig = T.DEFAULT_CONFIG,
    mode: Optional[str] = None,
    detected=None,
) -> Tuple[jnp.ndarray, T.FaultReport]:
    """O = D @ W (+ bias) with the full multischeme workflow.

    D may have arbitrary leading batch dims; they are flattened into the
    block-row axis (more rows = more checksum granularity, not less).
    `mode`/`detected` as in protect_matmul_output.
    """
    lead = d.shape[:-1]
    k = d.shape[-1]
    m = w.shape[-1]
    d2 = d.reshape(-1, k)
    if cfg is None or not cfg.enabled:
        with jax.named_scope("op"):
            o = _add_bias(op_output(op_matmul(d2, w), d.dtype), bias)
        return _clean_result(o.reshape(*lead, m), mode)

    pre = None
    if cfg.use_fused_kernel:
        from repro.kernels import ops as kops
        rb = pick_chunk(d2.shape[0], cfg.row_chunk)
        cb = wck.col_chunk if wck is not None else pick_chunk(m, cfg.col_chunk)
        if mode == "detect_only" and bias is None:
            # the single-launch detect path: chunk granularity == kernel
            # tile, the threshold compare runs inside the GEMM epilogue,
            # and the launch returns (raw O, one flag/score per tile) -
            # the only work outside the kernel is the O(K)-sized checksum
            # encode and two scalar max-reduces over the (nb, mb) tile
            # verdicts. Bias-carrying sites keep the partials route: the
            # kernel accumulates the raw product, and comparing raw-vs-raw
            # is only the same decision when no bias adjustment applies.
            # (sumsq - and so tau - also excludes the bias energy here; at
            # detection scale that undershoots the threshold by the bias'
            # share of the output energy, a no-op for bias-free sites.)
            with jax.named_scope("encode"):
                wck_d = wck if wck is not None \
                    else weight_checksums_matmul(w, cb)
                cd1, cd2 = _encode_d_chunked(d2, rb)
                cs = _scalar_checksums(cd1, cd2, wck_d)
            tau_a, tau_b = TH.tau_scalar_coeffs(k, d.dtype, cfg.tau_factor)
            # the kernel's epilogue compare is timed with the op
            with jax.named_scope("op"):
                res = kops.abft_matmul_detect(
                    d2, w, cs.c5, cs.c6, cs.c7, cs.absdot, rb=rb, cb=cb,
                    bk=(cfg.kernel_tiles or (0, 0, 512))[2], tau_a=tau_a,
                    tau_b=tau_b, weighted=cfg.detect_weighted,
                    interpret=cfg.resolve_interpret())
            if res is not None:
                o, flag, score = res
                with jax.named_scope("detect"):
                    ev = T.DetectEvidence(jnp.max(flag), jnp.max(score))
                return o.reshape(*lead, m), ev
        # plan-pinned tile targets when profiled, else the kernel's
        # defaults; a tile that does not divide the checksum chunks
        # recombines from O instead (ops.chunk_sums_from_partials)
        bm, bn, bk = cfg.kernel_tiles or (256, 256, 512)
        with jax.named_scope("op"):
            o, parts = kops.abft_matmul(
                d2, w, interpret=cfg.resolve_interpret(), bm=bm, bn=bn,
                bk=bk)
        with jax.named_scope("detect"):
            pre = kops.chunk_sums_from_partials(parts, rb, cb, o=o)
    else:
        with jax.named_scope("op"):
            o = op_output(op_matmul(d2, w), d.dtype)
    with jax.named_scope("op"):
        o = _add_bias(o, bias)
    o, rep = protect_matmul_output(d2, w, o, wck=wck, bias=bias, cfg=cfg,
                                   precomputed_sums=pre, mode=mode,
                                   detected=detected)
    return o.reshape(*lead, m), rep


# --------------------------------------------------------------------------
# backward protection (paper SS5.3)
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2,))
def abft_matmul_vjp(d, w, cfg):
    o, _ = protected_matmul(d, w, cfg=cfg)
    return o


def _fwd(d, w, cfg):
    o, _ = protected_matmul(d, w, cfg=cfg)
    return o, (d, w)


def _bwd(cfg, res, g):
    """dW = D^T @ dO and dD = dO @ W^T, each protected with checksums of the
    runtime operands (the paper's back-propagation extension: checksums of
    grad-O play the role of the kernel checksums)."""
    d, w = res
    lead = d.shape[:-1]
    k = d.shape[-1]
    d2 = d.reshape(-1, k)
    g2 = g.reshape(-1, g.shape[-1])
    if cfg.protect_backward:
        dd2, _ = protected_matmul(g2, w.T.astype(g2.dtype), cfg=cfg)
        dw, _ = protected_matmul(d2.T, g2.astype(d2.dtype), cfg=cfg)
    else:
        dd2 = op_matmul(g2, w.T.astype(g2.dtype))
        dw = op_matmul(d2.T, g2.astype(d2.dtype))
    return dd2.reshape(*lead, k).astype(d.dtype), dw.astype(w.dtype)


abft_matmul_vjp.defvjp(_fwd, _bwd)


# --------------------------------------------------------------------------
# the protected convolution (the paper's native object)
# --------------------------------------------------------------------------

def protected_conv(
    d: jnp.ndarray,
    w: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    stride: int = 1,
    padding="VALID",
    groups: int = 1,
    wck: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    cfg: T.ProtectConfig = T.DEFAULT_CONFIG,
    o: Optional[jnp.ndarray] = None,
    tamper_checksums: Optional[Callable] = None,
    mode: Optional[str] = None,
    detected=None,
) -> Tuple[jnp.ndarray, T.FaultReport]:
    """Protected conv (paper Eq. 1): D[N,Ch,H,H] (x) W[M,Ch,R,R] + bias.

    `o` lets tests inject into a precomputed output and must be the
    *complete* output (bias already included, matching
    protect_matmul_output's convention - adding bias here again would
    shift every element and turn any injection into a whole-tensor
    fault); `wck` carries the precomputed (C_w1, C_w2).
    `mode`/`detected` as in protect_matmul_output.
    """
    def recompute_fn():
        """The op and its bias add."""
        with jax.named_scope("op"):
            out = C.conv2d(d, w, stride=stride, padding=padding,
                           groups=groups)
            if bias is not None:
                out = (out.astype(F32) + bias[None, :, None, None]
                       .astype(F32)).astype(out.dtype)
            return out

    if o is None:
        o = recompute_fn()
    if cfg is None or not cfg.enabled:
        return _clean_result(o, mode)

    n_, m_ = o.shape[0], o.shape[1]
    p = o.shape[2] * o.shape[3]
    k_eq = d.shape[1] * w.shape[2] * w.shape[3]  # Ch*R*R contraction length

    with jax.named_scope("encode"):
        cd1, cd2 = C.encode_d_conv(d)
        if wck is None:
            wck = C.encode_w_conv(w, groups=groups)
    cw1, cw2 = wck

    def _bias_adjusted(cs):
        """Checksum-side bias additions (paper Table 5), the single place
        both detection (_cs) and verification apply them."""
        if bias is None:
            return cs
        b = bias.astype(F32)
        sum_n = n_ * (n_ - 1) / 2.0
        wm = jnp.arange(m_, dtype=F32)
        return T.OutputChecksums(
            None if cs.c1 is None else cs.c1 + n_ * b[:, None],
            None if cs.c2 is None else cs.c2 + jnp.sum(b),
            None if cs.c3 is None else cs.c3 + sum_n * b[:, None],
            None if cs.c4 is None
            else cs.c4 + jnp.dot(wm, b, precision=PRECISION),
            cs.c5 + n_ * jnp.sum(b),
            cs.c6 + sum_n * jnp.sum(b),
            cs.c7 + n_ * jnp.dot(wm, b, precision=PRECISION),
        )

    def _cs(need_rowcol):
        cs = C.output_checksums_conv(d, w, cd1, cd2, cw1, cw2, stride=stride,
                                     padding=padding, groups=groups,
                                     need_rowcol=need_rowcol)
        if tamper_checksums is not None:
            cs = tamper_checksums(cs)
        return _bias_adjusted(cs)

    # ---------------- CoC-D detection: the error-free hot path ------------
    # One fused checksum conv (c5/c6/c7 + the |.| threshold conv) and one
    # fused summation pass over O (s5/s6/s7/sumsq). Everything with full
    # row/column resolution - s1-s4, the c1-c4 checksum convs - lives
    # strictly inside the lax.cond correction branch below, so the
    # error-free cost is the conv itself plus O(|O|) fused work.
    # the stacked checksum conv is checksum-sized (cheap) and its absdot
    # output scales every ladder threshold, so it runs in correct mode too
    with jax.named_scope("detect"):
        c5d, c6d, c7d, absd = C.detect_checksums_conv(
            cd1, cd2, cw1, cw2, stride=stride, padding=padding)
        if mode == "correct" and detected is not None:
            # trust the carried CoC-D flag (deferred workflow): skip the
            # O(|O|) detection sums + compare - the ladder re-derives its own
            # sums, so the correction branch drops one full pass over O
            detected = jnp.asarray(detected).astype(jnp.bool_).reshape(())
        else:
            cs0 = T.OutputChecksums(None, None, None, None, c5d, c6d, c7d)
            if tamper_checksums is not None:
                cs0 = tamper_checksums(cs0)
            cs0 = _bias_adjusted(cs0)
            # kernel_tiles carries GEMM-space (bm, bn, bk) tiles - a different
            # tile space from the flattened-view reduction's (M-axis, payload)
            # tiles - so the conv route always derives its own from the shape
            s5, s6, s7, sumsq = C.detect_sums(
                o, use_kernel=cfg.use_fused_kernel,
                interpret=cfg.resolve_interpret())
            tau5 = TH.tau_scalar(sumsq * jnp.ones(()), k_eq, o.dtype,
                                 cfg.tau_factor, absd)
            tau5v = jnp.broadcast_to(tau5, (p,))
            flag, score = _detect_invariants(cs0.c5, cs0.c6, cs0.c7,
                                             s5, s6, s7, tau5v, n_, m_,
                                             cfg.detect_weighted)

            if mode == "detect_only":
                # the deferred-correction carry: raw output + compact
                # evidence, the ladder is not even traced
                return o, T.DetectEvidence(flag.astype(jnp.int32), score)
            if cfg.detect_only and mode != "correct":
                # CoC-D serving mode (same contract as the matmul path):
                # surface the verdict, let the caller recompute; the
                # correction ladder never enters the compiled program.
                det = flag.astype(jnp.int32)
                return o, T.FaultReport(det, jnp.zeros((), jnp.int32), det)
            detected = flag if detected is None else \
                jnp.asarray(detected).astype(jnp.bool_).reshape(())

    def _norm(o):
        return o.reshape(n_, m_, p)

    def _denorm(o3):
        return o3.reshape(o.shape)

    def _trusted():
        """The correction branch's checksum sets, derived once (the c1-c4
        checksum convs are the bulk of every rung): the rungs' set, and
        for verification the same set - or, when the detection-path set
        was tampered with (test hook), a clean re-encode."""
        cs = _cs(need_rowcol=True)
        if tamper_checksums is None:
            return cs, cs
        return cs, _bias_adjusted(C.output_checksums_conv(
            d, w, *C.encode_d_conv(d), *C.encode_w_conv(w, groups=groups),
            stride=stride, padding=padding, groups=groups,
            need_rowcol=True))

    def _verify(oo, trusted):
        ssv = C.output_sums_conv(oo)
        csf = trusted[1]
        t5 = TH.tau_scalar(ssv.sumsq * jnp.ones(()), k_eq, oo.dtype,
                           cfg.tau_factor, absd)
        t5 = jnp.broadcast_to(t5, (p,))
        return _verify_invariants(csf, ssv, t5, t5[None, :], n_, m_)

    def _run_scheme(fn, oo, tau_kind, trusted):
        o3 = _norm(oo)
        cs = trusted[0]
        ss = C.output_sums_conv(oo)
        t5 = TH.tau_scalar(ss.sumsq * jnp.ones(()), k_eq, oo.dtype,
                           cfg.tau_factor, absd)
        t5v = jnp.broadcast_to(t5, (p,))
        taus = _scheme_taus(tau_kind, t5v, t5v[None, :], n_, m_)
        fixed, ok = fn(o3, cs, ss, *taus)
        return _denorm(fixed), ok

    rungs = _ladder_rungs(cfg, _run_scheme)
    return run_ladder(o, detected, rungs, _verify, recompute_fn, _trusted)


# --------------------------------------------------------------------------
# grouped / expert-batched GEMM (paper SS5.2 applied to MoE)
# --------------------------------------------------------------------------

def protected_grouped_matmul(
    d: jnp.ndarray,   # (G, N, K) per-group inputs
    w: jnp.ndarray,   # (G, K, M) per-group weights (experts)
    wck: Optional[WeightChecksums] = None,   # stacked: leading G axis
    cfg: T.ProtectConfig = T.DEFAULT_CONFIG,
    mode: Optional[str] = None,
) -> Tuple[jnp.ndarray, T.FaultReport]:
    """Expert-batched protected GEMM: each group carries its own checksums
    (the grouped-convolution extension: groups never mix, so per-group
    invariants are exact). `wck` carries the plan's offline per-expert
    checksums with a leading group axis (stacked_weight_checksums_matmul);
    without it each group re-encodes from its runtime weight slice. In
    detect-only mode the evidence carry is the max over groups (any
    flagged expert flags the op)."""
    if cfg is None or not cfg.enabled:
        dt = op_operand_dtype(d.dtype)
        with jax.named_scope("op"):
            o = jnp.einsum("gnk,gkm->gnm", d.astype(dt), w.astype(dt),
                           precision=PRECISION,
                           preferred_element_type=F32).astype(d.dtype)
        return _clean_result(o, mode)

    if wck is not None and wck.cw1.shape[0] == w.shape[0]:
        def one_ck(dg, wg, c1, c2):
            return protected_matmul(
                dg, wg, wck=WeightChecksums(c1, c2, wck.col_chunk),
                cfg=cfg, mode=mode)

        o, reps = jax.vmap(one_ck)(d, w, wck.cw1, wck.cw2)
    else:
        def one(dg, wg):
            return protected_matmul(dg, wg, cfg=cfg, mode=mode)

        o, reps = jax.vmap(one)(d, w)
    if mode == "detect_only":
        with jax.named_scope("detect"):
            return o, T.DetectEvidence(jnp.max(reps.flag),
                                       jnp.max(reps.score))
    rep = T.FaultReport(jnp.max(reps.detected), jnp.max(reps.corrected_by),
                        jnp.max(reps.residual))
    return o, rep
