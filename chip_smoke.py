#!/usr/bin/env python3
"""Chip smoke test: the paper's main path on a TPU, end to end.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # batch-parallel protected inference
                                      # on four chips vs one, nothing else

One chip, in one process and in order:

  device   JAX's first device must be a TPU (no CPU fallback);
  plan     resnet18 (width 1.0, 224x224, batch 8, f32, weights from
           --seed) through build_plan with a cost model measured on this
           chip and profiled kernels;
  clean    protected forward, per-layer and deferred correction: logits
           bitwise equal to the unprotected forward, zero detections;
  faults   the examples/ft_cnn_inference.py injection protocol on the
           first, middle and last conv, both modes, FAULT_SEEDS
           injections each: each fault detected, attributed to its layer
           and corrected, and every uncorrected fault (the unprotected
           forward's logits) off by more than the corrected-logit limit;
  kernels  abft_matmul, abft_matmul_detect and conv_detect_sums compiled
           (interpret=False) against their kernels/ref.py oracles, and
           every pinned site's tpu_custom_call in the compiled forward;
  serving  ServingDriver on smollm-360m at its published widths: 4 slots,
           32-token prompts, 8 new tokens, token-identical to
           greedy_reference with zero faults detected.

Progress goes to stdout; the last line is one JSON object
{"ok": true, "device": {"platform", "kind", "count"}}, printed only when
every check passed. A failed check is logged and the run goes on, so one
run reports every failure; any failure exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
MODES = ("per_layer", "deferred")
BATCH, FOUR_CHIP_BATCH = 8, 32
# corrected logits vs clean, relative to max|clean logit|. It sits
# between the two readings the fault phase prints for every injection:
# a corrected fault's logit error (on a v5e, seeds 0-3: 0 for most, at
# most 1.4e-3 - where the op multiplies bf16 operands, a correction's
# f32 residue can move a downstream operand across a bf16 rounding
# boundary) and the same fault's left uncorrected (at least 9.7e-3).
# The phase fails should an uncorrected fault fall within it
LOGIT_RTOL = 2e-3
FAULT_SEEDS = 4     # injections per fault layer and mode
# compile threads: the smoke's five resnet18 programs compile in about
# 60% of the one-thread time on three, at ~13 GiB of host memory (a
# one-chip host has 40 GiB)
COMPILE_WORKERS = 3


FAILURES = []       # failed checks, "phase: what"
_PHASE = ["setup"]


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        FAILURES.append(f"{_PHASE[0]}: {msg}")
        log(f"FAILED: {msg}")


class Phase:
    """Times one phase and names its failed checks."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.failed = len(FAILURES)
        _PHASE[0] = self.name
        log(f"== {self.name}")
        return self

    def __exit__(self, kind, exc, tb):
        dt = time.perf_counter() - self.t0
        verdict = "ok" if len(FAILURES) == self.failed else "FAILED"
        if exc is None:
            log(f"== {self.name}: {verdict} ({dt:.1f} s)")
        return False


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_device(jax) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"JAX found no TPU (first device: {d0.platform})")
    log(f"device_kind={d0.device_kind} count={len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def resnet_inputs(jax, batch: int, seed: int, scale: float = 1.0,
                  img: int = 224):
    from repro.models import cnn
    cfg = dataclasses.replace(cnn.resnet18(scale), img=img)
    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    params = cnn.init_cnn(kp, cfg)
    x = jax.random.normal(kx, (batch, cfg.in_ch, cfg.img, cfg.img))
    return cfg, params, x


def phase_plan(params, cfg, batch: int):
    from repro.core import MeasuredCostModel, build_plan
    cm = MeasuredCostModel.from_host(refresh=True)
    log(f"peaks: {cm.peak_flops:.4g} FLOP/s, {cm.hbm_bw:.4g} B/s, "
        f"ridge {cm.ridge:.4g}, source={cm.source}")
    check(cm.source == "measured", f"peaks came from {cm.source!r}")
    plan = build_plan(params, cfg, batch=batch, cost_model=cm,
                      profile_kernels=True)
    pinned = sorted(p for p, e in plan.entries.items()
                    if e.cfg.use_fused_kernel)
    log(f"plan: {len(plan.entries)} sites, {len(pinned)} pinned to a "
        f"fused kernel: {pinned}")
    return plan, pinned


def forward_fn(jax, cfg, plan, mode):
    from repro.models import cnn
    return jax.jit(partial(cnn.forward_cnn, cfg=cfg, plan=plan,
                           correction=mode))


def unprotected_fn(jax, cfg):
    from repro.models import cnn
    off = dataclasses.replace(cfg, abft=False)
    return jax.jit(lambda p, x, **hook: cnn.forward_cnn(p, x, off,
                                                         **hook)[0])


def compile_all(lowered: dict, workers: int = COMPILE_WORKERS) -> dict:
    """Compile lowered programs concurrently: XLA compiles outside the
    GIL, and the protected resnet18 programs (a correction ladder per
    site) take minutes each to compile."""
    t0 = time.perf_counter()

    def one(key):
        t = time.perf_counter()
        exe = lowered[key].compile()
        log(f"  compiled {key} in {time.perf_counter() - t:.1f} s")
        return exe

    with ThreadPoolExecutor(max_workers=workers) as ex:
        futs = {k: ex.submit(one, k) for k in lowered}
        out = {k: f.result() for k, f in futs.items()}
    log(f"compiled {len(out)} programs in "
        f"{time.perf_counter() - t0:.1f} s ({workers} threads)")
    return out


def detections(rep) -> dict:
    return {k: int(r.detected) for k, r in rep.by_layer.items()}


def fault_layers(cfg):
    last = len(cfg.convs) - 1
    return (0, last // 2, last)


def injected(jax, params, x, cfg, layer: int, seed: int, row=None):
    """The example's protocol: corrupt one block row/column of layer
    `layer`'s clean conv output (`row` pins a block row)."""
    from repro.core import injection as inj
    from repro.models import cnn
    _, o_clean = cnn.conv_output_at(params, x, cfg, layer)
    n, m = o_clean.shape[:2]
    p = inj.plan(jax.random.PRNGKey(seed + layer + 100), n, m,
                 max_elems=100, axis=None if row is None else 0)
    if row is not None:
        p = p._replace(index=jax.numpy.int32(row))
    return inj.inject_conv(o_clean, p)


def fault_hook(jax, layer: int, outs: dict) -> dict:
    """forward_cnn's injection arguments: `outs` maps each fault layer
    to its corrupted output, and `layer` is picked at run time."""
    return {"inject_layer": jax.numpy.int32(layer), "inject_o": outs}


def fault_outputs(jax, params, x, cfg, seed: int) -> dict:
    return {i: injected(jax, params, x, cfg, i, seed)
            for i in fault_layers(cfg)}


def phase_compile(jax, params, x, cfg, plan, seed: int) -> dict:
    """Lower and compile every resnet18 program of the clean and fault
    phases. One fault program per forward serves all fault layers and
    seeds: the injection hook picks the layer at run time."""
    hook = fault_hook(jax, 0, fault_outputs(jax, params, x, cfg, seed))
    plain = unprotected_fn(jax, cfg)
    lowered = {}
    for mode in reversed(MODES):          # the largest programs first
        fwd = forward_fn(jax, cfg, plan, mode)
        lowered[mode, "fault"] = fwd.lower(params, x, **hook)
        lowered[mode] = fwd.lower(params, x)
    lowered["unprotected"] = plain.lower(params, x)
    lowered["unprotected", "fault"] = plain.lower(params, x, **hook)
    return compile_all(lowered)


def phase_clean(np, params, x, progs) -> np.ndarray:
    ref = np.asarray(progs["unprotected"](params, x))
    for mode in MODES:
        logits, rep = progs[mode](params, x)
        logits = np.asarray(logits)
        flagged = {k: v for k, v in detections(rep).items() if v}
        log(f"{mode}: bitwise={np.array_equal(logits, ref)} "
            f"max|diff|={np.max(np.abs(logits - ref)):.3g} "
            f"detections={flagged}")
        check(np.array_equal(logits, ref),
              f"{mode} logits differ from the unprotected forward")
        check(not flagged, f"{mode}: clean traffic flagged {flagged}")
    return ref


def logit_tol(np, clean) -> float:
    """The corrected-logit limit: LOGIT_RTOL x max|clean logit|."""
    return LOGIT_RTOL * float(np.max(np.abs(clean)))


def check_corrected(np, rep, logits, clean, layer: int, what: str) -> float:
    """Checks one fault run (detected, attributed, corrected within
    logit_tol); returns the corrected logits' max error."""
    from repro.core import SCHEME_NAMES
    r = rep.by_layer[f"conv{layer}"]
    scheme = SCHEME_NAMES[int(r.corrected_by)]
    others = {k: v for k, v in detections(rep).items()
              if v and k != f"conv{layer}"}
    err = float(np.max(np.abs(np.asarray(logits) - clean)))
    tol = logit_tol(np, clean)
    log(f"{what} conv{layer}: detected={int(r.detected)} "
        f"corrected_by={scheme} residual={int(rep.residual)} "
        f"max|logit err|={err:.3g} (tol {tol:.3g})")
    check(int(r.detected) == 1, f"{what}: conv{layer} fault not detected")
    check(not others, f"{what}: conv{layer} fault attributed to {others}")
    check(int(rep.residual) == 0 and scheme != "none",
          f"{what}: conv{layer} fault left uncorrected ({scheme})")
    check(err <= tol, f"{what}: corrected logits off by {err:.3g}")
    return err


def phase_faults(jax, np, params, x, cfg, progs, clean, seed: int):
    """Each fault layer x FAULT_SEEDS injections x both modes; per layer,
    the largest corrected-logit error and the smallest error of the same
    faults left uncorrected, which the limit must separate."""
    tol = logit_tol(np, clean)
    layers = fault_layers(cfg)
    fixed = {layer: [] for layer in layers}
    raw = {layer: [] for layer in layers}
    for s in range(seed, seed + FAULT_SEEDS):
        outs = fault_outputs(jax, params, x, cfg, s)
        for layer in layers:
            hook = fault_hook(jax, layer, outs)
            bad = np.asarray(progs["unprotected", "fault"](params, x,
                                                           **hook))
            raw[layer].append(float(np.max(np.abs(bad - clean))))
            for mode in MODES:
                logits, rep = progs[mode, "fault"](params, x, **hook)
                fixed[layer].append(check_corrected(
                    np, rep, logits, clean, layer, f"{mode} seed {s}"))
    for layer in layers:
        worst, least = max(fixed[layer]), min(raw[layer])
        log(f"conv{layer}: corrected max|logit err| <= {worst:.6g}, "
            f"uncorrected >= {least:.6g}, limit {tol:.6g}")
        check(least > tol, f"conv{layer}: an uncorrected fault "
                           f"({least:.3g}) passes the limit")


def phase_kernels(jax, np, progs, pinned, seed: int):
    import jax.numpy as jnp

    from repro.core import thresholds as TH
    from repro.kernels import ops, ref
    key = jax.random.PRNGKey(seed + 7)
    kd, kw, ko = jax.random.split(key, 3)

    def close(name, got, want, rtol=1e-5, atol_scale=1e-5):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        atol = atol_scale * (float(np.max(np.abs(want))) + 1.0)
        err = float(np.max(np.abs(got - want)))
        log(f"  {name}: max|err|={err:.3g} (atol {atol:.3g})")
        check(np.allclose(got, want, rtol=rtol, atol=atol),
              f"{name} differs from its oracle")

    # a 512x960x2560 GEMM (smollm-360m's ffn up-projection at 512 rows)
    n, k, m = 512, 960, 2560
    d = jax.random.normal(kd, (n, k))
    w = jax.random.normal(kw, (k, m)) * k ** -0.5
    o, parts = ops.abft_matmul(d, w, interpret=False)
    o_r, sums_r = ref.abft_matmul_ref(d, w, parts.bm)
    close("abft_matmul O", o, o_r)
    close("abft_matmul partials", parts.sums, sums_r, atol_scale=1e-4)

    rb, cb = 256, 256
    cs = ref.chunk_checksums_ref(d, w, rb, cb)
    tau_a, tau_b = TH.tau_scalar_coeffs(k, d.dtype, 32.0)
    for tamper in (False, True):
        c5 = cs[0].at[1, 3].add(1e3) if tamper else cs[0]
        got = ops.abft_matmul_detect(d, w, c5, *cs[1:], rb=rb, cb=cb,
                                     tau_a=tau_a, tau_b=tau_b,
                                     interpret=False)
        check(got is not None, "abft_matmul_detect refused its tiles")
        if got is None:
            return
        want = ref.abft_matmul_detect_ref(d, w, c5, *cs[1:], rb, cb,
                                          tau_a, tau_b)
        what = "tampered" if tamper else "clean"
        close(f"abft_matmul_detect O ({what})", got[0], want[0])
        check(np.array_equal(np.asarray(got[1]), np.asarray(want[1])),
              f"abft_matmul_detect flags ({what}) differ from the oracle")
        check(int(np.sum(np.asarray(got[1]))) == int(tamper),
              f"abft_matmul_detect ({what}) flagged "
              f"{int(np.sum(np.asarray(got[1])))} tiles")
    log(f"  abft_matmul_detect flags: clean 0, tampered 1 (tile (1, 3))")

    # resnet18's first stage conv output at batch 8
    o4 = jax.random.normal(ko, (BATCH, 64, 56, 56), jnp.float32)
    got = ops.conv_detect_sums(o4, interpret=False)
    for name, a, b in zip(("s5", "s6", "s7", "sumsq"), got,
                          ref.conv_detect_sums_ref(o4)):
        close(f"conv_detect_sums {name}", a, b, atol_scale=1e-4)

    # every pinned site launches its kernel in the compiled forward
    for mode in MODES:
        calls = [ln for ln in progs[mode].as_text().splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        missing = [p for p in pinned
                   if not any(f"/{p}/" in ln for ln in calls)]
        log(f"  {mode}: {len(calls)} tpu_custom_call, pinned sites "
            f"without one: {missing}")
        check(not missing, f"{mode}: pinned sites without a kernel "
                           f"launch: {missing}")


def phase_serving(jax, np, seed: int, arch: str = "smollm-360m"):
    import repro.configs as C
    from repro.launch.serve import serve, serving_inputs
    from repro.serving import greedy_reference
    slots, plen, gen = 4, 32, 8
    toks, stats = serve(arch, batch=slots, prompt_len=plen, gen=gen,
                        seed=seed)
    rep = stats["report"]
    log(f"serving: completed={rep['completed']} "
        f"faults_detected={stats['faults_detected']} "
        f"prefill_detected={stats['prefill_detected']} "
        f"ttft_p50={rep['ttft_p50_s']} s")
    check(rep["completed"] == slots, f"{rep['completed']}/{slots} served")
    check(stats["faults_detected"] == 0 and stats["prefill_detected"] == 0,
          "clean traffic flagged")
    cfg = C.get(arch)
    params, prompts = serving_inputs(cfg, slots, plen, seed)
    ucfg = cfg.replace(abft=False)
    same = 0
    for i in range(slots):
        want = greedy_reference(params, ucfg, prompts[i], gen, plen + gen)
        got = [int(t) for t in toks[i]]
        same += got == want
        check(got == want, f"request {i}: served {got}, "
                           f"greedy_reference {want}")
    log(f"serving: {same} of {slots} requests token-identical to "
        f"greedy_reference")


def phase_four_chips(jax, np, seed: int) -> None:
    """resnet18 at batch 32 over a 1-D ('data',) mesh of four chips,
    deferred correction, against the unprotected forward of the same
    inputs on one chip (the one-chip phases hold the protected forward
    bitwise equal to it), run at batch 32 and at each chip's batch."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import build_plan
    from repro.runtime.sharding import make_mesh
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    if len(devs) < 4:
        return
    cfg, params, x = resnet_inputs(jax, FOUR_CHIP_BATCH, seed)
    per_chip = FOUR_CHIP_BATCH // 4
    # analytic plan, no kernel pinning: a Pallas call is opaque to the
    # SPMD partitioner, which would gather its sharded operands
    plan = build_plan(params, cfg, batch=FOUR_CHIP_BATCH)
    mesh = make_mesh((4,), ("data",), devices=devs[:4])
    shard = NamedSharding(mesh, P("data"))
    ps = jax.device_put(params, NamedSharding(mesh, P()))
    xs = jax.device_put(x, shard)
    layer = len(cfg.convs) // 2
    row = 2 * per_chip + 1                      # a sample on device 2
    o_bad = jax.device_put(injected(jax, params, x, cfg, layer, seed,
                                    row=row), shard)
    fault = fault_hook(jax, layer, {layer: o_bad})
    fwd = forward_fn(jax, cfg, plan, "deferred")
    plain = unprotected_fn(jax, cfg)
    lowered = {"one": plain.lower(params, x),
               "one/8": plain.lower(params, x[:per_chip])}
    # one sharded program serves the clean run too: the injection hook
    # picks no layer when inject_layer matches none (chip time is four
    # times dearer here)
    with jax.set_mesh(mesh):
        lowered["four"] = fwd.lower(ps, xs, **fault)
    progs = compile_all(lowered)

    one = np.asarray(progs["one"](params, x))
    # the same inputs on one chip, batch by the sharded program's
    # per-chip batch: separates a sharding effect from a batch-size one
    one8 = np.concatenate([
        np.asarray(progs["one/8"](params, x[i:i + per_chip]))
        for i in range(0, FOUR_CHIP_BATCH, per_chip)])
    with jax.set_mesh(mesh):
        four, rep4 = progs["four"](ps, xs, **{**fault, "inject_layer":
                                              jax.numpy.int32(-1)})
        logits, rep = progs["four"](ps, xs, **fault)
    four = np.asarray(four)
    flagged = {k: v for k, v in detections(rep4).items() if v}
    log(f"4 chips vs 1 chip at batch {FOUR_CHIP_BATCH}: "
        f"bitwise={np.array_equal(four, one)} "
        f"max|diff|={np.max(np.abs(four - one)):.3g}; vs 1 chip at batch "
        f"{per_chip}: bitwise={np.array_equal(four, one8)} "
        f"max|diff|={np.max(np.abs(four - one8)):.3g}; "
        f"detections={flagged}")
    # where batch 32 differs, this line tells a batch-size effect (one
    # chip alone, no sharding) from a sharding one
    log(f"1 chip, batch {FOUR_CHIP_BATCH} vs batch {per_chip}: "
        f"bitwise={np.array_equal(one, one8)} "
        f"max|diff|={np.max(np.abs(one - one8)):.3g}")
    check(np.array_equal(four, one), "sharded logits differ from one chip")
    check(not flagged, f"clean traffic flagged {flagged}")
    check_corrected(np, rep, logits, one, layer,
                    f"4 chips, sample {row} (device 2)")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    import numpy as np
    t0 = time.perf_counter()
    with Phase("device"):
        device = phase_device(jax)
        log(f"compile cache: {cache}")
    if FAILURES:
        print(f"chip_smoke FAILED: {FAILURES}", file=sys.stderr)
        return 1
    if args.chips == 4:
        with Phase("four chips"):
            phase_four_chips(jax, np, args.seed)
    else:
        cfg, params, x = resnet_inputs(jax, BATCH, args.seed)
        with Phase("plan"):
            plan, pinned = phase_plan(params, cfg, BATCH)
        with Phase("compile"):
            progs = phase_compile(jax, params, x, cfg, plan, args.seed)
        with Phase("clean"):
            clean = phase_clean(np, params, x, progs)
        with Phase("faults"):
            phase_faults(jax, np, params, x, cfg, progs, clean, args.seed)
        with Phase("kernels"):
            phase_kernels(jax, np, progs, pinned, args.seed)
        del progs           # release the executables before serving
        with Phase("serving"):
            phase_serving(jax, np, args.seed)
    elapsed = time.perf_counter() - t0
    if FAILURES:
        log(f"{len(FAILURES)} check(s) failed in {elapsed:.1f} s")
        print("chip_smoke FAILED:\n  " + "\n  ".join(FAILURES),
              file=sys.stderr)
        return 1
    log(f"all phases passed in {elapsed:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
