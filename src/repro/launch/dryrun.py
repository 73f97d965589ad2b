import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input shape) cell on the production meshes and extract the
roofline terms from the compiled artifact.

No arrays are materialised: parameters, optimizer state, caches and batch
all enter jit.lower() as ShapeDtypeStructs with NamedShardings attached.
Compile success proves the distribution config is coherent (sharding
propagation, collective legality); memory_analysis() gives bytes/device;
cost_analysis() + HLO collective parsing feed SSRoofline.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  python -m repro.launch.dryrun --all                 # every cell, 1 pod
  python -m repro.launch.dryrun --all --multi-pod     # every cell, 2 pods
"""
import argparse
import functools
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as C
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (init_train_state, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.models.transformer import init_params
from repro.optim import OptConfig
from repro.runtime import sharding as SH

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")

# per-arch training knobs: optimizer flavour and microbatch count (memory)
TRAIN_OVERRIDES: Dict[str, Dict[str, Any]] = {
    "kimi-k2-1t-a32b": dict(opt="adafactor", micro=16, state_dtype="bfloat16"),
    "llama4-maverick-400b-a17b": dict(opt="adafactor", micro=8,
                                      state_dtype="bfloat16"),
    "chameleon-34b": dict(micro=8),
    "gemma2-9b": dict(micro=4),
    "yi-9b": dict(micro=4),
    "h2o-danube-3-4b": dict(micro=4),
    "musicgen-large": dict(micro=2),
    "mamba2-1.3b": dict(micro=4),
    "recurrentgemma-2b": dict(micro=4),
    "smollm-360m": dict(micro=4),
}

_COLL_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\b")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3": 1,
                "f8e5m2": 1, "s16": 2, "u16": 2}


def _bytes_of(hlo_line: str) -> int:
    """Sum output-operand bytes on an HLO instruction line (LHS shapes)."""
    lhs = hlo_line.split("=", 1)
    target = lhs[1] if len(lhs) > 1 else hlo_line
    # first shape(s) after '=' are the op result (tuple or single)
    total = 0
    for dt, dims in _SHAPE_RE.findall(target.split("(", 1)[0]):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo: str) -> Dict[str, Any]:
    """Per-kind collective bytes from optimised HLO, with while-loop trip
    multipliers: a collective inside a loop body counts trip-count times.
    Trip counts are estimated from the loop condition's comparison
    constant (the jax.lax.scan lowering)."""
    computations: Dict[str, str] = {}
    cur_name, cur_lines = None, []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s*\([^)]*\)\s*->", line)
        if m and "{" in line:
            if cur_name:
                computations[cur_name] = "\n".join(cur_lines)
            cur_name, cur_lines = m.group(1), []
        elif cur_name is not None:
            cur_lines.append(line)
    if cur_name:
        computations[cur_name] = "\n".join(cur_lines)

    # map while bodies -> trip count estimate
    trip: Dict[str, int] = {}
    for name, body in computations.items():
        for m in re.finditer(r"while\([^)]*\).*?condition=%?([\w\.\-]+).*?"
                             r"body=%?([\w\.\-]+)", body):
            cond, wbody = m.group(1), m.group(2)
            t = 1
            cond_src = computations.get(cond, "")
            consts = [int(c) for c in
                      re.findall(r"s32\[\]\s+constant\((\d+)\)", cond_src)]
            if consts:
                t = max(consts)
            trip[wbody] = max(trip.get(wbody, 1), t)

    def multiplier(comp: str, depth=0) -> int:
        if depth > 4:
            return 1
        return trip.get(comp, 1)

    out: Dict[str, Any] = {"total_bytes": 0, "by_kind": {}, "count": 0,
                           "loop_trips": trip}
    for name, body in computations.items():
        mult = multiplier(name)
        for line in body.splitlines():
            m = _COLL_RE.search(line)
            if not m or "-done" in line or "-update" in line:
                continue
            kind = m.group(1)
            b = _bytes_of(line) * mult
            out["by_kind"][kind] = out["by_kind"].get(kind, 0) + b
            out["total_bytes"] += b
            out["count"] += 1
    return out


class Policy:
    """SSPerf hillclimb knobs, applied uniformly to a dryrun invocation."""

    def __init__(self, dp_only=False, fsdp=False, state_dtype=None,
                 micro=None, grad_dtype=None, abft_mode="off"):
        self.dp_only = dp_only
        self.fsdp = fsdp
        self.state_dtype = state_dtype
        self.micro = micro
        self.grad_dtype = grad_dtype
        # abft mode of the COST compiles: 'off' = model hot path without
        # protection; 'detect' = paper-faithful CoC-D always-on (the
        # error-free production config, measurable because detect_only
        # compiles no correction branches)
        self.abft_mode = abft_mode


DEFAULT_POLICY = Policy()


def build_step(cfg, shape_name: str, mesh, spec, force_micro=None,
               policy: Policy = DEFAULT_POLICY):
    """Returns (jitted_fn, arg_shapes tuple) for the cell."""
    dp = SH.data_axes(mesh)
    if policy.dp_only:
        dp = dp + ("model",)
    dp_ax = dp if len(dp) > 1 else dp[0]
    mesh_axes = (dp_ax, None if policy.dp_only else "model")
    specs = C.input_specs(cfg, shape_name)
    kind = C.SHAPES[shape_name].kind
    key = jax.random.PRNGKey(0)

    def _psh(tree):
        return SH.param_shardings(tree, mesh, cfg, dp_only=policy.dp_only,
                                  fsdp=policy.fsdp)

    if kind == "train":
        ov = TRAIN_OVERRIDES.get(cfg.name, {})
        opt_cfg = OptConfig(
            kind=ov.get("opt", "adamw"),
            state_dtype=policy.state_dtype or ov.get("state_dtype",
                                                     "float32"))
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        micro = min(policy.micro or ov.get("micro", 1),
                    max(C.SHAPES[shape_name].global_batch // dp_size, 1))
        if force_micro is not None:
            micro = force_micro
        step = make_train_step(cfg, opt_cfg, microbatches=micro,
                               mesh_axes=mesh_axes,
                               grad_dtype=policy.grad_dtype)
        state_shapes = jax.eval_shape(
            functools.partial(init_train_state, key, cfg, opt_cfg))
        state_sh = {
            "params": _psh(state_shapes["params"]),
            "opt": _psh(state_shapes["opt"]),
            "step": NamedSharding(mesh, P()),
        }
        bspec = P(dp_ax, *([None] * (len(specs["tokens"].shape) - 1)))
        batch_sh = {k: NamedSharding(mesh, bspec) for k in specs}
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     donate_argnums=(0,))
        return fn, (state_shapes, specs)

    params_shapes = jax.eval_shape(functools.partial(init_params, key, cfg))
    params_sh = _psh(params_shapes)

    if kind == "prefill":
        step = make_prefill_step(cfg, max_len=C.SHAPES[shape_name].seq_len)
        bspec = P(dp_ax, *([None] * (len(specs["tokens"].shape) - 1)))
        batch_sh = {"tokens": NamedSharding(mesh, bspec)}
        fn = jax.jit(step, in_shardings=(params_sh, batch_sh))
        return fn, (params_shapes, specs)

    # decode
    b = specs["tokens"].shape[0]
    step = make_serve_step(cfg)
    cache_sh = SH.cache_shardings(specs["caches"], mesh, b)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    tok_spec = (P(dp_ax, *([None] * (len(specs["tokens"].shape) - 1)))
                if b % dp_size == 0 else
                P(*([None] * len(specs["tokens"].shape))))
    batch_sh = {"tokens": NamedSharding(mesh, tok_spec),
                "positions": NamedSharding(mesh, P()),
                "caches": cache_sh}
    fn = jax.jit(step, in_shardings=(params_sh, batch_sh),
                 donate_argnums=(1,))
    return fn, (params_shapes, specs)


def _compile_once(cfg, shape_name, mesh, save_hlo_path=None,
                  force_micro=None,
                  policy=None) -> Dict[str, Any]:
    t0 = time.time()
    with jax.set_mesh(mesh):
        fn, args = build_step(cfg, shape_name, mesh, C.SHAPES[shape_name],
                              force_micro=force_micro,
                              policy=policy or DEFAULT_POLICY)
        lowered = fn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    if save_hlo_path:
        with open(save_hlo_path, "w") as f:
            f.write(hlo)
    return {
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "flops": cost.get("flops", 0.0) if cost else 0.0,
        "bytes_accessed": cost.get("bytes accessed", 0.0) if cost else 0.0,
        "collectives": coll,
        "memory": {
            k: getattr(mem, k)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes")
            if mem is not None and hasattr(mem, k)},
        "hlo_bytes": len(hlo),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save_hlo: bool = False,
             policy: Optional[Policy] = None,
             skip_full: bool = False) -> Dict[str, Any]:
    """Full compile (scan-over-stages: memory truth + compile-coherence
    proof) plus two small unrolled compiles at stage_repeats 1 and 2 whose
    difference gives the exact per-stage HLO cost terms - XLA's
    cost_analysis counts while-loop bodies once, so the scanned program's
    raw numbers undercount by the trip count. Extrapolation:
        total = cost(R=1) + (R-1) * [cost(R=2) - cost(R=1)]
    (prefix/remainder/embedding terms cancel in the delta).
    """
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    result: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                              "mesh": mesh_name}
    cfg = C.get(arch)
    ok, why = C.cell_supported(cfg, shape_name)
    if not ok:
        result["status"] = "skipped"
        result["reason"] = why
        return result
    mesh = make_production_mesh(multi_pod=multi_pod)
    pattern, reps, rem = cfg.stages()
    try:
        hlo_path = None
        if save_hlo:
            os.makedirs(ART_DIR, exist_ok=True)
            hlo_path = os.path.join(
                ART_DIR, f"{arch}_{shape_name}_{mesh_name}.hlo")
        if skip_full:
            # hillclimb mode: cost terms only (memory truth unchanged from
            # the baseline artifact)
            full = {"lower_s": 0, "compile_s": 0, "memory": {},
                    "hlo_bytes": 0, "flops": 0, "bytes_accessed": 0,
                    "collectives": {"total_bytes": 0}}
        else:
            full = _compile_once(cfg, shape_name, mesh,
                                 save_hlo_path=hlo_path, policy=policy)
        if multi_pod:
            # the multi-pod pass proves the 'pod' axis shards + gives
            # memory; the roofline table is single-pod (SSRoofline)
            result.update({"status": "ok", **{
                k: full[k] for k in ("lower_s", "compile_s", "memory",
                                     "hlo_bytes")},
                "scan_raw": {"flops": full["flops"],
                             "bytes_accessed": full["bytes_accessed"],
                             "collective_bytes":
                                 full["collectives"]["total_bytes"]}})
            return result
        # hot-path costing: abft=False removes the (rarely-executed)
        # correction branches that XLA's static cost_analysis would
        # otherwise count as if always taken; the error-free ABFT overhead
        # (one pass over D + the fused/extra summation pass) is reported
        # separately by the benchmarks
        pol = policy or DEFAULT_POLICY
        if pol.abft_mode == "detect":
            cost_base = dict(remainder_pattern=rem, scan_stages=False,
                             abft=True, abft_detect_only=True)
        else:
            cost_base = dict(remainder_pattern=rem, scan_stages=False,
                             abft=False)
        c1 = _compile_once(cfg.replace(stage_repeats=1, **cost_base),
                           shape_name, mesh, force_micro=1, policy=policy)
        c2 = _compile_once(cfg.replace(stage_repeats=2, **cost_base),
                           shape_name, mesh, force_micro=1, policy=policy)
    except Exception as e:  # a failure here is a bug in the system
        result["status"] = "failed"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
        return result

    def extrap(key):
        return c1[key] + (reps - 1) * (c2[key] - c1[key])

    coll_kinds = set(c1["collectives"]["by_kind"]) | \
        set(c2["collectives"]["by_kind"])
    coll = {}
    for k in coll_kinds:
        v1 = c1["collectives"]["by_kind"].get(k, 0)
        v2 = c2["collectives"]["by_kind"].get(k, 0)
        coll[k] = int(v1 + (reps - 1) * (v2 - v1))
    result.update({
        "status": "ok",
        "lower_s": full["lower_s"],
        "compile_s": full["compile_s"],
        "flops_per_device": extrap("flops"),
        "bytes_accessed_per_device": extrap("bytes_accessed"),
        "collective_bytes_per_device": int(sum(coll.values())),
        "collectives_by_kind": coll,
        "memory": full["memory"],
        "hlo_bytes": full["hlo_bytes"],
        "scan_raw": {"flops": full["flops"],
                     "bytes_accessed": full["bytes_accessed"],
                     "collective_bytes":
                         full["collectives"]["total_bytes"]},
        "stage_reps": reps,
        "cost_compiles_s": [c1["compile_s"], c2["compile_s"]],
    })
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(C.SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default=None)
    # SSPerf hillclimb knobs
    ap.add_argument("--dp-only", action="store_true",
                    help="replicate params; batch over both mesh axes")
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-3: shard weights' free axis over data")
    ap.add_argument("--micro", type=int, default=None)
    ap.add_argument("--state-dtype", default=None,
                    choices=[None, "float32", "bfloat16"])
    ap.add_argument("--grad-dtype", default=None,
                    choices=[None, "float32", "bfloat16"])
    ap.add_argument("--abft-mode", default="off",
                    choices=["off", "detect"],
                    help="cost-compile ABFT mode (detect = paper-faithful "
                         "CoC-D hot path)")
    ap.add_argument("--tag", default="",
                    help="suffix for artifact filenames (perf variants)")
    ap.add_argument("--skip-full", action="store_true",
                    help="hillclimb mode: only the two cost compiles")
    args = ap.parse_args()
    policy = Policy(dp_only=args.dp_only, fsdp=args.fsdp,
                    state_dtype=args.state_dtype, micro=args.micro,
                    grad_dtype=args.grad_dtype, abft_mode=args.abft_mode)

    cells = []
    archs = C.list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(C.SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            cells.append((a, s))

    os.makedirs(ART_DIR, exist_ok=True)
    results = []
    for arch, shape in cells:
        print(f"=== dryrun {arch} x {shape} "
              f"({'2x16x16' if args.multi_pod else '16x16'}) ===", flush=True)
        r = run_cell(arch, shape, args.multi_pod, save_hlo=args.save_hlo,
                     policy=policy, skip_full=args.skip_full)
        print(json.dumps({k: v for k, v in r.items()
                          if k not in ("traceback",)}, indent=2,
                         default=str), flush=True)
        if r["status"] == "failed":
            print(r.get("traceback", ""), flush=True)
        results.append(r)
        tag = f"_{args.tag}" if args.tag else ""
        fname = (f"{arch}_{shape}_"
                 f"{'pod2x16x16' if args.multi_pod else 'pod16x16'}"
                 f"{tag}.json")
        with open(os.path.join(ART_DIR, fname), "w") as f:
            json.dump(r, f, indent=2, default=str)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2, default=str)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "failed" for r in results)
    print(f"\ndryrun summary: {n_ok} ok, {n_skip} skipped (documented), "
          f"{n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
