#!/usr/bin/env python3
"""Benchmark of protected CNN inference on a TPU: one cell, one run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json; its configuration
is `bench/configs/<config>.json`, its traffic `bench/traffic/<traffic>.json`
and each metric is read by `bench/metrics/<metric>.py`. `--trace 0`
prints the cell's end-to-end metrics, `--trace 1` its per-layer ones from
a profiler trace of a shorter window (the traffic's `trace_seconds`).

The run fails, and prints no result, when JAX's first device is not a
TPU, when there are fewer chips than the cell asks for, or when the
device kind is not in `bench/peaks.json`. The last line of standard
output is one JSON object: correct, attempted, failed, metrics, device
(and breakdown with --trace 1), and last the numbers compared, each with
its limit; the same numbers are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str, code: int = 1) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(doc: dict, workload: str, kind: str):
    """The metrics of `kind` ("end_to_end" or "per_layer") that the cell
    reports."""
    return [m for m in doc[kind]
            if "workloads" not in m or workload in m["workloads"]]


def cell_of(doc: dict, name: str):
    for w in doc["workloads"]:
        if w["name"] == name:
            return w
    return None


def enable_cache() -> str:
    """JAX's persistent compile cache: $JAX_COMPILATION_CACHE_DIR when it
    is set, else a fixed directory inside the checkout. Every program is
    written to it, so a warm run compiles none."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def device_info(jax, chips: int, peaks_table: dict):
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        return None, f"the first device is {d0.platform!r}, not a TPU"
    if len(devs) < chips:
        return None, f"the cell needs {chips} chips, JAX found {len(devs)}"
    if d0.device_kind not in peaks_table:
        return None, (f"device kind {d0.device_kind!r} is not in "
                      "bench/peaks.json")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_json = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(bench_json):
        return fail(f"no BENCHMARK.json at {ROOT}", 2)
    doc = load_json(bench_json)
    wl = cell_of(doc, args.workload)
    if wl is None:
        return fail(f"no workload {args.workload!r} in BENCHMARK.json", 2)
    cfg = load_json(BENCH, "configs", f"{wl['config']}.json")
    traffic = load_json(BENCH, "traffic", f"{wl['traffic']}.json")
    peaks_table = load_json(BENCH, "peaks.json")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"no system under test: {ROOT}/src/repro is missing", 2)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax
    cache = enable_cache()
    device, err = device_info(jax, wl["chips"], peaks_table)
    if err:
        return fail(err)
    log(f"device: {device}; compile cache: {cache}")

    from bench import cell, flops, trace_reduce
    kind = "per_layer" if args.trace else "end_to_end"
    wanted = metrics_of(doc, wl["name"], kind)
    readers = {m["name"]: load_reader(m["name"]) for m in wanted}
    counter = cell.Counter()
    seconds = args.seconds
    trace_dir = None
    if args.trace:
        seconds = min(seconds, traffic["trace_seconds"])
        trace_dir = os.path.join(ROOT, ".bench_trace", wl["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)

    rec, result, peak = cell.run(cfg, traffic, args.seed, seconds, T_START,
                                 counter, trace_dir=trace_dir, log=log)
    if rec.compiled_window:
        return fail(f"{rec.compiled_window} program(s) compiled inside "
                    "the measured window")

    summary = None
    if args.trace:
        path = trace_reduce.find_xplane(trace_dir)
        if path is None:
            return fail(f"the profiler wrote no trace under {trace_dir}")
        summary = trace_reduce.summarize(trace_reduce.extract(path))
    ctx = {"run": rec, "trace": summary, "cfg": cfg, "traffic": traffic,
           "peaks": peaks_table[device["kind"]],
           "sites": flops.sites(cfg, traffic["batch"]),
           "flops_per_image": flops.flops_per_image(cfg)}
    metrics = {}
    for m in wanted:
        v = readers[m["name"]](ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    numbers = result["numbers"]
    checks = {k: {"value": v, "limit": cell.LIMITS[k]}
              for k, v in numbers.items()}
    correct = cell.is_correct(numbers)
    device["memory_peak_bytes"] = peak
    out = {"correct": correct, "attempted": rec.attempted_images,
           "failed": result["bad_images"], "metrics": metrics,
           "device": device}
    if summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
