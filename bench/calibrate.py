#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, for one cell, on
the chip, in one process (the step compiles or loads once for all seeds).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--seconds 1]

Per seed: a short window of the cell's own traffic through the timed
step, then, as a run's check does, every request's `logit_gap` against
the reference; the control's gap (the reference at the configuration's
`control_operand_dtype`, put in the program's place); and, where the
traffic injects faults, the gap of each fault left uncorrected (the
system's unprotected forward with the same injection). One JSON line per
seed, then one with the largest gap of the system, the smallest of the
control and the smallest of an uncorrected fault. The benchmark's runs
never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import run as bench_run
    import jax
    bench_run.enable_cache()
    doc = bench_run.load_json(ROOT, "BENCHMARK.json")
    wl = bench_run.cell_of(doc, args.workload)
    cfg = bench_run.load_json(BENCH, "configs", f"{wl['config']}.json")
    traffic = bench_run.load_json(BENCH, "traffic", f"{wl['traffic']}.json")
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    summary = control_gaps(cfg, traffic, [int(s) for s in
                                          args.seeds.split(",")],
                           args.seconds)
    print(json.dumps(summary), flush=True)
    return 0


def control_gaps(cfg, traffic, seeds, seconds):
    import jax
    from bench import cell
    from repro.models import cnn
    pcfg = cell.program_config(cfg)
    plain = jax.jit(lambda p, x, layer, o: cnn.forward_cnn(
        p, x, dataclasses.replace(pcfg, abft=False), inject_layer=layer,
        inject_o=o)[0])
    compiled = None
    rows = []
    for seed in seeds:
        prep = cell.prepare(cfg, traffic, seed, pcfg)
        if compiled is None:
            compiled = cell.compile_step(prep, pcfg, cfg)
        first = cell.warm_up(compiled, prep, traffic)
        done = cell.window(compiled, prep.requests, prep.pick, seconds,
                           traffic["in_flight"], first_step=first)[0]
        refs = cell.reference_logits(prep.params, prep.xs, cfg,
                                     cfg["operand_dtype"],
                                     cell.REFERENCE_BLOCK)
        ctrl = cell.reference_logits(prep.params, prep.xs, cfg,
                                     cfg["control_operand_dtype"],
                                     cell.REFERENCE_BLOCK)
        res = cell.check(done, prep.meta, refs)["numbers"]
        row = {"seed": seed, "requests": len(done), **res,
               "control_gap": max(cell.logit_gap(c, r)
                                  for c, r in zip(ctrl, refs))}
        faulted = [i for i, (_, layer) in enumerate(prep.meta) if layer >= 0]
        if faulted:
            row["uncorrected_gap"] = []
            for i in faulted:
                params, x, _, layer, hook = prep.requests[i]
                got = plain(params, x, layer, hook)
                row["uncorrected_gap"].append(
                    [prep.meta[i][1], cell.logit_gap(got,
                                                     refs[prep.meta[i][0]])])
        print(json.dumps(row), flush=True)
        rows.append(row)
    out = {"seeds": len(rows),
           "logit_gap_max": max(r["logit_gap"] for r in rows),
           "control_gap_min": min(r["control_gap"] for r in rows),
           "false_alarms": sum(r["false_alarms"] for r in rows),
           "missed_faults": sum(r["missed_faults"] for r in rows)}
    if any("uncorrected_gap" in r for r in rows):
        out["uncorrected_gap_min"] = min(
            g for r in rows for _, g in r.get("uncorrected_gap", ()))
    return out


if __name__ == "__main__":
    sys.exit(main())
