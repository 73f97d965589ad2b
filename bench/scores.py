#!/usr/bin/env python3
"""The CoC-D detection score of every protected site over a cell's
requests, in one process (the step compiles or loads once for all seeds).

    python3 bench/scores.py --workload <name> --seeds 1,2,3 [--batch 1]

A site's score is max |C - S| / tau over the invariants its detection
compares: above 1 the site flags. Per seed, every request of the cell
runs once through the program the benchmark times (the same forward,
plan and correction), here returning the deferred report's per-site
scores. One JSON line per seed, then one with, per site, the largest
score over clean requests and, at each injected site, the smallest over
the requests that fault it. `--batch` replaces the traffic's batch size.
The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def make_score_step(pcfg, plan, correction: str, sites: List[str]):
    """The timed step's forward, returning a float32 (sites,) array of
    the detect pass's scores. A stopgap copy of `cell.make_step`'s body:
    once the timed step returns the scores beside the verdicts, this
    tool reads them from it and this function goes."""
    import jax
    import jax.numpy as jnp

    from bench import cell
    from repro.models import cnn

    def score_step(params, x, wcks, inject_layer=None, inject_o=None):
        hook = ({} if inject_o is None else
                {"inject_layer": inject_layer, "inject_o": inject_o})
        _, rep = cnn.forward_cnn(params, x, pcfg,
                                 plan=cell._with_checksums(plan, wcks),
                                 correction=correction, **hook)
        return jnp.stack([rep.scores[n] for n in sites])

    return jax.jit(score_step)


def scores(cfg: dict, traffic: dict, seeds: List[int], log=print) -> Dict:
    import numpy as np

    from bench import cell
    pcfg = cell.program_config(cfg)
    step, sites = None, None
    clean: Dict[str, float] = {}
    faulted: Dict[str, float] = {}
    for seed in seeds:
        prep = cell.prepare(cfg, traffic, seed, pcfg)
        if step is None:
            sites = list(prep.plan.entries)
            step = make_score_step(pcfg, prep.plan, cfg["correction"], sites)
        row_clean: Dict[str, float] = {}
        row_faulted: Dict[str, float] = {}
        for args, (_, layer) in zip(prep.requests, prep.meta):
            sc = np.asarray(step(*args), np.float64)
            if layer < 0:
                for n, v in zip(sites, sc):
                    row_clean[n] = max(row_clean.get(n, 0.0), float(v))
            else:
                n = sites[layer]
                row_faulted[n] = min(row_faulted.get(n, np.inf),
                                     float(sc[layer]))
        log(json.dumps({"seed": seed, "clean_max": row_clean,
                        "faulted_min": row_faulted}))
        for n, v in row_clean.items():
            clean[n] = max(clean.get(n, 0.0), v)
        for n, v in row_faulted.items():
            faulted[n] = min(faulted.get(n, np.inf), v)
    return {"seeds": len(seeds), "batch": traffic["batch"],
            "clean_max": clean,
            "clean_max_all": max(clean.values()) if clean else None,
            "faulted_min": faulted}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--batch", type=int, default=None)
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
    if args.batch is not None:
        traffic["batch"] = args.batch
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    out = scores(cfg, traffic, [int(s) for s in args.seeds.split(",")],
                 log=lambda msg: print(msg, flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
