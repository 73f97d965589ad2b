"""Benchmark harness entry point - one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (see each module's docstring
for the table it reproduces)."""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: schemes,error_free,erroneous,mm_abft,"
                         "transformer,kernels,parallel,roofline,campaign,"
                         "plan,serve")
    ap.add_argument("--quick", action="store_true",
                    help="skip the slow erroneous/parallel/campaign suites")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_campaign, bench_error_free, bench_erroneous,
                   bench_kernels, bench_mm_abft, bench_parallel, bench_plan,
                   bench_schemes, bench_serve, bench_transformer, roofline)

    suites = {
        "schemes": bench_schemes.run,            # Fig. 6 / Table 4
        "error_free": bench_error_free.run,      # Fig. 10(a)
        "erroneous": bench_erroneous.run,        # Fig. 10(b)(c) / Fig. 11
        "campaign": bench_campaign.run,          # SS6 / Table 7 rates
        "plan": bench_plan.run,                  # offline-encode reuse gap
        "serve": bench_serve.run,                # protected serving parity
        "mm_abft": bench_mm_abft.run,            # Table 6
        "transformer": bench_transformer.run,    # beyond-paper LLM overhead
        "kernels": bench_kernels.run,            # fused epilogue accounting
        "parallel": bench_parallel.run,          # Fig. 15
        "roofline": roofline.run,                # SSRoofline table
    }
    if args.only:
        keep = args.only.split(",")
        suites = {k: v for k, v in suites.items() if k in keep}
    elif args.quick:
        for k in ("erroneous", "parallel", "campaign"):
            suites.pop(k, None)

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        try:
            fn()
        except Exception:
            failures += 1
            print(f"{name},0.0,SUITE_FAILED", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
