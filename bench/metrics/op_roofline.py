"""Share of its roofline that the protected sites' own ops reach, in
percent. A site's own ops are the ops under its scope, not under
`checksum_conv`, that are either a convolution-category op doing at
least half of the site's flops by the compiler's count (the checksum
encodes and the detection sums under the same scope do far fewer) or a
custom call (a Pallas kernel the plan pins to the site). Numerator: for
every execution of the step in the traced window, the sum over sites of
the least time the chip could take for the site's conv or dot
(bench/flops.py: the larger of its flops over peak and its bytes over
bandwidth). Denominator: the device time of the sites' own ops. Where a
site has no own op in the trace, numerator and denominator would cover
different work, so the metric is left out."""
import sys

from bench import trace_reduce
from bench.flops import site_min_seconds


def is_custom_call(name: str, category: str) -> bool:
    return any(k in f"{name} {category}"
               for k in ("custom-call", "custom_call", "custom call"))


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["steps_s"]:
        return None
    sites = {s["name"]: s for s in ctx["sites"]}
    spent = dict.fromkeys(sites, 0.0)
    for dur, scope, category, flops, name in tr["ops"]:
        if "checksum_conv" in scope:
            continue
        site = trace_reduce.site_of(scope, sites)
        if site is None:
            continue
        own_conv = ("convolution" in category
                    and flops >= 0.5 * sites[site]["flops"])
        if own_conv or is_custom_call(name, category):
            spent[site] += dur
    missing = [s for s, t in spent.items() if t <= 0]
    if missing:
        print(f"op_roofline: no own op of site(s) {', '.join(missing)} in "
              "the trace; left out", file=sys.stderr, flush=True)
        return None
    least = sum(site_min_seconds(s, ctx["peaks"]) for s in sites.values())
    return 100.0 * least * len(tr["steps_s"]) / sum(spent.values())
