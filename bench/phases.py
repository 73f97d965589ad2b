"""The phase of a device op inside a protected site, read from the scope
path the program attaches to it.

The program names each site's work with one phase scope under the site's
path (`repro.core.plan.protect_site`): `op` (the op and its bias add),
`encode` (the input checksums), `detect` (CoC-D), `correct` (a ladder,
and the deferred workflow's whole rerun) and `inject` (the fault hook's
planted output). An op under `correct` is correction work whatever else
its path names. Ops under a site but in no phase are `unphased`; ops
under no site are `-`. A program without the phase scopes reads as
`unphased` everywhere, and the readers of the phases then report nothing
and say why on standard error (`phased`).
"""
from __future__ import annotations

import sys
from typing import Dict, Iterable, Tuple

from bench.trace_reduce import site_of

PHASES = ("op", "encode", "detect", "correct", "inject")
COLUMNS = PHASES + ("unphased", "-")


def phase_of(scope: str, sites: Iterable[str]) -> str:
    """The phase of a scope path, matched on whole path components (an
    op the compiler made from several source ops joins their paths with
    `;`, which never touches a site or phase component)."""
    parts = scope.split("/")
    if "correct" in parts:
        return "correct"
    for c in parts:
        if c in PHASES:
            return c
    return "unphased" if site_of(scope, sites) else "-"


def phased(ops, metric: str) -> bool:
    """Whether any of the summary's ops lies in a phase. Where none does,
    says so on standard error for `metric`: the program has no phase
    scopes, or its executable came from a compile cache that another
    checkout filled (the cache's key leaves scope names out)."""
    if any(phase_of(op[1], ()) in PHASES for op in ops):
        return True
    print(f"{metric}: no op in the trace has a phase scope (a program "
          "without them, or an executable loaded from a compile cache "
          "another checkout filled: give each checkout its own "
          "JAX_COMPILATION_CACHE_DIR)", file=sys.stderr, flush=True)
    return False


def phase_seconds(ops, phase: str) -> float:
    """Device seconds of the summary's ops in `phase` (sites are not
    needed to tell a phase apart from `unphased` and `-`)."""
    return sum(op[0] for op in ops if phase_of(op[1], ()) == phase)


def table(ops, sites: Iterable[str]) -> Dict[Tuple[str, str], float]:
    """Device seconds per (site, phase) of the summary's ops."""
    sites = list(sites)
    out: Dict[Tuple[str, str], float] = {}
    for dur, scope, *_ in ops:
        key = (site_of(scope, sites) or "-", phase_of(scope, sites))
        out[key] = out.get(key, 0.0) + dur
    return out


def print_table(ops, sites: Iterable[str], busy_s: float,
                file=None) -> None:
    """The per-site phase table, in milliseconds over the traced window,
    one row per site and one for work under no site, with each column's
    share of busy time last (on standard error unless `file` is given).
    A stopgap that the `detect_share` reader prints: its place is
    `trace_reduce.summarize`, which a benchmark change can edit."""
    file = file or sys.stderr
    sites = list(sites)
    t = table(ops, sites)
    print("phase table (device ms in the traced window): site "
          + " ".join(COLUMNS), file=file)
    for site in sites + ["-"]:
        row = [t.get((site, p), 0.0) for p in COLUMNS]
        if any(row):
            print(f"phase table: {site} "
                  + " ".join(f"{1e3 * v:.3f}" for v in row), file=file)
    if busy_s > 0:
        tot = [sum(v for (_, p), v in t.items() if p == c) for c in COLUMNS]
        print("phase table: share of busy % "
              + " ".join(f"{100 * v / busy_s:.2f}" for v in tot), file=file)
    file.flush()


def reruns(run) -> int:
    """Finished steps of the run whose verdicts flag any site: the steps
    in which the deferred workflow's cond took the corrective branch."""
    return sum(1 for d in run.done if d.verdicts[:, 0].any())
