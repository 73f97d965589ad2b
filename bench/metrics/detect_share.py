"""Device time of the ops in the protected sites' `detect` phase (CoC-D:
the checksum convs under `checksum_conv`, the detection sums over O, the
thresholds and the compare), outside any correction, over device busy
time, in percent. Also prints the per-site phase table on standard
error (bench/phases.py). Nothing, with the reason on standard error,
where no op of the trace lies in a phase."""
from bench import phases


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["busy_s"] <= 0 or not phases.phased(tr["ops"],
                                                         "detect_share"):
        return None
    phases.print_table(tr["ops"], [s["name"] for s in ctx["sites"]],
                       tr["busy_s"])
    t = phases.phase_seconds(tr["ops"], "detect")
    return 100.0 * t / tr["busy_s"] if t else None
