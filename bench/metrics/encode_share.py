"""Device time of the ops in the protected sites' `encode` phase (the
input checksums of each site), outside any correction, over device busy
time, in percent. Nothing, with the reason on standard error, where no
op of the trace lies in a phase."""
from bench import phases


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["busy_s"] <= 0 or not phases.phased(tr["ops"],
                                                         "encode_share"):
        return None
    t = phases.phase_seconds(tr["ops"], "encode")
    return 100.0 * t / tr["busy_s"] if t else None
