"""Device time of one corrective rerun, in milliseconds: the device time
of the ops under a `correct` scope in the traced window over the reruns
in it (`reruns`, printed on standard error: the finished steps whose
verdicts flag a site, which are the steps whose deferred cond took the
corrective branch). Nothing where no step reran, and nothing, with the
reason on standard error, where no op of the trace lies in a phase."""
import sys

from bench import phases


def read(ctx):
    tr, run = ctx["trace"], ctx["run"]
    if not tr:
        return None
    n = phases.reruns(run)
    print(f"reruns: {n} of {len(run.done)} traced steps", file=sys.stderr,
          flush=True)
    if not n or not phases.phased(tr["ops"], "rerun_ms"):
        return None
    t = phases.phase_seconds(tr["ops"], "correct")
    return 1e3 * t / n if t else None
