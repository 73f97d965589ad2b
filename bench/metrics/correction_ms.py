"""Device time a faulted step costs over a clean one, in milliseconds:
the mean duration of the step program's executions that injected a
fault, minus the mean of those that did not. Executions are matched to
requests in dispatch order."""


def read(ctx):
    tr, run = ctx["trace"], ctx["run"]
    if not tr:
        return None
    steps = tr["steps_s"]
    faulted = [run.meta[d.req][1] >= 0 for d in run.done]
    if len(steps) != len(faulted) or all(faulted) or not any(faulted):
        return None
    bad = [t for t, f in zip(steps, faulted) if f]
    good = [t for t, f in zip(steps, faulted) if not f]
    return 1e3 * (sum(bad) / len(bad) - sum(good) / len(good))
