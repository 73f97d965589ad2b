"""Seconds from process start to the first timed step: device start-up,
weights and inputs from the seed, the plan, compiling or loading the
step from the compile cache, and the warm-up (host clock)."""


def read(ctx):
    return ctx["run"].setup_s
