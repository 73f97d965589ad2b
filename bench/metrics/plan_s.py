"""Host seconds of `build_plan` (and its weight checksums reaching the
device) in set-up."""


def read(ctx):
    return ctx["run"].plan_s
