"""Host seconds of lowering the step and compiling it, or loading it
from the compile cache, in set-up."""


def read(ctx):
    return ctx["run"].compile_s
