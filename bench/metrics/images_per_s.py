"""Images whose logits reached the host inside the window, per second
of the window (host clock)."""


def read(ctx):
    run = ctx["run"]
    return run.images_done / run.seconds
