"""Model FLOPs of the unprotected network (bench/flops.py) times the
images finished in the traced window, over the window times the chip's
bf16 peak, in percent. Checksum and correction work does not count."""


def read(ctx):
    tr, run = ctx["trace"], ctx["run"]
    if not tr or tr["window_s"] <= 0 or not run.images_done:
        return None
    flops = ctx["flops_per_image"] * run.images_done
    return 100.0 * flops / (tr["window_s"] * ctx["peaks"]["flops_per_s"])
