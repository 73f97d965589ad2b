"""Median time from a request's dispatch to its logits on the host,
over every request finished inside the window, in milliseconds (host
clock)."""
import numpy as np


def read(ctx):
    lat = ctx["run"].latencies_s
    if not lat:
        return None
    return float(np.percentile(lat, 50)) * 1e3
