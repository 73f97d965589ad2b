"""Device time of the ops under a `checksum_conv` scope (the checksum
convolutions of the protected op), over device busy time, in percent."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["busy_s"] <= 0:
        return None
    t = sum(op[0] for op in tr["ops"] if "checksum_conv" in op[1])
    if not t:
        return None
    return 100.0 * t / tr["busy_s"]
