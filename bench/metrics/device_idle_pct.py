"""Device: share of the profiled window in which no operation ran on the
chip (1 - union of the ``XLA Ops`` intervals / window), in percent."""


def read(ctx):
    if ctx.device is None or ctx.device["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.device["busy_s"] / ctx.device["window_s"])
