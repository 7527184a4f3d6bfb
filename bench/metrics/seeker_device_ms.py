"""Probe / seekers: device time of the fused seeker programs
(``jit_*_seeker_seg``) in the profiled window, per request served in it."""
from bench import tracereduce


def read(ctx):
    if ctx.device is None:
        return None
    reqs = tracereduce.requests_in_window(ctx)
    progs = tracereduce.program_seconds(ctx.device)
    s = sum(v for n, v in progs.items() if n.endswith("_seeker_seg"))
    if not reqs or s <= 0:
        return None
    return 1e3 * s / len(reqs)
