"""Kernels: the probe's share of its roofline.  The least bytes any probe of
the window's requests must move (4 per distinct query value, 4 per matched
posting, counted by the benchmark's reference from the requests alone; see
``bench/reference.py`` ``least_bytes``) over the chip's HBM bandwidth, divided
by the device time of the seeker programs that did it."""
from bench import tracereduce


def read(ctx):
    if ctx.device is None:
        return None
    reqs = tracereduce.requests_in_window(ctx)
    progs = tracereduce.program_seconds(ctx.device)
    s = sum(v for n, v in progs.items() if n.endswith("_seeker_seg"))
    if not reqs or s <= 0:
        return None
    least_s = sum(r.least_bytes() for r in reqs) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / s
