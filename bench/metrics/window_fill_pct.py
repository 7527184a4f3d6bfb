"""Probe / seekers: the share of the flat probe window's slots that hold a
matched posting, 100 x the summed ``matched`` over the summed ``slots`` of
the ``execute/capacity`` spans of the window's batches.  The rest pads the
last chunk that each launch walks.  None where the program records no
window sizes."""
from bench.spans import batch_sums


def read(ctx):
    slots = batch_sums(ctx, "execute/capacity",
                       lambda s: s.attrs.get("slots", 0))
    matched = batch_sums(ctx, "execute/capacity",
                         lambda s: s.attrs.get("matched", 0))
    if not slots or not sum(slots):
        return None
    return 100.0 * sum(matched) / sum(slots)
