"""Probe / seekers: flat probe window slots per request, the summed
``slots`` of the ``execute/capacity`` spans over the requests of the
``execute`` spans (``requests``) of the window's batches.  None where the
program records no window sizes."""
from bench.spans import batch_sums


def read(ctx):
    slots = batch_sums(ctx, "execute/capacity",
                       lambda s: s.attrs.get("slots", 0))
    reqs = batch_sums(ctx, "execute", lambda s: s.attrs.get("requests", 0))
    if not slots or not sum(slots) or not sum(reqs):
        return None
    return sum(slots) / sum(reqs)
