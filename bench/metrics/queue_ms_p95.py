"""Front tier: 95th percentile of the time a request waited in the server's
queue before its batch dispatched (``DiscoveryResponse.queue_seconds``)."""
from bench.harness import percentile


def read(ctx):
    q = [r.queue_s for r in ctx.requests]
    return percentile(q, 95) * 1e3 if q else None
