"""Front tier: mean of ``DiscoveryResponse.batch_size`` over the window's
responses (how many requests the server coalesced into each one's batch)."""


def read(ctx):
    b = [r.batch_size for r in ctx.requests]
    return sum(b) / len(b) if b else None
