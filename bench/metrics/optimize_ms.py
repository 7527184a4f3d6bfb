"""Optimizer: mean time per batch in the flight recorder's
``execute/optimize`` span -- seeker ranking, with the value hashing and
``host_counts(live_only=True)`` scans of ``Executor.seeker_stats``."""
from bench.spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "execute/optimize")
