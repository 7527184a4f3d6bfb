"""Capacity statistics: mean time per batch in the flight recorder's
``execute/capacity`` span -- the batch's one ``host_counts`` lookup and the
capacity-rung picks."""
from bench.spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "execute/capacity")
