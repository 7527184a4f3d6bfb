"""Query compile: mean time per batch in the flight recorder's
``execute/plan`` span -- BlendQL compile or plan-memo lookup and the
result-cache lookup of every request of the batch."""
from bench.spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "execute/plan")
