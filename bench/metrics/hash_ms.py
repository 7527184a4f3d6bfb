"""Hashing: mean time per batch in the flight recorder's ``execute/hash``
span -- query values hashed through the executor's memo, C pairs, MC
superkeys."""
from bench.spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "execute/hash")
