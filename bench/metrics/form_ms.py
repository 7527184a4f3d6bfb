"""Front tier: mean time per batch in the flight recorder's ``batch/form``
span -- the batch former's window, from when the dispatcher was free and
the batch's oldest request waited to the batch's start."""
from bench.spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "form")
