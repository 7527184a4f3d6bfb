"""Engine: mean duration of the flight recorder's ``drain`` span per batch,
the host waiting for the batch's device programs to finish."""


def read(ctx):
    d = [s.duration for b in ctx.batches for s in b.children
         if s.name == "drain"]
    return 1e3 * sum(d) / len(d) if d else None
