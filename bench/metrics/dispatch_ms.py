"""Engine host: mean duration of the flight recorder's ``execute`` span per
batch -- plan compile and rewrite, value hashing, capacity lookups and the
enqueue of every device program of the batch."""


def read(ctx):
    d = [s.duration for b in ctx.batches for s in b.children
         if s.name == "execute"]
    return 1e3 * sum(d) / len(d) if d else None
