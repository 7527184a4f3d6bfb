"""Hashing: share of the batch's hash-memo lookups that missed, so that
``hash_value`` ran: 100 x the misses over the lookups, summed over the
``execute/optimize`` spans (``hash_misses`` / ``hash_values``: the
optimizer's statistics hash first where it ranks seekers) and the
``execute/hash`` spans (``misses`` / ``values``)."""
from bench.spans import batch_sums


def read(ctx):
    sums = [batch_sums(ctx, path, lambda s, k=k: s.attrs.get(k, 0))
            for path, k in (("execute/optimize", "hash_misses"),
                            ("execute/hash", "misses"),
                            ("execute/optimize", "hash_values"),
                            ("execute/hash", "values"))]
    misses = sum(sum(v) for v in sums[:2] if v is not None)
    values = sum(sum(v) for v in sums[2:] if v is not None)
    return 100.0 * misses / values if values else None
