"""Optimizer: mean number per batch of planner-statistics ``host_counts``
scans, the ``stats_scans`` attribute of the ``execute/optimize`` span."""
from bench.spans import batch_sums


def read(ctx):
    sums = batch_sums(ctx, "execute/optimize",
                      lambda s: s.attrs.get("stats_scans", 0))
    return sum(sums) / len(sums) if sums is not None else None
