"""What the per-layer readers of the engine host share: sums over one
batch's flight-recorder spans at a path (``execute/hash``, ``form``), and
their mean over the window's batches.

A batch counts where it has an ``execute`` span, that is where the engine
ran.  A batch without a span at the path adds 0.  A program that records
no span at the path in any batch has nothing to read: the reader returns
None rather than 0."""


def at(batch, path: str) -> list:
    """The spans at ``path`` below ``batch``, one name per level."""
    spans = [batch]
    for name in path.split("/"):
        spans = [c for s in spans for c in s.children if c.name == name]
    return spans


def executed(ctx) -> list:
    """The window's batches that ran the engine."""
    return [b for b in ctx.batches if at(b, "execute")]


def batch_sums(ctx, path: str, value) -> list | None:
    """Per executed batch, the sum of ``value(span)`` over its spans at
    ``path``; None where no batch has a span there."""
    per = [[value(s) for s in at(b, path)] for b in executed(ctx)]
    if not any(per):
        return None
    return [sum(v) for v in per]


def mean_ms(ctx, path: str) -> float | None:
    """Mean over the executed batches of the time in spans at ``path``."""
    sums = batch_sums(ctx, path, lambda s: s.duration)
    return 1e3 * sum(sums) / len(sums) if sums is not None else None
