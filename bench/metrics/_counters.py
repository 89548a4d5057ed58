"""Deltas of the engine's ``stats()`` counters over the window."""


def delta(ctx, key):
    return ctx["stats1"][key] - ctx["stats0"][key]


def search_batches(ctx):
    """Executor calls in the window: every search goes through the
    coalescer, one executor call per coalesced dispatch."""
    return delta(ctx, "coalesce_dispatches")


def total(stats, ratio_key):
    """A per-batch ratio back to its total over all batches so far."""
    return stats[ratio_key] * stats["coalesce_dispatches"]
