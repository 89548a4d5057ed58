"""Query rows per coalesced dispatch in the window: how much the SLO
tier and coalescer (``core/slo.py``, ``CoalescingScheduler``) merge."""
from bench.metrics._counters import search_batches, total

LAYER = "SLO tier and coalescer"
SOURCE = "program_counter"
UNIT = "queries"
BETTER = "higher"
MOVES = {"open": "p50_ms"}


def read(ctx):
    n = search_batches(ctx)
    if n <= 0:
        return None
    rows = (total(ctx["stats1"], "coalesce_batch_mean")
            - total(ctx["stats0"], "coalesce_batch_mean"))
    return rows / n
