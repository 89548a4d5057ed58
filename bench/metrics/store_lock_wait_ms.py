"""Seconds threads waited to take the tiered store's lock
(``core/tiers.py`` ``TieredStore.lock_wait_s``: the epoch fence's
re-read, the insert's write-through and every search fetch cross it),
per executor call in the window, in ms. A program without the counter
reads nothing."""
from bench.metrics._counters import delta, search_batches

LAYER = "tier cascade"
SOURCE = "program_counter"
UNIT = "ms"
BETTER = "lower"
MOVES = {"churn": "qps"}


def read(ctx):
    if "store_lock_wait_s" not in ctx["stats1"]:
        return None
    n = search_batches(ctx)
    if n <= 0:
        return None
    return delta(ctx, "store_lock_wait_s") / n * 1e3
