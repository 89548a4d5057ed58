"""Epoch-fence wholesale re-reads of the resident topology rows
(``TopoCache.validate``) per executor call in the window."""
from bench.metrics._counters import delta, search_batches

LAYER = "tier cascade"
SOURCE = "program_counter"
UNIT = "flushes"
BETTER = "lower"
MOVES = {"churn": "qps"}


def read(ctx):
    n = search_batches(ctx)
    if n <= 0:
        return None
    return delta(ctx, "topo_flushes") / n
