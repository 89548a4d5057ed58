"""Device dispatches per executor call in the window (entry, fused
rounds, host re-entries, re-rank): the executor shell's count
(``core/search.py``; the engine's ``search_dispatches_per_batch``)."""
from bench.metrics._counters import search_batches, total

LAYER = "executor shell"
SOURCE = "program_counter"
UNIT = "dispatches"
BETTER = "lower"
MOVES = {"open": "p50_ms", "churn": "qps"}


def read(ctx):
    n = search_batches(ctx)
    if n <= 0:
        return None
    return (total(ctx["stats1"], "search_dispatches_per_batch")
            - total(ctx["stats0"], "search_dispatches_per_batch")) / n
