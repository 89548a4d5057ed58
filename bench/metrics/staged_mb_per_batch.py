"""Host-to-device bytes staged per executor call: every host array the
search and update paths put on the device (queries, ids and masks,
re-rank vectors, the PQ codes and topology table re-put when dirty;
the engine's ``h2d_bytes``), over the window's executor calls, in MB
(1e6 bytes). A program without the counter reads nothing."""
from bench.metrics._counters import delta, search_batches

LAYER = "executor shell"
SOURCE = "program_counter"
UNIT = "MB"
BETTER = "lower"
MOVES = {"open": "p50_ms", "churn": "qps"}


def read(ctx):
    if "h2d_bytes" not in ctx["stats1"]:
        return None
    n = search_batches(ctx)
    if n <= 0:
        return None
    return delta(ctx, "h2d_bytes") / n / 1e6
