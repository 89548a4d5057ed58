"""Mean wait of a request in the SLO tier's queue, from its submit to
the dispatcher popping it into a batch (``core/slo.py``
``ServingTier.queue_wait_s``, summed over admitted requests), per
request admitted in the window. A program without the counter reads
nothing."""
from bench.metrics._counters import delta

LAYER = "SLO tier and coalescer"
SOURCE = "program_counter"
UNIT = "ms"
BETTER = "lower"
MOVES = {"open": "p50_ms"}


def read(ctx):
    if "queue_wait_s" not in ctx["stats1"]:
        return None
    n = delta(ctx, "coalesce_requests")
    if n <= 0:
        return None
    return delta(ctx, "queue_wait_s") / n * 1e3
