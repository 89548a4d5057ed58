"""Share of the window's vector accesses served by the device's exact
hot cache (``core/cache.py``, ``core/tiers.py``): hits over accesses."""
from bench.metrics._counters import delta

LAYER = "tier cascade"
SOURCE = "program_counter"
UNIT = "ratio"
BETTER = "higher"
MOVES = {"open": "p50_ms", "churn": "qps"}


def read(ctx):
    acc = delta(ctx, "accesses")
    if acc <= 0:
        return None
    return 1.0 - delta(ctx, "misses") / acc
