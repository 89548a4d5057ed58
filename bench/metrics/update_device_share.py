"""Device-busy share of the time inside the benchmark's own spans around
``engine.insert`` and ``engine.delete`` (``core/update.py``): how much
of an update step the device works, and how much the host."""
LAYER = "update path"
SOURCE = "device_trace"
UNIT = "ratio"
BETTER = "higher"
MOVES = {"churn": "updates_per_s"}


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return t.span_busy_share({"bench.insert", "bench.delete"})
