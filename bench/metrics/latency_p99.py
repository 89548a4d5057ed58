"""99th percentile (nearest rank) of the window's due-to-answer times,
by the host's clock: how long the SLO tier and coalescer make the
slowest requests wait. Read in the traced run, whose tracer slows the
host, so it reads above an untraced run's."""
import math

LAYER = "SLO tier and coalescer"
SOURCE = "host_clock"
UNIT = "ms"
BETTER = "lower"
MOVES = {"open": "p50_ms"}


def read(ctx):
    v = ctx["e2e"]["p99_ms"]
    return None if math.isnan(v) else v
