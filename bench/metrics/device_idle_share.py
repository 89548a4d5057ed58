"""Share of the traced window in which no operation ran on the device."""
LAYER = "device"
SOURCE = "device_trace"
UNIT = "ratio"
BETTER = "lower"
MOVES = {"open": "p50_ms", "churn": "qps"}


def read(ctx):
    t = ctx["trace"]
    if t is None or t.window_s <= 0:
        return None
    return 1.0 - t.busy_s / t.window_s
