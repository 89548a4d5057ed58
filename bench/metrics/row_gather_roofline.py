"""``row_gather``'s share of the HBM roofline over the traced window: the
bytes its calls need (``roofline.row_gather_bytes`` at the graph's
degree, not the wrapper's 128-lane padding) over the bytes the chip's
HBM moves in the calls' device time."""
from bench import roofline

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = {"open": "p50_ms", "churn": "qps"}


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx.get("device_kind"):
        return None
    calls = t.kernel_calls("row_gather")
    if not calls:
        return None
    r = ctx["config"]["degree"]
    total = sum(roofline.row_gather_bytes(shp[0][1][0], shp[0][1][1], r)
                for _, shp in calls)
    return roofline.share(total, sum(s for s, _ in calls),
                          ctx["device_kind"])
