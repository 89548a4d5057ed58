"""``pq_adc``'s share of the HBM roofline over the traced window: the
bytes its calls need (``roofline.pq_adc_bytes``, unpadded) over the
bytes the chip's HBM moves in the calls' device time."""
from bench import roofline

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = {"open": "p50_ms", "churn": "qps"}


def _lanes(padded, config):
    """The candidates a call scores before the wrapper pads them to whole
    128-lane tiles: the entry pool, or a round's beam x degree."""
    for c in (config["beam"] * config["degree"], config["pool"]):
        if -(-c // 128) * 128 == padded:
            return c
    return padded


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx.get("device_kind"):
        return None
    calls = t.kernel_calls("pq_adc")
    if not calls:
        return None
    total, secs = 0, 0.0
    for s, shp in calls:
        (_, (b, _, cp)), (_, (_, m, k)) = shp[0], shp[1]
        total += roofline.pq_adc_bytes(b, _lanes(cp, ctx["config"]), m, k)
        secs += s
    return roofline.share(total, secs, ctx["device_kind"])
