"""Host time of WAVP placement per executor call: the seconds of the
window inside the engine's ``svf.wavp_place`` spans (``apply_wavp_host``
and the wait for ``_cache_lock``, ``core/engine.py``), over the window's
executor calls. A trace without those spans reads nothing."""
from bench.metrics._counters import search_batches
from bench.trace_reduce import clip

LAYER = "tier cascade"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = {"open": "p50_ms", "churn": "qps"}


def read(ctx):
    t = ctx["trace"]
    n = search_batches(ctx)
    if t is None or n <= 0:
        return None
    spans = clip(t.spans({"svf.wavp_place"}), t.w0, t.w1)
    if not spans:
        return None
    return sum(e - s for s, e in spans) * 1e-9 / n * 1e3
