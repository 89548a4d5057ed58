"""Mean duration of the engine's ``svf.insert`` spans in the window
(``core/engine.py``: one ``engine.insert`` call, candidate search,
linking, WAL append, store writes and topology install), in ms. A
trace without those spans reads nothing."""
LAYER = "update path"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = {"churn": "updates_per_s"}


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    spans = t.spans({"svf.insert"})
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e-6
