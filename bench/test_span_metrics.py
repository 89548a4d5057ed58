"""CPU tests of the per-layer readers of the engine's own spans and
counters: each reads its number from a window's counter deltas or from
the ``svf.*`` spans of a trace, and reads nothing, without raising, from
a program that has neither."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness, trace_reduce  # noqa: E402

MS = 1e6
# counters of a program without this instrumentation, then with it
OLD0 = {"coalesce_dispatches": 10, "coalesce_requests": 100}
OLD1 = {"coalesce_dispatches": 30, "coalesce_requests": 500}
NEW0 = dict(OLD0, queue_wait_s=1.0, h2d_bytes=1_000_000,
            store_lock_wait_s=0.5)
NEW1 = dict(OLD1, queue_wait_s=41.0, h2d_bytes=61_000_000,
            store_lock_wait_s=1.3)


def _trace(svf):
    """A window of 0-100 ms on one TPU, with the host spans ``svf``."""
    return trace_reduce.Reduced({
        "devices": {"/device:TPU:0": [("%fusion.1 = f32[8]{0} fusion()",
                                       10 * MS, 8 * MS)]},
        "host": [("python", "bench.window", 0.0, 100 * MS)] + svf})


SPANS = [("python", "svf.wavp_place", 20 * MS, 30 * MS),
         ("python", "svf.wavp_place", 90 * MS, 20 * MS),   # ends past close
         ("python", "svf.insert", -10 * MS, 40 * MS),
         ("python", "svf.insert", 50 * MS, 20 * MS)]


@pytest.mark.parametrize("name,value", [
    ("queue_wait_ms.open", 40.0 / 400 * 1e3),
    ("staged_mb_per_batch.open", 60e6 / 20 / 1e6),
    ("staged_mb_per_batch.churn", 60e6 / 20 / 1e6),
    ("store_lock_wait_ms.churn", 0.8 / 20 * 1e3),
    ("wavp_ms_per_batch.open", (30 + 10) / 20),
    ("wavp_ms_per_batch.churn", (30 + 10) / 20),
    ("insert_ms_per_step.churn", (40 + 20) / 2),
])
def test_reader_reads_the_engines_spans_and_counters(name, value):
    reader = harness.metric_reader(name)
    new = {"stats0": NEW0, "stats1": NEW1, "trace": _trace(SPANS)}
    old = {"stats0": OLD0, "stats1": OLD1, "trace": _trace([])}
    assert reader.read(new) == pytest.approx(value)
    assert reader.read(old) is None
    assert reader.read(dict(old, trace=None)) is None
