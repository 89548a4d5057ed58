"""What a window records, and the reductions the harness makes of it.

A ``Request`` is one search request: when it was due, sent and answered,
its queries and its answer, and, for a filtered request, the ids its
filter admits. An ``Op`` is one acknowledged (or failed)
insert or delete. The end-to-end metrics are taken from these records by
the host's clock alone; nothing is read from the engine's own timings.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np


@dataclass
class Request:
    due: float
    queries: np.ndarray
    sent: Optional[float] = None
    done: Optional[float] = None
    future: Any = None
    ids: Optional[np.ndarray] = None
    dists: Optional[np.ndarray] = None
    error: Optional[BaseException] = None
    probe_ids: Optional[np.ndarray] = None   # ids probed by the last rows
    # the ids the request's filter admits, a bool mask over every id the
    # run can store (None: every id); requests of one filter share one mask
    allowed: Optional[np.ndarray] = None

    @property
    def answered(self) -> bool:
        return self.error is None and self.ids is not None


@dataclass
class Op:
    kind: str                       # "insert" | "delete"
    start: float
    ack: Optional[float] = None
    ids: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    error: Optional[BaseException] = None


def pow2_sizes(limit: int):
    """1, 2, 4, ... up to ``limit``: the batch shapes a padding engine
    compiles."""
    b = 1
    while b <= limit:
        yield b
        b *= 2


def nearest_rank(values, p: float) -> float:
    """The p-th percentile by nearest rank: the smallest value with at
    least p% of the values at or below it."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(p / 100.0 * len(v)) - 1)]


def latencies_ms(reqs, t0, t1, give_up):
    """Due-to-answer times of the requests due in [t0, t1), in ms. A
    request that failed or never came counts as answered at ``give_up``,
    the moment the run stopped waiting for it."""
    out = []
    for r in reqs:
        if not t0 <= r.due < t1:
            continue
        end = r.done if r.answered and r.done is not None else give_up
        out.append((max(end, r.due) - r.due) * 1e3)
    return out
