"""Open-loop arrivals: requests sent on a schedule, whatever the answers.

Mix parameters (the cell's ``traffic_params``):

- ``rate_qps``: offered queries per second;
- ``rows_per_request``: queries in one request;
- ``warm_seconds``: seconds of the same traffic, on other queries, sent
  in set-up after the shape sweep.

Inter-arrival gaps are the exponential distribution's quantiles at
(i + 0.5) / n for the n requests of the window, shuffled by the seed:
Poisson-like arrivals in which every seed offers the same count and the
same set of gaps, in another order. Each request is timed from its due
time, so a stall delays every request due while it lasts.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench.data import rng_for
from bench.window import Request, pow2_sizes


class Traffic:
    def __init__(self, params, seconds, seed, max_batch):
        self.rate = float(params["rate_qps"])
        self.rows = int(params["rows_per_request"])
        self.warm_seconds = float(params.get("warm_seconds", 0.0))
        self.max_batch = max_batch
        self.offsets = self._offsets(seconds, seed, 2)
        self.warm_offsets = self._offsets(self.warm_seconds, seed, 3)

    def _offsets(self, seconds, seed, stream):
        n = int(round(self.rate / self.rows * seconds))
        if n == 0:
            return np.zeros(0)
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) * self.rows / self.rate
        rng_for(seed, stream).shuffle(gaps)
        return np.cumsum(gaps)

    def query_rows(self):
        """Query rows this mix draws: the window's, then the warm-up's."""
        return (len(self.offsets) + len(self.warm_offsets)) * self.rows

    def insert_room(self):
        """Ids beyond the initial set that this mix may insert: none."""
        return 0

    def warm(self, engine, queries, updates):
        """Compile and load every batch shape the coalescer can form (the
        engine pads a dispatch to a power of two rows), then send
        ``warm_seconds`` of this traffic."""
        for b in pow2_sizes(self.max_batch):
            for rep in range(2):
                engine.search(queries[-(rep + 1) * b:][:b])
        w = queries[len(self.offsets) * self.rows:]
        self._send(engine, w, self.warm_offsets, time.perf_counter())

    def run(self, engine, queries, updates, t0, seconds):
        return self._send(engine, queries, self.offsets, t0), []

    def _send(self, engine, queries, offsets, t0):
        reqs = [Request(due=t0 + off,
                        queries=queries[i * self.rows:(i + 1) * self.rows])
                for i, off in enumerate(offsets)]
        sent: queue.Queue = queue.Queue()
        end = t0 + (offsets[-1] if len(offsets) else 0.0)

        def wait_all():
            while True:
                r = sent.get()
                if r is None:
                    return
                try:
                    with TraceAnnotation("bench.wait"):
                        r.ids, r.dists = r.future.result(
                            timeout=max(1.0, end + 60.0 - time.perf_counter()))
                except Exception as e:     # failed or never came
                    r.error = e
                r.done = time.perf_counter()
                r.future = None

        waiter = threading.Thread(target=wait_all, daemon=True)
        waiter.start()
        try:
            for r in reqs:
                gap = r.due - time.perf_counter()
                if gap > 0:
                    time.sleep(gap)
                r.sent = time.perf_counter()
                try:
                    with TraceAnnotation("bench.submit"):
                        r.future = engine.submit_search(r.queries)
                except Exception as e:     # refused at submit
                    r.error, r.done = e, time.perf_counter()
                    continue
                sent.put(r)
        finally:
            sent.put(None)
            waiter.join(timeout=max(1.0, end + 120.0 - time.perf_counter()))
        return reqs
