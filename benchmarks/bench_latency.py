"""Paper Fig. 8: p50/p95/p99 search+insert latency vs offered QPS
(open-loop arrivals via the multi-stream runner). Search requests flow
through the engine's cross-query coalescing scheduler, so higher offered
rates should show deeper merged micro-batches (``coalesce_batch_mean``)
rather than proportionally higher dispatch counts. Inserts run on a
stream of their own, each timed here."""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from benchmarks.common import SVFusionAdapter, csv_row
from repro.core.engine import MultiStreamRunner
from repro.utils import percentile


def main(n=4000, dim=32, rates=(200, 1000, 4000), duration=3.0):
    rng = np.random.default_rng(0)
    base = rng.normal(size=(n, dim)).astype(np.float32)
    results = {}
    for rate in rates:
        idx = SVFusionAdapter(dim, degree=16, cache_slots=768,
                              capacity=1 << 15)
        idx.insert(base)
        idx.search(rng.normal(size=(8, dim)).astype(np.float32))  # warm jit
        idx.search(rng.normal(size=(64, dim)).astype(np.float32))
        runner = MultiStreamRunner(idx.engine, n_search_streams=2,
                                   max_batch=64, batch_timeout=0.002)
        runner.start()
        ins: list = []
        todo: queue.Queue = queue.Queue()

        def inserter():
            while (vecs := todo.get()) is not None:
                t0 = time.perf_counter()
                idx.insert(vecs)
                ins.append(time.perf_counter() - t0)

        th = threading.Thread(target=inserter, daemon=True)
        th.start()
        t_end = time.perf_counter() + duration
        interval = 8.0 / rate                    # 8 queries per request
        nsub = 0
        while time.perf_counter() < t_end:
            runner.submit_search(
                rng.normal(size=(8, dim)).astype(np.float32), tag=nsub)
            if nsub % 10 == 0:
                todo.put(rng.normal(size=(8, dim)).astype(np.float32))
            nsub += 1
            time.sleep(interval)
        todo.put(None)
        th.join()
        runner.drain_and_stop()
        lats = sorted(r[2] for r in runner.results)
        ins.sort()
        est = idx.engine.stats()
        s = {
            "p50_ms": percentile(lats, 50) * 1e3,
            "p95_ms": percentile(lats, 95) * 1e3,
            "p99_ms": percentile(lats, 99) * 1e3,
            "insert_p99_ms": percentile(ins, 99) * 1e3 if ins else 0.0,
            "completed": len(lats),
            "coalesce_batch_mean": est.get("coalesce_batch_mean", 1.0),
        }
        results[rate] = s
        csv_row(f"fig8_qps_{rate}", s["p50_ms"] * 1e3, **s)
    return results


if __name__ == "__main__":
    main()
