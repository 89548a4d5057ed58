"""Paper Fig. 15: CPU-GPU search methods vs dataset scale relative to
device-memory capacity (cache covers 100% .. 10% of the data)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import SVFusionAdapter, csv_row, exact_topk, recall


def run_method(name, dim, data, queries, cache_slots):
    if name == "svfusion":
        idx = SVFusionAdapter(dim, degree=16, cache_slots=cache_slots,
                              capacity=1 << 15, policy="wavp")
    elif name == "uvm_like":       # promote every miss (UVM behavior)
        idx = SVFusionAdapter(dim, degree=16, cache_slots=cache_slots,
                              capacity=1 << 15, policy="always")
    elif name == "cpu_only":       # never use the bandwidth tier
        idx = SVFusionAdapter(dim, degree=16, cache_slots=cache_slots,
                              capacity=1 << 15, policy="never")
    else:
        raise ValueError(name)
    ids = idx.insert(data)
    id2row = {int(i): r for r, i in enumerate(ids)}
    idx.search(queries[:8])   # warm
    t0 = time.perf_counter()
    found = idx.search(queries)
    dt = time.perf_counter() - t0
    truth_rows = exact_topk(np.asarray(ids), data, queries, 10)
    rec = recall(found, truth_rows)
    s = idx.stats()
    return {"qps": len(queries) / dt, "recall": rec,
            "miss_rate": s["miss_rate"],
            "transfers": s["transfers"]}


def main(n=5000, dim=32):
    rng = np.random.default_rng(0)
    data = rng.normal(size=(n, dim)).astype(np.float32)
    queries = rng.normal(size=(128, dim)).astype(np.float32)
    results = {}
    for frac in (1.0, 0.5, 0.25, 0.1):
        slots = max(64, int(n * frac))
        for method in ("svfusion", "uvm_like", "cpu_only"):
            r = run_method(method, dim, data, queries, slots)
            results[(frac, method)] = r
            csv_row(f"fig15_scale{int(1/frac)}x_{method}",
                    1e6 / max(r["qps"], 1e-9), **r)
    return results


if __name__ == "__main__":
    main()
