"""Paper Fig. 9 + 10: replacement strategies (WAVP vs LRU/LFU/LRFU vs
no-WAVP) and GPU-memory-ratio sweep."""
from __future__ import annotations

import numpy as np

from benchmarks.common import SVFusionAdapter, csv_row, run_workload
from repro.train.data import sliding_window


def run_policy(policy, n, dim, cache_slots, max_steps=50):
    idx = SVFusionAdapter(dim, degree=16, cache_slots=cache_slots,
                          capacity=1 << 15, policy=policy)
    wl = sliding_window(n=n, dim=dim, t_max=40)
    m = run_workload(idx, wl, max_steps=max_steps,
                     name=f"cache/{policy}")
    return m.summary()


def main(n=4000, dim=32):
    results = {}
    # Fig 9: replacement strategies at fixed cache size
    for policy in ("wavp", "lrfu", "lfu", "lru", "never"):
        s = run_policy(policy, n, dim, cache_slots=512)
        results[("policy", policy)] = s
        csv_row(f"fig9_policy_{policy}", 1e6 / max(s["search_qps"], 1e-9),
                recall=s["recall"], search_qps=s["search_qps"],
                p99_ms=s["search_p99_ms"], miss_rate=s.get("miss_rate", 0))
    # Fig 10: memory-ratio sweep (cache slots as % of live set ~2000)
    for ratio in (0.2, 0.4, 0.6, 0.8, 1.0):
        slots = int(2000 * ratio)
        s = run_policy("wavp", n, dim, cache_slots=slots)
        results[("ratio", ratio)] = s
        csv_row(f"fig10_ratio_{int(ratio*100)}",
                1e6 / max(s["search_qps"], 1e-9),
                search_qps=s["search_qps"], miss_rate=s.get("miss_rate", 0))
    return results


if __name__ == "__main__":
    main()
