"""Shared benchmark harness.

Adapters give every index (SVFusion + baselines) the same API; the runner
replays a streaming workload, maintaining an exact ground-truth mirror for
recall, and reports recall / search-qps / insert-qps / p-latencies /
miss-rate.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from repro.core.engine import EngineConfig, SVFusionEngine
from repro.core.types import SearchParams
from repro.utils import percentile


class SVFusionAdapter:
    name = "svfusion"

    def __init__(self, dim, degree=16, cache_slots=1024, capacity=1 << 16,
                 policy="wavp", pool=64, sync=True, seed=0, alpha=1.0,
                 beta=1.0):
        sp = SearchParams(k=10, pool=pool, max_iters=96, policy=policy)
        self.engine = SVFusionEngine(
            np.zeros((8, dim), np.float32) + np.arange(8)[:, None],
            EngineConfig(degree=degree, cache_slots=cache_slots,
                         capacity=capacity, search=sp, sync=sync, seed=seed))
        # the 8 seed rows are placeholders; mark them deleted
        self.engine.delete(np.arange(8))
        import jax.numpy as jnp
        st = self.engine.state
        self.engine._state = st._replace(cache=st.cache._replace(
            alpha=jnp.float32(alpha), beta=jnp.float32(beta)))

    def insert(self, vectors):
        return self.engine.insert(vectors)

    def delete(self, ids):
        self.engine.delete(ids)

    def search(self, queries, k=10):
        ids, _ = self.engine.search(queries)
        return ids[:, :k]

    def stats(self):
        return self.engine.stats()


@dataclass
class RunMetrics:
    name: str
    recalls: list = field(default_factory=list)
    search_lat: list = field(default_factory=list)
    insert_lat: list = field(default_factory=list)
    n_queries: int = 0
    n_inserted: int = 0
    n_deleted: int = 0
    extra: dict = field(default_factory=dict)

    def summary(self) -> dict:
        st = sum(self.search_lat) or 1e-9
        it = sum(self.insert_lat) or 1e-9
        return {
            "name": self.name,
            "recall": float(np.mean(self.recalls)) if self.recalls else 0.0,
            "search_qps": self.n_queries / st,
            "insert_qps": self.n_inserted / it,
            "search_p50_ms": percentile(self.search_lat, 50) * 1e3,
            "search_p99_ms": percentile(self.search_lat, 99) * 1e3,
            "insert_p99_ms": percentile(self.insert_lat, 99) * 1e3,
            **self.extra,
        }


def exact_topk(mirror_ids, mirror_vecs, queries, k):
    if len(mirror_ids) == 0:
        return np.full((len(queries), k), -2, np.int64)
    d = ((queries[:, None, :] - mirror_vecs[None, :, :]) ** 2).sum(-1)
    order = np.argsort(d, axis=1)[:, :k]
    return mirror_ids[order]


def recall(found, truth):
    hits = (found[:, :, None] == truth[:, None, :]).any(1)
    return float(hits.mean())


def run_workload(index, workload, k=10, name=None, max_steps=None) -> RunMetrics:
    m = RunMetrics(name or getattr(index, "name", type(index).__name__))
    id2vec: dict[int, np.ndarray] = {}
    for step_no, op in enumerate(workload):
        if max_steps and step_no >= max_steps:
            break
        if op.kind == "insert":
            t0 = time.perf_counter()
            ids = index.insert(op.vectors)
            m.insert_lat.append(time.perf_counter() - t0)
            m.n_inserted += len(ids)
            for i, v in zip(ids, op.vectors):
                id2vec[int(i)] = v
        elif op.kind == "delete":
            ids = np.asarray(op.ids).ravel()
            index.delete(ids)
            m.n_deleted += len(ids)
            for i in ids:
                id2vec.pop(int(i), None)
        else:
            t0 = time.perf_counter()
            found = index.search(op.queries, k=k)
            m.search_lat.append(time.perf_counter() - t0)
            m.n_queries += len(op.queries)
            mid = np.fromiter(id2vec.keys(), np.int64, len(id2vec))
            mv = np.stack([id2vec[int(i)] for i in mid]) if len(mid) else \
                np.zeros((0, op.queries.shape[1]), np.float32)
            truth = exact_topk(mid, mv, op.queries, k)
            m.recalls.append(recall(found, truth))
    if hasattr(index, "stats"):
        s = index.stats()
        m.extra["miss_rate"] = s.get("miss_rate", 0.0)
    if hasattr(index, "rebuilds"):
        m.extra["rebuilds"] = index.rebuilds
    return m


def csv_row(name, us_per_call, **derived):
    kv = ",".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                  for k, v in derived.items())
    print(f"{name},{us_per_call:.1f},{kv}", flush=True)
