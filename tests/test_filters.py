"""Filtered & hybrid search property suite: filter-spec compilation /
canonicalization, host-vs-device predicate bit-parity, filtered search
(graph lane AND brute-force fallback lane) bit-compared against exact
post-filtering of an unfiltered full scan across selectivities
{100%, 50%, ~1%, 0 matches}, interleaved insert/delete epoch flushes,
selectivity-router engagement, coalescer filter-compatibility demux, and
per-tenant token-bucket rate limits at the SLO admission gate."""
import tempfile
import threading

import numpy as np
import pytest

from repro.core import cache as C
from repro.core import update
from repro.core.build import build_tiered_backend
from repro.core.engine import EngineConfig, SVFusionEngine
from repro.core.filters import (AttributeSchema, FilterSpec, compile_filter,
                                device_pass_mask, estimate_selectivity,
                                host_pass)
from repro.core.search import search_tiered
from repro.core.tiers import AttributeStore
from repro.core.types import SearchParams

SCHEMA = AttributeSchema(tag_fields=("cat",), num_fields=("score",))


def _mk_attrs(n, rng=None):
    """Deterministic attribute columns: cat = i % 4, score = i / n."""
    return {"cat": np.arange(n) % 4, "score": np.arange(n) / max(n, 1)}


def _attach(be, n):
    a = _mk_attrs(n)
    tags, nums = SCHEMA.coerce(a, n)
    be.attach_attrs(AttributeStore(SCHEMA, be.capacity, tags=tags,
                                   nums=nums))


# selectivity cases over cat = i % 4, score = i / n (n ~ 200):
#   100%  — all-pass numeric range
#   50%   — cat in {0, 1}
#   ~1%   — score in [0, 0.011)
#   0     — impossible range
CASES = [
    ("all", FilterSpec(ranges={"score": (None, None)})),
    ("half", FilterSpec(tags={"cat": {0, 1}})),
    ("one_pct", FilterSpec(ranges={"score": (0.0, 0.011)})),
    ("none", FilterSpec(ranges={"score": (2.0, 3.0)})),
]


# ---------------------------------------------------------------------------
# FilterSpec / schema / predicate unit behavior
# ---------------------------------------------------------------------------

def test_filterspec_canonical_key_and_eq():
    a = FilterSpec(tags={"cat": {2, 0}}, ranges={"score": (0.1, None)})
    b = FilterSpec(tags={"cat": {0, 2}}, ranges={"score": (0.1, None)})
    assert a == b and hash(a) == hash(b) and a.key() == b.key()
    c = FilterSpec(tags={"cat": {0}})
    assert a != c and a.key() != c.key()
    with pytest.raises(ValueError):
        FilterSpec(tags={"cat": set()})          # empty tag set matches nothing


def test_schema_validation_and_meta_roundtrip():
    s = AttributeSchema(tag_fields=("a", "b"), num_fields=("x",),
                        tag_domain=16)
    assert AttributeSchema.from_meta(s.to_meta()) == s
    with pytest.raises(ValueError):
        AttributeSchema(tag_fields=("a",), tag_domain=64)   # > uint32 mask
    with pytest.raises(ValueError):
        compile_filter(FilterSpec(tags={"zzz": {0}}), s)    # unknown field
    with pytest.raises(ValueError):
        compile_filter(FilterSpec(tags={"a": {16}}), s)     # out of domain


def test_host_device_predicate_bit_parity():
    rng = np.random.default_rng(0)
    n = 257
    tags = (np.arange(n) % 4)[:, None].astype(np.int32)
    nums = rng.uniform(size=(n, 1)).astype(np.float32)
    be_attrs = AttributeStore(SCHEMA, 512, tags=tags, nums=nums)
    for _, spec in CASES:
        cf = compile_filter(spec, SCHEMA)
        hm = host_pass(cf, be_attrs.tags, be_attrs.nums)
        dm = np.asarray(device_pass_mask(be_attrs, cf))
        np.testing.assert_array_equal(hm, dm)


def test_estimate_selectivity_small_n_exact_and_deterministic():
    n = 200
    tags, nums = SCHEMA.coerce(_mk_attrs(n), n)
    attrs = AttributeStore(SCHEMA, 512, tags=tags, nums=nums)
    alive = np.zeros(512, bool)
    alive[:n] = True
    cf = compile_filter(FilterSpec(tags={"cat": {0, 1}}), SCHEMA)
    s1 = estimate_selectivity(cf, attrs, alive, n)
    s2 = estimate_selectivity(cf, attrs, alive, n)
    assert s1 == s2 == 0.5           # n <= sample: exact fraction


# ---------------------------------------------------------------------------
# bit-parity vs exact post-filtering of an unfiltered full scan
# ---------------------------------------------------------------------------

def _post_filter_topk(ids, dists, hmask, k):
    """Exact reference: post-filter an unfiltered k=pool result row-wise,
    keep the first k passing entries, pad with -1/+inf."""
    B = ids.shape[0]
    out_i = np.full((B, k), -1, np.int64)
    out_d = np.full((B, k), np.inf, np.float32)
    for b in range(B):
        keep = [(i, d) for i, d in zip(ids[b], dists[b])
                if i >= 0 and np.isfinite(d) and hmask[i]][:k]
        for j, (i, d) in enumerate(keep):
            out_i[b, j], out_d[b, j] = i, d
    return out_i, out_d


def _parity_setup(td, n=220, D=12, deg=8):
    rng = np.random.default_rng(3)
    vecs = rng.normal(size=(n, D)).astype(np.float32)
    queries = rng.normal(size=(4, D)).astype(np.float32)
    be = build_tiered_backend(vecs, deg, td, host_window=64,
                              disk_capacity=512)
    _attach(be, n)
    hp = C.HostPlacement(be.capacity, 16, D)
    return be, hp, vecs, queries


def _entries(n, pool, B):
    """Entry pool covering every id (pool >= n): the entry stage alone
    evaluates the whole dataset, so top-k == exact top-k."""
    return np.tile(np.clip(np.arange(pool), 0, n - 1)[None], (B, 1))


@pytest.mark.parametrize("name,spec", CASES)
@pytest.mark.parametrize("lane", ["graph", "fallback"])
def test_filtered_exact_lane_bit_parity(name, spec, lane):
    """Exact arm: filtered results must be BIT-identical (ids and dists)
    to post-filtering an unfiltered full scan, on both the graph lane
    (threshold 0 -> never fall back) and the forced brute-force lane
    (threshold 1.1 -> always fall back)."""
    pool = 256
    sp = SearchParams(k=10, pool=pool, max_iters=8, beam=2)
    spf = SearchParams(k=pool, pool=pool, max_iters=8, beam=2)
    thresh = 0.0 if lane == "graph" else 1.1
    with tempfile.TemporaryDirectory() as td:
        be, hp, vecs, queries = _parity_setup(td)
        try:
            n = int(be.n)
            ent = _entries(n, pool, len(queries))
            ref = search_tiered(be, hp, queries, 0, spf, entry_ids=ent)
            cf = compile_filter(spec, SCHEMA)
            hmask = host_pass(cf, be.attrs.tags, be.attrs.nums)
            want_i, want_d = _post_filter_topk(
                np.asarray(ref.ids), np.asarray(ref.dists), hmask, sp.k)
            got = search_tiered(be, hp, queries, 0, sp, entry_ids=ent,
                                filter=spec,
                                filter_fallback_selectivity=thresh)
            np.testing.assert_array_equal(np.asarray(got.ids), want_i)
            np.testing.assert_array_equal(np.asarray(got.dists), want_d)
            assert got.filter_path == ("fallback" if lane == "fallback"
                                       else "graph")
        finally:
            be.close()


@pytest.mark.parametrize("lane", ["graph", "fallback"])
def test_filtered_pq_lane_bit_parity(lane):
    """PQ arm with a lossless codebook and rerank_depth == pool: filtered
    results bit-identical to post-filtering the unfiltered PQ run."""
    from test_pq import _lossless_codes
    pool = 256
    sp = SearchParams(k=10, pool=pool, max_iters=8, beam=2)
    spf = SearchParams(k=pool, pool=pool, max_iters=8, beam=2)
    thresh = 0.0 if lane == "graph" else 1.1
    with tempfile.TemporaryDirectory() as td:
        be, hp, vecs, queries = _parity_setup(td)
        try:
            n = int(be.n)
            pq = _lossless_codes(vecs, be.capacity)
            be.attach_pq(pq)
            ent = _entries(n, pool, len(queries))
            ref = search_tiered(be, hp, queries, 0, spf, entry_ids=ent,
                                pq=pq, rerank_depth=pool)
            for name, spec in CASES:
                cf = compile_filter(spec, SCHEMA)
                hmask = host_pass(cf, be.attrs.tags, be.attrs.nums)
                want_i, want_d = _post_filter_topk(
                    np.asarray(ref.ids), np.asarray(ref.dists), hmask,
                    sp.k)
                got = search_tiered(be, hp, queries, 0, sp, entry_ids=ent,
                                    pq=pq, rerank_depth=pool, filter=spec,
                                    filter_fallback_selectivity=thresh)
                np.testing.assert_array_equal(np.asarray(got.ids), want_i,
                                              err_msg=name)
                np.testing.assert_array_equal(np.asarray(got.dists),
                                              want_d, err_msg=name)
        finally:
            be.close()


def test_filtered_parity_across_interleaved_updates():
    """Insert (attribute-bearing) and delete between filtered searches:
    parity must hold at every epoch — fresh ids become filterable the
    moment their INSERT applies, deleted ids vanish from every lane."""
    pool = 256
    sp = SearchParams(k=10, pool=pool, max_iters=8, beam=2)
    spf = SearchParams(k=pool, pool=pool, max_iters=8, beam=2)
    spec = FilterSpec(tags={"cat": {0, 1}})
    rng = np.random.default_rng(5)
    with tempfile.TemporaryDirectory() as td:
        be, hp, vecs, queries = _parity_setup(td, n=180)
        try:
            def check():
                n = int(be.n)
                ent = _entries(n, pool, len(queries))
                ref = search_tiered(be, hp, queries, 0, spf,
                                    entry_ids=ent)
                cf = compile_filter(spec, SCHEMA)
                hmask = host_pass(cf, be.attrs.tags, be.attrs.nums)
                wi, wd = _post_filter_topk(np.asarray(ref.ids),
                                           np.asarray(ref.dists), hmask,
                                           sp.k)
                got = search_tiered(be, hp, queries, 0, sp,
                                    entry_ids=ent, filter=spec,
                                    filter_fallback_selectivity=0.0)
                np.testing.assert_array_equal(np.asarray(got.ids), wi)
                np.testing.assert_array_equal(np.asarray(got.dists), wd)
                return got

            check()
            for round_ in range(2):
                n0 = int(be.n)
                newv = rng.normal(size=(20, 12)).astype(np.float32)
                new_attrs = {"cat": np.arange(n0, n0 + 20) % 4,
                             "score": np.full(20, 0.5)}
                ids, _ = update.insert_tiered(be, hp, newv, sp, 7,
                                              attributes=new_attrs)
                check()
                # delete a slice that includes filter-passing ids
                update.delete_tiered(be, np.asarray(ids[:8]))
                got = check()
                assert not np.isin(np.asarray(ids[:8]),
                                   np.asarray(got.ids)).any()
        finally:
            be.close()


@pytest.mark.parametrize("pq", [False, True], ids=["exact", "pq"])
def test_graph_lane_recall_at_ten_percent(tmp_path, pq):
    """10% selectivity served on the graph lane through the engine:
    recall@10 >= 0.9 (the repo's gate for this lane) against the exact
    top-10 over the passing set. Nodes that fail the filter must stay
    traversable: a walk restricted to passing nodes measured 0.43 here
    (and below 0.9 at every size from 120 to 8,000 ids)."""
    rng = np.random.default_rng(0)
    n, d = 1200, 16
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    queries = rng.normal(size=(64, d)).astype(np.float32)
    cat = rng.integers(0, 10, n)
    eng = SVFusionEngine(vecs, EngineConfig(
        degree=16, cache_slots=64, capacity=2048,
        disk_path=str(tmp_path / "t"), disk_capacity=2048, host_window=512,
        search=SearchParams(k=10, pool=64, max_iters=64, beam=16),
        attributes=SCHEMA, filter_fallback_selectivity=0.05,
        pq_enabled=pq, pq_m=8, coalesce=False),
        init_attrs={"cat": cat, "score": np.zeros(n)})
    try:
        ids, _ = eng.search(queries, filter=FilterSpec(tags={"cat": {0}}))
        assert eng.stats()["filter_last_path"] == "graph"
        assert (cat[ids[ids >= 0]] == 0).all()
        idx = np.where(cat == 0)[0]
        d2 = ((vecs[idx][None] - queries[:, None]) ** 2).sum(-1)
        truth = idx[np.argsort(d2, axis=1)[:, :10]]
        recall = np.mean([len(set(a) & set(b)) / 10
                          for a, b in zip(ids, truth)])
        assert recall >= 0.9, recall
    finally:
        eng.close()


def test_filtered_walk_pool_width():
    """The filtered walk holds ~k/selectivity nodes: a power of two, no
    narrower than the configured pool, at most four times it."""
    from repro.core.search import filtered_walk_pool
    assert filtered_walk_pool(64, 10, 1.0) == 64
    assert filtered_walk_pool(64, 10, 0.5) == 64
    assert filtered_walk_pool(64, 10, 0.1) == 128
    assert filtered_walk_pool(64, 10, 0.05) == 256
    assert filtered_walk_pool(64, 10, 0.001) == 256
    assert filtered_walk_pool(256, 10, 0.01) == 1024


def test_filter_requires_attribute_store():
    with tempfile.TemporaryDirectory() as td:
        rng = np.random.default_rng(0)
        vecs = rng.normal(size=(100, 8)).astype(np.float32)
        be = build_tiered_backend(vecs, 8, td, host_window=32,
                                  disk_capacity=256)
        hp = C.HostPlacement(be.capacity, 16, 8)
        try:
            with pytest.raises(ValueError, match="attribute store"):
                search_tiered(be, hp, vecs[:2], 0,
                              SearchParams(k=5, pool=32),
                              filter=FilterSpec(tags={"cat": {0}}))
            with pytest.raises(ValueError, match="attribute store"):
                update.insert_tiered(be, hp, vecs[:4],
                                     SearchParams(k=5, pool=32), 0,
                                     attributes={"cat": np.zeros(4)})
        finally:
            be.close()


# ---------------------------------------------------------------------------
# selectivity router + engine threading
# ---------------------------------------------------------------------------

def test_selectivity_router_and_stats(tmp_path):
    """Below-threshold filters auto-engage the brute-force fallback and
    the routing decision is visible in engine.stats()."""
    rng = np.random.default_rng(11)
    n, d = 400, 8
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    eng = SVFusionEngine(vecs, EngineConfig(
        degree=8, cache_slots=64, capacity=1024,
        disk_path=str(tmp_path / "t"), disk_capacity=1024,
        host_window=128, search=SearchParams(k=5, pool=64),
        attributes=SCHEMA, filter_fallback_selectivity=0.1,
        coalesce=False), init_attrs=_mk_attrs(n))
    try:
        q = vecs[:2]
        eng.search(q, filter=FilterSpec(tags={"cat": {0, 1}}))   # 50%
        st = eng.stats()
        assert st["filtered_searches"] == 1
        assert st["filter_fallbacks"] == 0
        assert st["filter_last_path"] == "graph"
        ids, dists = eng.search(
            q, filter=FilterSpec(ranges={"score": (0.0, 0.011)}))  # ~1%
        st = eng.stats()
        assert st["filter_fallbacks"] == 1
        assert st["filter_last_path"] == "fallback"
        assert st["filter_last_selectivity"] < 0.1
        assert (ids[ids >= 0] <= 4).all()        # score < 0.011 -> id <= 4
        eng.search(q)                             # unfiltered: counters idle
        assert eng.stats()["filtered_searches"] == 2
    finally:
        eng.close()


def test_engine_device_mode_rejects_filter():
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(200, 8)).astype(np.float32)
    eng = SVFusionEngine(vecs, EngineConfig(degree=8, capacity=512,
                                            coalesce=False))
    try:
        with pytest.raises(ValueError, match="three-tier"):
            eng.search(vecs[:1], filter=FilterSpec(tags={"cat": {0}}))
        with pytest.raises(ValueError, match="three-tier"):
            eng.insert(vecs[:1], attributes={"cat": [0]})
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# coalescer filter-compatibility demux
# ---------------------------------------------------------------------------

def test_coalescer_filter_demux(tmp_path):
    """Concurrent submissions with two distinct filter specs plus
    unfiltered traffic: only filter-spec-equal requests share a dispatch,
    every caller gets its own filter's results."""
    rng = np.random.default_rng(13)
    n, d = 400, 8
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    eng = SVFusionEngine(vecs, EngineConfig(
        degree=8, cache_slots=64, capacity=1024,
        disk_path=str(tmp_path / "t"), disk_capacity=1024,
        host_window=128, search=SearchParams(k=5, pool=64),
        attributes=SCHEMA, filter_fallback_selectivity=0.0,
        coalesce=True, coalesce_window=5e-3), init_attrs=_mk_attrs(n))
    try:
        spec_a = FilterSpec(tags={"cat": {0}})
        spec_b = FilterSpec(tags={"cat": {1}})
        # equal specs constructed independently must coalesce (key-equal)
        spec_a2 = FilterSpec(tags={"cat": {0}})
        q = rng.normal(size=(1, d)).astype(np.float32)
        futs = []
        for spec in [spec_a, spec_b, None, spec_a2, None, spec_b]:
            futs.append(eng.submit_search(q, filter=spec))
        outs = [f.result() for f in futs]
        for (ids, _), spec in zip(outs, [spec_a, spec_b, None, spec_a2,
                                         None, spec_b]):
            live = ids[ids >= 0]
            if spec is spec_a or spec is spec_a2:
                assert (live % 4 == 0).all()
            elif spec is spec_b:
                assert (live % 4 == 1).all()
        # unfiltered and the two specs can never share a dispatch
        st = eng.stats()
        assert st["coalesce_dispatches"] >= 3
    finally:
        eng.close()


def test_coalescer_demux_under_concurrency(tmp_path):
    """Hammer the scheduler from threads with mixed specs: every result
    must satisfy its own filter (a cross-spec merge would leak ids)."""
    rng = np.random.default_rng(17)
    n, d = 300, 8
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    eng = SVFusionEngine(vecs, EngineConfig(
        degree=8, cache_slots=64, capacity=1024,
        disk_path=str(tmp_path / "t"), disk_capacity=1024,
        host_window=128, search=SearchParams(k=5, pool=64),
        attributes=SCHEMA, filter_fallback_selectivity=0.0,
        coalesce=True, coalesce_window=2e-3), init_attrs=_mk_attrs(n))
    try:
        specs = [None, FilterSpec(tags={"cat": {0}}),
                 FilterSpec(tags={"cat": {1, 2}})]
        errs, results = [], []
        lock = threading.Lock()

        def worker(i):
            spec = specs[i % 3]
            q = rng.normal(size=(1, d)).astype(np.float32)
            try:
                ids, _ = eng.submit_search(q, filter=spec).result(
                    timeout=30)
                with lock:
                    results.append((i % 3, ids))
            except Exception as e:           # pragma: no cover
                with lock:
                    errs.append(e)

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(18)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        assert len(results) == 18
        for kind, ids in results:
            live = ids[ids >= 0]
            if kind == 1:
                assert (live % 4 == 0).all()
            elif kind == 2:
                assert np.isin(live % 4, [1, 2]).all()
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# per-tenant token-bucket rate limits (SLO admission)
# ---------------------------------------------------------------------------

def test_token_bucket_serving_tier():
    import time

    from repro.core.slo import (RateLimitError, ServingTier, SLOPolicy)

    class _Ev:
        def set(self):
            pass

    class _Fut:
        def __init__(self, tenant="a"):
            self.error = None
            self.queries = np.zeros((1, 4), np.float32)
            self.tenant = tenant
            self.deadline = None
            self.submitted = time.perf_counter()
            self._event = _Ev()

    tier = ServingTier(SLOPolicy(tenant_rate_limits={"a": (20.0, 2.0)}))
    rejected = []
    for _ in range(5):                      # burst of 5: burst=2 admitted
        f = _Fut()
        if not tier.offer(f):
            assert isinstance(f.error, RateLimitError)
            rejected.append(f)
    assert len(rejected) == 3
    time.sleep(0.2)                         # refill 4 tokens, capped at 2
    admitted = sum(1 for _ in range(5) if tier.offer(_Fut()))
    assert admitted == 2
    st = tier.stats()
    assert st["rate_limited"] == 6
    assert st["tenants"]["a"]["rate_limited"] == 6
    # unlisted tenants are never limited
    for _ in range(4):
        assert tier.offer(_Fut(tenant="b"))
    with pytest.raises(ValueError):
        SLOPolicy(tenant_rate_limits={"a": 0.0}).rate_limit("a")


def test_engine_rate_limit_knob(tmp_path):
    from repro.core.slo import RateLimitError
    rng = np.random.default_rng(19)
    vecs = rng.normal(size=(300, 8)).astype(np.float32)
    eng = SVFusionEngine(vecs, EngineConfig(
        degree=8, cache_slots=64, capacity=1024,
        disk_path=str(tmp_path / "t"), disk_capacity=1024,
        host_window=128, search=SearchParams(k=5, pool=32),
        coalesce=True, slo_tenant_rate_limits={"t0": (1.0, 1.0)}))
    try:
        q = vecs[:1]
        # compile the dispatches first: a first search slower than the
        # bucket's 1 s refill would let the second t0 request through
        eng.search(q, tenant="other")
        eng.search(q, tenant="t0")           # first request drains the bucket
        with pytest.raises(RateLimitError):
            eng.search(q, tenant="t0")
        eng.search(q, tenant="other")        # unlimited tenant unaffected
        st = eng.stats()["slo"]
        assert st["rate_limited"] == 1
        assert st["tenants"]["t0"]["rate_limited"] == 1
    finally:
        eng.close()
