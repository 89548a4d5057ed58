"""Fused multi-round executor tests.

The device-resident topology tier (``cache.TopoCache``) + K-round
``lax.while_loop`` dispatch must be *bitwise transparent*: whatever the
topology hit rate, the fused executor returns exactly the per-round
executor's results — pinned here across K ∈ {1, 2, 4} and uncapped,
under forced 100% residency (full-warm cache), forced 0% residency
(zero-slot cache: every round runs the per-round fallback), demand
installs from cold, and interleaved insert/delete batches that move the
store's write epoch (the fence re-reads every resident row wholesale).
Also pins the row_gather kernel against its jnp oracle and the dispatch
economics: a warm topology collapses ~rounds+2 dispatches to 3
(entry + fused loop + re-rank)."""
import tempfile

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:   # no network route: replay fixed seeded examples
    from _hypothesis_shim import given, settings, st

from repro.core import cache as C
from repro.core import quant, update
from repro.core.build import build_tiered_backend
from repro.core.engine import EngineConfig, SVFusionEngine
from repro.core.search import search_tiered
from repro.core.types import SearchParams

D = 12


def _make(tmp, n, deg, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n, D)).astype(np.float32)
    be = build_tiered_backend(vecs, deg, tmp, disk_capacity=4 * n,
                              host_window=max(32, n // 4))
    hp = C.HostPlacement(be.capacity, 64, D)
    cb = quant.train_codebook(vecs, m=4, bits=6, iters=5, seed=seed)
    pq = quant.PQCodes(cb, be.capacity, codes=quant.encode(cb, vecs))
    be.attach_pq(pq)
    return vecs, be, hp, pq


def _warm_topo(be, slots=None):
    """A TopoCache holding every live row (forced 100% hit rate)."""
    topo = C.TopoCache(be.capacity, slots or be.capacity, be.degree)
    topo.validate(be.store)
    live = np.flatnonzero(np.asarray(be.alive[:be.n]))
    topo.install(live, be.store.peek_rows(live))
    return topo


# ---------------------------------------------------------------------------
# row_gather kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,R,N,B,W", [
    (64, 8, 200, 2, 4), (16, 16, 64, 3, 8), (128, 4, 500, 1, 16),
])
def test_row_gather_kernel_matches_ref(S, R, N, B, W):
    """Kernel (interpret mode) vs jnp oracle, with idle (-1) frontier
    lanes and non-resident ids (h2s == -1) mixed in — both must surface
    as all--1 rows."""
    from repro.kernels.row_gather.kernel import row_gather
    from repro.kernels.row_gather.ref import row_gather_ref
    rng = np.random.default_rng(S + R)
    table = rng.integers(-1, N, (S, R)).astype(np.int32)
    h2s = np.full((N,), -1, np.int32)
    res = rng.permutation(N)[:S]            # S resident ids
    h2s[res] = np.arange(S)
    ids = rng.integers(0, N, (B, W)).astype(np.int32)
    ids[rng.random((B, W)) < 0.3] = -1      # idle lanes
    out = np.asarray(row_gather(table, h2s, ids, interpret=True))
    ref = np.asarray(row_gather_ref(table, h2s, ids))
    np.testing.assert_array_equal(out, ref)
    bad = (ids < 0) | (h2s[np.clip(ids, 0, None)] < 0)
    assert (out[bad] == -1).all()
    ok = ~bad
    if ok.any():
        np.testing.assert_array_equal(out[ok], table[h2s[ids[ok]]])


# ---------------------------------------------------------------------------
# bitwise parity: fused executor vs per-round executor
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(80, 240),
       st.sampled_from([1, 2, 4, 0]))
def test_fused_bit_identical_to_per_round(seed, n, K):
    """Property: whatever the K-round budget (0 = uncapped) and whatever
    the topology residency — full (100% hits), empty (0% hits: the
    per-round fallback serves every round), or demand-filled from cold —
    the fused executor's ids, distances AND per-round visit log are
    bit-identical to the per-round executor's."""
    rng = np.random.default_rng(seed)
    with tempfile.TemporaryDirectory() as td:
        vecs, be, hp, pq = _make(td, n, 8, seed=seed % 97)
        try:
            queries = rng.normal(size=(4, D)).astype(np.float32)
            sp = SearchParams(k=5, pool=16, max_iters=24, beam=2)
            entries = rng.integers(0, n, (4, sp.pool))
            base = search_tiered(be, hp, queries, 0, sp,
                                 entry_ids=entries, pq=pq,
                                 rerank_depth=sp.pool, speculate=False)
            for topo in (_warm_topo(be),                     # 100% hits
                         C.TopoCache(be.capacity, 0, 8),     # 0% hits
                         C.TopoCache(be.capacity, 64, 8)):   # demand fill
                got = search_tiered(be, hp, queries, 0, sp,
                                    entry_ids=entries, pq=pq,
                                    rerank_depth=sp.pool, speculate=False,
                                    topo=topo, fused_rounds=K)
                np.testing.assert_array_equal(got.ids, base.ids)
                np.testing.assert_array_equal(got.dists, base.dists)
                np.testing.assert_array_equal(got.acc_ids, base.acc_ids)
        finally:
            be.close()


def test_fused_forced_hit_rates_and_dispatch_budget():
    """Dispatch economics + counter wiring at the two forced extremes:
    a full-warm topology runs the whole walk in ONE fused dispatch
    (entry + loop + re-rank = 3 total, vs rounds+2 per-round) with zero
    misses; a zero-slot topology reports zero hits and needs exactly the
    per-round executor's dispatch count."""
    rng = np.random.default_rng(11)
    with tempfile.TemporaryDirectory() as td:
        vecs, be, hp, pq = _make(td, 220, 8, seed=1)
        try:
            queries = rng.normal(size=(4, D)).astype(np.float32)
            sp = SearchParams(k=5, pool=16, max_iters=24, beam=2)
            entries = rng.integers(0, 220, (4, sp.pool))
            kw = dict(entry_ids=entries, pq=pq, rerank_depth=sp.pool,
                      speculate=False)
            base = search_tiered(be, hp, queries, 0, sp, **kw)
            warm = search_tiered(be, hp, queries, 0, sp, **kw,
                                 topo=_warm_topo(be))
            assert warm.dispatches == 3 < base.dispatches
            assert warm.topo_misses == 0 and warm.topo_hits > 0
            assert warm.topo_hit_rate == 1.0
            cold = search_tiered(be, hp, queries, 0, sp, **kw,
                                 topo=C.TopoCache(be.capacity, 0, 8))
            assert cold.topo_hits == 0 and cold.topo_misses > 0
            assert cold.topo_hit_rate == 0.0
            assert cold.dispatches == base.dispatches
            # speculation stays transparent through the fused shell too
            spec = search_tiered(be, hp, queries, 0, sp,
                                 entry_ids=entries, pq=pq,
                                 rerank_depth=sp.pool, speculate=True,
                                 topo=C.TopoCache(be.capacity, 64, 8))
            np.testing.assert_array_equal(spec.ids, base.ids)
            np.testing.assert_array_equal(spec.acc_ids, base.acc_ids)
        finally:
            be.close()


def test_fused_epoch_flush_on_interleaved_updates(tmp_path):
    """Interleaved insert/delete between fused searches: the write-epoch
    fence re-reads every resident row (TopoCache.flushes advances), so a
    post-update fused search is bit-identical to a per-round search over
    the mutated graph — cached topology is never served stale."""
    rng = np.random.default_rng(5)
    n = 260
    vecs, be, hp, pq = None, None, None, None
    vecs, be, hp, pq = _make(str(tmp_path), n, 8, seed=2)
    try:
        topo = _warm_topo(be)
        sp = SearchParams(k=5, pool=16, max_iters=24, beam=2)
        queries = rng.normal(size=(4, D)).astype(np.float32)
        entries = rng.integers(0, n, (4, sp.pool))
        kw = dict(entry_ids=entries, pq=pq, rerank_depth=sp.pool,
                  speculate=False)
        search_tiered(be, hp, queries, 0, sp, **kw, topo=topo)
        for batch in range(3):
            newv = rng.normal(size=(8, D)).astype(np.float32)
            ids, _ = update.insert_tiered(be, hp, newv, sp, 100 + batch)
            dead = np.asarray(ids[:3], np.int64)
            be.alive[dead] = False          # engine.delete's tiered path
            be.version[dead] += 1
            base = search_tiered(be, hp, queries, 0, sp, **kw)
            got = search_tiered(be, hp, queries, 0, sp, **kw, topo=topo)
            np.testing.assert_array_equal(got.ids, base.ids)
            np.testing.assert_array_equal(got.dists, base.dists)
            np.testing.assert_array_equal(got.acc_ids, base.acc_ids)
        assert topo.flushes >= 3     # every insert batch moved the epoch
        # residency survived the flushes (rows re-read, not dropped), so
        # the mutated-but-resident part of the walk still fuses
        assert topo.resident >= n
    finally:
        be.close()


def test_engine_fused_dispatch_budget_and_stats(tmp_path):
    """Engine wiring: PQ-on engines build + warm the topology tier at
    init, steady-state batches cost 3 dispatches, and ``stats()`` is the
    single source for the acceptance metric
    (``search_dispatches_per_batch``, fed by the per-result counters)
    plus the topology hit-rate."""
    rng = np.random.default_rng(9)
    n = 500
    vecs = rng.normal(size=(n, 16)).astype(np.float32)
    eng = SVFusionEngine(vecs, EngineConfig(
        degree=8, cache_slots=64, capacity=4 * n,
        disk_path=str(tmp_path / "t"), disk_capacity=4 * n,
        host_window=n // 4, search=SearchParams(k=8, pool=32, max_iters=48),
        seed=0, pq_enabled=True, pq_m=4, pq_bits=6, coalesce=False))
    try:
        q = rng.normal(size=(8, 16)).astype(np.float32)
        for _ in range(4):
            eng.search(q)
        st = eng.stats()
        assert st["search_dispatches_per_batch"] <= 3.0
        assert st["topo_hit_rate"] == 1.0
        assert st["topo_misses"] == 0
        assert st["bytes_per_tier"]["device_topo_rows"] > 0
        # tier_counts surfaces the TopoCache counters
        assert st["topo_resident"] >= n
        # knob: topo_cache_slots < 0 disables the fused path entirely
    finally:
        eng.close()


def test_engine_topo_disabled_knob(tmp_path):
    """``topo_cache_slots=-1`` opts out of the fused path: no topology
    tier is attached and dispatch counts match the per-round executor."""
    rng = np.random.default_rng(13)
    n = 400
    vecs = rng.normal(size=(n, 16)).astype(np.float32)
    eng = SVFusionEngine(vecs, EngineConfig(
        degree=8, cache_slots=64, capacity=2 * n,
        disk_path=str(tmp_path / "t"), disk_capacity=2 * n,
        host_window=n // 4, search=SearchParams(k=8, pool=32, max_iters=48),
        seed=0, pq_enabled=True, pq_m=4, pq_bits=6, coalesce=False,
        topo_cache_slots=-1))
    try:
        q = rng.normal(size=(8, 16)).astype(np.float32)
        eng.search(q)
        st = eng.stats()
        assert st["search_dispatches_per_batch"] > 3
        assert st["topo_hits"] == 0 and st["topo_misses"] == 0
        assert "topo_resident" not in st
    finally:
        eng.close()
