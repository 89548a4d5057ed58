"""Speculative pipeline + cross-query coalescer tests.

The speculative stage must be *bitwise transparent*: staged rows/vectors
are the same values the demand path would fetch, so ``search_tiered``
results cannot depend on prediction quality — pinned here under forced
0% and forced 100% misprediction, plus an interleaved insert/delete run
showing the write-epoch flush keeps MVCC reads coherent while rows are
staged. The coalescing scheduler must demultiplex exactly (every request
gets its own rows back) and its adaptive window must shrink under light
load."""
import tempfile
import threading

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:   # no network route: replay fixed seeded examples
    from _hypothesis_shim import given, settings, st

from repro.core import cache as C
from repro.core.build import build_tiered_backend
from repro.core.engine import (CoalescingScheduler, EngineConfig,
                               SVFusionEngine)
from repro.core.search import predict_frontier, search_tiered
from repro.core.types import SearchParams

D = 16


def _predict_all(ids, valid, f_lam, width, d_host=None):
    """Forced 0% misprediction: stage every valid candidate, so the real
    frontier is always a subset of the staged set."""
    return np.where(valid, ids, -1)


def _predict_none(ids, valid, f_lam, width, d_host=None):
    """Forced 100% misprediction: never stage anything."""
    return np.full((ids.shape[0], 1), -1, np.int64)


def _make(tmp, n, deg, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n, D)).astype(np.float32)
    be = build_tiered_backend(vecs, deg, tmp, disk_capacity=2 * n,
                              host_window=max(32, n // 4))
    hp = C.HostPlacement(be.capacity, 64, D)
    return vecs, be, hp


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(80, 240), st.integers(4, 8))
def test_speculation_bit_identical_under_forced_misprediction(seed, n, deg):
    """Property: speculative and non-speculative search return
    bit-identical pools whatever the predictor does — always right
    (superset staging), always wrong (empty staging), or the real F_λ /
    distance-ranked guesses."""
    with tempfile.TemporaryDirectory() as td:
        vecs, be, hp = _make(td, n, deg, seed % 1000)
        rng = np.random.default_rng(seed)
        q = rng.normal(size=(6, D)).astype(np.float32)
        sp = SearchParams(k=8, pool=24, max_iters=24, beam=4)
        entries = rng.integers(0, n, (6, sp.pool))
        base = search_tiered(be, hp, q, 0, sp, entry_ids=entries,
                             speculate=False)
        variants = {
            "forced-hit": dict(spec_predict=_predict_all),
            "forced-miss": dict(spec_predict=_predict_none),
            "flam": dict(spec_rank="flam"),
            "dist": dict(spec_rank="dist"),
        }
        for tag, kw in variants.items():
            got = search_tiered(be, hp, q, 0, sp, entry_ids=entries,
                                speculate=True, **kw)
            np.testing.assert_array_equal(base.ids, got.ids, err_msg=tag)
            np.testing.assert_array_equal(base.dists, got.dists,
                                          err_msg=tag)
            np.testing.assert_array_equal(base.acc_ids, got.acc_ids,
                                          err_msg=tag)
            np.testing.assert_array_equal(base.acc_hit, got.acc_hit,
                                          err_msg=tag)
        be.close()


def test_speculation_hit_rate_extremes(tmp_path):
    """The hit-rate accounting matches the forcing: superset staging
    scores all hits, empty staging scores all misses. Single query: with
    B > 1 an id demand-fetched for one query legitimately serves another
    query's later round from the memo, which is cross-query reuse, not
    prediction."""
    vecs, be, hp = _make(str(tmp_path), 400, 8)
    rng = np.random.default_rng(1)
    q = rng.normal(size=(1, D)).astype(np.float32)
    sp = SearchParams(k=8, pool=32, max_iters=32, beam=4)
    always = search_tiered(be, hp, q, 0, sp, spec_predict=_predict_all)
    never = search_tiered(be, hp, q, 0, sp, spec_predict=_predict_none)
    off = search_tiered(be, hp, q, 0, sp, speculate=False)
    assert always.spec_hit_rate == 1.0
    assert never.spec_hit_rate == 0.0
    assert never.spec_misses > 0
    assert off.spec_hits == 0 and off.spec_misses == 0
    be.close()


def test_speculation_epoch_flush_on_write(tmp_path):
    """A write between staging and use flushes the memo: the staged row
    is dropped, not served stale (the correctness core of MVCC-while-
    staging)."""
    vecs, be, hp = _make(str(tmp_path), 300, 8)
    from repro.core.search import _SpecPipeline
    f_lam = hp.scores(be.e_in)
    view = hp.view
    spec = _SpecPipeline(be, view.h2d, view.vectors, f_lam)
    ids = np.arange(10)
    spec.stage(ids)
    assert (spec.rows.loc[ids] >= 0).all()
    new_row = np.full((1, be.degree), 7, np.int32)
    be.store.write(np.array([3]), nbrs=new_row)     # concurrent mutation
    spec.validate()
    assert (spec.rows.loc[ids] == -1).all()         # memo flushed wholesale
    got = spec.rows_for(np.array([3]))
    np.testing.assert_array_equal(got[0], new_row[0])   # fresh, not stale
    be.close()


def test_speculation_consistent_under_interleaved_updates(tmp_path):
    """Interleaved insert/delete while speculation stages rows: searches
    through the engine stay consistent — acknowledged inserts are
    findable, deleted ids never surface, and the store's residency stays
    exact (the write-epoch flush is what makes this safe)."""
    rng = np.random.default_rng(3)
    n = 600
    vecs = rng.normal(size=(n, D)).astype(np.float32)
    eng = SVFusionEngine(vecs, EngineConfig(
        degree=8, cache_slots=64, capacity=4 * n,
        disk_path=str(tmp_path / "t"), disk_capacity=4 * n,
        host_window=n // 4, search=SearchParams(k=8, pool=48, max_iters=96),
        seed=0, consolidate_threshold=2.0))
    try:
        stop = threading.Event()
        errors = []

        def churn():
            r = np.random.default_rng(7)
            try:
                while not stop.is_set():
                    ids = eng.insert(
                        r.normal(size=(8, D)).astype(np.float32))
                    eng.delete(ids[:4])
            except Exception as e:  # pragma: no cover
                errors.append(e)

        th = threading.Thread(target=churn)
        th.start()
        raw_hits = []
        try:
            for i in range(15):
                newv = rng.normal(size=(4, D)).astype(np.float32)
                ids = eng.insert(newv)
                found, _ = eng.search(newv)
                # read-after-write quality is aggregated below: under
                # churn a single probe can miss without any write loss
                raw_hits.append(float((found[:, 0] == ids).mean()))
                eng.delete(ids)
                found2, _ = eng.search(newv)
                # deletions are exact: a deleted id must NEVER surface
                assert not np.isin(ids, found2).any()
        finally:
            stop.set()
            th.join()
        assert not errors, errors[0]
        assert float(np.mean(raw_hits)) > 0.7, raw_hits
        assert eng.stats()["spec_hits"] + eng.stats()["spec_misses"] > 0
        store = eng.state.tiered.store
        occ = store.slot_id >= 0
        np.testing.assert_array_equal(
            store.loc[store.slot_id[occ]], np.where(occ)[0])
    finally:
        eng.close()


def test_predict_frontier_ranking():
    """The F_λ probe returns the hottest valid candidates; host distances
    override it when provided (entry stage)."""
    ids = np.array([[5, 9, 2, 7]])
    valid = np.array([[True, True, False, True]])
    f_lam = np.zeros(10, np.float32)
    f_lam[[5, 9, 7]] = [3.0, 1.0, 2.0]
    got = predict_frontier(ids, valid, f_lam, 2)
    assert got.tolist() == [[5, 7]]
    d_host = np.array([[0.5, 0.1, 0.0, 0.9]])
    got = predict_frontier(ids, valid, f_lam, 2, d_host=d_host)
    assert got.tolist() == [[9, 5]]
    # no valid candidate -> all -1, never a bogus id
    got = predict_frontier(ids, np.zeros_like(valid), f_lam, 2)
    assert (got == -1).all()


# ---------------------------------------------------------------------------
# cross-query coalescing scheduler
# ---------------------------------------------------------------------------

def test_coalescer_demux_exact():
    """Concurrent requests of different sizes merge into shared dispatches
    and every request gets exactly its own rows back."""
    calls = []

    def search_fn(qs, batch=None):
        calls.append(len(qs))
        return qs[:, :1].astype(np.int32), qs[:, :1]

    co = CoalescingScheduler(search_fn, max_batch=64, max_window=5e-3)
    rng = np.random.default_rng(0)
    reqs = [rng.normal(size=(b, 4)).astype(np.float32)
            for b in (1, 3, 2, 5, 4, 1, 7, 2)]
    futs = [co.submit(q) for q in reqs]
    for q, f in zip(reqs, futs):
        ids, dists = f.result(timeout=10)
        assert len(ids) == len(q)
        np.testing.assert_allclose(dists, q[:, :1])
        assert f.latency > 0
    assert co.requests == len(reqs)
    assert co.queries == sum(len(q) for q in reqs)
    assert co.dispatches <= len(reqs)        # at least some merging
    co.stop()


def test_coalescer_adaptive_window_shrinks_when_idle():
    """Uncoalesced dispatches shrink the window toward the floor so a
    lone caller's p50 converges to the direct-call latency; merged ones
    grow it (bounded)."""
    co = CoalescingScheduler(lambda qs, batch=None: (qs, qs), max_batch=8,
                             max_window=2e-3, min_window=5e-5)
    co.window = 2e-3
    q = np.zeros((1, 4), np.float32)
    for _ in range(12):
        co.search(q)                         # serial -> never coalesces
    assert co.window == co.min_window
    assert co.coalesced == 0
    co.stop()


def test_coalescer_propagates_errors():
    def boom(qs, batch=None):
        raise RuntimeError("executor failed")

    co = CoalescingScheduler(boom)
    fut = co.submit(np.zeros((2, 4), np.float32))
    with pytest.raises(RuntimeError, match="executor failed"):
        fut.result(timeout=10)
    co.stop()


def test_engine_coalesces_across_threads(tmp_path):
    """Engine-level: N submitter threads share executor dispatches (mean
    coalesced batch > one request's rows) and results are per-request
    correct (each query's own nearest neighbor comes back first)."""
    rng = np.random.default_rng(5)
    n = 500
    vecs = rng.normal(size=(n, D)).astype(np.float32)
    eng = SVFusionEngine(vecs, EngineConfig(
        degree=8, cache_slots=64, capacity=2 * n,
        disk_path=str(tmp_path / "t"), disk_capacity=2 * n,
        host_window=n // 4, search=SearchParams(k=4, pool=48, max_iters=96),
        seed=0, coalesce_window=5e-3))
    try:
        eng.search(vecs[:8], update_cache=False)     # warm the pipeline
        hits = []
        errors = []

        def client(lo):
            try:
                for i in range(6):
                    sel = (lo + 7 * i) % n
                    ids, _ = eng.search(vecs[sel:sel + 4])
                    hits.append(float((ids[:, 0]
                                       == np.arange(sel, sel + 4)).mean()))
            except Exception as e:  # pragma: no cover
                errors.append(e)

        ths = [threading.Thread(target=client, args=(s,))
               for s in (0, 100, 200, 300)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert not errors, errors[0]
        assert np.mean(hits) > 0.9           # demux returned the right rows
        st = eng.stats()
        assert st["coalesce_requests"] >= 24
        assert st["coalesce_batch_mean"] > 4.0   # > one request's rows
    finally:
        eng.close()
