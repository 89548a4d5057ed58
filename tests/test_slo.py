"""SLO serving-tier tests: degradation ladder, pressure hysteresis,
weighted-fair draining, deadline admission, shed-as-last-resort — plus
regression tests for the three scheduler/serving bugfixes riding this
change (coalescer shutdown race, `max_batch` overshoot, ServeEngine
straggler EWMA poisoning) and a submit/stop interleaving stress test."""
import threading
import time

import numpy as np
import pytest

from repro.core import slo
from repro.core.engine import (CoalescingScheduler, EngineConfig,
                               SVFusionEngine, _SearchFuture)
from repro.core.search import effective_rerank_depth
from repro.core.types import SearchParams

D = 16


def _fut(rows, tenant=None, deadline=None):
    return _SearchFuture(np.zeros((rows, D), np.float32),
                         tenant=tenant, deadline=deadline)


# -- degradation ladder ---------------------------------------------------

def test_degrade_params_progression():
    sp = SearchParams(k=10, pool=64, max_iters=96, beam=16)
    # level 0: identity
    assert slo.degrade_params(sp, 0, 0) == (sp, 0)
    # level 1: re-rank depth halves from the whole-pool sentinel
    sp1, rr1 = slo.degrade_params(sp, 0, 1)
    assert sp1 == sp and rr1 == 32
    # level 2: beam halves WITH the hop budget (round count constant)
    sp2, rr2 = slo.degrade_params(sp, 0, 2)
    assert rr2 == 32 and sp2.beam == 8 and sp2.max_iters == 48
    # level 3: fused round budget halves again
    sp3, rr3 = slo.degrade_params(sp, 0, 3)
    assert rr3 == 32 and sp3.beam == 8 and sp3.max_iters == 24


def test_degrade_params_floors():
    sp = SearchParams(k=10, pool=16, max_iters=4, beam=4)
    sp3, rr3 = slo.degrade_params(sp, 10, 3)
    assert rr3 == 10                      # floor k
    assert sp3.beam == 4                  # floor 4
    assert sp3.max_iters == sp3.beam      # floor one beam's worth
    # shares the executor's sentinel resolution
    assert effective_rerank_depth(0, 10, 16) == 16
    assert effective_rerank_depth(3, 10, 16) == 10


def test_degrade_params_unknown_stage_raises():
    sp = SearchParams(k=4, pool=16)
    with pytest.raises(ValueError):
        slo.degrade_params(sp, 0, 1, order=("nope",))


# -- latency reservoir / pressure controller ------------------------------

def test_latency_reservoir_ring_and_quantiles():
    r = slo.LatencyReservoir(cap=4)
    assert len(r) == 0 and r.quantile(99) is None
    for x in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0):
        r.add(x)
    assert len(r) == 4                    # newest cap samples survive
    assert r.quantile(0) == 3.0 and r.quantile(100) == 6.0


def test_pressure_controller_hysteresis():
    pol = slo.SLOPolicy(target_p99=0.05, degrade_at=0.5, shed_at=1.0,
                        restore_after=2)
    pc = slo.PressureController(pol)
    assert pc.update(0.9) == 3            # escalation is immediate
    assert pc.update(0.1) == 3            # one calm dispatch is noise
    assert pc.update(0.1) == 2            # restore_after -> one level
    assert pc.update(0.9) == 3            # flap re-escalates instantly
    for _ in range(3 * pol.restore_after):
        pc.update(0.0)
    assert pc.level == 0                  # knobs fully restore when calm


# -- weighted-fair admission ----------------------------------------------

def test_weighted_fair_drain_interleaves_cold_tenant():
    tier = slo.ServingTier(slo.SLOPolicy())
    hot = [_fut(1, tenant="hot") for _ in range(10)]
    for f in hot:
        tier.offer(f)
    cold = _fut(1, tenant="cold")
    tier.offer(cold)
    batch = tier.collect(4, 1e-4, threading.Event())
    # stride scheduling: the lone cold request rides the FIRST dispatch
    # even behind a 10-deep hot backlog
    assert cold in batch and len(batch) == 4


def test_deadline_skip_and_fail():
    tier = slo.ServingTier(slo.SLOPolicy())
    fut = _fut(2, deadline=0.005)
    assert tier.offer(fut)
    time.sleep(0.02)                      # deadline now unmeetable
    batch = tier.collect(8, 1e-4, threading.Event())
    assert batch == []
    with pytest.raises(slo.DeadlineMissError):
        fut.result(timeout=1.0)
    st = tier.stats()
    assert st["deadline_misses"] == 1
    assert st["tenants"][slo.DEFAULT_TENANT]["deadline_misses"] == 1


def test_shed_only_at_max_degradation():
    pol = slo.SLOPolicy(target_p99=0.01, shed_at=1.0)
    tier = slo.ServingTier(pol)
    tier.rows_per_s = 100.0               # modeled service rate
    admitted = [_fut(20, tenant="t") for _ in range(3)]
    for f in admitted:
        # modeled wait grows far past shed_at x target, but degradation
        # has headroom (level 0) -> every request is still admitted
        assert tier.offer(f)
    assert tier.shed_total == 0
    tier.controller.level = pol.n_levels  # degradation maxed out
    shed = _fut(20, tenant="t")
    assert not tier.offer(shed)           # now, and only now, shed
    with pytest.raises(slo.LoadShedError):
        shed.result(timeout=1.0)
    assert tier.shed_total == 1
    assert tier.stats()["tenants"]["t"]["shed"] == 1


def test_disabled_policy_never_sheds_or_pressures():
    tier = slo.ServingTier(slo.SLOPolicy(target_p99=0.0))
    tier.rows_per_s = 1.0
    tier.controller.level = 3
    f = _fut(50)
    assert tier.offer(f)                  # no shedding when disabled
    tier.complete([], 50, 0.5, ok=True)
    assert tier.pressure == 0.0


# -- bugfix regressions ---------------------------------------------------

def test_overshoot_peek_dont_admit():
    """Regression: the dispatcher admitted one more request after the
    row cap was reached, so a 5+5+5-row arrival at max_batch=8 dispatched
    10 rows and jumped the pow2 padding bucket. The head that would cross
    the cap must stay queued for the next dispatch."""
    tier = slo.ServingTier(slo.SLOPolicy())
    futs = [_fut(5) for _ in range(3)]
    for f in futs:
        tier.offer(f)
    stop = threading.Event()
    sizes = [sum(len(f.queries) for f in tier.collect(8, 1e-4, stop))
             for _ in range(3)]
    assert sizes == [5, 5, 5]             # legacy code produced [10, 5]
    assert tier.overshoot_avoided >= 2
    # a single oversized request still dispatches alone (no livelock)
    big = _fut(16)
    tier.offer(big)
    assert tier.collect(8, 1e-4, stop) == [big]


def _ok_search(q, degrade=0, batch=None):
    k = 4
    return (np.zeros((len(q), k), np.int64),
            np.zeros((len(q), k), np.float32))


def test_stop_drains_queued_futures_and_rejects_new():
    gate = threading.Event()

    def blocked(q, degrade=0, batch=None):
        gate.wait(5.0)
        return _ok_search(q)

    co = CoalescingScheduler(blocked, max_batch=8, max_window=1e-4)
    f1 = co.submit(np.zeros((8, D), np.float32))   # fills the batch ->
    time.sleep(0.05)                               # dispatched, stuck
    f2 = co.submit(np.zeros((2, D), np.float32))   # still queued
    with pytest.raises(RuntimeError, match="did not exit"):
        co.stop(join_timeout=0.2)                  # loud, not silent
    with pytest.raises(RuntimeError):
        f2.result(timeout=1.0)                     # drained, not hung
    with pytest.raises(RuntimeError):
        co.submit(np.zeros((1, D), np.float32))    # closed to new work
    gate.set()                                     # release the thread
    f1.result(timeout=5.0)                         # in-flight completes


def test_coalescer_submit_stop_stress():
    """Regression for the shutdown race: stop() used to drain the queue
    while a timed-out-but-alive dispatcher kept popping it, so a future
    could complete twice or never. Hammer submit/stop interleavings and
    assert every future resolves (result or error) within its timeout."""
    for trial in range(4):
        def slow(q, degrade=0, batch=None):
            time.sleep(0.002)
            return _ok_search(q)

        co = CoalescingScheduler(slow, max_batch=16, max_window=2e-4)
        futs, lock = [], threading.Lock()

        def client():
            for _ in range(8):
                try:
                    f = co.submit(np.zeros((2, D), np.float32))
                except RuntimeError:
                    return                # stopped under us: fine
                with lock:
                    futs.append(f)

        ths = [threading.Thread(target=client) for _ in range(6)]
        for t in ths:
            t.start()
        time.sleep(0.003 * (trial + 1))   # vary the interleaving
        co.stop()
        for t in ths:
            t.join()
        for f in futs:
            try:
                ids, _ = f.result(timeout=10.0)   # TimeoutError = hang
                assert len(ids) == 2
            except RuntimeError:
                pass                      # drained at shutdown: fine


def test_serve_straggler_consecutive_detection():
    """Regression: tick() folded a straggler's dt into the EWMA before
    the check, inflating the threshold so the second of two consecutive
    stragglers went undetected. Both must be flagged and neither may
    move the EWMA."""
    from repro.serve.engine import ServeEngine
    eng = object.__new__(ServeEngine)
    eng.straggler_factor = 8.0
    eng.tick_ewma = None
    eng.stragglers = 0
    assert not eng._observe_tick(0.01)    # seeds the EWMA
    assert eng._observe_tick(0.2)         # straggler #1
    assert eng._observe_tick(0.2)         # straggler #2 (was invisible:
    #                                       poisoned EWMA 0.029 * 8 > 0.2)
    assert eng.stragglers == 2
    assert eng.tick_ewma == pytest.approx(0.01)


# -- engine-level integration --------------------------------------------

def test_engine_slo_stats_and_degraded_exec(tmp_path):
    rng = np.random.default_rng(11)
    n = 300
    vecs = rng.normal(size=(n, D)).astype(np.float32)
    eng = SVFusionEngine(vecs, EngineConfig(
        degree=8, cache_slots=64, capacity=2 * n,
        disk_path=str(tmp_path / "t"), disk_capacity=2 * n,
        host_window=n // 4, search=SearchParams(k=4, pool=48, max_iters=96),
        seed=0, slo_target_p99=30.0))     # huge target: active, never shed
    try:
        ids, _ = eng.search(vecs[:4], tenant="alice")
        assert ids.shape == (4, 4)
        eng.search(vecs[4:8], tenant="bob", deadline=30.0)
        st = eng.stats()
        assert st["coalesce_overshoot_avoided"] == 0
        assert st["degraded_dispatches"] == 0
        s = st["slo"]
        assert s["target_p99_ms"] == pytest.approx(30e3)
        assert set(s["tenants"]) == {"alice", "bob"}
        assert s["tenants"]["alice"]["completed"] == 1
        assert s["tenants"]["alice"]["p99_ms"] is not None
        # degraded executor paths return well-formed results (level > 0
        # reaches search_tiered via SearchParams/rerank overrides)
        for lvl in (1, 2, 3):
            ids, dists = eng._search_exec(vecs[:2], update_cache=False,
                                          degrade=lvl)
            assert ids.shape == (2, 4) and np.all(ids >= 0)
    finally:
        eng.close()
