"""Spans and counters inside the engine: what a profiled run of a small
three-tier PQ engine writes (every ``svf.*`` span, how the spans nest on
their threads and the identifier each carries), and the running totals
that ``engine.stats()`` reports (queue wait, store-lock wait,
host-to-device bytes, WAL fsyncs and bytes)."""
from __future__ import annotations

import contextlib
import glob
import signal
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core.engine import CoalescingScheduler, EngineConfig, SVFusionEngine
from repro.core.types import SearchParams
from repro.core.wal import WriteAheadLog

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

N, DIM = 500, 16

# the spans a run of searches, one insert and one delete writes
SPANS = {
    "svf.coalesce_wait", "svf.dispatch", "svf.f_lambda", "svf.entry",
    "svf.fused", "svf.device_sync", "svf.topo_validate", "svf.topo_install",
    "svf.topo_sync", "svf.rerank_fetch", "svf.wavp_place", "svf.store.fetch",
    "svf.store.fetch_rows", "svf.store.peek", "svf.store.peek_rows",
    "svf.store.write", "svf.store.prefetch", "svf.insert", "svf.delete",
    "svf.insert.search", "svf.insert.link", "svf.insert.apply",
    "svf.wal.append", "svf.wal.fsync", "svf.maintain", "svf.checkpoint",
}


@contextlib.contextmanager
def time_limit(seconds):
    """Fail the test body once it has run ``seconds``."""
    def fail(signum, frame):
        raise TimeoutError(f"the test ran over its {seconds} s limit")
    old = signal.signal(signal.SIGALRM, fail)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def _engine(path, **kw):
    rng = np.random.default_rng(9)
    vecs = rng.normal(size=(N, DIM)).astype(np.float32)
    cfg = dict(degree=8, cache_slots=64, capacity=4 * N,
               disk_path=str(path), disk_capacity=4 * N, host_window=N // 4,
               search=SearchParams(k=8, pool=32, max_iters=48), seed=0,
               pq_enabled=True, pq_m=4, pq_bits=6)
    cfg.update(kw)
    return SVFusionEngine(vecs, EngineConfig(**cfg)), rng


def _svf_spans(path):
    """[(thread line, name, start_ns, end_ns, {keyword: value})] of every
    ``svf.*`` span in one xplane file, whatever its length."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("svf."):
                    out.append((i, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _inside(span, outer, key):
    """The ``outer`` spans on ``span``'s thread that cover it and carry the
    same ``key``."""
    line, _, s, e, kw = span
    return [o for o in outer if o[0] == line and o[2] <= s and e <= o[3]
            and o[4].get(key) == kw.get(key)]


def test_engine_spans_nest_and_carry_their_identifier(tmp_path):
    with time_limit(240):
        eng, rng = _engine(tmp_path / "t", wal_group_commit=2,
                           snapshot_every_epochs=2,
                           consolidate_threshold=0.01)
        try:
            q = rng.normal(size=(64, DIM)).astype(np.float32)
            for rows in (4, 8, 16):          # compile the batch shapes
                eng.search(q[:rows])
            stop = threading.Event()

            def client(c):
                i = c
                while not stop.is_set():
                    eng.search(q[4 * (i % 8):4 * (i % 8) + 4])
                    i += 2

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path / "trace"),
                                     profiler_options=opts)
            try:
                ths = [threading.Thread(target=client, args=(c,))
                       for c in range(2)]
                for th in ths:
                    th.start()
                time.sleep(0.5)
                new = eng.insert(rng.normal(size=(8, DIM)).astype(np.float32))
                eng.delete(np.arange(10))
                time.sleep(0.5)              # searches see the new epoch
                stop.set()
                for th in ths:
                    th.join(timeout=60)
                    assert not th.is_alive()
                store = eng._backend.store
                cold = np.flatnonzero(store.loc[:N] < 0)[:16]
                before = store.prefetched
                store.prefetch(cold, None, tag={"batch": 999})
                t_end = time.perf_counter() + 30
                while store.prefetched == before \
                        and time.perf_counter() < t_end:
                    time.sleep(0.01)
                assert store.prefetched > before
            finally:
                jax.profiler.stop_trace()
            eng.wait_background()
            st = eng.stats()
        finally:
            eng.close()
        for key in ("queue_wait_s", "h2d_bytes", "store_lock_wait_s",
                    "cache_lock_wait_s", "wal_fsyncs", "wal_bytes"):
            assert st[key] > 0, key

        path = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        spans = _svf_spans(path)
        names = {s[1] for s in spans}
        assert SPANS <= names, sorted(SPANS - names)
        # the benchmark's reduction sees the long spans by name
        host = {n for _, n, _, _ in trace_reduce.extract(path)["host"]}
        assert {"svf.dispatch", "svf.insert", "svf.delete"} <= host

        def named(n):
            return [s for s in spans if s[1] == n]
        dispatch = named("svf.dispatch")
        assert all(isinstance(d[4]["batch"], int) and d[4]["rows"] > 0
                   and d[4]["requests"] > 0 and "level" in d[4]
                   for d in dispatch)
        for name in ("svf.wavp_place", "svf.fused", "svf.rerank_fetch"):
            assert named(name)
            for sp in named(name):
                assert len(_inside(sp, dispatch, "batch")) == 1, sp
        (ins,) = named("svf.insert")
        assert ins[4]["rows"] == len(new)
        for name in ("svf.insert.search", "svf.insert.link",
                     "svf.insert.apply"):
            assert all(_inside(sp, [ins], "step") for sp in named(name))
        (dele,) = named("svf.delete")
        assert dele[4]["step"] == ins[4]["step"] + 1
        # the update path's WAL appends (consolidation logs untagged)
        logged = [w for w in named("svf.wal.append") if "step" in w[4]]
        assert [bool(_inside(w, [ins], "step")) for w in logged] == [
            True, False]
        assert _inside(logged[1], [dele], "step")
        # the prefetcher runs on its own thread, tagged with the batch
        pre = [s for s in named("svf.store.prefetch")
               if s[4].get("batch") == 999]
        assert pre and pre[0][0] != ins[0]


def test_queue_wait_grows_by_a_planted_hold_of_the_dispatcher():
    with time_limit(60):
        entered, release = threading.Event(), threading.Event()

        def held(q, batch=None, **knobs):
            if batch == 1:
                entered.set()
                release.wait(10.0)
            return (np.zeros((len(q), 4), np.int64),
                    np.zeros((len(q), 4), np.float32))

        co = CoalescingScheduler(held, max_batch=8, max_window=1e-4)
        try:
            first = co.submit(np.zeros((1, 4), np.float32))
            assert entered.wait(10.0)
            waited0 = co.tier.queue_wait_s
            second = co.submit(np.zeros((1, 4), np.float32))
            time.sleep(0.2)                  # the hold the second waits out
            release.set()
            first.result(timeout=10.0)
            second.result(timeout=10.0)
            assert co.tier.queue_wait_s - waited0 >= 0.2
        finally:
            release.set()
            co.stop()


def test_store_lock_wait_counts_a_hold_by_another_thread(tmp_path):
    with time_limit(120):
        eng, _ = _engine(tmp_path / "t", coalesce=False)
        try:
            store = eng._backend.store
            held = threading.Event()

            def holder():
                with store._lock:
                    held.set()
                    time.sleep(0.06)

            w0 = eng.stats()["store_lock_wait_s"]
            th = threading.Thread(target=holder)
            th.start()
            assert held.wait(10.0)
            store.fetch(np.arange(4))
            th.join(timeout=10.0)
            assert not th.is_alive()
            assert eng.stats()["store_lock_wait_s"] - w0 >= 0.05
        finally:
            eng.close()


@pytest.mark.parametrize("after_insert", [False, True])
def test_h2d_bytes_of_one_batch_are_the_bytes_it_ships(tmp_path,
                                                      after_insert):
    """One steady PQ batch of 8 queries ships the queries, the entry ids
    and their alive mask, per fused dispatch its two round numbers and
    the alive bitset, then the re-rank ids, vectors, lane map and mask.
    After an insert it also re-puts the whole topology table and its
    directory (the epoch fence re-read it) and scatters the new codes."""
    with time_limit(120):
        eng, rng = _engine(tmp_path / "t", coalesce=False, prefetch=False)
        try:
            q = rng.normal(size=(8, DIM)).astype(np.float32)
            for _ in range(2):
                eng.search(q)
            fresh = 0
            if after_insert:
                fresh = len(eng.insert(
                    rng.normal(size=(8, DIM)).astype(np.float32)))
            st0 = eng.stats()
            eng.search(q)
            st1 = eng.stats()
        finally:
            eng.close()
        be = eng._backend
        sp, cap, B = eng.cfg.search, be.capacity, len(q)
        depth = eng.cfg.rerank_depth
        fused = round(st1["search_dispatches_per_batch"] * 3
                      - st0["search_dispatches_per_batch"] * 2) - 2
        assert fused >= 1
        ship = (B * DIM * 4                          # queries
                + B * sp.pool * (4 + 1)              # entry ids, alive
                + fused * (4 + cap + 4)              # r, alive, r_stop
                + B * depth * (4 + DIM * 4 + 4 + 1))  # re-rank
        if after_insert:
            ship += cap * be.degree * 4 + cap * 4    # topology re-put
            ship += fresh * (4 + be.pq.m)            # code scatter
        assert st1["h2d_bytes"] - st0["h2d_bytes"] == ship


def test_wal_counts_fsyncs_and_bytes(tmp_path):
    with time_limit(60):
        path = tmp_path / "wal.log"
        wal = WriteAheadLog(str(path), group_commit=4)
        try:
            for i in range(12):
                wal.append(1, {"ids": np.arange(i + 1)})
            assert wal.appended == 12
            assert wal.fsyncs == wal.appended // wal.group_commit
            assert wal.bytes == path.stat().st_size
        finally:
            wal.close()


def test_h2d_bytes_lose_no_count_under_concurrent_puts(tmp_path):
    """Many threads stage arrays at once, the interpreter switching
    threads as often as it can: the running total is exact."""
    with time_limit(120):
        eng, _ = _engine(tmp_path / "t", coalesce=False)
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            be = eng._backend
            b0 = be.h2d_bytes
            x = np.zeros((25,), np.float32)          # 100 bytes a put

            def stage():
                for _ in range(200):
                    be.to_device(x)

            ths = [threading.Thread(target=stage) for _ in range(16)]
            for th in ths:
                th.start()
            for th in ths:
                th.join(timeout=60)
                assert not th.is_alive()
            assert be.h2d_bytes - b0 == 16 * 200 * x.nbytes
        finally:
            sys.setswitchinterval(old)
            eng.close()
