"""CPU tests of the benchmark harness: loading by name, the open-loop
clock, the trace reduction, the kernels' byte counts, the refusal
without a TPU, and the comparison that decides ``correct`` (sound runs
pass; the bfloat16 control and planted faults fail)."""
from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness, roofline, trace_reduce  # noqa: E402
from bench.window import Request, latencies_ms, nearest_rank  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


# -- found by name ---------------------------------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_loader_finds_cell_config_and_traffic_by_name(name):
    cell = harness.load_cell(name)
    assert cell.config["name"] == cell.entry["config"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == cell.entry["config"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    assert hasattr(harness.traffic_module(cell.spec["traffic_kind"]),
                   "Traffic")
    assert callable(harness.engine_module(cell.spec["engine"]).build)
    assert cell.spec["why"] == cell.entry["why"]
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                    "recall_at_10"}


@pytest.mark.parametrize("name", PER_LAYER)
def test_metric_reader_declares_what_benchmark_says(name):
    m = next(x for x in BENCH["per_layer"] if x["name"] == name)
    reader = harness.metric_reader(name)
    assert (reader.LAYER, reader.SOURCE, reader.UNIT, reader.BETTER) == (
        m["layer"], m["source"], m["unit"], m["better"])
    # a cell added later may reuse the reader without editing it
    assert reader.MOVES.get(name.split(".")[-1], m["moves"]) == m["moves"]
    for cell in m["workloads"]:
        assert m["moves"] in {e["name"] for e in harness.load_cell(
            cell).end_to_end}


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell")


# -- the open loop's clock ---------------------------------------------------

class _Done:
    def __init__(self, q):
        self.q = q

    def result(self, timeout=None):
        return (np.zeros((len(self.q), 10), np.int64),
                np.zeros((len(self.q), 10), np.float32))


class _StallOnce:
    """Answers at once, except that one submit blocks for ``stall`` s."""

    def __init__(self, at, stall):
        self.n, self.at, self.stall = 0, at, stall

    def submit_search(self, q):
        self.n += 1
        if self.n == self.at:
            time.sleep(self.stall)
        return _Done(q)


def test_open_loop_times_requests_from_their_due_time():
    traffic = harness.traffic_module("open").Traffic(
        {"rate_qps": 200, "rows_per_request": 1}, 1.0, 7, 16)
    q = np.zeros((traffic.query_rows(), 4), np.float32)
    t0 = time.perf_counter() + 0.05
    reqs, _ = traffic.run(_StallOnce(at=40, stall=0.3), q, None, t0, 1.0)
    assert len(reqs) == 200 and all(r.answered for r in reqs)
    from_due = latencies_ms(reqs, t0, t0 + 1.0, time.perf_counter())
    from_send = [(r.done - r.sent) * 1e3 for r in reqs]
    # the requests due during the stall were sent late: only the due
    # time shows what they waited
    assert nearest_rank(from_due, 99) > 200.0
    assert nearest_rank(from_send, 90) < 50.0


def test_open_loop_offers_the_same_gaps_for_every_seed():
    mod = harness.traffic_module("open")
    a = mod.Traffic({"rate_qps": 500, "rows_per_request": 1}, 2.0, 1, 16)
    b = mod.Traffic({"rate_qps": 500, "rows_per_request": 1}, 2.0, 2**33, 16)
    assert len(a.offsets) == len(b.offsets) == 1000
    assert np.allclose(np.sort(np.diff(a.offsets, prepend=0.0)),
                       np.sort(np.diff(b.offsets, prepend=0.0)))
    assert not np.allclose(a.offsets, b.offsets)
    assert a.offsets[-1] < 2.0


def test_failed_request_counts_as_answered_when_the_run_gave_up():
    r = Request(due=1.0, queries=np.zeros((1, 4)))
    r.error = RuntimeError("shed")
    assert latencies_ms([r], 0.0, 2.0, give_up=5.0) == [4000.0]


def test_end_to_end_median_and_tail_and_the_tails_reader():
    from bench import checks
    reqs = [Request(due=i * 0.01, queries=np.zeros((1, 4)))
            for i in range(100)]
    for i, r in enumerate(reqs):
        r.ids, r.done = np.zeros((1, 10), np.int64), r.due + (i + 1) * 1e-3
    e2e = checks.end_to_end(reqs, [], {"recall": 1.0}, 0.0, 1.0, 5.0,
                            give_up=9.0, log=lambda s: None)
    assert e2e["p50_ms"] == pytest.approx(50.0)
    assert e2e["p99_ms"] == pytest.approx(99.0)
    reader = harness.metric_reader("latency_p99.open")
    assert reader.read({"e2e": e2e}) == e2e["p99_ms"]
    none = checks.end_to_end([], [], {"recall": 0.0}, 0.0, 1.0, 5.0,
                             give_up=9.0, log=lambda s: None)
    assert reader.read({"e2e": none}) is None


# -- the trace reduction -----------------------------------------------------

def _synthetic_trace():
    ms = 1e6
    adc = ("%pq_adc.4 = f32[256,1,512]{2,1,0} custom-call(f32[256,48,256]"
           "{2,1,0} %a, u8[256,512,48]{2,1,0} %b, s32[256,1,512]{2,1,0} %c)")
    return {"devices": {"/device:TPU:0": [
        ("%while.3 = (s32[]) while(s32[] %x)", 10 * ms, 20 * ms),
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %y)", 10 * ms, 8 * ms),
        (adc, 15 * ms, 5 * ms),
        ("%copy.2 = f32[8]{0} copy(f32[8]{0} %z)", 60 * ms, 10 * ms),
        ("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %w)", 95 * ms, 20 * ms)]},
        "host": [("python", "bench.window", 0.0, 100 * ms),
                 ("python", "bench.wait", 0.0, 100 * ms),
                 ("python", "bench.insert", 50 * ms, 30 * ms),
                 ("t1", "$engine.py:1 outer", 30 * ms, 30 * ms),
                 ("t1", "$cache.py:2 inner", 31 * ms, 28 * ms),
                 ("t2", "$threading.py:3 wait", 0 * ms, 100 * ms)]}


def test_trace_reduce_busy_kernels_spans_and_breakdown():
    red = trace_reduce.Reduced(_synthetic_trace())
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.035)        # 20 + 10 + 5 in window
    calls = red.kernel_calls("pq_adc")
    assert len(calls) == 1 and calls[0][0] == pytest.approx(0.005)
    assert calls[0][1][:3] == [("f32", (256, 1, 512)), ("f32", (256, 48, 256)),
                               ("u8", (256, 512, 48))]
    assert red.kernel_calls("row_gather") == []
    assert red.span_busy_share({"bench.insert"}) == pytest.approx(10 / 30)
    bd = red.breakdown()
    # the while loop holds the others: it is not an operation of its own
    assert [n for n, _ in bd["device_ops"]] == [
        "fusion.9", "copy.2", "fusion.1", "pq_adc.4"]
    # gaps: 30-60 ms (the innermost non-waiting host event that covers
    # half of it), 70-95 ms, 0-10 ms; waits name nothing
    assert bd["idle_gaps"] == [["$cache.py:2 inner", pytest.approx(0.03)],
                               ["bench.insert", pytest.approx(0.025)],
                               ["no host event", pytest.approx(0.01)]]


def test_trace_reduce_on_recorded_chip_trace():
    """A slice of a trace recorded on a TPU v5e (``fixtures/``)."""
    ev = json.loads((ROOT / "bench" / "fixtures" /
                     "trace_v5e_open.json").read_text())
    red = trace_reduce.Reduced(ev)
    assert red.window_s == pytest.approx(0.05)
    assert 0.0 < red.busy_s < red.window_s
    (secs, shp), = red.kernel_calls("pq_adc")      # the entry dispatch's
    assert secs > 0 and shp[0] == ("f32", (256, 1, 128))
    assert shp[1] == ("f32", (256, 48, 256)) and shp[2] == ("u8",
                                                            (256, 128, 48))
    bd = red.breakdown()
    assert len(bd["device_ops"]) == len(bd["idle_gaps"]) == 10
    assert bd["idle_gaps"][0][0] == "$cache.py:237 scores"


def test_union_and_clip():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        [0, 3], [5, 8]]
    assert trace_reduce.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


# -- the yardstick: bytes and peaks ------------------------------------------

def test_kernel_bytes_at_the_served_shapes():
    # pq_adc over 256 queries x 512 candidates, 48 subspaces of 256
    assert roofline.pq_adc_bytes(256, 512, 48, 256) == 256 * (
        48 * 256 * 4 + 512 * 48 + 512 * 8)
    # row_gather of 16 rows of degree 32 for 256 queries
    assert roofline.row_gather_bytes(256, 16, 32) == 256 * 16 * (4 + 256)


def test_peaks_table_knows_v5e_and_refuses_others():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    assert roofline.share(819e9, 1.0, "TPU v5 lite") == pytest.approx(100.0)
    assert roofline.share(1.0, 0.0, "TPU v5 lite") is None


# -- no TPU, no result -------------------------------------------------------

def test_run_without_tpu_prints_no_result(capsys):
    import bench.run as run
    rc = run.main(["--workload", CELLS[0], "--seed", str(2**31 + 3),
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err


# -- the comparison that decides correct -------------------------------------

SMALL = {"n": 2048, "cache_slots": 128, "max_batch": 16}
TRAFFIC = {"open": {"rate_qps": 60, "warm_seconds": 0.2},
           "churn": {"search_clients": 2, "rows_per_request": 16,
                     "probes_per_request": 4, "query_pool": 512,
                     "update_batch": 8, "warm_seconds": 0.3,
                     "insert_room": 2048}}


def _small_run(name, make_engine=None, seconds=1.5, seed=2**31 + 11):
    cell = harness.load_cell(name)
    cell.spec["traffic_params"].update(TRAFFIC[cell.spec["traffic_kind"]])
    return harness.run_cell(cell, seed, seconds, False,
                            t_start=time.perf_counter(),
                            make_engine=make_engine, config_override=SMALL,
                            log=lambda s: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = _small_run(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "device" not in res       # no chip, no device metrics
    assert list(res)[-1] == "checks"
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if "workloads" not in m or name in m["workloads"]}
    assert set(res["metrics"]) == e2e


def _half_batch(engine_cls):
    orig = engine_cls._search_exec

    def f(self, queries, *a, **kw):
        ids, d = orig(self, queries, *a, **kw)
        ids, d = ids.copy(), d.copy()
        h = len(ids) // 2
        ids[h:], d[h:] = -1, np.inf
        return ids, d
    return "_search_exec", f


def _altered_answer(engine_cls):
    orig = engine_cls._search_exec

    def f(self, queries, *a, **kw):
        ids, d = orig(self, queries, *a, **kw)
        ids = ids.copy()
        ids[:, 0] = (ids[:, 0] + 1) % SMALL["n"]
        return ids, d
    return "_search_exec", f


def _insert_unapplied(engine_cls):
    def f(self, vectors, chunk=512, attributes=None):
        time.sleep(0.02)         # an update step's time, without its effect
        n = int(self._backend.n)
        return np.arange(n, n + len(vectors))
    return "insert", f


@pytest.mark.parametrize("name,fault", [
    ("deep1b.pq.open", "control_bf16"),
    ("deep1b.pq.open", "half_batch"),
    ("deep1b.pq.open", "altered_answer"),
    ("deep1b.pq.churn", "control_bf16"),
    ("deep1b.pq.churn", "half_batch"),
    ("deep1b.pq.churn", "insert_unapplied"),
])
def test_control_and_planted_faults_come_out_not_correct(name, fault,
                                                         monkeypatch):
    from repro.core.engine import SVFusionEngine
    make = None
    if fault == "control_bf16":
        from bench.control import control_engine as make
    else:
        plant = {"half_batch": _half_batch, "altered_answer": _altered_answer,
                 "insert_unapplied": _insert_unapplied}[fault]
        attr, fn = plant(SVFusionEngine)
        monkeypatch.setattr(SVFusionEngine, attr, fn)
    res = _small_run(name, make_engine=make)
    assert res["correct"] is False, res["checks"]
    failed = {k for k, c in res["checks"].items()
              if not _passes(c["value"], c["limit"])}
    expect = {"control_bf16": "dist_gap", "half_batch": "recall_at_10",
              "altered_answer": "dist_gap",
              "insert_unapplied": "fresh_top1"}[fault]
    assert expect in failed, res["checks"]


def _passes(value, limit):
    op, bound = limit.split()
    return {"==": value == float(bound), ">=": value >= float(bound),
            "<=": value <= float(bound)}[op]


def test_threads_left_behind_by_a_run_are_none():
    before = threading.active_count()
    _small_run(CELLS[0], seconds=0.5)
    time.sleep(0.5)
    assert threading.active_count() <= before


def test_stall_watch_dumps_every_thread_when_the_gil_is_held(tmp_path):
    import ctypes
    watch = harness.StallWatch(str(tmp_path / "stalls.txt"), after=0.2,
                               beat=0.02)
    watch.start()
    time.sleep(0.1)
    ctypes.PyDLL(None).usleep(600_000)     # a C call that keeps the GIL
    time.sleep(0.1)
    stacks = watch.stop()
    assert watch.gap_max >= 0.5
    # the GIL was kept by a call that slept: the gap is on record, with
    # next to none of it spent on the CPU
    (st,) = watch.stalls
    assert st["gap_s"] >= 0.5 and st["cpu_s"] < 0.5 * st["gap_s"]
    assert 0.05 <= st["at_s"] < 0.3 and st.get("majflt", 0) >= 0
    assert stacks.count("Timeout (") == 1
    assert "test_bench_harness.py" in stacks


# -- the churn mix: updates made on demand, closed loop ----------------------

class _FastUpdates:
    """An engine that answers at once and inserts at once."""

    def __init__(self, n0):
        self.n = n0
        self.inserted: list = []

    def insert(self, vectors, attributes=None):
        self.inserted.append(np.array(vectors))
        ids = np.arange(self.n, self.n + len(vectors))
        self.n += len(vectors)
        return ids

    def delete(self, ids):
        pass

    def search(self, q):
        return _Done(q).result()

    def submit_search(self, q):
        time.sleep(0.001)
        return _Done(q)


def _churn(room, seed=2**33 + 5):
    mod = harness.traffic_module("churn")
    params = dict(TRAFFIC["churn"], insert_room=room, warm_steps=1,
                  warm_seconds=0.0)
    return mod.Traffic(params, 2.0, seed, 16)


def test_churn_updater_runs_closed_loop_until_its_room_is_spent():
    n0, room = 256, 12 * 8
    config = {"dim": 8, "latent_dim": 4,
              "attributes": {"tag_fields": ["cat"], "num_fields": ["score"],
                             "tag_domain": 10}}
    updates = harness.Updates(7, config, n0, n0 + room,
                              np.zeros((n0 + room, 8), np.float32))
    traffic = _churn(room)
    q = np.zeros((traffic.query_rows(), 8), np.float32)
    eng = _FastUpdates(n0)
    traffic.warm(eng, q, updates)
    _, ops = traffic.run(eng, q, updates, time.perf_counter(), 2.0)
    # unpaced: a fast system spends the room well inside the window
    assert traffic.notes() == {"update_steps": room // 8,
                               "insert_room_spent": True}
    assert sum(o.kind == "insert" for o in ops) == room // 8 - 1
    assert all(o.error is None for o in ops)
    # each step's vectors come from (seed, step), and are kept by id
    again = harness.Updates(7, config, n0, n0 + room,
                            np.zeros((n0 + room, 8), np.float32))
    assert np.array_equal(eng.inserted[3], again.fresh(3, 8)[0])
    assert np.array_equal(updates.by_id[n0 + 3 * 8:n0 + 4 * 8],
                          eng.inserted[3])


def test_fresh_vectors_follow_the_base_sets_distribution():
    from bench.data import fresh_batch, make_vectors
    schema = {"tag_fields": ["cat"], "num_fields": ["score"],
              "tag_domain": 10}
    base = make_vectors(2**31 + 9, 4096, 32, 4)
    fresh, attrs = fresh_batch(2**31 + 9, 5, 4096, 32, 4, schema)
    other, _ = fresh_batch(2**31 + 9, 6, 4096, 32, 4, schema)
    assert not np.array_equal(fresh, other)
    # one basis: the same covariance, up to sampling noise
    cb, cf = np.cov(base.T), np.cov(fresh.T)
    assert np.abs(cb - cf).max() < 0.15 * np.abs(cb).max()
    assert set(attrs) == {"cat", "score"} and attrs["cat"].max() < 10


# -- filtered requests -------------------------------------------------------

def test_a_filtered_request_is_judged_over_the_ids_its_filter_admits():
    from types import SimpleNamespace

    from bench.checks import judge_window
    from bench.reference import Reference
    rng = np.random.default_rng(3)
    n0, k = 64, 3
    ref = Reference(rng.standard_normal((n0, 4)).astype(np.float32), n0)
    q = rng.standard_normal((2, 4)).astype(np.float32)
    even = np.arange(n0) % 2 == 0
    d = ((ref.host[None] - q[:, None]) ** 2).sum(-1)
    want = np.argsort(np.where(even, d, np.inf), 1)[:, :k]
    config = {"k": k, "guarantees": {"recall_at_10_min": 0.9,
                                     "distance_gap_over_fp32_bound_max": 1.0}}
    cell = SimpleNamespace(spec={})

    def verdict(ids):
        r = Request(due=0.5, queries=q, sent=0.5, done=0.6, allowed=even,
                    ids=ids, dists=np.take_along_axis(d, ids, 1))
        return judge_window(cell, config, ref, [r], [], [], n0, 0.0, 1.0)

    ok = verdict(want)
    assert ok["correct"] and ok["recall"] == 1.0
    # the unfiltered nearest ids, odd ones among them, are bad answers
    plain = np.argsort(d, 1)[:, :k]
    assert (plain % 2).any()
    bad = verdict(plain)
    assert not bad["correct"]
    assert bad["checks"]["bad_ids"]["value"] == int((plain % 2).sum())
