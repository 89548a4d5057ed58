"""Closed-loop searches beside a closed-loop stream of updates.

Mix parameters (the cell's ``traffic_params``):

- ``search_clients``: clients, each sending its next request only when
  the last one is answered;
- ``rows_per_request``: queries in one request, of which
  ``probes_per_request`` are vectors inserted earlier in the run (a probe
  has the inserted id as its exact nearest neighbour, so an insert that
  was acknowledged but is not served shows);
- ``update_batch``: one update step inserts this many fresh vectors,
  made on demand from (seed, step), then deletes as many ids drawn
  uniformly from the live initial set, so the live count stays steady.
  One closed-loop updater runs step after step;
- ``insert_room``: ids the deployment has room for beyond its initial
  set. Once a step would pass it the updater stops, and the run says so
  (``insert_room_spent``): a system that updates that fast has outgrown
  the cell;
- ``warm_steps`` update steps and ``warm_seconds`` of this traffic in
  set-up, after the shape sweep.
"""
from __future__ import annotations

import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench.data import rng_for
from bench.window import Op, Request


class Traffic:
    def __init__(self, params, seconds, seed, max_batch):
        self.clients = int(params["search_clients"])
        self.rows = int(params["rows_per_request"])
        self.probes = int(params["probes_per_request"])
        self.step = int(params["update_batch"])
        self.warm_steps = int(params.get("warm_steps", 0))
        self.warm_seconds = float(params.get("warm_seconds", 0.0))
        self.pool_rows = int(params["query_pool"])
        self.room = int(params["insert_room"])
        self.seed = seed
        self.steps = 0                  # update steps started
        self.room_spent = False
        self._acked: list = []          # ids of acknowledged inserts
        self._live_init = None          # live ids of the initial set

    def query_rows(self):
        """Query rows this mix draws from: a pool the clients cycle over."""
        return self.pool_rows

    def insert_room(self):
        """Ids beyond the initial set that this mix may insert."""
        return self.room

    def notes(self):
        """What the run's log says of this mix after the window."""
        return {"update_steps": self.steps,
                "insert_room_spent": self.room_spent}

    def warm(self, engine, queries, updates):
        self._live_init = np.arange(updates.n0)
        self._by_id = updates.by_id
        self._rng = rng_for(self.seed, 4)
        ops = []
        for _ in range(self.warm_steps):
            if not self._update_step(engine, updates, ops):
                raise RuntimeError("insert_room is spent in the warm steps")
        for rep in range(2):
            engine.search(self._request(queries, self._rng, rep).queries)
        t0 = time.perf_counter()
        self._loop(engine, queries, updates, t0, self.warm_seconds, ops)
        for op in ops:
            if op.error is not None:
                raise op.error
        updates.warm_ops = ops

    def run(self, engine, queries, updates, t0, seconds):
        ops: list = []
        reqs = self._loop(engine, queries, updates, t0, seconds, ops)
        return reqs, ops

    # -- one request, one update step ------------------------------------
    def _request(self, queries, rng, cursor):
        n_pool = self.rows - self.probes
        start = (cursor * n_pool) % (len(queries) - n_pool)
        q = queries[start:start + n_pool]
        acked = self._acked
        probe_ids = None
        if self.probes and acked:
            pick = rng.integers(0, len(acked), self.probes)
            probe_ids = np.asarray([acked[i] for i in pick], np.int64)
            q = np.concatenate([q, self._by_id[probe_ids]])
        elif self.probes:
            q = queries[start:start + self.rows]
        return Request(due=0.0, queries=np.ascontiguousarray(q),
                       probe_ids=probe_ids)

    def _update_step(self, engine, updates, ops):
        """Insert ``update_batch`` fresh vectors, then delete as many
        uniformly drawn live ids of the initial set. False, with nothing
        done, where the insert would pass ``insert_room``."""
        if (self.steps + 1) * self.step > self.room:
            self.room_spent = True
            return False
        vecs, attrs = updates.fresh(self.steps, self.step)
        self.steps += 1
        op = Op("insert", time.perf_counter())
        ops.append(op)
        with TraceAnnotation("bench.insert"):
            ids = engine.insert(vecs, attributes=attrs)
        op.ack, op.ids = time.perf_counter(), np.asarray(ids, np.int64)
        updates.remember(op.ids, vecs)
        self._acked.extend(op.ids.tolist())
        live = self._live_init
        pick = self._rng.choice(len(live), self.step, replace=False)
        gone = np.sort(live[pick])
        self._live_init = np.delete(live, pick)
        op = Op("delete", time.perf_counter(), ids=gone)
        ops.append(op)
        with TraceAnnotation("bench.delete"):
            engine.delete(gone)
        op.ack = time.perf_counter()
        return True

    # -- the loops ---------------------------------------------------------
    def _loop(self, engine, queries, updates, t0, seconds, ops):
        close = t0 + seconds
        reqs: list = []
        lock = threading.Lock()

        def client(c):
            rng = rng_for(self.seed, 100 + c)
            cursor = c * 7919
            while time.perf_counter() < close:
                r = self._request(queries, rng, cursor)
                cursor += 1
                r.due = r.sent = time.perf_counter()
                try:
                    with TraceAnnotation("bench.search"):
                        r.ids, r.dists = engine.submit_search(
                            r.queries).result(timeout=120.0)
                except Exception as e:
                    r.error = e
                r.done = time.perf_counter()
                with lock:
                    reqs.append(r)

        def updater():
            while time.perf_counter() < close:
                try:
                    if not self._update_step(engine, updates, ops):
                        return
                except Exception as e:
                    if ops and ops[-1].ack is None:
                        ops[-1].error = e
                    else:
                        ops.append(Op("insert", time.perf_counter(), error=e))
                    return

        ths = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(self.clients)]
        ths.append(threading.Thread(target=updater, daemon=True))
        for th in ths:
            th.start()
        for th in ths:
            th.join(timeout=seconds + 180.0)
        return reqs
