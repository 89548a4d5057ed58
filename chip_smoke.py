#!/usr/bin/env python3
"""Bring-up check: serve the SVFusion engine on one TPU chip.

    python3 chip_smoke.py             # one chip: the engine's served path
    python3 chip_smoke.py --chips 4   # four chips: the sharded search only

One chip: builds three-tier engines (``SVFusionEngine``) over 1,048,576
seeded vectors at the DEEP1B widths of ``configs/svfusion_deep1b.py``
(D=96, degree 32, pool 64, k=10, 131,072 hot-cache slots) and serves
batches of 256 queries through ``engine.search`` on the exact tiered lane
and on the PQ lane with exact re-rank, filtered searches at 1% (the
brute-force fallback lane) and 10% (the graph lane), then one insert wave
and one delete wave followed by more searches. Every phase is checked
against a plain reference that shares no code with the engine: exact
top-k by brute force on the device, at fp32 precision, over the live,
filter-passing set. A phase fails on recall@10 below 0.9 (the repo's own
floor for every lane), on a returned id that is dead, deleted or fails
the filter, or on a distance further from the float64 truth than fp32
rounding allows.

Four chips: ``make_distributed_search`` over a 4-device mesh, one index
shard per chip, against the same search replayed shard by shard on one
device (identical top-k sets) and a one-device brute-force reference.

Each phase prints one line: wall time, compile time, peak device bytes,
and which implementation each served kernel op was traced with. The last
line of standard output is ``{"ok": true, "device": {...}}``; any failed
check raises and exits non-zero before it. With no TPU the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402
import numpy as np                                    # noqa: E402
from jax import monitoring                            # noqa: E402

from repro.configs.svfusion_deep1b import DEEP1B     # noqa: E402
from repro.core.engine import EngineConfig, SVFusionEngine  # noqa: E402
from repro.core.filters import AttributeSchema, FilterSpec  # noqa: E402
from repro.core.types import SearchParams            # noqa: E402
from repro.kernels import ops                        # noqa: E402
from repro.utils import use_compile_cache            # noqa: E402

N = 1 << 20                 # ids served on one chip
N_SHARD = 1 << 18           # ids per chip on the four-chip mesh
BATCH = 256                 # queries per engine.search call
BATCHES = 4                 # batches per serving phase
WAVE = 4096                 # vectors inserted, ids deleted
LATENT = 16                 # intrinsic dimension of the generated data
RECALL_FLOOR = 0.9          # the repo's recall@10 floor on every lane
EPS32 = float(np.finfo(np.float32).eps)
SCHEMA = AttributeSchema(tag_fields=("cat",), num_fields=("score",),
                         tag_domain=10)
TEN_PCT = FilterSpec(tags={"cat": {0}})             # cat uniform on 0..9
ONE_PCT = FilterSpec(ranges={"score": (None, 0.01)})  # score uniform [0,1)
SEARCH = SearchParams(k=DEEP1B["search"].k, pool=DEEP1B["search"].pool,
                      max_iters=128, beam=16)
CUTS = [
    f"ids: {N:,} on one chip, not the 3,906,250 that are one chip's share "
    "of DEEP1B (1e9 over the config's 256 chips); build time: the graph "
    "build runs the O(n^2) exact kNN (_exact_knn), twice (exact and PQ "
    "engines), inside the 1200 s run",
    "data: seeded vectors at D=96 with intrinsic dimension 16 (a linear "
    "map of a 16-d Gaussian plus isotropic noise), not the DEEP1B "
    "descriptors, which are not downloaded; on i.i.d. 96-d Gaussians, "
    "which have no neighbourhood structure for a graph to follow, the "
    "walk measured recall@10 0.28-0.45 on CPU at 100k ids whatever its "
    "budget",
    f"query batch: {BATCH}, not the config's 10,240: the engine's "
    "coalescer dispatches at most coalesce_max_batch=256 rows",
    "search budget: max_iters=128 at beam 16 (8 rounds), not the "
    "config's 64 (4 rounds): on CPU at 100k ids of this data 4 rounds "
    "reach recall@10 0.93 unfiltered and 0.81 at 10% selectivity",
    f"updates: one wave of {WAVE} inserts and one of {WAVE} deletes",
]


class CompileMeter:
    """Sums JAX's compile-time events and persistent-cache hits."""

    def __init__(self):
        self.secs = 0.0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Phase:
    """Times one phase and prints its line: the phase's own numbers,
    then wall time, compile time, cache hits, peak device bytes and the
    implementations the served kernel ops were traced with so far."""

    def __init__(self, name, meter):
        self.name, self.meter, self.out = name, meter, {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0 = self.meter.secs, self.meter.hits
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        self.out["wall_s"] = time.perf_counter() - self.t0
        self.out["compile_s"] = self.meter.secs - self.c0
        self.out["cache_hits"] = self.meter.hits - self.h0
        self.out["peak_bytes_in_use"] = peak_bytes()
        self.out["ops"] = ",".join(sorted(f"{op}:{impl}"
                                          for op, impl in ops.traced))
        print(f"[{self.name}] " + " ".join(f"{k}={v}"
                                           for k, v in self.out.items()),
              flush=True)
        return False


def peak_bytes():
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def check_kernels_served():
    """On the TPU every served kernel op must have been traced with the
    compiled Pallas kernel, never with its jnp reference."""
    ref = sorted(op for op, impl in ops.traced if impl != ops.PALLAS)
    if ref:
        raise AssertionError(f"served ops ran the reference: {ref}")


# ---------------------------------------------------------------------------
# data and the brute-force reference (no engine code)
# ---------------------------------------------------------------------------

def make_data(seed, n, dim):
    """Seeded vectors, made in bulk: a random linear map of a LATENT-dim
    Gaussian plus isotropic noise (std 0.075 against a per-dimension
    signal std of 1), and uniform attributes."""
    rng = np.random.default_rng(seed)
    basis = rng.standard_normal((LATENT, dim), np.float32) / np.sqrt(LATENT)
    x = rng.standard_normal((n, LATENT), np.float32) @ basis
    x += np.float32(0.075) * rng.standard_normal((n, dim), np.float32)
    cat = rng.integers(0, 10, n).astype(np.int32)
    score = rng.random(n).astype(np.float32)
    return x, cat, score


@partial(jax.jit, static_argnames=("k",))
def _exact_topk(data, mask, q, k):
    """Brute-force top-k over the rows with ``mask`` set, fp32."""
    hi = jax.lax.Precision.HIGHEST
    d = (jnp.sum(q * q, 1, keepdims=True)
         - 2.0 * jnp.matmul(q, data.T, precision=hi)
         + jnp.sum(data * data, 1)[None, :])
    d = jnp.where(mask[None, :], d, jnp.inf)
    nd, idx = jax.lax.top_k(-d, k)
    return idx, -nd


class Reference:
    """Exact top-k over a device copy of every vector ever stored, with
    the live and filter-passing sets tracked by this script."""

    def __init__(self, vecs, capacity):
        pad = np.zeros((capacity, vecs.shape[1]), np.float32)
        pad[:len(vecs)] = vecs
        self.host = pad
        self.dev = jnp.asarray(pad)

    def add(self, ids, vecs):
        self.host[ids] = vecs
        self.dev = self.dev.at[jnp.asarray(ids)].set(jnp.asarray(vecs))

    def topk(self, q, mask, k):
        ids, d = _exact_topk(self.dev, jnp.asarray(mask), jnp.asarray(q), k)
        return np.asarray(ids), np.asarray(d)


def check_results(ref, q, ids, dists, mask, k):
    """recall@k against the reference, validity of every returned id,
    and every distance within fp32 rounding of the float64 truth."""
    want, want_d = ref.topk(q, mask, k)
    live_ids = ids[ids >= 0]
    bad = live_ids[~mask[live_ids]]
    if bad.size:
        raise AssertionError(f"returned {bad.size} ids that are dead, "
                             f"deleted or fail the filter: {bad[:8]}")
    recall = np.mean([len(set(a[a >= 0]) & set(b)) / k
                      for a, b in zip(ids, want)])
    worst = 0.0
    for got_i, got_d in ((ids, dists), (want, want_d)):
        ok = got_i >= 0
        x = ref.host[np.where(ok, got_i, 0)].astype(np.float64)
        q64 = q.astype(np.float64)[:, None, :]
        truth = ((x - q64) ** 2).sum(-1)
        # fp32 evaluation of |x|^2 - 2 x.q + |q|^2 over D terms
        bound = 2 * q.shape[1] * EPS32 * ((x ** 2).sum(-1)
                                          + (q64 ** 2).sum(-1))
        gap = np.abs(got_d.astype(np.float64) - truth)
        worst = max(worst, float(np.max(np.where(ok, gap / bound, 0.0))))
    if worst > 1.0:
        raise AssertionError(f"distance off the fp32 truth by {worst:.3g}x "
                             "the rounding bound")
    return float(recall), worst


def serve(engine, ref, queries, mask, meter, name, filter=None,
          path=None):
    """Serve batches through engine.search and check each one."""
    recalls, worst, lat = [], 0.0, []
    with Phase(name, meter) as ph:
        for b in range(0, len(queries), BATCH):
            q = queries[b:b + BATCH]
            t0 = time.perf_counter()
            ids, dists = engine.search(q, filter=filter)
            lat.append(time.perf_counter() - t0)
            r, w = check_results(ref, q, ids, dists, mask, SEARCH.k)
            recalls.append(r)
            worst = max(worst, w)
        st = engine.stats()
        ph.out.update(
            recall_at_10=float(np.mean(recalls)), recall_floor=RECALL_FLOOR,
            dist_gap_over_fp32_bound=worst, first_batch_s=lat[0],
            steady_batch_s=float(np.median(lat[1:])) if len(lat) > 1
            else lat[0],
            search_dispatches_per_batch=st["search_dispatches_per_batch"],
            topo_hit_rate=st["topo_hit_rate"])
        if filter is not None:
            ph.out.update(path=st["filter_last_path"],
                          sampled_selectivity=st["filter_last_selectivity"],
                          selectivity=float(mask.mean()))
        check_kernels_served()
        if filter is not None and st["filter_last_path"] != path:
            raise AssertionError(f"{name}: routed to "
                                 f"{st['filter_last_path']}, not {path}")
        if ph.out["recall_at_10"] < RECALL_FLOOR:
            raise AssertionError(f"{name}: recall@10 "
                                 f"{ph.out['recall_at_10']:.4f} < "
                                 f"{RECALL_FLOOR}")
    return ph.out


# ---------------------------------------------------------------------------
# one chip: the engine's served path
# ---------------------------------------------------------------------------

def engine_config(path, capacity, pq, seed):
    return EngineConfig(
        degree=DEEP1B["degree"], cache_slots=DEEP1B["cache_slots_per_chip"],
        capacity=capacity, disk_path=path, disk_capacity=capacity,
        search=SEARCH, seed=seed, attributes=SCHEMA,
        # 1% always routes to the fallback, 10% always to the graph lane,
        # whatever the admission sample's +-1% noise
        filter_fallback_selectivity=0.05,
        # fp32 payload: the config's 131,072 slots are 48 MB of fp32, and
        # an exact re-rank needs exact vectors
        cache_dtype="fp32",
        pq_enabled=pq, pq_m=48)


def run_one_chip(seed, meter, workdir):
    dim = DEEP1B["dim"]
    capacity = N + 2 * WAVE
    n_q = BATCH * BATCHES
    with Phase("data", meter) as ph:
        x, cat, score = make_data(seed, N + WAVE + 2 * n_q, dim)
        base, fresh = x[:N], x[N:N + WAVE]
        q_main, q_post = x[N + WAVE:N + WAVE + n_q], x[N + WAVE + n_q:]
        ref = Reference(base, capacity)
        live = np.zeros(capacity, bool)
        live[:N] = True
        cats = np.zeros(capacity, np.int32)
        scores = np.ones(capacity, np.float32)
        cats[:N], scores[:N] = cat[:N], score[:N]
        ph.out.update(n=N, dim=dim, queries=2 * n_q)
    init_attrs = {"cat": cat[:N], "score": score[:N]}
    ten = live & (cats == 0)
    one = live & (scores <= np.float32(0.01))

    for pq in (False, True):
        lane = "pq" if pq else "exact"
        with Phase(f"build_{lane}", meter) as ph:
            eng = SVFusionEngine(base, engine_config(
                os.path.join(workdir, lane), capacity, pq, seed),
                init_attrs=init_attrs)
            ph.out.update(n=eng.stats()["n"], pq=pq)
        try:
            serve(eng, ref, q_main, live, meter, f"{lane}_lane")
            serve(eng, ref, q_main[:2 * BATCH], live & ten, meter,
                  f"{lane}_filtered_10pct", filter=TEN_PCT, path="graph")
            if not pq:
                continue
            serve(eng, ref, q_main[:2 * BATCH], live & one, meter,
                  f"{lane}_filtered_1pct", filter=ONE_PCT, path="fallback")
            run_updates(eng, ref, live, cats, scores, fresh, cat[N:N + WAVE],
                        score[N:N + WAVE], q_post, meter, seed)
            run_kernels(base, q_main[:BATCH], meter, seed)
        finally:
            eng.close()


def run_updates(eng, ref, live, cats, scores, fresh, fcat, fscore, q_post,
                meter, seed):
    """One insert wave, one delete wave, then search again: inserted
    vectors are found (as their own nearest neighbours), deleted ids
    never come back."""
    with Phase("insert_wave", meter) as ph:
        ids = eng.insert(fresh, attributes={"cat": fcat, "score": fscore})
        ref.add(ids, fresh)
        live[ids] = True
        cats[ids], scores[ids] = fcat, fscore
        ph.out.update(inserted=len(ids))
    # delete every post-update query's current nearest neighbour, plus
    # random live ids of the initial set up to the wave size
    near, _ = ref.topk(q_post, live, 1)
    rng = np.random.default_rng(seed + 1)
    gone = np.unique(near[:, 0])
    others = rng.choice(np.where(live[:N])[0], 2 * WAVE, replace=False)
    gone = np.concatenate([gone, np.setdiff1d(others, gone)])[:WAVE]
    with Phase("delete_wave", meter) as ph:
        eng.delete(gone)
        live[gone] = False
        ph.out.update(deleted=len(gone))
    serve(eng, ref, q_post, live, meter, "after_updates")
    self_q = fresh[:BATCH]
    with Phase("inserted_self_hit", meter) as ph:
        got, _ = eng.search(self_q)
        want, _ = ref.topk(self_q, live, 1)
        ph.out.update(self_hit_at_1=float(np.mean(got[:, 0] == want[:, 0])))
        if ph.out["self_hit_at_1"] < RECALL_FLOOR:
            raise AssertionError("inserted vectors are not found")
    for spec, mask, path in ((TEN_PCT, cats == 0, "graph"),
                             (ONE_PCT, scores <= np.float32(0.01),
                              "fallback")):
        serve(eng, ref, q_post[:BATCH], live & mask, meter,
              f"after_updates_filtered_{path}", filter=spec, path=path)


def run_kernels(base, q, meter, seed):
    """Stand-alone kernel vs jnp-reference times at the served shapes and
    table sizes (input for tuning; not a served phase)."""
    from repro.kernels.l2_gather.kernel import l2_gather
    from repro.kernels.l2_gather.ref import l2_gather_ref
    from repro.kernels.pq_adc.kernel import pq_adc
    from repro.kernels.pq_adc.ref import pq_adc_ref
    from repro.kernels.row_gather.kernel import row_gather
    from repro.kernels.row_gather.ref import row_gather_ref
    rng = np.random.default_rng(seed + 2)
    C, R, M = SEARCH.beam * DEEP1B["degree"], DEEP1B["degree"], 48
    ids = jnp.asarray(rng.integers(0, N, (BATCH, C)), jnp.int32)
    front = jnp.asarray(rng.integers(0, N, (BATCH, SEARCH.beam)), jnp.int32)
    qj = jnp.asarray(q)
    table = jnp.asarray(base)
    codes = jnp.asarray(rng.integers(0, 256, (N, M)), jnp.uint8)
    lut = jnp.asarray(rng.random((BATCH, M, 256), np.float32))
    rows = jnp.asarray(rng.integers(-1, N, (N, R)), jnp.int32)
    h2s = jnp.asarray(np.where(rng.random(N) < 0.9, np.arange(N), -1),
                      jnp.int32)
    cases = {
        "l2_gather": (l2_gather, l2_gather_ref, (table, ids, qj)),
        "pq_adc": (pq_adc, pq_adc_ref, (codes, lut, ids)),
        "row_gather": (row_gather, row_gather_ref, (rows, h2s, front)),
    }
    for name, (kern, refn, args) in cases.items():
        with Phase(f"kernel_{name}", meter) as ph:
            times = {}
            outs = {}
            for label, fn in (("kernel", kern), ("ref", refn)):
                jax.block_until_ready(fn(*args))
                ts = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    outs[label] = jax.block_until_ready(fn(*args))
                    ts.append(time.perf_counter() - t0)
                times[label] = float(np.median(ts))
            a = np.asarray(outs["kernel"], np.float64)
            b = np.asarray(outs["ref"], np.float64)
            err = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))
            ph.out.update(shape=list(ids.shape if name != "row_gather"
                                     else front.shape),
                          kernel_s=times["kernel"], ref_s=times["ref"],
                          max_rel_err_vs_ref=err)
            if not err <= 1e-5:
                raise AssertionError(f"{name} kernel disagrees with its "
                                     f"reference: {err}")


# ---------------------------------------------------------------------------
# four chips: the sharded device-lane search
# ---------------------------------------------------------------------------

def run_four_chips(seed, meter):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.build import build_graph
    from repro.core.distributed import make_distributed_search
    from repro.core.search import frontier_search
    from repro.core.types import IndexState, init_cache_state, init_stats
    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devices)}")
    dim, degree = DEEP1B["dim"], DEEP1B["degree"]
    slots = 4096
    with Phase("build_shards", meter) as ph:
        x, _, _ = make_data(seed, 4 * N_SHARD + BATCH, dim)
        base, q = x[:4 * N_SHARD], x[4 * N_SHARD:]
        graphs = [build_graph(base[s * N_SHARD:(s + 1) * N_SHARD], degree)
                  for s in range(4)]
        ph.out.update(shards=4, ids_per_shard=N_SHARD)
    mesh = jax.make_mesh((4,), ("data",), devices=devices)
    shard = NamedSharding(mesh, P("data"))
    shard2 = NamedSharding(mesh, P("data", None))
    caches = [init_cache_state(N_SHARD, slots, dim) for _ in range(4)]
    cat = lambda f, cs: jnp.concatenate([f(c) for c in cs])   # noqa: E731
    idx = {
        "vectors": jax.device_put(cat(lambda g: g.vectors, graphs), shard2),
        "nbrs": jax.device_put(cat(lambda g: g.nbrs, graphs), shard2),
        "alive": jax.device_put(cat(lambda g: g.alive, graphs), shard),
        "e_in": jax.device_put(cat(lambda g: g.e_in, graphs), shard),
        "cache_vectors": jax.device_put(cat(lambda c: c.vectors, caches),
                                        shard2),
        "slot_hid": jax.device_put(cat(lambda c: c.slot_hid, caches), shard),
        "h2d": jax.device_put(cat(lambda c: c.h2d, caches), shard),
        "f_recent": jax.device_put(cat(lambda c: c.f_recent, caches), shard),
    }
    per_device = {}
    for arr in idx.values():
        for s in arr.addressable_shards:
            per_device[s.device.id] = (per_device.get(s.device.id, 0)
                                       + s.data.nbytes)
    key = jax.random.PRNGKey(seed)
    qj = jnp.asarray(q)
    with Phase("sharded_search", meter) as ph:
        step = jax.jit(make_distributed_search(mesh, SEARCH,
                                               data_axes=("data",),
                                               query_axis=None))
        t0 = time.perf_counter()
        ids, dists = jax.block_until_ready(step(idx, qj, key))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        ids, dists = jax.block_until_ready(step(idx, qj, key))
        steady = time.perf_counter() - t0
        ids, dists = np.asarray(ids), np.asarray(dists)
        # the same per-shard walks replayed on one device, then merged
        cand_i, cand_d = [], []
        for s in range(4):
            entries = jax.random.randint(jax.random.fold_in(key, s),
                                         (BATCH, SEARCH.pool), 0, N_SHARD,
                                         dtype=jnp.int32)
            state = jax.device_put(
                IndexState(graphs[s], caches[s], init_stats()), devices[0])
            r = frontier_search(state, qj, entries, SEARCH)
            cand_i.append(np.where(np.asarray(r.ids) >= 0,
                                   np.asarray(r.ids) + s * N_SHARD, -1))
            cand_d.append(np.asarray(r.dists))
        cand_i, cand_d = np.concatenate(cand_i, 1), np.concatenate(cand_d, 1)
        order = np.argsort(cand_d, axis=1, kind="stable")[:, :SEARCH.k]
        want = np.take_along_axis(cand_i, order, 1)
        same = float(np.mean([set(a) == set(b) for a, b in zip(ids, want)]))
        ref = Reference(base, len(base))
        recall, worst = check_results(ref, q, ids, dists,
                                      np.ones(len(base), bool), SEARCH.k)
        ph.out.update(recall_at_10=recall, same_topk_sets_as_replay=same,
                      dist_gap_over_fp32_bound=worst, first_call_s=first,
                      steady_call_s=steady,
                      index_bytes_per_device={str(d): b for d, b in
                                              sorted(per_device.items())})
        check_kernels_served()
        if same != 1.0:
            raise AssertionError("sharded top-k sets differ from the "
                                 "one-device replay")
        if recall < RECALL_FLOOR:
            raise AssertionError(f"sharded recall@10 {recall:.4f} < "
                                 f"{RECALL_FLOOR}")
        if len(per_device) != 4 or min(per_device.values()) == 0:
            raise AssertionError(f"index not spread over 4 devices: "
                                 f"{per_device}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 2
    meter = CompileMeter()
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={cache_dir}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        print(f"[cut] ids: 4 shards of {N_SHARD:,} (1,048,576 in all, the "
              "one-chip run's size); build time: the four shard graphs are "
              "built one after another on one device", flush=True)
        run_four_chips(args.seed, meter)
    else:
        for cut in CUTS:
            print(f"[cut] {cut}", flush=True)
        print(f"[config] n={N} dim={DEEP1B['dim']} "
              f"degree={DEEP1B['degree']} k={SEARCH.k} pool={SEARCH.pool} "
              f"max_iters={SEARCH.max_iters} beam={SEARCH.beam} "
              f"cache_slots={DEEP1B['cache_slots_per_chip']} batch={BATCH}",
              flush=True)
        with tempfile.TemporaryDirectory() as workdir:
            run_one_chip(args.seed, meter, workdir)
    print(f"[total] wall_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
