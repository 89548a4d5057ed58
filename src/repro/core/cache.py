"""Workload-Aware Vector Placement (paper §4.3, Algorithm 2) + baselines.

The prediction function F_λ(x) = α·F_recent(x,t) + β·log(1+E_in(x)) reduces
the gain test gain(x) > 0 to the threshold test F_λ(x) > θ with
θ = T_transfer/(T_CPU − T_GPU) (paper's theoretical analysis). Placement is
applied once per search batch with transfers amortized over the batch
(paper: 2048-vector transfer batches).

Eviction is the paper's clock-sweep with predicted-frequency tie-break,
*vectorized* for the TPU (DESIGN.md §2): empty slots are used first, then
slots with reference bit 0 in ascending F_λ; ref bits are refreshed by the
batch's cache hits (one sweep per batch). An exact sequential clock lives in
``clock_reference.py`` as the semantics oracle for tests.

Baseline policies (paper §6.3): LRU, LFU, LRFU, ``never`` (w/o WAVP — always
compute misses on the capacity tier), ``always`` (promote every miss).
"""
from __future__ import annotations

import threading
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.types import CacheState, GraphState, IndexState, SearchParams, Stats


def f_lambda(cache: CacheState, graph: GraphState):
    """F_λ(x) = α·F_recent + β·log(1+E_in) (paper eq. 2)."""
    return (cache.alpha * cache.f_recent
            + cache.beta * jnp.log1p(graph.e_in.astype(jnp.float32)))


def f_lambda_np(f_recent, e_in, alpha=1.0, beta=1.0):
    """Host-side F_λ over numpy mirrors — the SAME predictor drives both
    ends of the hierarchy: device-cache promotion (here / apply_wavp) and
    host-window demotion order in ``tiers.TieredStore`` (paper §4.3)."""
    return (np.float32(alpha) * np.asarray(f_recent, np.float32)
            + np.float32(beta) * np.log1p(np.asarray(e_in, np.float32)))


def _policy_scores(policy, cache, graph):
    """Higher score = more worth caching. f_recent holds the policy's own
    statistic: timestamps for LRU, raw counts for LFU, decayed counts (CRF)
    for LRFU/WAVP."""
    if policy in ("wavp", "always"):
        return f_lambda(cache, graph)
    return cache.f_recent


@partial(jax.jit, static_argnames=("sp",))
def apply_wavp(state: IndexState, acc_ids, acc_hit, sp: SearchParams,
               now=0) -> IndexState:
    """Post-batch placement pass (Algorithm 2, batched).

    acc_ids [B, rounds·beam·R] accessed ids (-1 pad) from the frontier
    executor's round logs, acc_hit [B, rounds·beam·R] hit flags.
    """
    graph, cache, stats = state.graph, state.cache, state.stats
    N = graph.capacity
    M = cache.n_slots

    ids = acc_ids.reshape(-1)
    hit = acc_hit.reshape(-1)
    valid = ids >= 0
    cid = jnp.clip(ids, 0)

    counts = jnp.zeros((N,), jnp.float32).at[cid].add(valid.astype(jnp.float32))
    miss_counts = jnp.zeros((N,), jnp.float32).at[cid].add(
        (valid & ~hit).astype(jnp.float32))

    if sp.policy == "lru":
        f_recent = jnp.where(counts > 0, jnp.float32(now) + 1.0,
                             cache.f_recent)
    else:
        decay = jnp.float32(1.0 if sp.policy == "lfu" else sp.decay)
        f_recent = cache.f_recent * decay + counts
    cache = cache._replace(f_recent=f_recent)

    n_acc = valid.sum()
    n_hit = (valid & hit).sum()
    stats = stats._replace(
        accesses=stats.accesses + n_acc.astype(jnp.int32),
        hits=stats.hits + n_hit.astype(jnp.int32),
        misses=stats.misses + (n_acc - n_hit).astype(jnp.int32),
    )

    if sp.policy == "never":
        # w/o WAVP: all misses computed in place on the capacity tier
        stats = stats._replace(cpu_computed=stats.cpu_computed
                               + (n_acc - n_hit).astype(jnp.int32))
        return IndexState(graph, cache, stats)

    score = _policy_scores(sp.policy, cache, graph)

    # ---- selective prefetch (Alg. 2 lines 1-2): F_λ(x) > θ to promote ----
    thr = cache.theta if sp.policy == "wavp" else jnp.float32(-jnp.inf)
    cand_mask = (miss_counts > 0) & (cache.h2d < 0) & graph.alive \
        & (score > thr)
    cand_score = jnp.where(cand_mask, score, -jnp.inf)
    P = min(sp.max_promote, M)
    prom_score, prom_ids = jax.lax.top_k(cand_score, P)
    prom_valid = jnp.isfinite(prom_score)

    # ---- predictive replacement (Alg. 2 lines 3-11), vectorized clock ----
    occ_score = jnp.where(cache.slot_hid >= 0,
                          score[jnp.clip(cache.slot_hid, 0)], -jnp.inf)
    # eviction priority: empty slots first, then ref==0 by ascending F_λ;
    # ref==1 slots are protected this sweep (second chance).
    empty = cache.slot_hid < 0
    protected = (cache.ref > 0) & ~empty
    evict_key = jnp.where(empty, -jnp.inf,
                          jnp.where(protected, jnp.inf, occ_score))
    victim_order = jnp.argsort(evict_key)
    victims = victim_order[:P]
    victim_ok = ~protected[victims]
    # only evict a victim whose score is lower than the incomer's
    improves = prom_valid & victim_ok & (
        (evict_key[victims] < prom_score) | empty[victims])

    vslot = jnp.where(improves, victims, M)        # M = scatter no-op row
    old_hid = jnp.where(improves, cache.slot_hid[jnp.clip(victims, 0)], -1)
    new_hid = jnp.where(improves, prom_ids, -1)

    h2d = cache.h2d.at[jnp.clip(old_hid, 0)].set(
        jnp.where(old_hid >= 0, -1, cache.h2d[jnp.clip(old_hid, 0)]))
    h2d = h2d.at[jnp.clip(new_hid, 0)].set(
        jnp.where(new_hid >= 0, vslot.astype(jnp.int32),
                  h2d[jnp.clip(new_hid, 0)]))

    slot_hid = jnp.concatenate([cache.slot_hid, jnp.full((1,), -1, jnp.int32)])
    slot_hid = slot_hid.at[vslot].set(jnp.where(improves, new_hid, -1))[:M]
    # pad row carries the cache dtype: a default-fp32 pad would silently
    # promote a bf16 bandwidth tier to fp32 (2x device-cache memory)
    vec_pad = jnp.concatenate(
        [cache.vectors,
         jnp.zeros((1, cache.vectors.shape[1]), cache.vectors.dtype)], 0)
    vec_pad = vec_pad.at[vslot].set(
        graph.vectors[jnp.clip(new_hid, 0)].astype(cache.vectors.dtype))
    vectors = vec_pad[:M]
    ver_pad = jnp.concatenate([cache.slot_ver, jnp.zeros((1,), jnp.int32)])
    ver_pad = ver_pad.at[vslot].set(graph.version[jnp.clip(new_hid, 0)])

    # clock ref refresh: slots hit this batch get a second chance
    hit_slot = jnp.where(valid & hit, cache.h2d[cid], -1)
    ref = jnp.zeros((M + 1,), jnp.int8).at[jnp.clip(hit_slot, 0)].set(
        jnp.int8(1))
    ref = ref.at[vslot].set(jnp.int8(1))[:M]       # fresh entries referenced

    n_prom = improves.sum().astype(jnp.int32)
    n_evict = (improves & (old_hid >= 0)).sum().astype(jnp.int32)
    cache = cache._replace(vectors=vectors, slot_hid=slot_hid, h2d=h2d,
                           ref=ref, slot_ver=ver_pad[:M])

    # ---- θ adaptation (paper §4.4): more selective when misses rise with
    # high predicted demand ----
    if sp.policy == "wavp":
        miss_rate = (n_acc - n_hit) / jnp.maximum(n_acc, 1)
        mean_f = jnp.where(cand_mask, score, 0.0).sum() / jnp.maximum(
            cand_mask.sum(), 1)
        pressure = miss_rate * mean_f
        theta = jnp.clip(cache.theta * 0.95 + 0.05 * pressure, 1e-3, 1e6)
        cache = cache._replace(theta=theta)

    stats = stats._replace(
        promotions=stats.promotions + n_prom,
        evictions=stats.evictions + n_evict,
        transfers=stats.transfers + n_prom,
        cpu_computed=stats.cpu_computed
        + (n_acc - n_hit).astype(jnp.int32) - n_prom)
    return IndexState(graph, cache, stats)


def miss_rate(stats: Stats) -> float:
    a = max(int(stats.accesses), 1)
    return float(stats.misses) / a


# ---------------------------------------------------------------------------
# Host-side placement for the tiered (disk-backed) engine
# ---------------------------------------------------------------------------

class CacheView(NamedTuple):
    """Immutable (h2d, vectors) pair readers resolve device hits against.
    Published as ONE attribute store so a concurrent placement pass can
    never pair an old mapping with new payloads (torn read)."""
    h2d: np.ndarray
    vectors: np.ndarray


class HostPlacement:
    """Numpy mirror of CacheState + Stats for the three-tier engine.

    When the capacity tier lives behind a ``TieredStore`` the placement
    pass cannot run inside jit (promoted payloads may need a disk read),
    so the engine keeps the bandwidth-tier bookkeeping in host arrays and
    runs Algorithm 2 here with identical semantics to ``apply_wavp``.
    Readers (search threads) take ``self.view`` once — a single immutable
    snapshot — without the engine's cache lock; the update pass builds
    fresh arrays and publishes them through one ``view`` assignment, so a
    concurrent reader sees a consistent (possibly one-batch stale) pair.

    Division of labor with the PQ code lane (``quant.PQCodes``): WAVP
    manages EXACT-vector slots only — the scarce fp32 payload the
    re-rank stage reads. PQ codes are ~D·4/m times smaller and therefore
    unconditionally device-resident; they never compete for these slots
    and never appear in the placement pass.
    """

    def __init__(self, n_ids: int, n_slots: int, dim: int, *, theta=1.0,
                 alpha=1.0, beta=1.0, dtype=np.float32):
        self.vectors = np.zeros((n_slots, dim), dtype)
        self.slot_hid = np.full((n_slots,), -1, np.int32)
        self.h2d = np.full((n_ids,), -1, np.int32)
        self.ref = np.zeros((n_slots,), np.int8)
        self.slot_ver = np.zeros((n_slots,), np.int32)
        self.f_recent = np.zeros((n_ids,), np.float32)
        self.theta = float(theta)
        self.alpha = float(alpha)
        self.beta = float(beta)
        self.counters = {f: 0 for f in Stats._fields}
        self.view = CacheView(self.h2d, self.vectors)

    @property
    def n_slots(self) -> int:
        return self.vectors.shape[0]

    @property
    def vector_bytes(self) -> int:
        """Device-resident exact-vector payload (the WAVP-managed slots;
        per-tier footprint reporting in ``engine.stats()``)."""
        return int(self.vectors.nbytes)

    def scores(self, e_in):
        return f_lambda_np(self.f_recent, e_in, self.alpha, self.beta)

    def warm(self, ids, vectors):
        """Cold-start preload (paper §4.4): fill slots [0, len(ids))."""
        m = min(len(ids), self.n_slots)
        sl = np.arange(m, dtype=np.int32)
        self.vectors[sl] = np.asarray(vectors[:m], self.vectors.dtype)
        self.slot_hid[sl] = np.asarray(ids[:m], np.int32)
        self.h2d[np.asarray(ids[:m])] = sl
        self.view = CacheView(self.h2d, self.vectors)

    def to_cache_state(self) -> CacheState:
        """Materialize the jit-side CacheState view (for engine.state)."""
        return CacheState(
            vectors=jnp.asarray(self.vectors),
            slot_hid=jnp.asarray(self.slot_hid),
            h2d=jnp.asarray(self.h2d),
            ref=jnp.asarray(self.ref),
            slot_ver=jnp.asarray(self.slot_ver),
            f_recent=jnp.asarray(self.f_recent),
            theta=jnp.asarray(self.theta, jnp.float32),
            alpha=jnp.asarray(self.alpha, jnp.float32),
            beta=jnp.asarray(self.beta, jnp.float32),
        )

    def to_stats(self) -> Stats:
        return Stats(*(jnp.asarray(self.counters[f], jnp.int32)
                       for f in Stats._fields))


class TopoCache:
    """Device-resident topology tier: a row-slot lane caching the hot
    subgraph's adjacency rows next to (not inside) the exact-vector
    cache, so the fused multi-round executor can walk the graph without
    a host round-trip per round (FusionANNS-style device-resident coarse
    structure: rows are degree·4 bytes/id vs dim·4 for a vector).

    Residency is ordered by the SAME WAVP F_λ predictor that manages the
    exact-vector slots: admission is demand-driven (the fused shell
    installs the frontier's missing rows before re-entering the loop) and
    eviction takes the lowest-F_λ residents first, with the current
    frontier protected so an install can never thrash the very rows the
    next dispatch needs.

    Write fencing mirrors ``_StageMap``: ``validate`` snapshots the
    store's write epoch and, when it moves (``update.insert_tiered``
    writes rows through ``TieredStore.write``), invalidates the cached
    topology wholesale — every resident row is re-read from the store in
    one bulk ``peek_rows`` and the device mirror republished, so a served
    row is never staler than the per-round path's demand fetch. Re-reading
    (rather than emptying) keeps the residency set, which is what keeps
    dispatches/query low across the interleaved insert batches of the
    streaming bench.

    Host arrays are the truth; ``synced`` publishes the device mirror
    (full re-put on change — installs are batched, so this is one
    transfer per host re-entry at worst). All mutation happens under one
    lock: concurrent search shells may install/validate concurrently.
    """

    def __init__(self, capacity: int, slots: int, degree: int):
        self.capacity = int(capacity)
        self.slots = int(slots)
        self.degree = int(degree)
        self.rows = np.full((max(self.slots, 1), degree), -1, np.int32)
        self.slot_hid = np.full((max(self.slots, 1),), -1, np.int64)
        self.h2s = np.full((capacity,), -1, np.int32)
        self.epoch = None            # set on first validate()
        self.hits = 0                # frontier ids found resident
        self.misses = 0              # frontier ids needing a delta fetch
        self.installs = 0
        self.evictions = 0
        self.flushes = 0             # epoch-fence wholesale refreshes
        self.h2d_bytes = 0           # bytes ``synced`` put on the device
        self._cursor = 0             # slots allotted once, like TieredStore
        self._dirty = True
        self._rows_j = None
        self._h2s_j = None
        self._lock = threading.Lock()

    @property
    def row_bytes(self) -> int:
        """Device-resident topology payload (bytes_per_tier reporting)."""
        return int(self.rows.nbytes + self.h2s.nbytes) if self.slots else 0

    @property
    def resident(self) -> int:
        return int((self.slot_hid >= 0).sum())

    @property
    def hit_rate(self) -> float:
        t = self.hits + self.misses
        return self.hits / t if t else 0.0

    def validate(self, store, tag=None) -> None:
        """Epoch fence: when the store's write epoch moved, re-read every
        resident row wholesale (one bulk peek) and republish. One
        ``svf.topo_validate`` span, tagged with ``tag``."""
        tag = tag or {}
        with TraceAnnotation("svf.topo_validate", **tag):
            ep = store.write_epoch
            with self._lock:
                if self.epoch is None:
                    self.epoch = ep
                    return
                if ep == self.epoch:
                    return
                occ = self.slot_hid >= 0
                if occ.any():
                    self.rows[occ] = store.peek_rows(self.slot_hid[occ],
                                                     tag=tag)
                    self._dirty = True
                self.epoch = ep
                self.flushes += 1

    def install(self, ids, rows, f_lam=None, protect=None) -> bool:
        """Install rows for unique non-resident ``ids``; returns False
        (installing nothing) when they cannot all fit without evicting a
        protected id — the caller falls back to a per-round dispatch.
        Eviction order: free slots first, then ascending F_λ."""
        ids = np.asarray(ids)
        m = len(ids)
        if m == 0:
            return True
        if self.slots == 0 or m > self.slots:
            return False
        with self._lock:
            free = self.slots - self._cursor
            spill = max(0, m - free)
            take = m - spill
            slots = np.empty((m,), np.int64)
            if spill:
                occ_ids = self.slot_hid
                if f_lam is not None:
                    key = np.asarray(f_lam, np.float64)[
                        np.clip(occ_ids, 0, None)].copy()
                else:
                    key = np.arange(len(occ_ids), dtype=np.float64)
                key[occ_ids < 0] = np.inf       # unpublished slots: not victims
                if protect is not None:
                    ps = self.h2s[np.asarray(protect)]
                    key[ps[ps >= 0]] = np.inf
                victims = np.argpartition(key, spill - 1)[:spill]
                if not np.isfinite(key[victims]).all():
                    return False                # would evict a protected row
                old = occ_ids[victims]
                self.h2s[old[old >= 0]] = -1
                slots[take:] = victims
                self.evictions += int(spill)
            if take:
                slots[:take] = np.arange(self._cursor, self._cursor + take)
                self._cursor += take
            self.rows[slots] = np.asarray(rows, np.int32)
            self.slot_hid[slots] = ids
            self.h2s[ids] = slots.astype(np.int32)
            self.installs += m
            self._dirty = True
            return True

    def lookup(self, ids):
        """(rows [m, R], resident [m]) host snapshot for unique ids — one
        locked read, so a concurrent install can never pair an id with
        another id's just-evicted slot contents."""
        ids = np.asarray(ids)
        with self._lock:
            s = self.h2s[ids]
            ok = s >= 0
            rows = np.full((len(ids), self.degree), -1, np.int32)
            rows[ok] = self.rows[s[ok]]
            return rows, ok

    def synced(self, tag=None):
        """Publish (rows, h2s) device mirrors; both republished together
        so a dispatch can never pair an old directory with new rows. A
        re-put is one ``svf.topo_sync`` span, tagged with ``tag``."""
        with self._lock:
            if self._dirty or self._rows_j is None:
                with TraceAnnotation("svf.topo_sync", **(tag or {})):
                    self._rows_j = jnp.asarray(self.rows)
                    self._h2s_j = jnp.asarray(self.h2s)
                self.h2d_bytes += self._rows_j.nbytes + self._h2s_j.nbytes
                self._dirty = False
            return self._rows_j, self._h2s_j


def warm_topo_cache(backend, slots: int) -> TopoCache:
    """Build, warm and attach the device-resident topology row cache for
    a tiered backend: full residency when ``slots`` covers the capacity,
    else the top-E_in live rows. The cache is a PURE cache of the store's
    adjacency truth — the engine calls this both at fresh build and after
    crash recovery (``wal.recover``), where every device mirror is
    rebuilt from the recovered host state."""
    cap = backend.capacity
    slots = slots or cap
    topo = TopoCache(cap, slots, backend.degree)
    topo.validate(backend.store)
    live = np.flatnonzero(backend.alive[:backend.n])
    if live.size > slots:          # partial cache: warm the hottest rows
        live = live[np.argsort(-backend.e_in[live], kind="stable")[:slots]]
    if live.size:
        topo.install(live, backend.store.peek_rows(live))
    backend.attach_topo(topo)
    return topo


def apply_wavp_host(hp: HostPlacement, acc_ids, acc_hit, sp: SearchParams,
                    *, alive, e_in, fetch_vectors, now=0,
                    cascade_promote: bool = True) -> None:
    """Post-batch placement (Algorithm 2) over host mirrors — the tiered
    twin of ``apply_wavp`` with the same decision rules.

    acc_ids/acc_hit: [B, rounds·beam·R] accessed ids (-1 pad) and
    device-hit flags from the frontier executor's round logs. alive/e_in:
    host graph metadata arrays. fetch_vectors(ids) resolves promoted
    payloads through the cascading host-window/disk lookup.

    ``cascade_promote``: batched serving touches every cached resident
    every batch, so the strict clock rule (ref==1 slots are untouchable
    this sweep) re-protects the whole cache each pass and promotion
    freezes at the cold-start set — cascade hits (ids served by host or
    disk during search) can then never earn a device slot no matter
    their F_λ. With the flag on (default), clock protection *orders* the
    sweep (ref==0 residents are still evicted first) but a protected
    resident is displaced when the incomer's F_λ strictly beats it —
    predictive replacement stays in charge, the freeze is gone.
    """
    N = hp.h2d.shape[0]
    M = hp.n_slots
    ids = np.asarray(acc_ids).reshape(-1)
    hit = np.asarray(acc_hit).reshape(-1)
    valid = ids >= 0

    # bincount, not np.add.at: the access log is ~rounds·beam·R·B ids per
    # batch and add.at's generalized fancy-index path costs ~10x a bincount
    counts = np.bincount(ids[valid], minlength=N).astype(np.float32)
    miss_counts = np.bincount(ids[valid & ~hit],
                              minlength=N).astype(np.float32)

    if sp.policy == "lru":
        f_recent = np.where(counts > 0, np.float32(now) + 1.0, hp.f_recent)
    else:
        decay = np.float32(1.0 if sp.policy == "lfu" else sp.decay)
        f_recent = hp.f_recent * decay + counts
    hp.f_recent = f_recent.astype(np.float32)

    n_acc = int(valid.sum())
    n_hit = int((valid & hit).sum())
    c = hp.counters
    c["accesses"] += n_acc
    c["hits"] += n_hit
    c["misses"] += n_acc - n_hit

    if sp.policy == "never":
        c["cpu_computed"] += n_acc - n_hit
        return

    if sp.policy in ("wavp", "always"):
        score = hp.scores(e_in)
    else:
        score = hp.f_recent

    thr = hp.theta if sp.policy == "wavp" else -np.inf
    cand_mask = (miss_counts > 0) & (hp.h2d < 0) & np.asarray(alive, bool) \
        & (score > thr)
    cand_ids = np.where(cand_mask)[0]
    P = min(sp.max_promote, M, cand_ids.size)
    n_prom = n_evict = 0
    # copy-on-write: concurrent search threads resolve hits through
    # hp.view, so mutations land on fresh copies published in one
    # ``view`` assignment (stale-by-one-batch reads fine, torn reads not)
    h2d, slot_hid = hp.h2d.copy(), hp.slot_hid.copy()
    vectors, slot_ver = hp.vectors, hp.slot_ver
    vslot = np.empty((0,), np.int64)
    if P > 0:
        top = cand_ids[np.argpartition(-score[cand_ids], P - 1)[:P]]
        top = top[np.argsort(-score[top])]
        prom_score = score[top]

        occ = hp.slot_hid >= 0
        occ_score = np.where(occ, score[np.clip(hp.slot_hid, 0, None)],
                             -np.inf)
        protected = (hp.ref > 0) & occ
        if cascade_promote:
            # empty first, then ref==0 ascending F_λ, then ref==1
            # ascending F_λ; any occupant yields to a strictly hotter
            # incomer (see docstring — protection orders, never freezes)
            victims = np.lexsort((occ_score, protected))[:P]
            improves = ~occ[victims] | (occ_score[victims] < prom_score)
        else:
            evict_key = np.where(~occ, -np.inf,
                                 np.where(protected, np.inf, occ_score))
            victims = np.argsort(evict_key, kind="stable")[:P]
            improves = ~protected[victims] & (
                (evict_key[victims] < prom_score) | ~occ[victims])

        vslot = victims[improves]
        new_hid = top[improves]
        old_hid = hp.slot_hid[vslot]
        evicted = old_hid[old_hid >= 0]
        vectors, slot_ver = hp.vectors.copy(), hp.slot_ver.copy()
        h2d[evicted] = -1
        payload = np.asarray(fetch_vectors(new_hid), vectors.dtype)
        vectors[vslot] = payload
        slot_hid[vslot] = new_hid.astype(np.int32)
        h2d[new_hid] = vslot.astype(np.int32)
        slot_ver[vslot] = 0
        n_prom = int(improves.sum())
        n_evict = int(evicted.size)

    # clock ref refresh EVERY batch, promotions or not (same as the jit
    # twin): hits this batch + fresh entries get a second chance
    ref = np.zeros((M,), np.int8)
    hit_ids = ids[valid & hit]
    hit_slots = h2d[hit_ids]
    ref[hit_slots[hit_slots >= 0]] = 1
    ref[vslot] = 1
    hp.vectors, hp.slot_hid, hp.h2d = vectors, slot_hid, h2d
    hp.slot_ver, hp.ref = slot_ver, ref
    hp.view = CacheView(h2d, vectors)

    if sp.policy == "wavp":
        mr = (n_acc - n_hit) / max(n_acc, 1)
        mean_f = (float(score[cand_mask].sum()) / max(int(cand_mask.sum()), 1))
        hp.theta = float(np.clip(hp.theta * 0.95 + 0.05 * mr * mean_f,
                                 1e-3, 1e6))

    c["promotions"] += n_prom
    c["evictions"] += n_evict
    c["transfers"] += n_prom
    c["cpu_computed"] += (n_acc - n_hit) - n_prom
