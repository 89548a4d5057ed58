"""ANNS with CPU-GPU co-processing (paper Algorithm 1), TPU adaptation.

Both serving paths run through ONE **hop-batched frontier executor**: a
beam of ``sp.beam`` frontier candidates is expanded per *round*, their
neighborhoods are resolved in bulk through the tier cascade, and a single
jitted gather + distance + top-k-merge dispatch covers every hop in the
beam — the paper's CUDA multi-stream coordination of batched frontier
expansions (§4/§6) mapped onto XLA dispatch amortization:

* **device arm** (``search_batch``): the capacity tier is device-resident,
  so all rounds fuse into one jitted program (``lax.while_loop`` over
  rounds); distances come from the ``kernels/l2_gather`` arm with the
  device-cache overlay.
* **tiered arm** (``search_tiered``): the host owns traversal + residency
  over the disk-backed store, and runs as a **two-stage speculative
  pipeline** (paper §4.4 multi-stream overlap): while round N's single
  jitted distance+merge dispatch is in flight, the host predicts round
  N+1's frontier (entry stage: exact host distances; later rounds: the
  WAVP F_λ probe), stages the predicted rows and their neighborhoods'
  vectors, and enqueues disk prefetch one hop further. When the real
  frontier reads back, staged ids feed the next dispatch immediately and
  only mispredicted ids cost a delta fetch — the per-round read-back sync
  no longer serializes host IO behind device compute.

XLA-CPU note: a variadic (key, payload) sort — what ``jnp.argsort``
lowers to — costs ~10x a single-operand sort on this backend, and the
executor's merge used three of them per round. The core ops are built on
``lax.top_k`` (stable: equal values keep ascending-index order, matching
stable-argsort semantics) plus, for duplicate detection, ONE single-key
sort of ids packed with their lane index; semantics are unchanged (the
parity suite pins them against the per-hop reference).

Every expansion consults the cache mapping table; hits read the bandwidth
tier, misses the capacity tier, and both are logged for the post-batch
WAVP pass (cache.py) which amortizes transfer cost over the batch.

Returns per-query top-k plus the access/hit logs consumed by
``repro.core.cache.apply_wavp`` / ``apply_wavp_host``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import adc_lut
from repro.core.types import CacheState, GraphState, IndexState, SearchParams
from repro.kernels.ops import adc_gather, gather_l2, gather_rows

INF = jnp.float32(jnp.inf)


class SearchResult(NamedTuple):
    ids: jax.Array        # [B, k]
    dists: jax.Array      # [B, k]
    acc_ids: jax.Array    # [B, rounds*beam*R] accessed vertex ids (-1 pad)
    acc_hit: jax.Array    # [B, rounds*beam*R] cache-hit flags
    iters: jax.Array      # [B] expansion rounds used


def _n_rounds(sp: SearchParams) -> int:
    """Round budget: ceil(total hop budget / beam width)."""
    beam = max(1, sp.beam)
    return max(1, -(-sp.max_iters // beam))


# ---------------------------------------------------------------------------
# Shared executor core (pure jnp, batched over queries). Both arms build
# their jitted dispatch out of these three pieces.
# ---------------------------------------------------------------------------

def _lane_bits(width: int) -> int:
    return max(1, (width - 1).bit_length())


def _packable(id_bound, width: int) -> bool:
    """True when (id, lane) pairs over ``width`` lanes pack exactly into an
    int32 key: ids below ``id_bound`` shifted left still fit, and -1 pad
    lanes keep distinct negative keys (arithmetic shift recovers the id)."""
    return (id_bound is not None
            and int(id_bound) < (1 << (31 - _lane_bits(width))))


def _take(a, idx):
    return jnp.take_along_axis(a, idx, axis=-1)


def dup_mask_jnp(a, id_bound=None):
    """Later-occurrence duplicate flags for id batches [..., C] (the first
    occurrence survives). This is the cross-tier round dedup: the same id
    arriving from different tiers or different beam slots in one round
    collapses to a single candidate, so it can never occupy multiple pool
    slots. When ``id_bound`` (exclusive id upper bound, static) packs, the
    sort is ONE single-operand key sort of ``id·2^bits + lane`` — ~10x
    cheaper than the argsort pair-sort fallback on the CPU backend, with
    identical semantics (keys are unique, so sort stability is moot)."""
    C = a.shape[-1]
    if _packable(id_bound, C):
        bits = _lane_bits(C)
        lead = a.shape[:-1]
        flat = a.reshape((-1, C)).astype(jnp.int32)
        iota = jnp.arange(C, dtype=jnp.int32)
        s = jnp.sort((flat << bits) | iota, axis=-1)
        sid = s >> bits                      # arithmetic shift: -1 pads ok
        dup_sorted = jnp.concatenate(
            [jnp.zeros((flat.shape[0], 1), bool),
             sid[:, 1:] == sid[:, :-1]], axis=-1)
        pos = s & ((1 << bits) - 1)
        bidx = jnp.arange(flat.shape[0], dtype=jnp.int32)[:, None]
        out = jnp.zeros(flat.shape, bool).at[bidx, pos].set(dup_sorted)
        return out.reshape(lead + (C,))
    order = jnp.argsort(a, axis=-1, stable=True)
    srt = jnp.take_along_axis(a, order, axis=-1)
    dup_sorted = jnp.concatenate(
        [jnp.zeros(srt.shape[:-1] + (1,), bool),
         srt[..., 1:] == srt[..., :-1]], axis=-1)
    inv = jnp.argsort(order, axis=-1, stable=True)
    return jnp.take_along_axis(dup_sorted, inv, axis=-1)


def select_frontier(pool_ids, pool_d, visited, beam: int):
    """Pick the best ``beam`` unvisited finite pool slots per query and
    mark them visited. Returns (curr [B, beam] ids, -1 for idle lanes;
    visited'). ``lax.top_k`` keeps stable-argsort order (ties resolve to
    the lower index)."""
    sel = jnp.where(visited | ~jnp.isfinite(pool_d), INF, pool_d)
    negd, order = jax.lax.top_k(-sel, beam)
    ok = jnp.isfinite(negd)
    curr = jnp.where(ok, _take(pool_ids, order), -1)
    upd = _take(visited, order) | ok
    bidx = jnp.arange(pool_ids.shape[0], dtype=jnp.int32)[:, None]
    visited = visited.at[bidx, order].set(upd)
    return curr, visited


def merge_round(pool_ids, pool_d, visited, cand_ids, cand_d, id_bound=None):
    """Merge one round's candidate batch [B, C] into the pool [B, L].
    ``cand_d`` must already be INF on invalid/dead lanes; duplicates
    within the batch and ids already pooled are dropped here, preserving
    the pool's one-slot-per-id invariant.

    Fast path: pool and candidate ids concatenate into ONE packed-key
    sort — within a sorted id run, pool lanes (lane < L) precede
    candidate lanes, so a lane is a duplicate exactly when it continues
    a run (already pooled OR repeated in the batch). The top-L selection
    (``lax.top_k``) then runs directly in id-sorted lane order: gathers
    only, no scatter back to original lanes. Equal finite distances on
    *distinct* ids may tie-break differently from original-lane order —
    for exact duplicates (the only systematic ties) the survivor set is
    unchanged, so pool contents are unaffected on non-degenerate data.
    The argsort-era O(C·L) compare + pair-sorts remain as the fallback
    for unpackable id ranges."""
    L = pool_ids.shape[1]
    all_ids = jnp.concatenate([pool_ids, cand_ids], axis=1)
    T = all_ids.shape[1]
    all_vis = jnp.concatenate(
        [visited, jnp.zeros(cand_ids.shape, bool)], axis=1)
    if _packable(id_bound, T):
        bits = _lane_bits(T)
        iota = jnp.arange(T, dtype=jnp.int32)
        s = jnp.sort((all_ids.astype(jnp.int32) << bits) | iota, axis=-1)
        sid = s >> bits
        pos = s & ((1 << bits) - 1)
        cont = jnp.concatenate(
            [jnp.zeros((s.shape[0], 1), bool), sid[:, 1:] == sid[:, :-1]],
            axis=-1)
        all_d = jnp.concatenate([pool_d, cand_d], axis=1)
        d_srt = jnp.where(cont & (pos >= L), INF, _take(all_d, pos))
        _, keep = jax.lax.top_k(-d_srt, L)
        return (_take(sid, keep), _take(d_srt, keep),
                _take(all_vis, _take(pos, keep)))
    in_pool = (cand_ids[:, :, None] == pool_ids[:, None, :]).any(-1)
    cand_d = jnp.where(in_pool | dup_mask_jnp(cand_ids, id_bound),
                       INF, cand_d)
    all_d = jnp.concatenate([pool_d, cand_d], axis=1)
    _, keep = jax.lax.top_k(-all_d, L)
    return _take(all_ids, keep), _take(all_d, keep), _take(all_vis, keep)


def init_pool(entry_ids, entry_d, id_bound=None):
    """Sort the (deduped) entry pool into executor state."""
    d = jnp.where(dup_mask_jnp(entry_ids, id_bound), INF, entry_d)
    _, order = jax.lax.top_k(-d, d.shape[1])
    return (_take(entry_ids, order), _take(d, order),
            jnp.zeros(entry_ids.shape, bool))


def result_pool(ids, d, keep, id_bound=None):
    """Filtered search's result pool [B, L] (ids, dists), seeded from an
    entry or candidate batch: only lanes with ``keep`` set (alive AND
    passing the filter) enter it, while the traversal pool goes on
    walking through nodes that fail the filter — a graph restricted to
    the passing nodes falls apart at low selectivity. None when the
    search is unfiltered (``keep`` None)."""
    if keep is None:
        return None
    r_ids, r_d, _ = init_pool(ids, jnp.where(keep, d, INF), id_bound)
    return r_ids, r_d


def merge_result(res, cand_ids, cand_d, keep, id_bound=None):
    """Merge one round's kept candidates into the result pool (identity
    when unfiltered)."""
    if res is None:
        return None
    r_ids, r_d, _ = merge_round(res[0], res[1], jnp.zeros(res[0].shape, bool),
                                cand_ids, jnp.where(keep, cand_d, INF),
                                id_bound)
    return r_ids, r_d


def _run_fused_rounds(state, r_stop, beam, id_bound, row_fn, dist_fn,
                      keep_fn=None):
    """The ONE fused multi-round executor core both arms share: a
    ``lax.while_loop`` running row gather -> distance -> topk merge ->
    next-frontier select entirely on device, round after round, until the
    round budget ``r_stop`` (a traced operand: callers re-enter without a
    recompile), the pool runs dry, or a row lookup stalls.

    ``state`` carry: (r, pool_ids, pool_d, visited, curr, acc_ids
    [B, rounds, C], acc_hit, iters [B], res, stall), ``res`` the filtered
    search's result pool or None. The frontier ``curr`` is
    selected at the END of each body (entry select happens outside), so
    the loop condition reads residual work straight off the idle-lane
    sentinel — same gating as the old device-arm loop, where the select
    ran at the top of the body.

    ``row_fn(curr [B, beam]) -> (nb [B, beam, R], resident [B, beam])``
    resolves frontier adjacency. The device arm's capacity tier is always
    resident; the tiered arm gathers through the device topology cache
    (``kernels/row_gather``) and reports non-resident frontier ids. Any
    true (id >= 0) non-resident lane STALLS the loop: the body's updates
    are discarded wholesale (the round is not half-applied) and the loop
    exits with ``stall`` set so the host shell can delta-fetch the rows
    and re-enter at the same ``r`` — the miss costs one extra dispatch,
    never a wrong merge.

    ``dist_fn(nb [B, C]) -> (d, hit, valid)`` scores a flattened
    candidate batch, +inf on invalid lanes. ``keep_fn(nb) -> [B, C]``
    (filtered search) marks the lanes that may enter the result pool.
    """
    def cond(s):
        r, _ids, _d, _vis, curr, _ai, _ah, _it, _res, stall = s
        return (r < r_stop) & ~stall & (curr >= 0).any()

    def body(s):
        r, ids, dists, visited, curr, acc_ids, acc_hit, iters, res, _ = s
        B, C = acc_ids.shape[0], acc_ids.shape[2]
        nb, res_ok = row_fn(curr)                     # [B, beam, R]
        stall = ((curr >= 0) & ~res_ok).any()
        nb = jnp.where(curr[..., None] >= 0, nb, -1).reshape(B, C)
        d, hit, valid = dist_fn(nb)
        active = (curr >= 0).any(1)                   # [B]
        ids2, d2, vis2 = merge_round(ids, dists, visited, nb, d, id_bound)
        curr2, vis2 = select_frontier(ids2, d2, vis2, beam)
        if keep_fn is not None:
            res2 = merge_result(res, nb, d, keep_fn(nb) & valid, id_bound)
        else:
            res2 = res
        new = (r + 1, ids2, d2, vis2, curr2,
               acc_ids.at[:, r].set(jnp.where(valid, nb, -1)),
               acc_hit.at[:, r].set(hit & valid),
               iters + active.astype(jnp.int32), res2)
        old = (r, ids, dists, visited, curr, acc_ids, acc_hit, iters, res)
        # a stalled round is discarded atomically: every carry leaf keeps
        # its pre-round value so the host re-enters at the same state
        return jax.tree.map(lambda o, n: jnp.where(stall, o, n),
                            old, new) + (stall,)

    return jax.lax.while_loop(cond, body, state)


# ---------------------------------------------------------------------------
# Device arm: in-memory tiers, one fused jitted program
# ---------------------------------------------------------------------------

def _device_distances(graph: GraphState, cache: CacheState, ids, queries):
    """Distances for an id batch [B, C] through the two device tiers: the
    ``l2_gather`` kernel arm against the capacity table, overlaid with the
    bandwidth-tier copy on cache hits. Invalid ids (< 0) come back +inf.
    Returns (dists [B, C] fp32, device_hit [B, C])."""
    cid = jnp.clip(ids, 0)
    slot = cache.h2d[cid]
    hit = (slot >= 0) & (ids >= 0)
    d_cap = gather_l2(graph.vectors, ids, queries)
    d_dev = gather_l2(cache.vectors, jnp.where(hit, slot, -1), queries)
    return jnp.where(hit, d_dev, d_cap), hit


def _frontier_search(graph: GraphState, cache: CacheState, queries, entries,
                     sp: SearchParams) -> SearchResult:
    """Hop-batched frontier executor, device arm (traceable; callers jit).
    queries [B, D], entries [B, L]. Rounds run through the shared
    ``_run_fused_rounds`` core — the capacity tier is device-resident, so
    ``row_fn`` never stalls and every round fuses into the one jitted
    while_loop, exactly the old bespoke loop's schedule (the parity suite
    pins this against the per-hop reference)."""
    B = queries.shape[0]
    L, R = sp.pool, graph.degree
    beam = max(1, min(sp.beam, L))
    rounds = _n_rounds(sp)
    C = beam * R
    id_bound = graph.capacity            # static: drives the packed dedup
    queries = queries.astype(graph.vectors.dtype)

    d0, _ = _device_distances(graph, cache, entries, queries)
    d0 = jnp.where(graph.alive[jnp.clip(entries, 0)] & (entries >= 0),
                   d0, INF)
    pool_ids0, pool_d0, visited0 = init_pool(entries, d0, id_bound)
    curr0, visited0 = select_frontier(pool_ids0, pool_d0, visited0, beam)

    def row_fn(curr):
        nb = graph.nbrs[jnp.clip(curr, 0)]            # always resident
        return nb, jnp.ones(curr.shape, bool)

    def dist_fn(nb):
        valid = (nb >= 0) & graph.alive[jnp.clip(nb, 0)]
        d, hit = _device_distances(graph, cache, nb, queries)
        return jnp.where(valid, d, INF), hit, valid

    state0 = (jnp.int32(0), pool_ids0, pool_d0, visited0, curr0,
              jnp.full((B, rounds, C), -1, jnp.int32),
              jnp.zeros((B, rounds, C), bool),
              jnp.zeros((B,), jnp.int32), None, jnp.bool_(False))
    (_, ids, dists, _, _, acc_ids, acc_hit, iters, _, _) = _run_fused_rounds(
        state0, rounds, beam, id_bound, row_fn, dist_fn)

    topk_ids = jnp.where(jnp.isfinite(dists[:, :sp.k]), ids[:, :sp.k], -1)
    return SearchResult(topk_ids, dists[:, :sp.k],
                        acc_ids.reshape(B, -1), acc_hit.reshape(B, -1),
                        iters)


@partial(jax.jit, static_argnames=("sp",))
def frontier_search(state: IndexState, queries, entries, sp: SearchParams
                    ) -> SearchResult:
    """Jitted executor entry with caller-chosen entry points (parity tests
    and update paths pass deterministic entries here)."""
    return _frontier_search(state.graph, state.cache,
                            queries.astype(jnp.float32), entries, sp)


@partial(jax.jit, static_argnames=("sp",))
def search_batch(state: IndexState, queries, key, sp: SearchParams
                 ) -> SearchResult:
    """Batched ANNS — thin entry point over the frontier executor.
    queries [B, D]. Entry points are random (paper §4.2: GPU-friendly, no
    seed maintenance under updates)."""
    B = queries.shape[0]
    n = jnp.maximum(state.graph.n, 1)
    entries = jax.random.randint(key, (B, sp.pool), 0, n, dtype=jnp.int32)
    return _frontier_search(state.graph, state.cache,
                            queries.astype(jnp.float32), entries, sp)


# ---------------------------------------------------------------------------
# Tiered arm: CPU traversal + disk IO, one device dispatch per round,
# speculative double-buffered staging between rounds
# ---------------------------------------------------------------------------

@jax.jit
def _batch_sqdist(x, q):
    """[B, C, D] gathered rows vs [B, D] queries -> [B, C] fp32 distances.
    Expansion form (‖x‖² − 2x·q + ‖q‖²): the inner product maps onto the
    batched-matmul path, ~1.4x the subtract-then-reduce einsum on CPU."""
    hi = jax.lax.Precision.HIGHEST    # fp32-exact on TPU, whose default
    #                                   rounds matmul operands to bf16
    xq = jnp.matmul(x, q[:, :, None], precision=hi,
                    preferred_element_type=jnp.float32)[..., 0]
    x2 = jnp.einsum("bcd,bcd->bc", x, x, precision=hi,
                    preferred_element_type=jnp.float32)
    q2 = jnp.einsum("bd,bd->b", q, q, precision=hi,
                    preferred_element_type=jnp.float32)[:, None]
    return x2 - 2.0 * xq + q2


@partial(jax.jit, static_argnames=("beam", "id_bound"))
def _tiered_entry_dispatch(entry_ids, entry_vecs, entry_valid, queries,
                           beam, id_bound, entry_keep=None):
    """Entry-pool distances + dedup + sort + first frontier selection:
    the first of the per-round dispatches (shares the executor core with
    the device arm). Pool state stays device-resident across rounds; only
    the tiny [B, beam] frontier id matrix crosses back to the host.
    ``entry_keep`` (filtered search) seeds the result pool."""
    d = _batch_sqdist(entry_vecs, queries)
    d = jnp.where(entry_valid, d, INF)
    pool_ids, pool_d, visited = init_pool(entry_ids, d, id_bound)
    curr, visited = select_frontier(pool_ids, pool_d, visited, beam)
    res = result_pool(entry_ids, d, entry_keep, id_bound)
    return pool_ids, pool_d, visited, curr, res


@partial(jax.jit, static_argnames=("beam", "id_bound"))
def _tiered_round_dispatch(pool_ids, pool_d, visited, cand_ids, uniq_vecs,
                           cand_inv, cand_valid, queries, beam, id_bound,
                           res=None, cand_keep=None):
    """ONE jitted gather+distance+topk-merge(+next frontier selection)
    dispatch covering every hop in the round's beam — the tiered arm of
    the shared executor. The host ships each round's *unique* vectors
    ``uniq_vecs [U, D]`` (U padded to a power-of-two bucket to bound jit
    specializations) plus the lane->unique map ``cand_inv [B, C]``; the
    [B, C, D] candidate matrix is gathered here, so transfer volume
    scales with unique ids, not beam·degree lanes. Pool state never
    round-trips through the host."""
    xv = uniq_vecs[cand_inv]
    d = _batch_sqdist(xv, queries)
    d = jnp.where(cand_valid, d, INF)
    pool_ids, pool_d, visited = merge_round(pool_ids, pool_d, visited,
                                            cand_ids, d, id_bound)
    curr, visited = select_frontier(pool_ids, pool_d, visited, beam)
    res = merge_result(res, cand_ids, d, cand_keep, id_bound)
    return pool_ids, pool_d, visited, curr, res


# ---------------------------------------------------------------------------
# PQ code lane (quant.py): ADC dispatches over device-resident codes.
# Rounds never fetch vectors through the tier cascade — only adjacency
# rows cross tiers — and a final re-rank stage pulls exact vectors for
# the top pool entries through the cascade.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("beam", "id_bound"))
def _pq_entry_dispatch(entry_ids, entry_valid, codes, centroids, queries,
                       beam, id_bound, entry_keep=None):
    """Entry-pool ADC scan + dedup + sort + first frontier selection —
    the code-lane twin of ``_tiered_entry_dispatch``. Builds the per-query
    ADC lookup tables in the same dispatch and returns them for reuse by
    every later round (the LUT is the only query-dependent PQ state)."""
    lut = adc_lut(centroids, queries)
    d = adc_gather(codes, lut, entry_ids)
    d = jnp.where(entry_valid, d, INF)
    pool_ids, pool_d, visited = init_pool(entry_ids, d, id_bound)
    curr, visited = select_frontier(pool_ids, pool_d, visited, beam)
    res = result_pool(entry_ids, d, entry_keep, id_bound)
    return pool_ids, pool_d, visited, curr, lut, res


@partial(jax.jit, static_argnames=("beam", "id_bound"))
def _pq_round_dispatch(pool_ids, pool_d, visited, cand_ids, cand_valid,
                       codes, lut, beam, id_bound, res=None, cand_keep=None):
    """ONE jitted code-gather + ADC + topk-merge (+ next frontier
    selection) dispatch covering every hop in the round's beam. Unlike
    the exact lane's ``_tiered_round_dispatch`` the host ships NOTHING
    per round — candidates are scored from the unconditionally resident
    codes, so per-round cross-tier traffic is adjacency rows only."""
    d = adc_gather(codes, lut, cand_ids)
    d = jnp.where(cand_valid, d, INF)
    pool_ids, pool_d, visited = merge_round(pool_ids, pool_d, visited,
                                            cand_ids, d, id_bound)
    curr, visited = select_frontier(pool_ids, pool_d, visited, beam)
    res = merge_result(res, cand_ids, d, cand_keep, id_bound)
    return pool_ids, pool_d, visited, curr, res


@partial(jax.jit, static_argnames=("beam", "id_bound"))
def _pq_fused_dispatch(pool_ids, pool_d, visited, curr, r, acc_ids,
                       topo_rows, topo_h2s, codes, lut, alive, r_stop,
                       beam, id_bound, res=None, fmask=None):
    """K consecutive PQ rounds in ONE jitted dispatch — the tiered arm's
    instantiation of the shared ``_run_fused_rounds`` core. While the
    frontier stays inside the device-resident topology cache the loop
    runs row gather (``kernels/row_gather`` over the cached adjacency
    table) -> ``pq_adc`` ADC scan -> topk merge -> next-frontier select
    entirely on device; a topology-cache miss stalls the loop atomically
    and returns the pre-round state, so the host shell delta-fetches the
    rows and re-enters at the same ``r``. ``r``/``r_stop`` are traced
    operands: re-entries and K-budget changes never recompile.

    Bit-parity with the per-round ``_pq_round_dispatch`` path holds by
    construction: the cached rows equal the store rows (epoch-fenced),
    the candidate mask/merge/select are the same shared core ops in the
    same order, and a stalled round is discarded wholesale. ``fmask``
    (filtered search: the device pass mask over ids) admits candidates
    to the result pool ``res``."""
    def row_fn(c):
        nb = gather_rows(topo_rows, topo_h2s, c)       # [B, beam, R]
        slot = topo_h2s[jnp.clip(c, 0)]
        return nb, (slot >= 0) | (c < 0)               # idle lanes never stall

    def dist_fn(nb):
        valid = (nb >= 0) & alive[jnp.clip(nb, 0)]
        d = adc_gather(codes, lut, nb)
        # code-lane rounds log no per-round device hits: the PQ result's
        # hit flags are derived from exact-cache residency at the end
        return jnp.where(valid, d, INF), jnp.zeros(nb.shape, bool), valid

    keep_fn = None if fmask is None else (lambda nb: fmask[jnp.clip(nb, 0)])
    B = pool_ids.shape[0]
    state0 = (r, pool_ids, pool_d, visited, curr, acc_ids,
              jnp.zeros(acc_ids.shape, bool), jnp.zeros((B,), jnp.int32),
              res, jnp.bool_(False))
    (r1, ids1, d1, vis1, curr1, acc1, _, _, res1, _) = _run_fused_rounds(
        state0, r_stop, beam, id_bound, row_fn, dist_fn, keep_fn)
    return ids1, d1, vis1, curr1, r1, acc1, res1


def _fused_topo_shell(store, topo, spec, alive, f_lam, pq, codes_j,
                      codes_epoch, lut, pool_ids, pool_d, visited, curr_j,
                      beam, rounds, id_bound, fused_rounds, stage_width=0,
                      res=None, hmask=None, fmask_j=None):
    """Host fallback shell around ``_pq_fused_dispatch``: the executor's
    round loop when a topology cache is attached. Steady state is ONE
    fused dispatch covering every remaining round (dispatches/query drops
    to entry + fused + re-rank = 3); the host is re-entered only on a
    topology-cache miss (install the frontier's missing rows, re-enter at
    the same round) or the K-round budget (``fused_rounds``; 0 =
    uncapped). When the missing rows cannot be installed — the cache is
    too small or every slot is protected by the live frontier — ONE
    per-round ``_pq_round_dispatch`` runs with host-shipped ids (the
    forced-0%-hit-rate degenerate case runs entirely on this fallback and
    must stay bit-identical to the per-round executor, which it is: same
    dispatch, same inputs).

    ``_SpecPipeline`` integration re-targets speculation to *topology*
    one cache-miss ahead: while the fused dispatch is in flight the host
    ranks the frontier's non-resident next-hop candidates by F_λ and
    stages their store rows, so a future miss-exit's delta fetch is a
    memo hit instead of disk IO.

    Filtered search threads its result pool ``res`` through, admitting
    candidates by the device pass mask ``fmask_j`` in fused rounds and by
    its host twin ``hmask`` in per-round fallback rounds.

    Returns (pool_ids, pool_d, acc [B, rounds, C] np.int32, rounds
    executed, dispatches issued, topo hits, topo misses, res)."""
    B = int(pool_ids.shape[0])
    R = topo.degree
    C = beam * R
    K = fused_rounds if fused_rounds > 0 else rounds
    acc_j = jnp.full((B, rounds, C), -1, jnp.int32)
    acc_np = None
    fb_rounds: list = []
    dispatches = hits = misses = 0
    r = 0
    curr = np.asarray(curr_j)
    no_progress = 0
    while r < rounds and (curr >= 0).any():
        topo.validate(store)
        ep = store.write_epoch
        if ep != codes_epoch:       # concurrent insert: fold fresh codes
            codes_epoch = ep
            codes_j = pq.synced_codes()
        ucur = np.unique(curr[curr >= 0])
        cached_rows, resm = topo.lookup(ucur)
        need = ucur[~resm]
        hits += int(resm.sum())
        topo.hits += int(resm.sum())
        rows_need = None
        installed = True
        if need.size:
            misses += int(need.size)
            topo.misses += int(need.size)
            if spec is not None:
                spec.validate()
                rows_need = spec.rows_for(need)
            else:
                rows_need = store.fetch_rows(need, f_lam)
            # the live frontier is protected: an install can never evict
            # the rows the dispatch it feeds is about to gather
            installed = topo.install(need, rows_need, f_lam, protect=ucur)
        if installed and no_progress < 3:
            rows_j, h2s_j = topo.synced()
            out = _pq_fused_dispatch(
                pool_ids, pool_d, visited, curr_j,
                jnp.asarray(r, jnp.int32), acc_j, rows_j, h2s_j, codes_j,
                lut, jnp.asarray(alive),
                jnp.asarray(min(r + K, rounds), jnp.int32), beam, id_bound,
                res, fmask_j)
            dispatches += 1
            if spec is not None:
                # topology prefetch one cache-miss ahead, overlapping the
                # in-flight dispatch: stage store rows for the hottest
                # non-resident candidates reachable from this frontier
                if rows_need is not None:
                    cached_rows[~resm] = rows_need
                nxt = np.unique(cached_rows[cached_rows >= 0])
                nxt = nxt[topo.h2s[nxt] < 0]
                if nxt.size:
                    w = max(stage_width, 1) * B
                    if nxt.size > w:
                        nxt = nxt[np.argpartition(-f_lam[nxt], w - 1)[:w]]
                    spec.stage(nxt)
            pool_ids, pool_d, visited, curr_j, r_j, acc_j, res = out
            curr = np.asarray(curr_j)         # the shell's only sync point
            new_r = int(r_j)
            # a dispatch that advanced no round means residency changed
            # under us (concurrent install/evict): bounded retries, then
            # force the per-round fallback so the shell always progresses
            no_progress = no_progress + 1 if new_r == r else 0
            r = new_r
        else:
            if rows_need is not None:
                cached_rows[~resm] = rows_need
            nb = np.full((B, beam, R), -1, np.int32)
            okm = curr >= 0
            nb[okm] = cached_rows[np.searchsorted(ucur, curr[okm])]
            nb = nb.reshape(B, C)
            valid = (nb >= 0) & alive[np.clip(nb, 0, None)]
            keep = None if hmask is None else \
                jnp.asarray(valid & hmask[np.clip(nb, 0, None)])
            pool_ids, pool_d, visited, curr_j, res = _pq_round_dispatch(
                pool_ids, pool_d, visited, jnp.asarray(nb),
                jnp.asarray(valid), codes_j, lut, beam, id_bound, res, keep)
            dispatches += 1
            if acc_np is None:
                acc_np = np.full((B, rounds, C), -1, np.int32)
            acc_np[:, r] = np.where(valid, nb, -1)
            fb_rounds.append(r)
            curr = np.asarray(curr_j)
            r += 1
            no_progress = 0
    acc = np.array(acc_j)   # copy: jax buffers are read-only views
    if fb_rounds:   # overlay host-logged fallback rounds onto the device log
        acc[:, fb_rounds] = acc_np[:, fb_rounds]
    return pool_ids, pool_d, acc, r, dispatches, hits, misses, res


@partial(jax.jit, static_argnames=("depth",))
def _pq_filtered_scan_dispatch(codes, centroids, queries, cand_ids, depth):
    """Brute-force ADC scan over a filtered id set — the low-selectivity
    fallback's coarse stage: ONE ``pq_adc`` dispatch scoring every
    matching id (shipped as a -1-padded [B, Mp] matrix; the kernels map
    id -1 to +inf exactly as the graph lane's invalid-lane masking does)
    and keeping the top ``depth`` for the unchanged exact re-rank. No
    traversal: below the selectivity threshold a graph walk starves
    (too few passing candidates to sustain a frontier), while one flat
    scan over the matched set is small by definition."""
    lut = adc_lut(centroids, queries)
    d = adc_gather(codes, lut, cand_ids)
    d = jnp.where(cand_ids >= 0, d, INF)
    nd, idx = jax.lax.top_k(-d, depth)
    ids = jnp.take_along_axis(cand_ids, idx, axis=1)
    return jnp.where(jnp.isfinite(-nd), ids, -1), -nd


@partial(jax.jit, static_argnames=("k",))
def _pq_rerank_dispatch(top_ids, uniq_vecs, cand_inv, valid, queries, k):
    """Tier-cascade exact re-rank: the top ``depth`` ADC-ranked pool
    entries, their exact vectors fetched through the cascade by the host
    (shipped as unique rows + lane->unique map, like the exact round
    dispatch), re-scored with the same ``_batch_sqdist`` the exact lane
    uses and re-sorted. At ``depth == pool`` this makes the PQ lane's
    output identical to the exact executor's whenever the traversal
    visited the same pool (pinned by the parity suite with a lossless
    codebook)."""
    xv = uniq_vecs[cand_inv]                       # [B, depth, D]
    d = _batch_sqdist(xv, queries)
    d = jnp.where(valid, d, INF)
    nd, order = jax.lax.top_k(-d, d.shape[1])
    ids = jnp.take_along_axis(top_ids, order, axis=1)
    ds = -nd
    ids = jnp.where(jnp.isfinite(ds), ids, -1)
    return ids[:, :k], ds[:, :k]


def dedup_mask(a):
    """Per-row duplicate flags for an int array [B, C] (any one occurrence
    survives). Host twin of ``dup_mask_jnp``; shared by the tiered update
    paths."""
    order = np.argsort(a, axis=1, kind="stable")
    srt = np.take_along_axis(a, order, axis=1)
    dup_sorted = np.concatenate(
        [np.zeros((a.shape[0], 1), bool), srt[:, 1:] == srt[:, :-1]], axis=1)
    dup = np.empty_like(dup_sorted)
    np.put_along_axis(dup, order, dup_sorted, axis=1)
    return dup


class TieredSearchResult(NamedTuple):
    ids: np.ndarray       # [B, k]
    dists: np.ndarray     # [B, k]
    acc_ids: np.ndarray   # [B, rounds*beam*R] accessed vertex ids (-1 pad)
    acc_hit: np.ndarray   # [B, rounds*beam*R] device-cache-hit flags
    iters: int            # expansion rounds executed
    dispatches: int       # jitted device dispatches issued (per-round:
    #                       1 + iters + rerank; fused: entry + fused
    #                       re-entries + fallback rounds + rerank)
    spec_hits: int = 0    # frontier rows already staged at read-back
    spec_misses: int = 0  # frontier rows delta-fetched after read-back
    topo_hits: int = 0    # frontier ids resident in the topology cache
    topo_misses: int = 0  # frontier ids delta-fetched + installed
    filter_path: str = "none"        # "none" | "graph" | "fallback"
    filter_selectivity: float = 1.0  # admission-time sampled estimate

    @property
    def spec_hit_rate(self) -> float:
        t = self.spec_hits + self.spec_misses
        return self.spec_hits / t if t else 0.0

    @property
    def topo_hit_rate(self) -> float:
        t = self.topo_hits + self.topo_misses
        return self.topo_hits / t if t else 0.0


def _resolve_unique_vectors(ids, h2d, cache_vec, store, f_lam):
    """Vectors for a batch of *unique* non-negative ids through the
    cascade device cache (mirror) -> host window -> disk. Returns
    (vectors [U, D] fp32, device_hit [U])."""
    out = np.empty((len(ids), store.disk.dim), np.float32)
    slot = h2d[ids]
    hit = slot >= 0
    if hit.any():
        out[hit] = cache_vec[slot[hit]]
    miss = ~hit
    if miss.any():
        out[miss] = store.fetch(ids[miss], f_lam)[0]
    return out, hit


def _host_sqdist(x, q):
    """Numpy twin of ``_batch_sqdist`` for host-side frontier prediction:
    [B, C, D] vs [B, D] -> [B, C]."""
    diff = x - q[:, None, :]
    return np.einsum("bcd,bcd->bc", diff, diff)


def predict_frontier(ids, valid, f_lam, width, d_host=None):
    """Ranked next-frontier guess [B, width] (-1 = no guess) — the F_λ
    probe of the old prefetch predictor extended to return the guess
    itself: per query, the top-``width`` valid candidates by host-side
    score. The entry stage passes exact host distances (``d_host``, the
    entry vectors are host-resident anyway) and predicts the first
    frontier almost perfectly; later rounds rank by the WAVP F_λ
    predictor — hot hub candidates are the likeliest next expansions."""
    score = (-d_host if d_host is not None
             else f_lam[np.clip(ids, 0, None)])
    score = np.where(valid, score, -np.inf)
    w = min(width, ids.shape[1])
    part = np.argpartition(-score, w - 1, axis=1)[:, :w]
    got = np.take_along_axis(ids, part, axis=1)
    ok = np.isfinite(np.take_along_axis(score, part, axis=1))
    return np.where(ok, got, -1)


class _StageMap:
    """Append-only id -> payload staging memo (speculative buffers).

    Dense ``loc`` directory for O(1) vectorized lookup, doubling buffer
    for amortized O(1) installs, and O(installed) wholesale invalidation:
    the write-epoch check flushes the memo outright rather than patching
    it — speculation must never serve a stale row."""

    __slots__ = ("loc", "buf", "hit", "n", "_installed")

    def __init__(self, capacity: int, width: int, dtype, track_hit=False):
        self.loc = np.full((capacity,), -1, np.int64)
        self.buf = np.empty((0, width), dtype)
        self.hit = np.empty((0,), bool) if track_hit else None
        self.n = 0
        self._installed: list = []

    def add(self, ids, rows, hit=None):
        m = len(ids)
        if not m:
            return
        need = self.n + m
        if need > len(self.buf):
            cap = max(need, 2 * len(self.buf), 256)
            buf = np.empty((cap, self.buf.shape[1]), self.buf.dtype)
            buf[:self.n] = self.buf[:self.n]
            self.buf = buf
            if self.hit is not None:
                h = np.empty((cap,), bool)
                h[:self.n] = self.hit[:self.n]
                self.hit = h
        self.buf[self.n:need] = rows
        if self.hit is not None:
            self.hit[self.n:need] = hit
        self.loc[ids] = np.arange(self.n, need)
        self._installed.append(np.asarray(ids))
        self.n = need

    def clear(self):
        for blk in self._installed:
            self.loc[blk] = -1
        self._installed.clear()
        self.n = 0


class _SpecPipeline:
    """Speculative double-buffered stage for the tiered arm (§4.4).

    While round N's dispatch is in flight the host stages the predicted
    round-N+1 frontier: adjacency rows for the predicted ids, vectors for
    their neighborhoods, and an async disk prefetch one hop further. At
    read-back, staged frontier ids feed the next dispatch immediately;
    mispredictions cost a delta fetch of the missing rows only. Both
    memos are validated against the store's write epoch every round — a
    concurrent insert/delete flushes them wholesale, so speculation reads
    are never staler than the non-speculative path's per-round fetches
    (MVCC consistency is the store's, unchanged)."""

    def __init__(self, backend, h2d, cache_vec, f_lam, *,
                 prefetch_budget=0, probe=8, stage_vectors=True):
        self.store = backend.store
        self.h2d, self.cache_vec, self.f_lam = h2d, cache_vec, f_lam
        self.prefetch_budget = prefetch_budget
        self.probe = probe
        self.stage_vectors = stage_vectors   # False: PQ code lane — rounds
        #                                      never need vectors, stage
        #                                      rows (+ disk prefetch) only
        cap = backend.capacity
        self.rows = _StageMap(cap, backend.degree, np.int32)
        self.vecs = _StageMap(cap, backend.dim, np.float32, track_hit=True)
        self.epoch = self.store.write_epoch
        self.hits = 0
        self.misses = 0

    def validate(self):
        ep = self.store.write_epoch
        if ep != self.epoch:
            self.rows.clear()
            self.vecs.clear()
            self.epoch = ep

    def rows_for(self, uids, *, speculative=False):
        """Adjacency rows aligned with ``uids`` (unique, >= 0): staged ids
        come from the memo, the rest are delta-fetched and installed.
        Demand reads (``speculative=False``) score the hit-rate."""
        loc = self.rows.loc[uids]
        miss = loc < 0
        if not speculative:
            self.hits += int((~miss).sum())
            self.misses += int(miss.sum())
        if miss.any():
            mids = uids[miss]
            self.rows.add(mids, self.store.fetch_rows(mids, self.f_lam))
            loc = self.rows.loc[uids]
        return self.rows.buf[loc]

    def vectors_for(self, uids):
        """(vectors [U, D], device_hit [U]) aligned with unique ids."""
        loc = self.vecs.loc[uids]
        miss = loc < 0
        if miss.any():
            mids = uids[miss]
            v, h = _resolve_unique_vectors(mids, self.h2d, self.cache_vec,
                                           self.store, self.f_lam)
            self.vecs.add(mids, v, h)
            loc = self.vecs.loc[uids]
        return self.vecs.buf[loc], self.vecs.hit[loc]

    def stage(self, pred):
        """Speculative stage — runs while the dispatch is in flight."""
        ids = np.unique(pred[pred >= 0])
        if not ids.size:
            return
        self.validate()
        rows = self.rows_for(ids, speculative=True)
        nxt = np.unique(rows[rows >= 0])
        if not nxt.size:
            return
        if self.stage_vectors:
            self.vectors_for(nxt)
        if self.prefetch_budget > 0:
            self._prefetch_two_ahead(nxt)

    def _prefetch_two_ahead(self, cand):
        """Async disk prefetch one hop past the staged frontier (the old
        predicted-prefetch, now fed by the speculative stage): peek the
        hottest staged candidates' adjacency and enqueue their cold
        neighbors, overlapping the round after next as well."""
        if cand.size > self.probe:
            cand = cand[np.argpartition(-self.f_lam[cand],
                                        self.probe - 1)[:self.probe]]
        hrows = self.store.peek_rows(cand)
        nxt = np.unique(hrows[hrows >= 0])
        nxt = nxt[self.store.loc[nxt] < 0]
        if nxt.size:
            b = self.prefetch_budget
            if nxt.size > b:
                nxt = nxt[np.argpartition(-self.f_lam[nxt], b - 1)[:b]]
            self.store.prefetch(nxt, self.f_lam)


def _predict_prefetch(store, nb, valid, f_lam, budget, probe=8):
    """Predicted next-frontier prefetch for the NON-speculative path
    (paper §4.4 multi-stream overlap): peek the hottest candidates'
    adjacency while the dispatch is in flight, enqueue their non-resident
    neighbors to the background prefetcher."""
    cand = np.unique(nb[valid])
    if not cand.size:
        return
    if cand.size > probe:     # argpartition: this runs once per round
        cand = cand[np.argpartition(-f_lam[cand], probe - 1)[:probe]]
    hrows = store.peek_rows(cand)
    nxt = np.unique(hrows[hrows >= 0])
    nxt = nxt[store.loc[nxt] < 0]
    if nxt.size:
        if nxt.size > budget:
            nxt = nxt[np.argpartition(-f_lam[nxt], budget - 1)[:budget]]
        store.prefetch(nxt, f_lam)


def _ship_unique_vectors(ids, valid, resolve, pad_to=None):
    """The executor's ship-unique protocol, shared by the exact round
    dispatch and the PQ re-rank stage: dedup a [B, C] id matrix (invalid
    lanes collapse onto placeholder id 0 — their distances are masked in
    the dispatch), resolve vectors for the unique ids through
    ``resolve`` (cascade or speculative memo), and zero-pad the device
    transfer — to the pow4 bucket by default (O(log) compile
    specializations), or to the STATIC ``pad_to`` (>= B·C suffices,
    unique counts cannot exceed the lane count). The re-rank stage uses
    the static pad: it runs once per query batch and its unique count
    rides the 512/2048 bucket boundary as the dataset streams, which
    used to drop a fresh XLA compile into the serving path right after
    inserts. Returns (uvec [U, D], uhit [len(uc)], inv [B, C] int32)."""
    B, C = ids.shape
    uc, inv = np.unique(np.where(valid, ids, 0).reshape(-1),
                        return_inverse=True)
    uvec, uhit = resolve(uc)
    U = pad_to if pad_to is not None else _pow2_bucket(len(uc))
    if U != len(uc):
        uvec = np.concatenate(
            [uvec, np.zeros((U - len(uc), uvec.shape[1]), np.float32)])
    return uvec, uhit, inv.reshape(B, C).astype(np.int32)


def _pow2_bucket(u: int, floor: int = 512) -> int:
    """Pad unique-row counts to power-of-FOUR buckets (512 floor) so the
    round dispatch compiles a handful of specializations, not one per
    count — and, as important, so steady-state serving rarely straddles a
    bucket boundary (a mid-run boundary crossing is a fresh XLA compile
    on the hot path, which is exactly the tail-latency spike the
    percentile satellite hunts). Padded rows are zeros the lane->unique
    gather never references; their transfer cost is noise."""
    b = floor
    while b < u:
        b *= 4
    return b


def effective_rerank_depth(rerank_depth: int, k: int, pool: int) -> int:
    """Resolve the ``rerank_depth`` knob to the concrete pool prefix the
    exact re-rank stage pulls vectors for: ``<= 0`` is the whole-pool
    sentinel, anything else clamps to ``[k, pool]``. The SLO degradation
    ladder (core/slo.py) halves through this same resolution so a
    degraded depth and the executor agree on sentinel semantics."""
    return pool if rerank_depth <= 0 else max(k, min(rerank_depth, pool))


def filtered_walk_pool(pool: int, k: int, selectivity: float) -> int:
    """Traversal pool width for a filtered graph walk. The k nearest
    passing nodes sit among roughly the k/selectivity nearest of all, and
    a walk whose pool holds fewer runs dry before it has scored them:
    the next power of two >= k/selectivity, at least ``pool``, at most
    4·``pool`` (so at most three widths ever compile)."""
    need = int(np.ceil(k / max(selectivity, 1e-6)))
    return min(max(pool, 1 << (need - 1).bit_length()), 4 * pool)


def _filtered_brute_force(backend, queries, qj, hmask, alive_snap, sp,
                          pq, rerank_depth, h2d, cache_vec, f_lam,
                          filter_sel) -> TieredSearchResult:
    """Selectivity-adaptive fallback arm of ``search_tiered``: exact
    search restricted to the matched id set, no graph traversal. PQ
    mode: ONE ``pq_adc`` scan over the matched ids keeps the top
    ``rerank_depth``, then the executor's unchanged exact re-rank
    dispatch; exact mode: the re-rank dispatch alone over every match.
    Results are exact over the matched set by construction (modulo PQ
    pre-ranking when ``rerank_depth`` < matches), so this path's output
    at full depth is bit-identical to post-filtering an exhaustive
    scan — the property the filter suite pins."""
    B = queries.shape[0]
    k = sp.k
    n = max(backend.n, 1)
    matched = np.where(alive_snap[:n] & hmask[:n])[0]
    if matched.size == 0:
        z = np.zeros((B, 0), np.int32)
        return TieredSearchResult(
            np.full((B, k), -1, np.int32),
            np.full((B, k), np.inf, np.float32),
            z, z.astype(bool), 0, 0,
            filter_path="fallback", filter_selectivity=filter_sel)
    Mp = _pow2_bucket(matched.size)
    cand = np.full((Mp,), -1, np.int64)
    cand[:matched.size] = matched
    cand_ids = np.broadcast_to(cand, (B, Mp))
    dispatches = 0
    if pq is not None:
        codes_j = pq.synced_codes()
        depth = min(effective_rerank_depth(rerank_depth, k, sp.pool), Mp)
        top_j, _ = _pq_filtered_scan_dispatch(
            codes_j, pq.codebook.centroids, qj,
            jnp.asarray(cand_ids, jnp.int32), depth)
        dispatches += 1
        top_ids = np.asarray(top_j, np.int64)
    else:
        top_ids = cand_ids
    valid_r = top_ids >= 0
    uvec, _, inv = _ship_unique_vectors(
        top_ids, valid_r,
        lambda u: _resolve_unique_vectors(u, h2d, cache_vec, backend.store,
                                          f_lam))
    ids_k, d_k = _pq_rerank_dispatch(
        jnp.asarray(top_ids, jnp.int32), jnp.asarray(uvec),
        jnp.asarray(inv), jnp.asarray(valid_r), qj, k)
    dispatches += 1
    ids_np = np.asarray(ids_k, np.int32)
    d_np = np.asarray(d_k, np.float32)
    if ids_np.shape[1] < k:      # fewer matches than k: pad the tail
        pad = k - ids_np.shape[1]
        ids_np = np.pad(ids_np, ((0, 0), (0, pad)), constant_values=-1)
        d_np = np.pad(d_np, ((0, 0), (0, pad)), constant_values=np.inf)
    acc = np.where(valid_r, top_ids, -1).astype(np.int32)
    acc_hit = (h2d[np.clip(acc, 0, None)] >= 0) & (acc >= 0)
    return TieredSearchResult(ids_np, d_np, acc, acc_hit, 0, dispatches,
                              filter_path="fallback",
                              filter_selectivity=filter_sel)


def search_tiered(backend, cache_mirror, queries, seed, sp: SearchParams,
                  *, f_lam=None, prefetch_budget: int = 0,
                  entry_ids=None, speculate: bool = True,
                  spec_width: int = 0, spec_rank: str = "flam",
                  spec_predict=None, pq=None,
                  rerank_depth: int = 0, topo=None,
                  fused_rounds: int = 0, filter=None,
                  filter_fallback_selectivity: float = 0.0,
                  filter_sample: int = 1024) -> TieredSearchResult:
    """Hop-batched frontier search over a disk-backed graph (paper
    Algorithm 1 in its GPU-CPU-disk form) — the tiered arm of the shared
    executor, run as a two-stage speculative pipeline. Per round: ONE
    bulk (delta) row fetch, ONE unique-id vector cascade, ONE jitted
    distance+merge dispatch; while that dispatch is in flight the host
    predicts the next frontier and stages its rows/vectors
    (``_SpecPipeline``), so at the read-back sync only mispredicted ids
    still need IO. Speculation is bitwise-transparent: staged payloads
    are the same values the demand path would fetch (the write-epoch
    check flushes the memo on any concurrent mutation), so results are
    identical to ``speculate=False`` — the property suite enforces this
    under forced 0% and 100% misprediction.

    backend: ``tiers.TieredBackend``; cache_mirror: ``cache.HostPlacement``
    (readers snapshot its arrays once, see HostPlacement docs).
    ``entry_ids`` [B, pool] overrides the random entry pool (parity tests).
    ``spec_width``: predicted frontier ids staged per query per round
    (0 -> beam). ``spec_rank``: ``"flam"`` (default) ranks round
    predictions with the F_λ probe alone; ``"dist"`` re-ranks by exact
    host distances over the staged unique vectors — higher hit-rate but
    ~2ms/round of host compute, worth it only when delta fetches are
    genuinely IO-bound (disk much slower than this pod's page cache). ``spec_predict``: prediction
    hook with the signature of ``predict_frontier`` (tests force 0%/100%
    misprediction through it).

    ``pq``: a ``quant.PQCodes`` lane — when set, the executor runs in
    coarse-then-refine mode: every round scores candidates on device from
    the unconditionally resident PQ codes (ADC LUT gather; NO per-round
    vector cascade fetch — only adjacency rows cross tiers, and the
    speculative pipeline stages rows only), then a final re-rank stage
    pulls exact vectors for the top ``rerank_depth`` pool entries through
    the existing cascade (device cache -> host window -> disk) and
    re-scores them exactly. ``rerank_depth`` <= 0 re-ranks the whole
    pool; it is clamped to [k, pool]. At ``rerank_depth == pool`` with a
    lossless codebook the PQ lane reproduces the exact executor's
    results (parity suite). ``spec_rank="dist"`` degrades to the F_λ
    probe in PQ mode: the stage holds no host vectors to re-rank with.

    ``topo``: a ``cache.TopoCache`` device-resident topology lane — when
    set (PQ mode only; the exact lane needs host vectors every round
    regardless), the round loop runs through the K-round fused dispatch
    (``_pq_fused_dispatch`` + ``_fused_topo_shell``): while the frontier
    stays inside the cached topology, row gather -> ADC scan -> merge ->
    select all happen on device in one ``lax.while_loop`` dispatch, and
    the host is re-entered only on a topology-cache miss or the
    ``fused_rounds`` budget (0 = uncapped). Results are bit-identical to
    the per-round executor (parity suite pins K ∈ {1, 2, 4} and forced
    0%/100% topology hit rates).

    ``filter``: a ``filters.FilterSpec`` metadata predicate — requires an
    attached ``backend.attrs`` store. Selectivity is sampled at admission
    (``filter_sample`` ids, deterministic in ``seed``): at or above
    ``filter_fallback_selectivity`` the graph walk runs over every live
    node while a separate result pool keeps only the live nodes that pass
    the predicate (``result_pool``/``merge_result``, both arms; the
    results and the PQ re-rank come from it); below it the query routes
    to the brute-force scan over the matched set
    (``_filtered_brute_force``). The chosen path and the measured
    selectivity ride the result (``filter_path`` /
    ``filter_selectivity``).
    """
    store = backend.store
    alive = backend.alive
    # ONE snapshot read: h2d and vectors must come from the same publish
    # (see cache.CacheView) or a concurrent placement pass could pair an
    # old mapping with new payloads
    view = cache_mirror.view
    h2d, cache_vec = view.h2d, view.vectors
    if f_lam is None:   # callers doing several passes precompute O(N) once
        f_lam = cache_mirror.scores(backend.e_in)

    queries = np.asarray(queries, np.float32)
    B, D = queries.shape
    L, R, k = sp.pool, backend.degree, sp.k
    beam = max(1, min(sp.beam, L))
    rounds = _n_rounds(sp)
    C = beam * R
    n = max(backend.n, 1)
    id_bound = int(backend.capacity)
    qj = jnp.asarray(queries)

    # --- predicate lane (core/filters.py) -------------------------------
    filter_path, filter_sel = "none", 1.0
    hmask = fmask_j = None
    if filter is not None:
        from repro.core.filters import (compile_filter, device_pass_mask,
                                        estimate_selectivity, host_pass)
        attrs = backend.attrs
        if attrs is None:
            raise ValueError("filtered search requires an attached "
                             "attribute store (EngineConfig.attributes)")
        cf = compile_filter(filter, attrs.schema)
        hmask = host_pass(cf, attrs.tags, attrs.nums)
        filter_sel = estimate_selectivity(cf, attrs, alive, backend.n,
                                          sample=filter_sample, seed=seed)
        if filter_sel < filter_fallback_selectivity:
            # graph walk would starve: brute-force scan the matched set
            return _filtered_brute_force(backend, queries, qj, hmask,
                                         alive, sp, pq, rerank_depth,
                                         h2d, cache_vec, f_lam, filter_sel)
        filter_path = "graph"
        # nodes that fail the predicate stay traversable (the walk needs
        # them to cross the graph) but never enter the result pool: host
        # rounds admit by ``hmask``, fused rounds by its device twin,
        # evaluated on the epoch-synced attribute mirror
        if pq is not None and topo is not None:
            fmask_j = device_pass_mask(attrs, cf)
    if entry_ids is None:
        walk = L if hmask is None else filtered_walk_pool(L, k, filter_sel)
        rng = np.random.default_rng(seed)
        entry_ids = rng.integers(0, n, (B, walk))
    entry_ids = np.asarray(entry_ids, np.int64)

    use_pq = pq is not None
    if use_pq:
        # epoch read BEFORE the sync: a write racing the sync re-syncs
        # next round rather than never. The hazard is real — alive is
        # read live per round, so an id inserted mid-search can enter a
        # round via a reverse-edge-updated row and would otherwise be
        # scored from its still-zero code row.
        codes_epoch = store.write_epoch
        codes_j = pq.synced_codes()
        depth = effective_rerank_depth(rerank_depth, k, L)

    spec = None
    if speculate:
        spec = _SpecPipeline(backend, h2d, cache_vec, f_lam,
                             prefetch_budget=prefetch_budget,
                             stage_vectors=not use_pq)
        spec.validate()
        width = spec_width if spec_width > 0 else beam
        predict = spec_predict if spec_predict is not None else \
            predict_frontier

    entry_alive = alive[entry_ids]
    entry_keep = None if hmask is None else \
        jnp.asarray(entry_alive & hmask[entry_ids])
    if use_pq:
        # entry pool scored from device-resident codes: no vector fetch
        # at all (the lane's LUTs are built inside the same dispatch)
        pool_ids, pool_d, visited, curr_j, lut, res = _pq_entry_dispatch(
            jnp.asarray(entry_ids, jnp.int32), jnp.asarray(entry_alive),
            codes_j, pq.codebook.centroids, qj, beam, id_bound, entry_keep)
        dispatches = 1
        if spec is not None:
            # no host vectors in the code lane: the entry prediction
            # falls back to the F_λ probe (rows-only staging)
            spec.stage(predict(entry_ids, entry_alive, f_lam, width))
    else:
        # entry pool: one unique-id cascade + one entry dispatch
        ue, inv_e = np.unique(entry_ids.reshape(-1), return_inverse=True)
        if spec is not None:
            uev, _ = spec.vectors_for(ue)
        else:
            uev, _ = _resolve_unique_vectors(ue, h2d, cache_vec, store,
                                             f_lam)
        ev = uev[inv_e].reshape(entry_ids.shape + (D,))
        pool_ids, pool_d, visited, curr_j, res = _tiered_entry_dispatch(
            jnp.asarray(entry_ids, jnp.int32), jnp.asarray(ev),
            jnp.asarray(entry_alive), qj, beam, id_bound, entry_keep)
        dispatches = 1
        if spec is not None:
            # stage round 1 while the entry dispatch is in flight: the
            # entry vectors are host-resident, so the first frontier is
            # predicted from exact host distances
            pred = predict(entry_ids, entry_alive, f_lam, width,
                           d_host=_host_sqdist(ev, queries))
            spec.stage(pred)
    curr = np.asarray(curr_j)                 # [B, beam], -1 = idle lane

    acc_ids = np.full((B, rounds, C), -1, np.int32)
    acc_hit = np.zeros((B, rounds, C), bool)
    it = 0
    topo_hits = topo_misses = 0
    if use_pq and topo is not None:
        # fused multi-round executor: the shell owns the round loop and
        # issues ONE lax.while_loop dispatch per contiguous in-cache run
        (pool_ids, pool_d, acc_ids, it, extra, topo_hits,
         topo_misses, res) = _fused_topo_shell(
            store, topo, spec, alive, f_lam, pq, codes_j, codes_epoch,
            lut, pool_ids, pool_d, visited, curr_j, beam, rounds,
            id_bound, fused_rounds,
            stage_width=(width if spec is not None else 0),
            res=res, hmask=hmask, fmask_j=fmask_j)
        dispatches += extra
    else:
        for _ in range(rounds):
            ok = curr >= 0
            if not ok.any():
                break
            # ONE bulk row fetch for the whole beam (topology lives on
            # host/disk only; the device cache stores vectors). Staged rows
            # from the speculative stage short-circuit it to a delta fetch.
            ucur = np.unique(curr[ok])
            if spec is not None:
                spec.validate()
                urows = spec.rows_for(ucur)
            else:
                urows = store.fetch_rows(ucur, f_lam)
            nb = np.full((B, beam, R), -1, np.int32)
            # searchsorted over the (sorted) unique ids: O(|curr| log |ucur|),
            # no O(dataset) scratch on the per-round hot path
            nb[ok] = urows[np.searchsorted(ucur, curr[ok])]
            nb = nb.reshape(B, C)

            valid = (nb >= 0) & alive[np.clip(nb, 0, None)]
            keep = None if hmask is None else \
                jnp.asarray(valid & hmask[np.clip(nb, 0, None)])
            if use_pq:
                ep = store.write_epoch
                if ep != codes_epoch:   # concurrent insert: fold fresh codes
                    codes_epoch = ep
                    codes_j = pq.synced_codes()
                # code-lane round: candidates scored from device-resident
                # codes — nothing but the id matrix crosses to the device
                pool_ids, pool_d, visited, curr_j, res = _pq_round_dispatch(
                    pool_ids, pool_d, visited, jnp.asarray(nb),
                    jnp.asarray(valid), codes_j, lut, beam, id_bound, res,
                    keep)
                dispatches += 1
                acc_ids[:, it] = np.where(valid, nb, -1)
                if spec is not None:
                    if it + 1 < rounds:
                        spec.stage(predict(nb, valid, f_lam, width))
                elif prefetch_budget > 0:
                    _predict_prefetch(store, nb, valid, f_lam, prefetch_budget)
                curr = np.asarray(curr_j)         # the round's only sync point
                it += 1
                continue
            uvec, uhit, inv = _ship_unique_vectors(
                nb, valid,
                spec.vectors_for if spec is not None else
                (lambda u: _resolve_unique_vectors(u, h2d, cache_vec, store,
                                                   f_lam)))
            # launch the round's single device dispatch (async); pool state
            # stays device-resident, only `curr` crosses back. The speculative
            # stage below overlaps with the in-flight dispatch.
            pool_ids, pool_d, visited, curr_j, res = _tiered_round_dispatch(
                pool_ids, pool_d, visited, jnp.asarray(nb), jnp.asarray(uvec),
                jnp.asarray(inv), jnp.asarray(valid), qj, beam, id_bound,
                res, keep)
            dispatches += 1
            acc_ids[:, it] = np.where(valid, nb, -1)
            acc_hit[:, it] = uhit[inv] & valid
            if spec is not None:
                if it + 1 < rounds:   # the last round has no next to stage for
                    d_host = None
                    if spec_rank == "dist":
                        # re-rank by exact host distances (the unique vectors
                        # are already host-resident): sharper than the F_λ
                        # probe, and the cost hides under the in-flight
                        # dispatch like the rest of the stage
                        d_host = _host_sqdist(uvec[inv], queries)
                    spec.stage(predict(nb, valid, f_lam, width, d_host=d_host))
            elif prefetch_budget > 0:
                _predict_prefetch(store, nb, valid, f_lam, prefetch_budget)
            curr = np.asarray(curr_j)             # the round's only sync point
            it += 1

    if res is not None:      # filtered: results come from the result pool
        pool_ids, pool_d = res
    if use_pq:
        # device-hit flags for the placement pass: in the code lane an
        # access "hits" when its id sits in the exact-vector device cache
        # (the tier the re-rank stage reads), so WAVP keeps promoting the
        # hot re-rank set while codes stay unconditionally resident
        flat = acc_ids.reshape(B, -1)
        acc_hit_flat = (h2d[np.clip(flat, 0, None)] >= 0) & (flat >= 0)

        # tier-cascade exact re-rank of the top ADC-ranked pool entries
        pool_ids_np, pool_d_np = np.asarray(pool_ids), np.asarray(pool_d)
        top_ids = pool_ids_np[:, :depth]
        valid_r = (top_ids >= 0) & np.isfinite(pool_d_np[:, :depth])
        uvec, _, inv = _ship_unique_vectors(
            top_ids, valid_r,
            lambda u: _resolve_unique_vectors(u, h2d, cache_vec, store,
                                              f_lam),
            pad_to=top_ids.size)
        ids_k, d_k = _pq_rerank_dispatch(
            jnp.asarray(top_ids, jnp.int32), jnp.asarray(uvec),
            jnp.asarray(inv), jnp.asarray(valid_r), qj, k)
        dispatches += 1
        return TieredSearchResult(
            np.asarray(ids_k, np.int32), np.asarray(d_k),
            flat, acc_hit_flat, it, dispatches,
            spec.hits if spec else 0, spec.misses if spec else 0,
            topo_hits, topo_misses, filter_path, filter_sel)

    pool_ids, pool_d = np.asarray(pool_ids), np.asarray(pool_d)
    topk_ids = np.where(np.isfinite(pool_d[:, :k]), pool_ids[:, :k], -1)
    return TieredSearchResult(topk_ids.astype(np.int32), pool_d[:, :k],
                              acc_ids.reshape(B, -1),
                              acc_hit.reshape(B, -1), it, dispatches,
                              spec.hits if spec else 0,
                              spec.misses if spec else 0,
                              filter_path=filter_path,
                              filter_selectivity=filter_sel)


def brute_force_topk(graph: GraphState, queries, k):
    """Exact ground truth over alive vectors (recall oracle)."""
    d = (jnp.sum(queries ** 2, 1, keepdims=True)
         - 2.0 * jnp.matmul(queries, graph.vectors.T,
                            precision=jax.lax.Precision.HIGHEST)
         + jnp.sum(graph.vectors ** 2, 1)[None, :])
    d = jnp.where(graph.alive[None, :], d, INF)
    nd, idx = jax.lax.top_k(-d, k)
    return idx, -nd


def recall_at_k(found_ids, true_ids):
    """found/true [B, k] -> mean fraction of true ids found."""
    hits = (found_ids[:, :, None] == true_ids[:, None, :]).any(1)
    return jnp.mean(hits.astype(jnp.float32))
