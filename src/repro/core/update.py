"""Index updates (paper §5): batched insertion with rank-based candidate
reordering + reverse edges; three-stage deletion (logical bitset →
localized topology-aware repair → global consolidation).

All functions are functional: state in, state out. ``insert_batch`` also
returns the reverse-edge triplet log (v, v_new, d) consumed by the MVCC
merge protocol (paper §5.3) when a consolidation snapshot is in flight.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.build import compute_e_in, rank_based_reorder
from repro.core.search import _frontier_search, dedup_mask
from repro.core.types import GraphState, IndexState, SearchParams

INF = jnp.float32(jnp.inf)


class RevLog(NamedTuple):
    """Reverse-edge triplets (paper §5.3 'Reverse Edge Integration')."""
    v: jax.Array       # [E] existing vertex receiving the edge
    v_new: jax.Array   # [E] newly inserted vertex
    d: jax.Array       # [E] precomputed distance


def _reverse_edge_scatter(graph: GraphState, targets, new_ids, dists):
    """Vectorized reverse-edge insertion. For each edge (targets[e] ->
    new_ids[e]): use a free slot if any, else replace the current worst
    (farthest) neighbor if the new edge is closer. Write conflicts resolve
    last-writer-wins (the paper uses best-effort atomics + thread-local
    buffers; KNNG is approximate by construction)."""
    R = graph.degree
    t = jnp.clip(targets, 0)
    rows = graph.nbrs[t]                                   # [E, R]
    tvec = graph.vectors[t]
    nb_d = jnp.sum((graph.vectors[jnp.clip(rows, 0)]
                    - tvec[:, None, :]) ** 2, axis=-1)
    nb_d = jnp.where(rows >= 0, nb_d, -INF)                # free slots win
    worst = jnp.argmax(jnp.where(rows < 0, INF, nb_d), axis=1)
    has_free = (rows < 0).any(axis=1)
    free_idx = jnp.argmax(rows < 0, axis=1)
    slot = jnp.where(has_free, free_idx, worst)
    improves = has_free | (dists < jnp.max(nb_d, axis=1))
    ok = (targets >= 0) & improves
    row_idx = jnp.where(ok, t, graph.capacity)             # no-op row
    nbrs_pad = jnp.concatenate(
        [graph.nbrs, jnp.full((1, R), -1, jnp.int32)], axis=0)
    nbrs_pad = nbrs_pad.at[row_idx, slot].set(
        jnp.where(ok, new_ids, -1))
    return nbrs_pad[:graph.capacity]


@partial(jax.jit, static_argnames=("sp",))
def insert_batch(state: IndexState, new_vecs, key, sp: SearchParams):
    """Insert a batch. Returns (state, new_ids, RevLog)."""
    graph, cache, stats = state.graph, state.cache, state.stats
    Bi, D = new_vecs.shape
    new_vecs = new_vecs.astype(jnp.float32)
    ids = graph.n + jnp.arange(Bi, dtype=jnp.int32)

    # phase 1 (paper §5.1): GPU-side candidate search on the current graph
    # (through the shared hop-batched frontier executor)
    n = jnp.maximum(graph.n, 1)
    entries = jax.random.randint(key, (Bi, sp.pool), 0, n, dtype=jnp.int32)
    res = _frontier_search(graph, cache, new_vecs, entries,
                           sp._replace(k=sp.pool))
    cand_ids, cand_d = res.ids, res.dists                  # [Bi, L] sorted

    # phase 2: heuristic (rank-based) reordering then edge establishment
    sel = rank_based_reorder(cand_ids, cand_d, graph.nbrs, graph.degree)

    vectors = graph.vectors.at[ids].set(new_vecs)
    nbrs = graph.nbrs.at[ids].set(sel)
    alive = graph.alive.at[ids].set(True)
    version = graph.version.at[ids].set(1)
    graph = graph._replace(vectors=vectors, nbrs=nbrs, alive=alive,
                           version=version, n=graph.n + Bi)

    # reverse edges (flattened over the batch)
    flat_t = sel.reshape(-1)
    flat_new = jnp.repeat(ids, graph.degree)
    d_rev = jnp.sum((graph.vectors[jnp.clip(flat_t, 0)]
                     - graph.vectors[flat_new]) ** 2, axis=-1)
    d_rev = jnp.where(flat_t >= 0, d_rev, INF)
    nbrs = _reverse_edge_scatter(graph, flat_t, flat_new, d_rev)
    version = graph.version.at[jnp.clip(flat_t, 0)].add(
        (flat_t >= 0).astype(jnp.int32))
    graph = graph._replace(nbrs=nbrs, version=version)
    graph = graph._replace(e_in=compute_e_in(graph.nbrs, graph.capacity))
    return (IndexState(graph, cache, stats), ids,
            RevLog(flat_t, flat_new, d_rev))


@jax.jit
def delete_batch(state: IndexState, ids):
    """Stage 1 (paper §5.2.1): logical deletion. The bitset is shared by all
    tiers (immediate cross-tier sync); searches/insertions skip marked rows
    transparently."""
    graph, cache, stats = state.graph, state.cache, state.stats
    cid = jnp.clip(ids, 0)
    ok = (ids >= 0) & graph.alive[cid]
    alive = graph.alive.at[cid].set(jnp.where(ok, False, graph.alive[cid]))
    version = graph.version.at[cid].add(ok.astype(jnp.int32))
    return IndexState(graph._replace(alive=alive, version=version),
                      cache, stats)


def deleted_fraction(graph: GraphState) -> jax.Array:
    within = jnp.arange(graph.capacity) < graph.n
    dead = within & ~graph.alive
    return dead.sum() / jnp.maximum(graph.n, 1)


def affected_fraction(graph: GraphState):
    """Per-vertex fraction of deleted out-neighbors."""
    nb = graph.nbrs
    valid = nb >= 0
    dead = valid & ~graph.alive[jnp.clip(nb, 0)]
    return dead.sum(1) / jnp.maximum(valid.sum(1), 1)


@partial(jax.jit, static_argnames=("max_repair", "c"))
def repair_affected(state: IndexState, *, max_repair=256, c=2,
                    threshold=0.5):
    """Stage 2 (paper §5.2.2): localized topology-aware repair. For the most
    affected alive vertices (deleted-neighbor fraction > 50%), each deleted
    neighbor p contributes at most ``c`` of its own alive out-neighbors
    (nearest to v) as replacement edges — O(c) per deletion instead of the
    full consolidation O(|N_out(p)|)."""
    graph, cache, stats = state.graph, state.cache, state.stats
    frac = affected_fraction(graph)
    score = jnp.where(graph.alive & (frac > threshold), frac, -1.0)
    _, vsel = jax.lax.top_k(score, max_repair)
    do = score[vsel] > 0

    R = graph.degree

    def repair_one(v, active):
        row = graph.nbrs[v]
        valid = row >= 0
        dead = valid & ~graph.alive[jnp.clip(row, 0)]
        hop2 = graph.nbrs[jnp.clip(row, 0)]                # [R, R]
        vvec = graph.vectors[v]
        d2 = jnp.sum((graph.vectors[jnp.clip(hop2, 0)]
                      - vvec[None, None, :]) ** 2, axis=-1)
        ok2 = (hop2 >= 0) & graph.alive[jnp.clip(hop2, 0)] & (hop2 != v) \
            & dead[:, None]                                # only via deleted p
        # not already a live neighbor
        dup = (hop2[:, :, None] == jnp.where(dead, -1, row)[None, None, :]
               ).any(-1)
        d2 = jnp.where(ok2 & ~dup, d2, INF)
        # at most c per deleted neighbor
        dtop, itop = jax.lax.top_k(-d2, c)                 # [R, c]
        cand = jnp.take_along_axis(hop2, itop, axis=1).reshape(-1)
        cd = (-dtop).reshape(-1)
        # dedup candidates
        dupc = jnp.triu(cand[:, None] == cand[None, :], k=1).any(0)
        cd = jnp.where(jnp.isfinite(cd) & ~dupc, cd, INF)
        order = jnp.argsort(cd)
        cand, cd = cand[order], cd[order]
        n_dead = dead.sum()
        # fill dead slots with best candidates
        slot_rank = jnp.cumsum(dead) - 1                   # rank per dead slot
        fill = jnp.where(jnp.isfinite(cd[jnp.clip(slot_rank, 0, cand.shape[0] - 1)]),
                         cand[jnp.clip(slot_rank, 0, cand.shape[0] - 1)], -1)
        new_row = jnp.where(dead, fill, row)
        return jnp.where(active, new_row, row)

    new_rows = jax.vmap(repair_one)(jnp.clip(vsel, 0), do)
    nbrs = graph.nbrs.at[jnp.clip(vsel, 0)].set(new_rows)
    version = graph.version.at[jnp.clip(vsel, 0)].add(do.astype(jnp.int32))
    graph = graph._replace(nbrs=nbrs, version=version)
    graph = graph._replace(e_in=compute_e_in(graph.nbrs, graph.capacity))
    return IndexState(graph, cache, stats), do.sum()


# ---------------------------------------------------------------------------
# Tiered (disk-backed) update path — numpy twins of the jitted transforms,
# streaming through the TieredStore so the working set stays bounded.
# ---------------------------------------------------------------------------

def rank_based_reorder_host(cand_ids, cand_d, cand_rows, degree):
    """Numpy twin of ``build.rank_based_reorder`` for the tiered path:
    the candidates' neighbor rows arrive pre-fetched (``cand_rows``
    [B, C, R]) instead of being gathered from a device-resident table."""
    B, C = cand_ids.shape
    eq = (cand_rows[:, :, :, None] == cand_ids[:, None, None, :]).any(axis=2)
    tri = np.tril(np.ones((C, C), bool), k=-1).T         # j < i mask at [j, i]
    detours = (eq & tri[None]).sum(axis=1)               # [B, C_i]
    invalid = cand_ids < 0
    detours = np.where(invalid, C + 1, detours)
    rank_d = np.argsort(np.argsort(cand_d, axis=1, kind="stable"),
                        axis=1, kind="stable")
    order = np.argsort(detours.astype(np.float64) * 1e6 + rank_d,
                       axis=1, kind="stable")
    take = min(degree, C)
    sel_ids = np.take_along_axis(cand_ids, order[:, :take], axis=1)
    sel_det = np.take_along_axis(detours, order[:, :take], axis=1)
    sel = np.where(sel_det > C, -1, sel_ids).astype(np.int32)
    if take < degree:
        sel = np.concatenate(
            [sel, np.full((B, degree - take), -1, np.int32)], axis=1)
    return sel


def reverse_edge_rows_host(trow, tvec, nbr_vecs, inv, new_ids, d_edge):
    """One-shot reverse-edge application over fetched target rows — the
    numpy twin of ``_reverse_edge_scatter`` shared by ``insert_tiered``
    and the tiered MVCC merge. For edge e (target ``inv[e]`` -> vertex
    ``new_ids[e]`` at distance ``d_edge[e]``): use a free slot if any,
    else replace the current worst neighbor if the new edge is closer;
    write conflicts resolve last-writer-wins. Returns the updated rows
    [U, R] (``trow`` is not mutated)."""
    nb_d = ((nbr_vecs - tvec[:, None, :]) ** 2).sum(-1)          # [U, R]
    occ = trow >= 0
    worst = np.argmax(np.where(occ, nb_d, -np.inf), axis=1)
    has_free = (~occ).any(axis=1)
    free_idx = np.argmax(~occ, axis=1)
    slot = np.where(has_free, free_idx, worst)
    max_d = np.where(occ, nb_d, -np.inf).max(axis=1)
    improves = has_free[inv] | (d_edge < max_d[inv])
    out = trow.copy()
    out[inv[improves], slot[inv][improves]] = \
        np.asarray(new_ids)[improves].astype(np.int32)
    return out


def insert_tiered(backend, cache_mirror, new_vecs, sp: SearchParams, seed,
                  attributes=None, tag=None):
    """Batched insertion against the disk-backed capacity tier (paper §5.1
    over the three-tier hierarchy): candidate search cascades through the
    store, new rows are written through the host window, and reverse edges
    are applied to the fetched target rows with the same free-slot /
    replace-worst / last-writer-wins semantics as ``insert_batch``.
    Returns ``(new_ids, RevLog)`` — the reverse-edge triplet log (numpy
    arrays) is consumed by the tiered MVCC merge when a consolidation
    snapshot is in flight. Caller serializes (engine update stream).

    Durability split (core/wal.py): the op's FULL effect — selected rows
    and reverse-edge triplets — is computed here against the unmutated
    store (every reverse-edge target pre-exists the batch, so its vector
    is already durable), logged to the WAL when one is attached, and only
    then applied by ``apply_insert_tiered`` — the same function crash
    recovery replays, so a recovered index is bit-identical to an
    uninterrupted run by construction.

    ``attributes`` (optional) is the batch's filter-attribute payload in
    any form ``filters.AttributeSchema.coerce`` accepts (dict of
    columns, (tags, nums) pair, or None for schema defaults); requires
    an attached ``backend.attrs`` store.

    Spans, each tagged with ``tag`` (the engine's ``step=``):
    ``svf.insert.search`` (candidate search), ``svf.insert.link``
    (rank-based reorder and the reverse-edge triplets), the WAL's
    ``svf.wal.append``, ``svf.insert.apply`` (the store writes).
    """
    from repro.core.search import search_tiered
    tag = tag or {}
    store = backend.store
    new_vecs = np.asarray(new_vecs, np.float32)
    Bi = new_vecs.shape[0]
    R = backend.degree
    n0 = backend.n
    if n0 + Bi > backend.capacity:
        raise ValueError(f"disk tier full: {n0}+{Bi} > {backend.capacity}")
    ids = (n0 + np.arange(Bi)).astype(np.int64)
    # one O(capacity) F_λ pass shared by the candidate search, the row
    # fetches and the reverse-edge pass below
    with TraceAnnotation("svf.f_lambda", **tag):
        f_lam = cache_mirror.scores(backend.e_in)

    # phase 1: candidate search on the current graph
    with TraceAnnotation("svf.insert.search", **tag):
        res = search_tiered(backend, cache_mirror, new_vecs, seed,
                            sp._replace(k=sp.pool), f_lam=f_lam, tag=tag)
    cand_ids, cand_d = res.ids.astype(np.int64), res.dists

    with TraceAnnotation("svf.insert.link", **tag):
        # phase 2: rank-based reorder over the candidates' (fetched) rows
        uc = np.unique(np.clip(cand_ids, 0, None))
        _, urows = store.fetch(uc, f_lam, tag=tag)
        cand_rows = urows[np.searchsorted(uc, np.clip(cand_ids, 0, None))]
        cand_rows[cand_ids < 0] = -1
        sel = rank_based_reorder_host(cand_ids, cand_d, cand_rows, R)

        # reverse-edge triplets, pre-mutation (targets all pre-exist:
        # their vectors are immutable and the distances are computable)
        flat_t = sel.reshape(-1).astype(np.int64)
        flat_new = np.repeat(ids, R)
        ok = flat_t >= 0
        flat_t, flat_new = flat_t[ok], flat_new[ok]
        d_edge = np.zeros((0,), np.float32)
        if flat_t.size:
            ut, inv = np.unique(flat_t, return_inverse=True)
            tvec, _ = store.fetch(ut, f_lam, tag=tag)
            d_edge = ((tvec[inv] - new_vecs[(flat_new - n0)]) ** 2).sum(-1)
        rev = RevLog(flat_t.astype(np.int64), flat_new.astype(np.int64),
                     np.asarray(d_edge, np.float32))

    # attribute columns: coerce through the index schema so the WAL
    # record and the live apply share one validated column form
    tags = nums = None
    if backend.attrs is not None:
        tags, nums = backend.attrs.schema.coerce(attributes, Bi)
    elif attributes is not None:
        raise ValueError("attributes passed but no attribute store is "
                         "attached (set EngineConfig.attributes)")

    if backend.wal is not None:
        from repro.core import wal as walmod
        payload = {"ids": ids, "vecs": new_vecs, "sel": sel,
                   "rev_v": rev.v, "rev_vn": rev.v_new, "rev_d": rev.d}
        if tags is not None:
            payload["tags"], payload["nums"] = tags, nums
        backend.wal.append(walmod.REC_INSERT, payload, tag=tag)
    with TraceAnnotation("svf.insert.apply", **tag):
        apply_insert_tiered(backend, ids, new_vecs, sel, rev, f_lam=f_lam,
                            tags=tags, nums=nums, tag=tag)
    return ids, rev


def apply_insert_tiered(backend, ids, new_vecs, sel, rev: RevLog,
                        f_lam=None, tags=None, nums=None, tag=None) -> None:
    """Mutation half of ``insert_tiered``, shared verbatim with WAL
    replay (``wal.recover``): establish the new vertices, encode their PQ
    codes against the frozen codebook, then apply the logged reverse
    edges onto freshly fetched target rows. Replaying this over the
    snapshot's state walks the store through the exact same write
    sequence as the live run. ``tag`` identifies the store's spans."""
    from repro.core.wal import crash_point
    store = backend.store
    ids = np.asarray(ids, np.int64)
    new_vecs = np.asarray(new_vecs, np.float32)
    if not len(ids):
        return
    n0 = int(ids[0])
    if n0 != backend.n:
        raise ValueError(f"insert replay out of order: record starts at id "
                         f"{n0}, store high-water mark is {backend.n}")
    R = backend.degree

    # establish new vertices (write-through keeps the overlay coherent);
    # the PQ code lane encodes incrementally against its frozen codebook
    # so the device-resident ADC scan covers the new ids from the next
    # search's epoch sync onward
    store.write(ids, new_vecs, sel, tag=tag)
    crash_point("mid_memmap_write")   # new rows written, reverse edges not
    if backend.pq is not None:
        backend.pq.encode_write(ids, new_vecs)
    if backend.attrs is not None and tags is not None:
        backend.attrs.write(ids, tags, nums)
    backend.alive[ids] = True
    backend.version[ids] = 1
    sel = np.asarray(sel, np.int32)
    np.add.at(backend.e_in, sel[sel >= 0], 1)
    backend.n = int(n0 + len(ids))

    # reverse edges (flattened over the batch, original-rows semantics)
    v = np.asarray(rev.v, np.int64)
    if v.size:
        v_new = np.asarray(rev.v_new, np.int64)
        d_edge = np.asarray(rev.d, np.float32)
        ut, inv = np.unique(v, return_inverse=True)
        tvec, trow = store.fetch(ut, f_lam, tag=tag)
        rvec, _ = store.peek(np.clip(trow, 0, None).reshape(-1), tag=tag)
        new_rows = reverse_edge_rows_host(
            trow, tvec, rvec.reshape(ut.size, R, -1), inv, v_new, d_edge)
        np.add.at(backend.e_in, trow[trow >= 0], -1)
        np.add.at(backend.e_in, new_rows[new_rows >= 0], 1)
        store.write(ut, None, new_rows, tag=tag)
        backend.version[ut] += 1


def delete_tiered(backend, ids, tag=None) -> np.ndarray:
    """Logical deletion on the tiered backend (stage 1, paper §5.2.1):
    bounds-filter, drop already-dead ids, WAL the surviving set, then
    flip the bitset. Returns the ids actually deleted. Caller serializes
    (engine update stream); ``tag`` identifies the WAL's span."""
    ids_np = np.asarray(ids, np.int64)
    ids_np = ids_np[(ids_np >= 0) & (ids_np < backend.n)]
    ids_np = ids_np[backend.alive[ids_np]]
    if backend.wal is not None and ids_np.size:
        from repro.core import wal as walmod
        backend.wal.append(walmod.REC_DELETE, {"ids": ids_np}, tag=tag)
    apply_delete_tiered(backend, ids_np)
    return ids_np


def apply_delete_tiered(backend, ids_np) -> None:
    """Mutation half of ``delete_tiered`` (records are pre-filtered)."""
    ids_np = np.asarray(ids_np, np.int64)
    backend.alive[ids_np] = False
    backend.version[ids_np] += 1


def consolidate_tiered(backend, chunk=256, *, snapshot=None):
    """Stage 3 (paper §5.2.2) for the disk tier, MVCC form (paper §5.3):
    global consolidation computed against a *frozen* topology snapshot
    while inserts/deletes/searches continue on the active store. Per
    snapshot-alive vertex, the neighbor list is rebuilt from {alive
    out-neighbors} ∪ {alive out-neighbors of deleted out-neighbors},
    pruned to degree by distance; dead rows are cleared. Adjacency comes
    from ``snapshot.rows`` (never the live store); vectors are immutable
    per id, so they stream through ``peek`` (bounded chunks, no window
    thrash). Returns the rebuilt rows [snapshot.n, R] WITHOUT publishing
    them — callers publish via ``mvcc.merge_consolidated_tiered``, which
    re-applies the window's reverse-edge log and makes window deletions
    authoritative. When ``snapshot`` is None a snapshot is taken and the
    result merged in place (serial mode: no concurrent update stream).
    """
    from repro.core import mvcc
    serial = snapshot is None
    if serial:
        snapshot = mvcc.snapshot_tiered(backend)
    store = backend.store
    R = backend.degree
    snap_rows, snap_alive = snapshot.rows, snapshot.alive
    snap_n = snapshot.n
    new_rows = snap_rows.copy()
    for s in range(0, snap_n, chunk):
        ids = np.arange(s, min(s + chunk, snap_n))
        C = ids.size
        rows = snap_rows[ids]
        valid = rows >= 0
        dead = valid & ~snap_alive[np.clip(rows, 0, None)]
        if not dead.any() and bool(snap_alive[ids].all()):
            continue
        svec, _ = store.peek(ids)
        hop2 = np.full((C, R, R), -1, np.int32)
        if dead.any():
            hop2[dead] = snap_rows[rows[dead]]       # frozen topology
        cand = np.concatenate(
            [np.where(dead, -1, rows), hop2.reshape(C, R * R)], axis=1)
        okc = (cand >= 0) & snap_alive[np.clip(cand, 0, None)] \
            & (cand != ids[:, None])
        cu = np.unique(np.clip(cand, 0, None))
        cvec, _ = store.peek(cu)
        clut = np.zeros((int(cu.max()) + 2,), np.int64)
        clut[cu] = np.arange(cu.size)
        d = ((cvec[clut[np.clip(cand, 0, None)]]
              - svec[:, None, :]) ** 2).sum(-1)
        d = np.where(okc & ~dedup_mask(cand), d, np.inf)
        top = np.argpartition(d, min(R, d.shape[1]) - 1, axis=1)[:, :R]
        dtop = np.take_along_axis(d, top, axis=1)
        o = np.argsort(dtop, axis=1, kind="stable")
        top = np.take_along_axis(top, o, axis=1)
        dtop = np.take_along_axis(dtop, o, axis=1)
        out = np.where(np.isfinite(dtop),
                       np.take_along_axis(cand, top, axis=1),
                       -1).astype(np.int32)
        out[~snap_alive[ids]] = -1
        new_rows[ids] = out
    if serial:
        mvcc.merge_consolidated_tiered(backend, snapshot, new_rows, [])
    return new_rows


@partial(jax.jit, static_argnames=("chunk",))
def consolidate(state: IndexState, *, chunk=512):
    """Stage 3 (paper §5.2.2): global consolidation. For every alive vertex,
    rebuild its neighbor list from {alive out-neighbors} ∪ {alive
    out-neighbors of its deleted out-neighbors}, pruned to degree by
    distance. Dead rows are cleared. Runs on a snapshot in the engine
    (MVCC) so foreground ops never block on it."""
    graph, cache, stats = state.graph, state.cache, state.stats
    R = graph.degree
    N = graph.capacity

    def rebuild(v):
        row = graph.nbrs[v]
        valid = row >= 0
        dead = valid & ~graph.alive[jnp.clip(row, 0)]
        hop2 = graph.nbrs[jnp.clip(row, 0)]                # [R, R]
        hop2 = jnp.where(dead[:, None], hop2, -1)          # only via deleted
        cand = jnp.concatenate([jnp.where(dead, -1, row), hop2.reshape(-1)])
        okc = (cand >= 0) & graph.alive[jnp.clip(cand, 0)] & (cand != v)
        vvec = graph.vectors[v]
        d = jnp.sum((graph.vectors[jnp.clip(cand, 0)] - vvec) ** 2, axis=-1)
        dup = jnp.triu(cand[:, None] == cand[None, :], k=1).any(0)
        d = jnp.where(okc & ~dup, d, INF)
        nd, it = jax.lax.top_k(-d, R)
        new_row = jnp.where(jnp.isfinite(-nd), cand[it], -1)
        return jnp.where(graph.alive[v], new_row, jnp.full((R,), -1, jnp.int32))

    ids = jnp.arange(N, dtype=jnp.int32).reshape(-1, chunk)
    nbrs = jax.lax.map(jax.vmap(rebuild), ids).reshape(N, R)
    graph = graph._replace(nbrs=nbrs,
                           version=graph.version + 1)
    graph = graph._replace(e_in=compute_e_in(graph.nbrs, N))
    return IndexState(graph, cache, stats)
