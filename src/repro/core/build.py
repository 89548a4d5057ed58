"""Index construction (paper §4.2).

GPU-parallel strategy à la CAGRA: the dataset is partitioned to fit the
bandwidth tier, a KNN subgraph is built per partition with brute-force
distance GEMMs (MXU-friendly), and partitions are merged on the capacity
tier within a bounded memory window — cross-partition candidate edges come
from sampled inter-partition distance blocks, then rank-based reordering
(paper §5.1) prunes to the fixed out-degree and reverse edges are added.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import (GraphState, IndexState, init_cache_state,
                              init_graph_state, init_stats)


def pairwise_l2(a, b):
    """Squared L2 distances [n, m] via the GEMM form ||a||² - 2ab + ||b||²,
    at fp32 precision (the TPU default rounds matmul operands to bf16)."""
    a2 = jnp.sum(a * a, axis=1, keepdims=True)
    b2 = jnp.sum(b * b, axis=1, keepdims=True)
    ab = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    return a2 - 2.0 * ab + b2.T


@partial(jax.jit, static_argnames=("k",))
def _knn_block(vectors, start, block, k):
    """Top-k neighbor ids of the rows ``start + [0, len(block))``."""
    d = pairwise_l2(block, vectors)
    rows = jnp.arange(block.shape[0])
    d = d.at[rows, start + rows].set(jnp.inf)          # exclude self
    return jax.lax.top_k(-d, k)[1]


def _exact_knn(vectors, k, chunk=2048):
    """Top-k neighbor ids for every row (excluding self). Chunked GEMMs,
    each distance block held to 2^28 entries (1 GiB). If the dataset has
    fewer than k+1 rows, pads with -1."""
    vectors = jnp.asarray(vectors, jnp.float32)
    n = vectors.shape[0]
    k_eff = max(1, min(k, n - 1))
    chunk = max(1, min(chunk, (1 << 28) // max(n, 1)))
    ids = [_knn_block(vectors, s, vectors[s:s + chunk], k_eff)
           for s in range(0, n, chunk)]
    out = jnp.concatenate(ids, axis=0)
    if k_eff < k:
        out = jnp.concatenate(
            [out, jnp.full((n, k - k_eff), -1, out.dtype)], axis=1)
    return out


def rank_based_reorder(cand_ids, cand_dists, nbrs, degree):
    """Paper §5.1: sort candidates by detourable-path count (ascending).

    For candidate i, count occurrences of cand[i] in the neighbor lists of
    earlier candidates j < i; fewer detours = more valuable direct edge.
    cand_ids/[B, C] sorted by distance; nbrs [N, R]. Returns [B, degree].
    """
    B, C = cand_ids.shape

    def per_query(cids, cds):
        cn = nbrs[jnp.clip(cids, 0)]                       # [C, R]
        # detour[i] = #{j < i : cids[i] in nbrs[cids[j]]}
        eq = jnp.any(cn[:, :, None] == cids[None, None, :], axis=1)  # [C_j, C_i]
        tri = jnp.tril(jnp.ones((C, C), bool), k=-1).T      # j < i mask at [j, i]
        detours = jnp.sum(eq & tri, axis=0)                 # [C_i]
        invalid = cids < 0
        detours = jnp.where(invalid, C + 1, detours)
        # stable sort by (detours, distance)
        order = jnp.argsort(detours.astype(jnp.float32) * 1e6
                            + jnp.argsort(jnp.argsort(cds)).astype(jnp.float32))
        take = min(degree, C)
        sel = jnp.where(detours[order[:take]] > C, -1, cids[order[:take]])
        if take < degree:   # fewer candidates than out-degree: pad
            sel = jnp.concatenate(
                [sel, jnp.full((degree - take,), -1, jnp.int32)])
        return sel

    return jax.vmap(per_query)(cand_ids, cand_dists)


def _add_reverse_edges(nbrs_np: np.ndarray, n: int, rng: np.random.Generator):
    """Host-side reverse-edge pass (build time), vectorized: for each edge
    u->v whose reverse v->u is not already in v's row, add v->u into v's
    next free slot if it has one, else replace a uniformly random slot
    with probability 1/2. Rows take their reverse edges in ascending u
    order, so when several land on one slot the largest u wins — the
    order a sequential pass over u would apply them in. Membership is
    tested against the rows as built (before this pass)."""
    R = nbrs_np.shape[1]
    src = nbrs_np[:n].reshape(-1).astype(np.int64)
    u = np.repeat(np.arange(n, dtype=np.int64), R)[src >= 0]
    src = src[src >= 0]
    if not len(src):
        return nbrs_np
    # candidate reverse edges keyed v·n + u: sorted, they run row by row
    # in ascending u; drop those already present (mutual neighbors)
    rev = np.sort(src * n + u)
    edges = np.sort(u * n + src)
    pos = np.minimum(np.searchsorted(edges, rev), len(edges) - 1)
    rev = rev[edges[pos] != rev]
    v, u = rev // n, rev % n
    starts = np.flatnonzero(np.r_[True, v[1:] != v[:-1]])
    rank = np.arange(len(v)) - np.repeat(starts, np.diff(np.r_[starts,
                                                              len(v)]))
    free = nbrs_np < 0
    fill = rank < free.sum(1)[v]
    # the rank-th free slot of row v (free slots first, in slot order)
    free_pos = np.argsort(~free, axis=1, kind="stable")
    nbrs_np[v[fill], free_pos[v[fill], rank[fill]]] = u[fill]
    u, v = u[~fill], v[~fill]
    take = rng.random(len(u)) < 0.5
    slot = rng.integers(R, size=len(u))
    u, v, slot = u[take], v[take], slot[take]
    # one writer per (row, slot): the largest u, which a pass in
    # ascending u order would have written last
    key = np.sort((v * R + slot) * n + u)
    cell = key // n
    last = key[np.r_[cell[1:] != cell[:-1], True]]
    nbrs_np[last // n // R, last // n % R] = last % n
    return nbrs_np


def compute_e_in(nbrs, n_max):
    flat = nbrs.reshape(-1)
    valid = flat >= 0
    return jnp.zeros((n_max,), jnp.int32).at[
        jnp.clip(flat, 0)].add(valid.astype(jnp.int32))


def build_graph(vectors, degree, n_max=None, *, n_partitions=1,
                cross_samples=128, seed=0, reverse_edges=True):
    """Build a fixed-out-degree KNN graph. Returns GraphState.

    n_partitions > 1 exercises the partitioned build+merge path (bounded
    memory window); 1 = single-partition exact build.
    """
    vectors = jnp.asarray(vectors, jnp.float32)
    n, dim = vectors.shape
    n_max = n_max or n
    rng = np.random.default_rng(seed)

    if n_partitions <= 1:
        knn = _exact_knn(vectors, degree)
    else:
        # per-partition subgraphs ("GPU build"), then bounded-window merge:
        # only candidate columns are materialized, never the full matrix.
        bounds = np.linspace(0, n, n_partitions + 1).astype(int)
        knn_rows = []
        for p in range(n_partitions):
            s, e = bounds[p], bounds[p + 1]
            local = _exact_knn(vectors[s:e], min(degree, e - s - 1)) + s
            # cross-partition candidates: sampled global columns
            samp = rng.choice(n, size=min(cross_samples, n), replace=False)
            d_cross = pairwise_l2(vectors[s:e], vectors[samp])
            k_cross = min(degree, len(samp))
            _, ci = jax.lax.top_k(-d_cross, k_cross)
            cross = jnp.asarray(samp)[ci]
            cand = jnp.concatenate([local, cross], axis=1)     # [rows, C]
            cv = vectors[cand]                                 # bounded window
            d = jnp.sum((cv - vectors[s:e][:, None, :]) ** 2, axis=-1)
            rows = jnp.arange(s, e)
            d = jnp.where(cand == rows[:, None], jnp.inf, d)
            # drop duplicate candidate ids (keep first occurrence)
            dup = jnp.triu(cand[:, :, None] == cand[:, None, :], k=1).any(1)
            d = jnp.where(dup, jnp.inf, d)
            cand = jnp.where(dup, -1, cand)
            order = jnp.argsort(d, axis=1)
            knn_rows.append((jnp.take_along_axis(cand, order, axis=1),
                             jnp.take_along_axis(d, order, axis=1)))
        # rank-based reorder prunes merged candidates to the fixed degree
        zero_nbrs = jnp.full((n, degree), -1, jnp.int32)
        pruned = [rank_based_reorder(c.astype(jnp.int32), dd, zero_nbrs, degree)
                  for c, dd in knn_rows]
        knn = jnp.concatenate(pruned, axis=0)

    nbrs = np.full((n_max, degree), -1, np.int32)
    nbrs[:n, :knn.shape[1]] = np.asarray(knn, np.int32)
    if reverse_edges:
        nbrs = _add_reverse_edges(nbrs, n, rng)

    g = init_graph_state(n_max, dim, degree)
    g = g._replace(
        vectors=g.vectors.at[:n].set(vectors),
        nbrs=jnp.asarray(nbrs),
        alive=g.alive.at[:n].set(True),
        n=jnp.asarray(n, jnp.int32),
    )
    return g._replace(e_in=compute_e_in(g.nbrs, n_max))


def build_tiered_backend(vectors, degree, disk_path, *, disk_capacity=None,
                         host_window=None, **kw):
    """Build the full graph, spill vectors + rows to the disk tier and
    return a ``tiers.TieredBackend`` (paper Fig. 11: the GPU-CPU-disk
    form of the index). The graph build itself runs in memory — pass
    ``n_partitions > 1`` for the bounded-window partitioned build — and
    only the per-id metadata directory (alive/e_in/version) stays host-
    resident afterwards; vectors and adjacency live behind the store.
    """
    from repro.core.tiers import DiskTier, TieredBackend, TieredStore
    vectors = np.asarray(vectors, np.float32)
    n, dim = vectors.shape
    cap = disk_capacity or n
    if cap < n:
        raise ValueError(f"disk_capacity {cap} < initial dataset {n}")
    window = host_window or max(64, cap // 4)
    g = build_graph(vectors, degree, n_max=n, **kw)
    disk = DiskTier(disk_path, cap, dim, degree)
    disk.write(np.arange(n), vectors, np.asarray(g.nbrs[:n], np.int32))
    backend = TieredBackend(TieredStore(disk, window), n)
    backend.alive[:n] = np.asarray(g.alive[:n])
    backend.e_in[:n] = np.asarray(g.e_in[:n])
    return backend


def build_index(vectors, degree=32, cache_slots=1024, n_max=None,
                theta=1.0, alpha=1.0, beta=1.0, warm=True, **kw) -> IndexState:
    """Build graph + cache tiers. Cold-start warm-up (paper §4.4) preloads
    the top-F_lambda (== top in-degree at build time) vectors."""
    g = build_graph(vectors, degree, n_max=n_max, **kw)
    c = init_cache_state(g.capacity, cache_slots, g.vectors.shape[1],
                         theta=theta, alpha=alpha, beta=beta)
    if warm:
        score = jnp.where(g.alive, jnp.log1p(g.e_in.astype(jnp.float32)), -jnp.inf)
        m = min(cache_slots, int(g.n))
        _, top = jax.lax.top_k(score, m)
        slots = jnp.arange(m, dtype=jnp.int32)
        c = c._replace(
            vectors=c.vectors.at[slots].set(g.vectors[top]),
            slot_hid=c.slot_hid.at[slots].set(top.astype(jnp.int32)),
            h2d=c.h2d.at[top].set(slots),
        )
    return IndexState(graph=g, cache=c, stats=init_stats())
