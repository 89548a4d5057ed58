"""Pod-scale SVFusion: the paper's partitioned build/merge re-expressed over
ICI (DESIGN.md §7).

Layout on the production mesh (data axes = ("pod","data"), query axis =
"model"):

* capacity tier — vectors / graph / bitset sharded over the data axes:
  each chip owns N/P vectors and their subgraph (the paper's per-partition
  subgraphs);
* bandwidth tier — each chip's hot cache covers its own shard (mapping
  table is shard-local);
* queries — sharded over "model": each (data×model) cell searches its data
  shard for its query slice; per-shard top-k results are all-gathered over
  the data axes and merged (compute where the data lives, move only
  results — the WAVP "CPU-side compute" arm, ICI edition).

The returned step is shard_map-ped and jit-compatible; the dry-run lowers
it at Deep1B scale (1B × 96) on the 256- and 512-chip meshes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.search import _frontier_search
from repro.core.types import (CacheState, GraphState, SearchParams,
                              init_cache_state)


def shard_index_arrays(n_total, dim, degree, n_shards, cache_slots,
                       vec_dtype=jnp.float32):
    """Abstract shapes for the sharded index (dry-run inputs).

    vec_dtype=bf16 halves the stored footprint and (on native-bf16 TPU)
    the gather traffic of the memory-bound beam search; the CPU dry-run
    backend keeps f32 as default because its bf16 emulation materializes an
    fp32 table copy (see EXPERIMENTS.md §Perf svfusion iteration 2)."""
    import jax
    f32, i32 = jnp.float32, jnp.int32
    n_local = n_total // n_shards
    return {
        "vectors": jax.ShapeDtypeStruct((n_total, dim), vec_dtype),
        "nbrs": jax.ShapeDtypeStruct((n_total, degree), i32),
        "alive": jax.ShapeDtypeStruct((n_total,), jnp.bool_),
        "e_in": jax.ShapeDtypeStruct((n_total,), i32),
        "cache_vectors": jax.ShapeDtypeStruct(
            (n_shards * cache_slots, dim), vec_dtype),
        "slot_hid": jax.ShapeDtypeStruct((n_shards * cache_slots,), i32),
        "h2d": jax.ShapeDtypeStruct((n_total,), i32),
        "f_recent": jax.ShapeDtypeStruct((n_total,), f32),
    }


def index_shardings(data_axes=("pod", "data")):
    d = data_axes if len(data_axes) > 1 else data_axes[0]
    return {
        "vectors": P(d, None),
        "nbrs": P(d, None),
        "alive": P(d),
        "e_in": P(d),
        "cache_vectors": P(d, None),
        "slot_hid": P(d),
        "h2d": P(d),
        "f_recent": P(d),
    }


def make_distributed_search(mesh, sp: SearchParams,
                            data_axes=("pod", "data"), query_axis="model"):
    """Builds the sharded search step. Returns fn(index_arrays, queries,
    key) -> (ids [B, k], dists [B, k]) with globally valid ids.

    ``query_axis=None`` replicates queries: every chip searches its own
    partition for the whole batch (required at Deep1B scale, where the
    capacity tier must shard over every mesh axis to fit HBM)."""
    present = [a for a in data_axes if a in mesh.axis_names]
    dspec = tuple(present) if len(present) > 1 else present[0]

    qspec = P(query_axis, None) if query_axis else P(None, None)
    in_specs = (
        {"vectors": P(dspec, None), "nbrs": P(dspec, None),
         "alive": P(dspec), "e_in": P(dspec),
         "cache_vectors": P(dspec, None), "slot_hid": P(dspec),
         "h2d": P(dspec), "f_recent": P(dspec)},
        qspec,
        P(),
    )
    out_specs = (qspec, qspec)

    def step(idx, queries, key):
        n_local = idx["vectors"].shape[0]
        # shard offset -> global ids
        shard_lin = jnp.zeros((), jnp.int32)
        mul = 1
        for ax in reversed(present):
            shard_lin = shard_lin + jax.lax.axis_index(ax) * mul
            mul = mul * jax.lax.axis_size(ax)
        offset = shard_lin.astype(jnp.int32) * n_local

        graph = GraphState(
            vectors=idx["vectors"], nbrs=idx["nbrs"], alive=idx["alive"],
            e_in=idx["e_in"],
            version=jnp.zeros((n_local,), jnp.int32),
            n=jnp.asarray(n_local, jnp.int32))
        cache = init_cache_state(n_local, idx["cache_vectors"].shape[0],
                                 idx["vectors"].shape[1])
        cache = cache._replace(vectors=idx["cache_vectors"],
                               slot_hid=idx["slot_hid"], h2d=idx["h2d"],
                               f_recent=idx["f_recent"])

        B = queries.shape[0]
        keys = jax.random.fold_in(key, shard_lin)
        entries = jax.random.randint(keys, (B, sp.pool), 0, n_local,
                                     dtype=jnp.int32)
        res = _frontier_search(graph, cache, queries, entries, sp)
        gids = jnp.where(res.ids >= 0, res.ids + offset, -1)

        # hierarchical top-k merge over the data axes (results, not rows,
        # cross the wire: k * 8B per query per shard)
        all_ids, all_d = gids, res.dists
        for ax in present:
            ai = jax.lax.all_gather(all_ids, ax, axis=0, tiled=False)
            ad = jax.lax.all_gather(all_d, ax, axis=0, tiled=False)
            ai = jnp.moveaxis(ai, 0, 1).reshape(B, -1)
            ad = jnp.moveaxis(ad, 0, 1).reshape(B, -1)
            nd, sel = jax.lax.top_k(-ad, sp.k)
            all_ids = jnp.take_along_axis(ai, sel, axis=1)
            all_d = -nd
        return all_ids, all_d

    return jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def analytical_search_flops(sp: SearchParams, batch, dim, degree):
    """MODEL_FLOPS analogue for the search step (while-loop bodies are
    counted once by HLO cost analysis; this is the true per-step count):
    per query-iteration: R gathered rows × (3D flops for ||x-q||²) +
    pool merge sort ~ (L+R)·log(L+R) comparisons."""
    per_iter = degree * 3 * dim + (sp.pool + degree) * 12
    return batch * sp.max_iters * per_iter
