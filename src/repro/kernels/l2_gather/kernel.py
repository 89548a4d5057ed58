"""Fused row-gather + L2-distance Pallas TPU kernel.

The inner loop of SVFusion's hop-batched frontier executor: for each
query, fetch the K neighbor vectors named by the id matrix and compute
squared-L2 distances. On GPU this is a warp-per-row gather; the
TPU-native shape (DESIGN.md §2) is: neighbor ids scalar-prefetched
(SMEM), row DMAs HBM→VMEM per id (all K started, then all K awaited),
then the squared differences are summed by one ones-row contraction on
the MXU at ``Precision.HIGHEST`` — the sum lands lane-major as the
query's ``(1, K)`` output row, and HIGHEST keeps it fp32-exact (the
TPU's default matmul precision rounds operands to bf16).

The executor feeds the batched (Q, beam·degree) id matrix of a whole
expansion round, so K runs to beam·degree and ids may carry invalid
lanes (-1: padded beam slots, pruned edges). Invalid ids are clamped for
the DMA and their distances forced to +inf in-kernel — indexing the
table at -1 is never attempted.

Mosaic only DMAs whole 128-lane rows of a 32-bit table, so the wrapper
pads D up to a multiple of 128 (D=96 -> 128; XLA's HBM layout pads the
rows to 128 lanes anyway, the pad is a copy, not a bigger footprint)
and K to whole 128-lane output rows. Per-query operands travel as
``[B, 1, X]`` arrays with the leading axis squeezed from the block, so
every block's last two dims equal the array's own — the (8, 128) tiling
rule Mosaic enforces.

Grid: one step per query. Table stays in ANY/HBM; only the K gathered
rows ever touch VMEM (K·D·4 bytes, 512×128×4 = 256 KiB at the served
shape). Validated in interpret mode against ref.py; compiled for v5e by
tests/test_chip_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _kernel(ids_ref, q_ref, idv_ref, table_ref, out_ref, rows_ref, sem):
    K = rows_ref.shape[0]
    b = pl.program_id(0)

    def copy(k):
        idx = jnp.maximum(ids_ref[b, k], 0)    # clamp invalid lanes
        return pltpu.make_async_copy(table_ref.at[pl.ds(idx, 1), :],
                                     rows_ref.at[pl.ds(k, 1), :], sem)

    def start(k, _):
        copy(k).start()
        return 0

    def wait(k, _):
        copy(k).wait()
        return 0

    jax.lax.fori_loop(0, K, start, 0)
    jax.lax.fori_loop(0, K, wait, 0)
    diff = rows_ref[...] - q_ref[...]                    # [K, D]
    ones = jnp.ones((1, diff.shape[1]), jnp.float32)
    d = jax.lax.dot_general(ones, diff * diff, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # [1, K]
    out_ref[...] = jnp.where(idv_ref[...] >= 0, d, jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def l2_gather(table, ids, queries, *, interpret=False):
    """table [N, D]; ids [B, K] int32 (-1 = invalid lane);
    queries [B, D] -> [B, K] fp32, +inf on invalid lanes."""
    B, K0 = ids.shape
    N, D0 = table.shape
    K = -(-K0 // _LANES) * _LANES
    D = -(-D0 // _LANES) * _LANES
    ids = ids.astype(jnp.int32)
    if K != K0:
        ids = jnp.concatenate([ids, jnp.full((B, K - K0), -1, jnp.int32)], 1)
    table = table.astype(jnp.float32)
    queries = queries.astype(jnp.float32)
    if D != D0:
        # a row DMA must span whole 128-lane rows; zero lanes add nothing
        # to the squared difference
        table = jnp.pad(table, ((0, 0), (0, D - D0)))
        queries = jnp.pad(queries, ((0, 0), (0, D - D0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, 1, D), lambda b, ids: (b, 0, 0)),  # query
            pl.BlockSpec((None, 1, K), lambda b, ids: (b, 0, 0)),  # valid
            pl.BlockSpec(memory_space=pl.ANY),                  # table
        ],
        out_specs=pl.BlockSpec((None, 1, K), lambda b, ids: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, D), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, K), jnp.float32),
        interpret=interpret,
    )(ids, queries[:, None, :], ids[:, None, :], table)
    return out[:, 0, :K0]
