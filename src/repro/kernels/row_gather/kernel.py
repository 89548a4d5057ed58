"""Masked adjacency-row gather Pallas TPU kernel.

The in-loop topology read of the fused multi-round executor: for each
query, resolve the beam's frontier ids through the device-resident
topology cache (h2s id->slot directory, then the cached row table) and
emit the adjacency rows, with the -1 sentinel on every lane whose id is
idle (< 0) or not resident (h2s[id] < 0). The sentinel is what lets the
``lax.while_loop`` body detect a topology-cache miss without a host
round-trip: a non-resident id in the frontier surfaces as an all--1 row
*plus* a cleared residency bit, and the loop exits to the host for the
delta fetch.

TPU-native shape (same house idiom as ``l2_gather``): the wrapper
resolves each lane's slot through the directory (an XLA gather of one
int32 per lane — Mosaic cannot DMA a single element of the tiled [N]
directory) and scalar-prefetches the [B, W] slot matrix into SMEM, -1 on
idle or non-resident lanes. Each lane with a slot DMAs its row
HBM→VMEM straight into the output block; a lane without one stores a -1
row. The decision is a scalar one per lane, so no mask vector is built.
Mosaic DMAs only whole 128-lane rows of a 32-bit table, so the wrapper
pads R up to a multiple of 128 (R=32 -> 128; XLA's HBM layout pads the
rows to 128 lanes anyway) and slices the pad lanes off the result. The
row table stays in ANY/HBM; only W rows touch VMEM. Validated in
interpret mode against ref.py; compiled for v5e by
tests/test_chip_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _kernel(slots_ref, table_ref, out_ref, sem):
    W, R = out_ref.shape
    b = pl.program_id(0)

    def fetch(w, _):
        slot = slots_ref[b, w]

        @pl.when(slot >= 0)
        def _():
            cp = pltpu.make_async_copy(table_ref.at[pl.ds(slot, 1), :],
                                       out_ref.at[pl.ds(w, 1), :], sem)
            cp.start()
            cp.wait()

        @pl.when(slot < 0)
        def _():
            out_ref[pl.ds(w, 1), :] = jnp.full((1, R), -1, jnp.int32)

        return 0

    jax.lax.fori_loop(0, W, fetch, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def row_gather(table, h2s, ids, *, interpret=False):
    """table [S, R] int32 cached rows; h2s [N] int32 id->slot (-1 =
    non-resident); ids [B, W] int32 (-1 = idle lane) -> [B, W, R] int32
    adjacency rows, -1-filled on non-resident/idle lanes."""
    B, W = ids.shape
    S, R0 = table.shape
    R = -(-R0 // _LANES) * _LANES
    table = table.astype(jnp.int32)
    if R != R0:
        table = jnp.pad(table, ((0, 0), (0, R - R0)))
    ids = ids.astype(jnp.int32)
    slots = jnp.where(ids >= 0, h2s.astype(jnp.int32)[jnp.clip(ids, 0)], -1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],      # table HBM
        out_specs=pl.BlockSpec((None, W, R), lambda b, slots: (b, 0, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, W, R), jnp.int32),
        interpret=interpret,
    )(slots, table)
    return out[:, :, :R0]
