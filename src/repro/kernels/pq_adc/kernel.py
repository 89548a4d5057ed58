"""ADC-scan Pallas TPU kernel over gathered PQ code rows.

The inner loop of the PQ code lane (quant.py): for each query, score the
C candidate code rows named by the frontier executor's id matrix from
the query's precomputed lookup table — ``d[c] = Σ_s lut[s, codes[c,
s]]``.

The code rows are gathered by XLA (``codes[ids]``, m bytes per id)
before the kernel. A per-id DMA inside the kernel, as ``l2_gather`` does
for fp32 rows, is not expressible here: Mosaic DMAs only whole tiles of
an 8-bit table (32 rows) or whole 128-lane rows of a 32-bit one, and a
code row is m = 48 bytes.

The LUT gather runs on the VPU as a select: for each subspace s the
candidates' codes are compared against a lane iota over the K centroids
and ``lut[s]`` is accumulated where they match, into a [TILE, K] buffer
whose row sums are the distances. One ones-row contraction at
``Precision.HIGHEST`` then sums each row and lands it lane-major as a
(1, TILE) output slice; every addend is an exact LUT entry, so the
distance is an fp32 sum of the same terms the reference adds.

Ids may carry invalid lanes (-1: padded beam slots, pruned edges):
clamped for the gather, forced to +inf in-kernel — the code table is
never indexed at -1, same contract as l2_gather.

Grid: (query, candidate tile of 128). Only one tile's 128 code rows and
the [128, K] fp32 accumulator (128 KiB at K=256) touch VMEM, whatever C
is. The LUT block index does not change along the tile axis, so it is
fetched once per query. Validated in interpret mode against ref.py;
compiled for v5e by tests/test_chip_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_TILE = 128     # candidates per grid step: one 128-lane output slice


def _kernel(lut_ref, rows_ref, idv_ref, out_ref):
    m, n_cent = lut_ref.shape
    cod = rows_ref[...].astype(jnp.int32)                 # [TILE, m]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_cent), 1)
    acc = jnp.zeros((cod.shape[0], n_cent), jnp.float32)
    for s in range(m):
        acc = acc + jnp.where(cod[:, s:s + 1] == iota, lut_ref[s:s + 1, :],
                              0.0)
    ones = jnp.ones((1, n_cent), jnp.float32)
    d = jax.lax.dot_general(ones, acc, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # [1, TILE]
    out_ref[...] = jnp.where(idv_ref[...] >= 0, d, jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pq_adc(codes, lut, ids, *, interpret=False):
    """codes [N, m] uint8; lut [B, m, K] f32; ids [B, C] int32 (-1 =
    invalid lane) -> ADC distances [B, C] fp32, +inf on invalid lanes."""
    B, C0 = ids.shape
    m = codes.shape[1]
    n_cent = lut.shape[2]
    # pad the lane axis to whole tiles (-1 lanes come back +inf and are
    # sliced off below)
    C = -(-C0 // _TILE) * _TILE
    ids = ids.astype(jnp.int32)
    if C != C0:
        ids = jnp.concatenate([ids, jnp.full((B, C - C0), -1, jnp.int32)], 1)
    rows = codes.astype(jnp.uint8)[jnp.clip(ids, 0)]       # [B, C, m]
    out = pl.pallas_call(
        _kernel,
        grid=(B, C // _TILE),
        in_specs=[
            pl.BlockSpec((None, m, n_cent), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((None, _TILE, m), lambda b, t: (b, t, 0)),
            pl.BlockSpec((None, 1, _TILE), lambda b, t: (b, 0, t)),
        ],
        out_specs=pl.BlockSpec((None, 1, _TILE), lambda b, t: (b, 0, t)),
        out_shape=jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
        interpret=interpret,
    )(lut.astype(jnp.float32), rows, ids[:, None, :])
    return out[:, 0, :C0]
