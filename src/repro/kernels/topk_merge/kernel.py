"""Candidate-pool merge Pallas TPU kernel (bitonic partial sort).

Merges the L-entry candidate pool with R freshly computed neighbor
distances and keeps the best L — the per-iteration pool update of
Algorithm 1. A GPU implementation leans on warp shuffles; the TPU version
is a data-parallel bitonic network over the padded [L+R] lane vector in
VMEM (compare-exchange via strided reshapes on the VPU), carrying
(distance, id, visited) triples through the permutation.

Validated in interpret mode against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bitonic(d, i, v):
    """Full ascending bitonic sort of (d, i, v) rows [B, P], P = 2^m.

    The partner exchange (lane ``j ^ stride``) is a strided reshape +
    reverse, not a gather: lane j decomposes as (block, bit, offset) with
    ``bit = (j // stride) & 1``, and XOR-ing the stride flips exactly that
    axis. XLA compiles this in linear time, where the equivalent
    take_along_axis network blows up compile superlinearly (and gathers
    are the slow path on the VPU anyway).
    """
    B, P = d.shape
    m = P.bit_length() - 1
    idx = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    for stage in range(1, m + 1):
        up = ((idx >> stage) & 1) == 0              # ascending block?
        for sub in range(stage, 0, -1):
            stride = 1 << (sub - 1)

            def partner(x):
                y = x.reshape(B, P // (2 * stride), 2, stride)
                return y[:, :, ::-1, :].reshape(B, P)

            pd, pi, pv = partner(d), partner(i), partner(v)
            is_lo = (idx & stride) == 0
            keep_self = jnp.where(up, (d < pd) | ((d == pd) & (i <= pi)),
                                  (d > pd) | ((d == pd) & (i >= pi)))
            keep_self = jnp.where(is_lo, keep_self, ~keep_self)
            d = jnp.where(keep_self, d, pd)
            i = jnp.where(keep_self, i, pi)
            v = jnp.where(keep_self, v, pv)
    return d, i, v


def _kernel(pool_d_ref, pool_i_ref, pool_v_ref, new_d_ref, new_i_ref,
            out_d_ref, out_i_ref, out_v_ref):
    L = pool_d_ref.shape[1]
    R = new_d_ref.shape[1]
    P = 1 << (L + R - 1).bit_length()
    pad = P - (L + R)
    d = jnp.concatenate([pool_d_ref[...], new_d_ref[...],
                         jnp.full((1, pad), jnp.inf, jnp.float32)], axis=1)
    i = jnp.concatenate([pool_i_ref[...], new_i_ref[...],
                         jnp.full((1, pad), -1, jnp.int32)], axis=1)
    v = jnp.concatenate([pool_v_ref[...].astype(jnp.int32),
                         jnp.zeros((1, R + pad), jnp.int32)], axis=1)
    d, i, v = _bitonic(d, i, v)
    out_d_ref[...] = d[:, :L]
    out_i_ref[...] = i[:, :L]
    out_v_ref[...] = v[:, :L]


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_merge(pool_d, pool_i, pool_v, new_d, new_i, *, interpret=False):
    """Merge pools. pool_* [B, L]; new_* [B, R] -> best-L (d, i, visited)."""
    B, L = pool_d.shape
    R = new_d.shape[1]
    specs_in = [pl.BlockSpec((1, L), lambda b: (b, 0)),
                pl.BlockSpec((1, L), lambda b: (b, 0)),
                pl.BlockSpec((1, L), lambda b: (b, 0)),
                pl.BlockSpec((1, R), lambda b: (b, 0)),
                pl.BlockSpec((1, R), lambda b: (b, 0))]
    specs_out = [pl.BlockSpec((1, L), lambda b: (b, 0))] * 3
    out_d, out_i, out_v = pl.pallas_call(
        _kernel,
        grid=(B,),
        in_specs=specs_in,
        out_specs=specs_out,
        out_shape=[jax.ShapeDtypeStruct((B, L), jnp.float32),
                   jax.ShapeDtypeStruct((B, L), jnp.int32),
                   jax.ShapeDtypeStruct((B, L), jnp.int32)],
        interpret=interpret,
    )(pool_d.astype(jnp.float32), pool_i.astype(jnp.int32),
      pool_v.astype(jnp.int32), new_d.astype(jnp.float32),
      new_i.astype(jnp.int32))
    return out_d, out_i, out_v.astype(bool)
