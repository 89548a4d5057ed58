"""Distributed-optimization tricks: gradient compression + elastic remesh.

* ``ef_int8_psum`` — int8 error-feedback quantized all-reduce for the slow
  cross-pod hop: gradients are quantized per-row to int8 with the residual
  carried to the next step (1-bit-Adam-style EF), cutting cross-pod
  all-reduce bytes 4x vs fp32 / 2x vs bf16.
* ``remesh`` — elastic restart: re-shard a pytree from one mesh onto
  another (e.g. after losing a pod, continue data-parallel on the
  survivors with the same global state).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def quantize_int8(x, axis=-1):
    """Symmetric per-row int8 quantization. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def ef_compress(x, err):
    """Error-feedback compression step: returns (decompressed, new_err)."""
    y = x.astype(jnp.float32) + err
    q, s = quantize_int8(y)
    deq = dequantize_int8(q, s)
    return deq, y - deq


def ef_int8_psum(grad, err, axis_name):
    """Quantized cross-pod all-reduce with error feedback. Call under
    shard_map with ``axis_name`` = the slow axis ("pod")."""
    deq, new_err = ef_compress(grad, err)
    return jax.lax.pmean(deq, axis_name), new_err


def make_crosspod_grad_sync(mesh, spec_tree, axis_name="pod"):
    """Wrap per-pod gradients with an EF-int8 pmean over the pod axis."""
    def sync(grads, errs):
        def one(g, e, spec):
            inner = partial(ef_int8_psum, axis_name=axis_name)
            fn = jax.shard_map(
                inner, mesh=mesh,
                in_specs=(spec, spec), out_specs=(spec, spec))
            return fn(g, e)
        flat_g, tdef = jax.tree.flatten(grads)
        flat_e = jax.tree.leaves(errs)
        flat_s = jax.tree.leaves(spec_tree, is_leaf=lambda s: isinstance(s, P))
        outs = [one(g, e, s) for g, e, s in zip(flat_g, flat_e, flat_s)]
        return (jax.tree.unflatten(tdef, [o[0] for o in outs]),
                jax.tree.unflatten(tdef, [o[1] for o in outs]))
    return sync


# ---------------------------------------------------------------------------
# Elastic re-mesh
# ---------------------------------------------------------------------------

def remesh(tree, spec_tree, new_mesh):
    """Re-shard every leaf onto ``new_mesh`` with the same logical specs —
    the state half of elastic scaling (survivor pods pick up the load).
    Specs referencing axes absent from the new mesh fall back to
    replicated on those dims."""
    new_axes = set(new_mesh.axis_names)

    def fix_spec(spec):
        out = []
        for part in spec:
            if part is None:
                out.append(None)
            elif isinstance(part, str):
                out.append(part if part in new_axes else None)
            else:
                keep = tuple(a for a in part if a in new_axes)
                out.append(keep if keep else None)
        return P(*out)

    def place(x, spec):
        return jax.device_put(np.asarray(x),
                              NamedSharding(new_mesh, fix_spec(spec)))

    # spec_tree mirrors tree's structure with P leaves; tree.map flattens
    # up to tree's leaves so each P arrives whole
    return jax.tree.map(place, tree, spec_tree)
