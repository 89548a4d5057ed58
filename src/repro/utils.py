"""Shared utilities: pytree helpers, sharding helpers, timing, rng."""
from __future__ import annotations

import dataclasses
import os
import pathlib
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Keep JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads that variable itself; nothing is set in code then), or
    else in the checkout's git-ignored ``.jax_cache/``. The path is part
    of the cache key, so it is fixed: never a temporary name, process id
    or time. Returns the directory in use. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def tree_size(tree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def cast_tree(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def shape_struct(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def block_tree(tree):
    """Block until all leaves are ready (for timing)."""
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return tree


def percentile(xs: Iterable[float], p: float) -> float:
    xs = sorted(xs)
    if not xs:
        return float("nan")
    idx = min(len(xs) - 1, int(round(p / 100.0 * (len(xs) - 1))))
    return xs[idx]


def spec(*names) -> P:
    """Shorthand PartitionSpec constructor."""
    return P(*names)


def current_mesh_axis_sizes() -> dict[str, int]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return {}
    return dict(zip(mesh.axis_names, mesh.axis_sizes))
