"""Seeded data for the benchmark's deployments, made in bulk with numpy.

The vectors are a random linear map of a ``latent``-dimensional Gaussian
plus isotropic noise (std 0.075 against a per-dimension signal std of 1),
at the deployment's published width. The real DEEP1B and MSTuring
descriptor files are not in the repository; on i.i.d. Gaussians at these
widths, which have no neighbourhood structure for a graph to follow, no
graph index reaches a useful recall. Every row of one run (base set,
queries, vectors inserted later) comes from one generator and one basis,
so queries and inserts follow the base set's distribution.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream])


FRESH_STREAM = 1 << 20      # streams of the vectors inserted later: + step


def _basis(rng, dim: int, latent: int) -> np.ndarray:
    return rng.standard_normal((latent, dim), np.float32) / np.sqrt(latent)


def _rows(rng, basis, rows: int) -> np.ndarray:
    x = rng.standard_normal((rows, basis.shape[0]), np.float32) @ basis
    x += np.float32(0.075) * rng.standard_normal((rows, basis.shape[1]),
                                                  np.float32)
    return x


def make_vectors(seed: int, rows: int, dim: int, latent: int) -> np.ndarray:
    """``rows`` x ``dim`` float32 vectors of one run (see module doc)."""
    rng = rng_for(seed, 0)
    return _rows(rng, _basis(rng, dim, latent), rows).astype(np.float32)


def fresh_batch(seed: int, step: int, rows: int, dim: int, latent: int,
                schema: dict):
    """The vectors and attributes an update stream inserts at ``step``,
    made on demand from (seed, step) with the run's basis."""
    basis = _basis(rng_for(seed, 0), dim, latent)
    rng = rng_for(seed, FRESH_STREAM + int(step))
    return (_rows(rng, basis, rows).astype(np.float32),
            _attributes(rng, rows, schema))


def make_attributes(seed: int, rows: int, schema: dict) -> dict:
    """Uniform attribute columns: each tag field on [0, tag_domain), each
    numeric field on [0, 1)."""
    return _attributes(rng_for(seed, 1), rows, schema)


def _attributes(rng, rows: int, schema: dict) -> dict:
    out = {f: rng.integers(0, schema["tag_domain"], rows).astype(np.int32)
           for f in schema["tag_fields"]}
    out.update({f: rng.random(rows).astype(np.float32)
                for f in schema["num_fields"]})
    return out
