"""Product quantization for the device-resident code lane (FusionANNS-style
coarse-then-refine, PAPERS.md): the paper's whole machinery (WAVP caching,
cascading lookup, speculation) works around exact fp32 vectors not fitting
on the device — the complementary move is to keep *compressed* PQ codes
unconditionally device-resident and score every candidate there with an
asymmetric-distance (ADC) lookup-table scan, fetching exact vectors through
the tier cascade only for a small re-rank set.

Layout: D dims split into ``m`` contiguous subspaces of ``dsub = D/m``
dims; each subspace has its own ``K = 2**bits`` Lloyd/k-means codebook.
A vector encodes to ``m`` uint8 codes — at m=16, bits=8 that is D·4/16
times smaller than fp32 (32x at D=128), so datasets far larger than the
device cache get full-coverage device-side distance evaluation.

ADC: per query, ``adc_lut`` precomputes ``lut[s, k] = ||q_s − c_sk||²``
once ([m, K] floats); a candidate's distance is then ``Σ_s lut[s,
code[x, s]]`` — a gather + reduce, no FLOPs on the vector itself (the
``kernels/pq_adc`` pair runs it over the executor's (Q, beam·R) id
matrix with the same in-kernel invalid-lane masking as ``l2_gather``).

``PQCodes`` is the serving-side lane state: host-truth codes array with
write-through incremental encoding for streamed inserts
(``update.insert_tiered``) and an epoch-synced device mirror searches
read lock-free. Codebooks are trained once at index time on a sample and
frozen; streamed vectors are encoded against the frozen codebooks, the
standard PQ serving regime.
"""
from __future__ import annotations

import threading
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class PQCodebook(NamedTuple):
    """Per-subspace centroid tables."""
    centroids: jax.Array     # [m, K, dsub] float32

    @property
    def m(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_codes(self) -> int:
        return self.centroids.shape[1]

    @property
    def dsub(self) -> int:
        return self.centroids.shape[2]

    @property
    def dim(self) -> int:
        return self.m * self.dsub


def choose_m(dim: int, m: int) -> int:
    """Largest divisor of ``dim`` that is <= the requested subspace count
    (PQ needs D % m == 0; the engine degrades gracefully instead of
    refusing a dataset whose dim the knob doesn't divide)."""
    m = max(1, min(m, dim))
    while dim % m:
        m -= 1
    return m


def _sqdist_to_centroids(sub, cents):
    """Per-subspace squared distances, the ONE expansion all three PQ
    primitives share (train assignment, encode argmin, ADC LUT — they
    must agree numerically for ADC distances to mean anything):
    sub [..., m, dsub] vs cents [m, K, dsub] -> [..., m, K]."""
    return (jnp.sum(sub * sub, -1)[..., None]
            - 2.0 * jnp.einsum("...md,mkd->...mk", sub, cents,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
            + jnp.sum(cents * cents, -1))


@partial(jax.jit, static_argnames=("m", "k", "iters"))
def _train(vectors, key, m: int, k: int, iters: int):
    """Lloyd's k-means, vectorized over the m subspaces (one [n, m, K]
    assignment tensor per sweep; callers bound n by sampling)."""
    n, D = vectors.shape
    dsub = D // m
    sub = vectors.reshape(n, m, dsub)                          # [n, m, dsub]
    perm = jax.random.permutation(key, n)
    init = sub[perm[jnp.arange(k) % n]].transpose(1, 0, 2)     # [m, k, dsub]

    def step(c, _):
        d = _sqdist_to_centroids(sub, c)                       # [n, m, k]
        assign = jnp.argmin(d, -1)                             # [n, m]
        onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)  # [n, m, k]
        cnt = onehot.sum(0)                                    # [m, k]
        sums = jnp.einsum("nmk,nmd->mkd", onehot, sub,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
        # empty clusters keep their old centroid (never NaN-divide)
        new = jnp.where(cnt[..., None] > 0,
                        sums / jnp.maximum(cnt, 1.0)[..., None], c)
        return new, None

    c, _ = jax.lax.scan(step, init, None, length=iters)
    return c


def train_codebook(vectors, m: int, bits: int, *, iters: int = 20,
                   sample: int = 4096, seed: int = 0) -> PQCodebook:
    """Train per-subspace codebooks on (a sample of) the dataset.
    bits <= 8 so codes stay uint8 (the whole point of the lane)."""
    if not 1 <= bits <= 8:
        raise ValueError(f"pq bits must be in [1, 8], got {bits}")
    vectors = np.asarray(vectors, np.float32)
    n, D = vectors.shape
    if D % m:
        raise ValueError(f"dim {D} not divisible by m={m} "
                         f"(use choose_m to pick a divisor)")
    if sample and n > sample:
        idx = np.random.default_rng(seed).choice(n, sample, replace=False)
        vectors = vectors[np.sort(idx)]
    k = 1 << bits
    cents = _train(jnp.asarray(vectors), jax.random.PRNGKey(seed),
                   m, k, iters)
    return PQCodebook(centroids=cents)


@jax.jit
def _encode(centroids, vectors):
    m, k, dsub = centroids.shape
    n = vectors.shape[0]
    sub = vectors.reshape(n, m, dsub)
    return jnp.argmin(_sqdist_to_centroids(sub, centroids),
                      -1).astype(jnp.uint8)


def encode(codebook: PQCodebook, vectors, chunk: int = 4096) -> np.ndarray:
    """Vectors [n, D] -> codes [n, m] uint8. Chunked (padded to the chunk
    size so the jitted body compiles once) to bound the [chunk, m, K]
    assignment tensor at index-time scale."""
    vectors = np.asarray(vectors, np.float32)
    n = vectors.shape[0]
    out = np.empty((n, codebook.m), np.uint8)
    for s in range(0, n, chunk):
        part = vectors[s:s + chunk]
        pad = chunk - len(part)
        if pad > 0 and n > chunk:   # keep the single compiled shape
            part = np.concatenate(
                [part, np.zeros((pad, vectors.shape[1]), np.float32)])
        out[s:s + chunk] = np.asarray(
            _encode(codebook.centroids, jnp.asarray(part)))[:min(chunk,
                                                                 n - s)]
    return out


def codebook_to_array(codebook: PQCodebook) -> np.ndarray:
    """Host array form of the frozen centroid tables, for the durability
    snapshot (``wal.publish_snapshot``)."""
    return np.asarray(codebook.centroids, np.float32)


def codebook_from_array(centroids: np.ndarray) -> PQCodebook:
    """Rebuild the codebook from a persisted centroid array. Encoding is
    deterministic given the centroids, so replayed inserts re-encode to
    the same codes the crashed run wrote."""
    return PQCodebook(centroids=jnp.asarray(centroids, jnp.float32))


def decode(codebook: PQCodebook, codes) -> np.ndarray:
    """Codes [n, m] -> reconstructed vectors [n, D] float32."""
    codes = np.asarray(codes)
    cents = np.asarray(codebook.centroids)                    # [m, K, dsub]
    n, m = codes.shape
    out = cents[np.arange(m)[None, :], codes.astype(np.int64)]  # [n, m, dsub]
    return out.reshape(n, m * cents.shape[2]).astype(np.float32)


@jax.jit
def adc_lut(centroids, queries):
    """Per-query ADC lookup tables: queries [B, D] -> lut [B, m, K] with
    ``lut[b, s, k] = ||q_sub[b, s] − centroids[s, k]||²``."""
    m, k, dsub = centroids.shape
    B = queries.shape[0]
    qs = queries.astype(jnp.float32).reshape(B, m, dsub)
    return _sqdist_to_centroids(qs, centroids)


class PQCodes:
    """Serving-side PQ lane state: frozen codebook + unconditionally
    resident codes (host truth + device mirror).

    Unlike exact vectors — whose device residency WAVP has to ration —
    codes are ~D·4/m times smaller, so the WHOLE id space stays device-
    resident and every executor round scores all candidates on device.

    Write-through: the update stream encodes inserted vectors against the
    frozen codebook (``encode_write``; ``update.insert_tiered`` calls it)
    into the host array and logs the dirty block; searches call
    ``synced_codes()`` which folds pending blocks into the device mirror
    under a lock and returns the (immutable) device array — readers are
    never torn, at worst one-update-batch stale, exactly the alive/e_in
    directory consistency model."""

    def __init__(self, codebook: PQCodebook, capacity: int,
                 codes: np.ndarray = None):
        self.codebook = codebook
        self.codes = np.zeros((capacity, codebook.m), np.uint8)
        if codes is not None:
            self.codes[:len(codes)] = codes
        self._codes_j = jnp.asarray(self.codes)
        self._dirty: list = []
        self._lock = threading.Lock()
        self.encoded = 0          # rows encoded incrementally (stats)
        self.h2d_bytes = 0        # bytes ``synced_codes`` put on the device

    @property
    def m(self) -> int:
        return self.codebook.m

    @property
    def bits(self) -> int:
        return int(self.codebook.n_codes - 1).bit_length()

    def encode_write(self, ids, vectors) -> np.ndarray:
        """Incremental write-through encode (update stream only)."""
        c = encode(self.codebook, vectors)
        ids = np.asarray(ids)
        with self._lock:
            self.codes[ids] = c
            self._dirty.append(ids.copy())
            self.encoded += len(ids)
        return c

    def synced_codes(self) -> jax.Array:
        """Device mirror with all pending write-through blocks applied —
        folded in ONE scatter (each ``.at[].set`` copies the whole
        device array, so per-block application would cost one full copy
        per insert batch since the last search)."""
        with self._lock:
            if self._dirty:
                ids = np.unique(np.concatenate(self._dirty))
                idx, rows = jnp.asarray(ids), jnp.asarray(self.codes[ids])
                self.h2d_bytes += idx.nbytes + rows.nbytes
                self._codes_j = self._codes_j.at[idx].set(rows)
                self._dirty.clear()
            return self._codes_j

    def snapshot(self, n: int) -> np.ndarray:
        """Consistent copy of the host-truth codes over [0, n) for the
        durability snapshot — taken under the write-through lock so a
        concurrent ``encode_write`` can never tear the cut."""
        with self._lock:
            return self.codes[:n].copy()

    def code_bytes(self, n: int = None) -> int:
        """Device-resident code footprint (bytes) over ``n`` ids (whole
        array when None)."""
        if n is None:
            return self.codes.nbytes
        return int(n) * self.codes.shape[1] * self.codes.itemsize
