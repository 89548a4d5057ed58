"""The served entry points over the Pallas kernels.

The implementation is chosen once per process, by backend: on a TPU
every call runs the compiled kernel (``interpret=False``); on any other
backend it runs the kernel's jnp reference, which the interpret-mode
parity tests hold the kernels to. Nothing on the TPU path runs in
interpret mode. ``traced`` counts, per (op, implementation), the traces
that took each branch, so a run can show which one it served.
"""
from __future__ import annotations

import functools
from collections import Counter

import jax

from repro.kernels.l2_gather.kernel import l2_gather
from repro.kernels.l2_gather.ref import l2_gather_ref
from repro.kernels.pq_adc.kernel import pq_adc
from repro.kernels.pq_adc.ref import pq_adc_ref
from repro.kernels.row_gather.kernel import row_gather
from repro.kernels.row_gather.ref import row_gather_ref

PALLAS, REF = "pallas-compiled", "jnp-ref"
traced: Counter = Counter()


@functools.cache
def use_kernels() -> bool:
    """True on a TPU backend: serve the compiled kernels."""
    return jax.default_backend() == "tpu"


def _pick(op: str, kernel, ref):
    impl = PALLAS if use_kernels() else REF
    traced[(op, impl)] += 1
    return kernel if impl == PALLAS else ref


def gather_l2(table, ids, queries):
    """Squared-L2 distances from gathered table rows. [B,K] fp32."""
    return _pick("gather_l2", l2_gather, l2_gather_ref)(table, ids, queries)


def adc_gather(codes, lut, ids):
    """Asymmetric PQ distances (LUT gather) from gathered code rows —
    the code-lane twin of ``gather_l2``. [B,K] fp32, +inf invalid."""
    return _pick("adc_gather", pq_adc, pq_adc_ref)(codes, lut, ids)


def gather_rows(table, h2s, ids):
    """Adjacency rows for frontier ids through the device-resident
    topology cache (h2s directory -> cached row table) — the in-loop
    topology read of the fused multi-round executor. [B,W,R] int32,
    -1-sentinel rows on non-resident/idle lanes."""
    return _pick("gather_rows", row_gather, row_gather_ref)(table, h2s, ids)
