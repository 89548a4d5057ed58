"""The plain reference and the comparison that decides ``correct``.

The reference is exact top-k by brute force on the device, at fp32
(``Precision.HIGHEST``), over a device copy of every vector the run ever
stored, restricted to a mask of the ids that count. It imports nothing of
the engine and takes nothing it made. ``BF16Engine`` is the control: the
same brute force computed from bfloat16 operands, put in the engine's
place, which the comparison has to refuse.
"""
from __future__ import annotations

import queue
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EPS32 = float(np.finfo(np.float32).eps)
BLOCK = 256          # query rows per reference dispatch


@partial(jax.jit, static_argnames=("k",))
def _exact_topk(data, mask, q, k):
    """Brute-force top-k over the rows with ``mask`` set, fp32."""
    hi = jax.lax.Precision.HIGHEST
    d = (jnp.sum(q * q, 1, keepdims=True)
         - 2.0 * jnp.matmul(q, data.T, precision=hi)
         + jnp.sum(data * data, 1)[None, :])
    d = jnp.where(mask[None, :], d, jnp.inf)
    nd, idx = jax.lax.top_k(-d, k)
    return idx, -nd


@partial(jax.jit, static_argnames=("k",))
def _bf16_topk(data, mask, q, k):
    """The control: the same top-k from bfloat16 operands."""
    qb, db = q.astype(jnp.bfloat16), data.astype(jnp.bfloat16)
    ab = jnp.matmul(qb, db.T, preferred_element_type=jnp.float32)
    d = (jnp.sum(qb * qb, 1, keepdims=True, dtype=jnp.float32) - 2.0 * ab
         + jnp.sum(db * db, 1, dtype=jnp.float32)[None, :])
    d = jnp.where(mask[None, :], d, jnp.inf)
    nd, idx = jax.lax.top_k(-d, k)
    return idx, -nd


def _pad_rows(q):
    """Pad a query block to BLOCK rows so one program serves every block."""
    b = len(q)
    if b == BLOCK:
        return q, b
    out = np.zeros((BLOCK, q.shape[1]), np.float32)
    out[:b] = q
    return out, b


class Reference:
    """Every vector ever stored, by id, on the host and on the device."""

    def __init__(self, vecs, capacity):
        self.host = np.zeros((capacity, vecs.shape[1]), np.float32)
        self.host[:len(vecs)] = vecs
        self.dev = None

    def add(self, ids, vecs):
        self.host[np.asarray(ids)] = vecs

    def topk(self, q, mask, k, fn=_exact_topk, dev=None):
        """Top-k ids and fp32 distances of ``q`` over ``mask``, in blocks,
        over ``dev`` (by default the device copy of every vector)."""
        if dev is None:
            if self.dev is None:
                self.dev = jnp.asarray(self.host)
            dev = self.dev
        m = jnp.asarray(mask)
        ids, ds = [], []
        for s in range(0, len(q), BLOCK):
            qb, b = _pad_rows(q[s:s + BLOCK])
            i, d = fn(dev, m, jnp.asarray(qb), k)
            ids.append(np.asarray(i)[:b])
            ds.append(np.asarray(d)[:b])
        return np.concatenate(ids), np.concatenate(ds)

    def free(self):
        self.dev = None


def dist_gap(host, q, ids, dists):
    """Worst gap between a returned distance and the float64 truth, as a
    share of the fp32 rounding bound 2·D·eps32·(|x|²+|q|²) of the
    expansion |x|² - 2x·q + |q|² over D terms. -1 ids are skipped."""
    ok = ids >= 0
    if not ok.any():
        return 0.0
    x = host[np.where(ok, ids, 0)].astype(np.float64)
    q64 = q.astype(np.float64)[:, None, :]
    truth = ((x - q64) ** 2).sum(-1)
    bound = 2 * q.shape[1] * EPS32 * ((x ** 2).sum(-1) + (q64 ** 2).sum(-1))
    gap = np.abs(dists.astype(np.float64) - truth) / bound
    return float(np.max(np.where(ok, gap, 0.0)))


def judge(ref, q, ids, dists, stable, changed, k, fn=_exact_topk):
    """Compare one group of answers that share a live set.

    ``stable`` masks the ids live for the whole time the requests ran;
    ``changed`` the ids an insert or delete touched meanwhile, which
    count neither way: they are dropped from the answer, and each one
    dropped shortens the reference list it is compared with. Returns
    per-query recall, the number of returned ids that were neither live
    nor changed (dead, deleted or out of range), the worst distance gap
    and the reference's top-1 per query."""
    want, _ = ref.topk(q, stable, k, fn)
    cap = len(stable)
    inr = (ids >= 0) & (ids < cap)
    idc = np.where(inr, ids, 0)
    live = inr & stable[idc]
    moved = inr & changed[idc]
    bad = int(((ids >= cap) | (inr & ~live & ~moved)).sum())
    recall = np.empty(len(q))
    for r in range(len(q)):
        m = k - int(moved[r].sum())
        got = set(ids[r][live[r]].tolist())
        recall[r] = (len(got & set(want[r, :m].tolist())) / m) if m else 1.0
    keep = np.where(live | moved, ids, -1)
    return recall, bad, dist_gap(ref.host, q, keep, dists), want[:, 0]


class _Future:
    __slots__ = ("_event", "ids", "dists", "error", "queries")

    def __init__(self, queries):
        self.queries = queries
        self._event = threading.Event()
        self.ids = self.dists = self.error = None

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError("control search did not complete")
        if self.error is not None:
            raise self.error
        return self.ids, self.dists


class BF16Engine:
    """The control in the engine's place: brute force over the live ids
    from bfloat16 operands, behind the engine's ``submit_search``,
    ``search``, ``insert``, ``delete``, ``stats`` and ``close``. Pending
    requests are served together, at most ``BLOCK`` rows at a time."""

    def __init__(self, vecs, capacity, k):
        self.ref = Reference(vecs, capacity)
        self.ref.dev = jnp.asarray(self.ref.host)
        self.live = np.zeros(capacity, bool)
        self.live[:len(vecs)] = True
        self.n = len(vecs)
        self.k = k
        self._q: queue.Queue = queue.Queue()
        self._lock = threading.Lock()
        self._th = threading.Thread(target=self._serve, daemon=True)
        self._th.start()

    def _serve(self):
        while True:
            batch = [self._q.get()]
            if batch[0] is None:
                return
            rows = len(batch[0].queries)
            while rows < BLOCK:
                try:
                    f = self._q.get_nowait()
                except queue.Empty:
                    break
                if f is None:
                    self._q.put(None)
                    break
                batch.append(f)
                rows += len(f.queries)
            q = np.concatenate([f.queries for f in batch])
            with self._lock:
                live, dev = self.live.copy(), self.ref.dev
            ids, ds = self.ref.topk(q, live, self.k, _bf16_topk, dev)
            off = 0
            for f in batch:
                b = len(f.queries)
                f.ids, f.dists = ids[off:off + b], ds[off:off + b]
                off += b
                f._event.set()

    def submit_search(self, queries):
        f = _Future(np.asarray(queries, np.float32))
        self._q.put(f)
        return f

    def search(self, queries):
        return self.submit_search(queries).result()

    def insert(self, vectors, attributes=None):
        with self._lock:
            ids = np.arange(self.n, self.n + len(vectors))
            self.ref.host[ids] = vectors
            self.ref.dev = self.ref.dev.at[jnp.asarray(ids)].set(
                jnp.asarray(vectors))
            self.n += len(vectors)
            self.live[ids] = True
        return ids

    def delete(self, ids):
        with self._lock:
            self.live[np.asarray(ids)] = False

    def stats(self):
        return {}

    def close(self):
        self._q.put(None)
        self._th.join(timeout=60)
        self.ref.free()
