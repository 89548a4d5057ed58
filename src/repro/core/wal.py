"""Durability layer for the tiered store: write-ahead log + epoch-fenced
snapshots (ROADMAP "Durability & drift", durability half).

The paper's streaming story (§5) assumes the index survives the process;
this module makes the disk tier's truth crash-consistent so everything
device-resident — the WAVP exact cache, the PQ code mirror, the TopoCache
— can stay a *pure cache*, rebuilt at recovery (the FusionANNS split:
SSD-resident truth, GPU-resident accelerant).

Write protocol (update stream, serialized by the engine):

1. prepare — compute the op's full effect (candidate search, selected
   rows, reverse-edge triplets) against the *unmutated* store;
2. WAL append — one CRC-framed record per logical op, fsync batched by
   ``group_commit`` (records the OS buffered but never fsynced survive a
   process kill; only power/OS failure can lose the tail, and the CRC
   framing truncates any torn tail cleanly either way);
3. apply — mutate the store through the SAME apply function recovery
   replays, so a recovered index is bit-identical to an uninterrupted
   run by construction.

Snapshot protocol (``publish_snapshot``): fsync the WAL and both memmaps,
write ``snapshot-<epoch>.npz`` (adjacency rows [0, n), alive/e_in/version,
PQ codebook + codes — vectors are immutable per id and already durable in
the memmap), fsync + atomic-rename it, open a fresh WAL segment, then
atomically rename ``manifest.json`` to point at the pair. A crash anywhere
in the sequence leaves the previous manifest intact and its snapshot +
WAL segment untouched — recovery is always from the last *published*
epoch.

Recovery (``recover``): verify the snapshot against the manifest's CRC,
restore the metadata directory and adjacency rows (rows past the torn
tail of a crashed insert are cleared — the memmap beyond the snapshot's
high-water mark is not trusted), then replay the WAL segment through the
apply functions, truncating at the first record whose frame fails the
CRC/length check.

Fault injection: ``set_crash_hook`` installs a process-wide hook that
``crash_point(name)`` calls at the named crash sites (post_wal_append,
mid_memmap_write, pre_manifest_rename, mid_consolidation_merge);
``tests/faultinject.py`` arms it with an ``os._exit`` to simulate kill -9.
"""
from __future__ import annotations

import io
import json
import os
import pickle
import struct
import threading
import zlib
from typing import Callable, Optional

import numpy as np
from jax.profiler import TraceAnnotation

MAGIC = b"SVWL"
_HDR = struct.Struct("<4sBQII")       # magic, rtype, op_seq, payload_len, crc
MANIFEST = "manifest.json"
MANIFEST_FORMAT = 1

REC_INSERT = 1
REC_DELETE = 2
REC_CONSOLIDATE = 3


class WALError(RuntimeError):
    """Base class for durability-layer failures."""


class WALWriteError(WALError):
    """The WAL device failed an append/sync; the op was NOT applied.
    The engine degrades to read-only instead of crashing."""


class WALCorruptionError(WALError):
    """Manifest/snapshot failed validation at recovery."""


# ---------------------------------------------------------------------------
# Crash-point hooks (fault injection)
# ---------------------------------------------------------------------------

_CRASH_HOOK: Optional[Callable[[str], None]] = None

CRASH_POINTS = ("post_wal_append", "mid_memmap_write",
                "pre_manifest_rename", "mid_consolidation_merge")


def set_crash_hook(hook: Optional[Callable[[str], None]]) -> None:
    """Install (or clear, with None) the process-wide crash hook. The hook
    receives the crash-point name on every pass through an instrumented
    site and decides whether to die (``tests/faultinject.py``)."""
    global _CRASH_HOOK
    _CRASH_HOOK = hook


def crash_point(name: str) -> None:
    """Named crash site — free when no hook is installed."""
    hook = _CRASH_HOOK
    if hook is not None:
        hook(name)


# ---------------------------------------------------------------------------
# Record framing
# ---------------------------------------------------------------------------

def _frame(rtype: int, op_seq: int, payload: dict) -> bytes:
    body = pickle.dumps(payload, protocol=4)
    crc = zlib.crc32(struct.pack("<BQI", rtype, op_seq, len(body)) + body)
    return _HDR.pack(MAGIC, rtype, op_seq, len(body), crc) + body


def read_records(path: str):
    """Parse a WAL segment. Returns ``(records, valid_len)`` where records
    is ``[(rtype, op_seq, payload), ...]`` and ``valid_len`` is the byte
    offset of the first frame that fails the magic/length/CRC check — the
    torn tail a crashed group-commit batch may have left. Callers truncate
    the file to ``valid_len`` before appending again."""
    with open(path, "rb") as f:
        data = f.read()
    records, off = [], 0
    while off + _HDR.size <= len(data):
        magic, rtype, seq, plen, crc = _HDR.unpack_from(data, off)
        if magic != MAGIC or off + _HDR.size + plen > len(data):
            break
        body = data[off + _HDR.size: off + _HDR.size + plen]
        if zlib.crc32(struct.pack("<BQI", rtype, seq, plen) + body) != crc:
            break
        records.append((rtype, seq, pickle.loads(body)))
        off += _HDR.size + plen
    return records, off


def _fsync_dir(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class WriteAheadLog:
    """Append-only CRC-framed log with group-commit batching.

    ``append`` assigns a monotone ``op_seq`` (continued across segment
    rotations via ``start_seq``), writes the frame immediately and defers
    the fsync until ``group_commit`` records are pending — the classic
    group-commit throughput trade. A failed write/sync poisons the log
    (``failed``) so the engine can degrade to read-only; the store was
    not touched for the failed op (WAL-before-write).

    Each append is an ``svf.wal.append`` span and each record fsync an
    ``svf.wal.fsync`` span; ``fsyncs`` and ``bytes`` count them and the
    frame bytes written.
    """

    def __init__(self, path: str, *, group_commit: int = 8,
                 start_seq: int = 1):
        self.path = path
        self.group_commit = max(1, int(group_commit))
        self.appended = 0
        self.synced = 0
        self.fsyncs = 0       # fsyncs: group commits and sync()
        self.bytes = 0        # frame bytes written
        self.failed: Optional[str] = None
        self._next_seq = int(start_seq)
        self._pending = 0
        self._lock = threading.Lock()
        # unbuffered: every append hits the OS immediately, so a process
        # kill (as opposed to power loss) can never lose an appended
        # record to a userspace buffer — the contract the fault-injection
        # matrix (kill -9 at post_wal_append) relies on
        self._f = open(path, "ab", buffering=0)
        # the segment must exist durably before a manifest references it
        os.fsync(self._f.fileno())
        _fsync_dir(os.path.dirname(os.path.abspath(path)))

    @property
    def last_seq(self) -> int:
        return self._next_seq - 1

    @property
    def closed(self) -> bool:
        return self._f.closed

    def append(self, rtype: int, payload: dict, tag=None) -> int:
        """Log one record; ``tag`` identifies its spans."""
        tag = tag or {}
        with TraceAnnotation("svf.wal.append", **tag), self._lock:
            if self.failed:
                raise WALWriteError(self.failed)
            seq = self._next_seq
            try:
                frame = _frame(rtype, seq, payload)
                self._f.write(frame)
                self.bytes += len(frame)
                self._pending += 1
                if self._pending >= self.group_commit:
                    self._fsync(tag)
            except (OSError, ValueError) as e:
                self.failed = f"wal append failed: {e}"
                raise WALWriteError(self.failed) from e
            self._next_seq = seq + 1
            self.appended += 1
        crash_point("post_wal_append")
        return seq

    def _fsync(self, tag=None) -> None:
        """Make the pending records durable (caller holds the lock)."""
        with TraceAnnotation("svf.wal.fsync", **(tag or {})):
            self._f.flush()
            os.fsync(self._f.fileno())
        self.fsyncs += 1
        self.synced += self._pending
        self._pending = 0

    def sync(self) -> None:
        with self._lock:
            if self.failed:
                raise WALWriteError(self.failed)
            try:
                self._fsync()
            except (OSError, ValueError) as e:
                self.failed = f"wal sync failed: {e}"
                raise WALWriteError(self.failed) from e

    def close(self) -> None:
        if not self._f.closed:
            if not self.failed:
                try:
                    self.sync()
                except WALWriteError:
                    pass
            self._f.close()


# ---------------------------------------------------------------------------
# Manifest + snapshot publication
# ---------------------------------------------------------------------------

def _segment_name(epoch: int) -> str:
    return f"wal-{epoch:08d}.log"


def _snapshot_name(epoch: int) -> str:
    return f"snapshot-{epoch:08d}.npz"


def load_manifest(dirpath: Optional[str]) -> Optional[dict]:
    """The last published durable epoch, or None when the directory holds
    no recoverable index."""
    if not dirpath:
        return None
    path = os.path.join(dirpath, MANIFEST)
    if not os.path.exists(path):
        return None
    with open(path, "r") as f:
        man = json.load(f)
    if man.get("format") != MANIFEST_FORMAT:
        raise WALCorruptionError(
            f"manifest format {man.get('format')!r} unsupported "
            f"(expected {MANIFEST_FORMAT})")
    return man


def publish_snapshot(dirpath: str, backend, prev_wal: Optional[WriteAheadLog],
                     *, group_commit: int = 8, chunk: int = 8192):
    """Publish the backend's current state as the new durable epoch.
    Returns ``(manifest, new_wal)``; the previous WAL segment is closed
    and deleted once the manifest rename lands. Caller holds the engine's
    update lock (the snapshot must be a consistent cut of the update
    stream; concurrent searches only promote identical data)."""
    os.makedirs(dirpath, exist_ok=True)
    prev = load_manifest(dirpath)
    epoch = (int(prev["epoch"]) + 1) if prev else 0
    if prev_wal is not None:
        prev_wal.sync()
    store = backend.store
    store.disk.flush()

    n = int(backend.n)
    rows = np.empty((n, backend.degree), np.int32)
    for s in range(0, n, chunk):
        ids = np.arange(s, min(s + chunk, n))
        rows[ids] = store.peek_rows(ids)
    op_seq = prev_wal.last_seq if prev_wal is not None else 0
    arrays = dict(nbrs=rows, alive=backend.alive[:n].copy(),
                  version=backend.version[:n].copy(),
                  e_in=backend.e_in[:n].copy(),
                  n=np.asarray(n, np.int64),
                  op_seq=np.asarray(op_seq, np.int64))
    pq_meta = None
    if backend.pq is not None:
        from repro.core import quant
        arrays["pq_centroids"] = quant.codebook_to_array(backend.pq.codebook)
        arrays["pq_codes"] = backend.pq.snapshot(n)
        pq_meta = {"m": backend.pq.m, "bits": backend.pq.bits}
    attrs_meta = None
    if backend.attrs is not None:
        tags, nums = backend.attrs.snapshot(n)
        arrays["attr_tags"], arrays["attr_nums"] = tags, nums
        attrs_meta = backend.attrs.schema.to_meta()

    snap_name = _snapshot_name(epoch)
    snap_tmp = os.path.join(dirpath, snap_name + ".tmp")
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    raw = buf.getvalue()
    with open(snap_tmp, "wb") as f:
        f.write(raw)
        f.flush()
        os.fsync(f.fileno())
    os.replace(snap_tmp, os.path.join(dirpath, snap_name))
    _fsync_dir(dirpath)

    wal_name = _segment_name(epoch)
    new_wal = WriteAheadLog(os.path.join(dirpath, wal_name),
                            group_commit=group_commit, start_seq=op_seq + 1)
    manifest = {
        "format": MANIFEST_FORMAT, "epoch": epoch, "op_seq": op_seq,
        "n": n, "capacity": int(backend.capacity), "dim": int(backend.dim),
        "degree": int(backend.degree), "snapshot": snap_name,
        "snapshot_crc": zlib.crc32(raw), "wal": wal_name, "pq": pq_meta,
        "attrs": attrs_meta,
    }
    crash_point("pre_manifest_rename")
    man_tmp = os.path.join(dirpath, MANIFEST + ".tmp")
    with open(man_tmp, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(man_tmp, os.path.join(dirpath, MANIFEST))
    _fsync_dir(dirpath)

    if prev_wal is not None:
        prev_wal.close()
    _cleanup_stale(dirpath, manifest)
    return manifest, new_wal


def _cleanup_stale(dirpath: str, manifest: dict) -> None:
    """Drop snapshot/WAL files the published manifest no longer
    references (previous epochs, or orphans from a crash mid-publish)."""
    keep = {manifest["snapshot"], manifest["wal"]}
    for name in os.listdir(dirpath):
        if name in keep:
            continue
        if (name.startswith("snapshot-") or name.startswith("wal-")):
            try:
                os.remove(os.path.join(dirpath, name))
            except OSError:        # best effort: stale files are inert
                pass


# ---------------------------------------------------------------------------
# Recovery
# ---------------------------------------------------------------------------

def _replay(backend, records) -> None:
    from repro.core import mvcc, update
    for rtype, _seq, p in records:
        if rtype == REC_INSERT:
            rev = update.RevLog(p["rev_v"], p["rev_vn"], p["rev_d"])
            # attribute columns ride newer records only; .get keeps
            # pre-attribute WAL segments replayable
            update.apply_insert_tiered(backend, p["ids"], p["vecs"],
                                       p["sel"], rev,
                                       tags=p.get("tags"),
                                       nums=p.get("nums"))
        elif rtype == REC_DELETE:
            update.apply_delete_tiered(backend, p["ids"])
        elif rtype == REC_CONSOLIDATE:
            mvcc.apply_merge_edits(backend,
                                   list(zip(p["ids"], p["rows"])))
        else:
            raise WALCorruptionError(f"unknown WAL record type {rtype}")


def recover(dirpath: str, *, host_window: int, group_commit: int = 8,
            chunk: int = 8192):
    """Open the last published epoch and roll the WAL forward. Returns
    ``(backend, wal, report)``: a fully rebuilt ``TieredBackend`` (PQ lane
    attached when the manifest records one; device mirrors are the
    engine's to re-warm — they are pure caches), the reopened WAL
    positioned after the last valid record, and a report dict."""
    from repro.core.tiers import DiskTier, TieredBackend, TieredStore
    man = load_manifest(dirpath)
    if man is None:
        raise WALCorruptionError(f"no manifest in {dirpath!r}")
    spath = os.path.join(dirpath, man["snapshot"])
    with open(spath, "rb") as f:
        raw = f.read()
    if zlib.crc32(raw) != man["snapshot_crc"]:
        raise WALCorruptionError(
            f"snapshot {man['snapshot']} failed CRC validation")
    snap = np.load(io.BytesIO(raw))
    cap, dim, R = int(man["capacity"]), int(man["dim"]), int(man["degree"])
    n = int(snap["n"])

    disk = DiskTier(dirpath, cap, dim, R, create=False)
    # adjacency truth comes from the snapshot: rows a killed writer tore
    # mid-memmap-write (including any past the durable high-water mark)
    # are overwritten/cleared before replay re-applies the logged ops
    rows = np.asarray(snap["nbrs"], np.int32)
    for s in range(0, n, chunk):
        disk.nbr[s:min(s + chunk, n)] = rows[s:min(s + chunk, n)]
    for s in range(n, cap, chunk):
        disk.nbr[s:min(s + chunk, cap)] = -1

    backend = TieredBackend(TieredStore(disk, host_window), n)
    backend.alive[:n] = snap["alive"]
    backend.version[:n] = snap["version"]
    backend.e_in[:n] = snap["e_in"]
    if man.get("pq"):
        from repro.core import quant
        cb = quant.codebook_from_array(np.asarray(snap["pq_centroids"]))
        backend.attach_pq(quant.PQCodes(cb, cap,
                                        codes=np.asarray(snap["pq_codes"])))
    # pre-attribute manifests (no "attrs" key) recover without a store;
    # the engine attaches an empty one if its config declares a schema
    if man.get("attrs"):
        from repro.core.filters import AttributeSchema
        from repro.core.tiers import AttributeStore
        schema = AttributeSchema.from_meta(man["attrs"])
        backend.attach_attrs(AttributeStore(
            schema, cap, tags=np.asarray(snap["attr_tags"]),
            nums=np.asarray(snap["attr_nums"])))

    wpath = os.path.join(dirpath, man["wal"])
    truncated = 0
    if os.path.exists(wpath):
        records, valid = read_records(wpath)
        truncated = os.path.getsize(wpath) - valid
        if truncated:
            os.truncate(wpath, valid)
    else:                           # segment lost entirely: nothing to roll
        records = []
    _replay(backend, records)
    last_seq = records[-1][1] if records else int(man["op_seq"])
    wal = WriteAheadLog(wpath, group_commit=group_commit,
                        start_seq=last_seq + 1)
    backend.wal = wal
    report = {"epoch": int(man["epoch"]), "snapshot_seq": int(man["op_seq"]),
              "replayed": len(records), "last_seq": last_seq,
              "truncated_bytes": int(truncated)}
    return backend, wal, report
