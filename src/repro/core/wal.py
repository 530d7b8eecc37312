"""Write-ahead batch log for the online engines.

Every state-mutating stream operation (``ingest``, ``retract``, ``evict``)
is journaled to an append-only segment file BEFORE its commit barrier
acknowledges, so a crashed engine can be rebuilt bitwise: restore the last
good checkpoint, then replay the WAL tail in order through the normal
ingest path.  Because estimates are deterministic functions of canonical
group content alone (the bit-identity contract), restore-then-replay is
bitwise equal to the never-crashed twin.

Record format (little-endian), one record per operation::

    magic   u32   0x5A51_574C ("ZQWL")
    kind    u8    1 = INGEST, 2 = RETRACT, 3 = EVICT
    epoch   u32   primary term that created the record (fencing token)
    seq     u64   monotonically increasing, never reused
    len     u32   payload byte length
    crc     u32   crc32 of payload
    hcrc    u32   crc32 of the 25 header bytes above
    payload len bytes

Batch payloads are a JSON column header (names, dtypes, row count, valid
bitmap dtype) followed by the raw column bytes in header order — built from
HOST numpy data, so appending a record never touches a device buffer (the
ingest hot path stays transfer-clean).  Evict payloads are a small JSON
object (``{"ttl": n}``).

Durability rule: ``append_*`` writes and flushes; :meth:`BatchLog.sync`
fsyncs.  The durable engine fsyncs before the commit barrier acknowledges
(per-record in synchronous mode, once per commit barrier in MVCC overlap
mode — either way no commit is acknowledged before its records are on
disk; lint rule ZQL008 checks the ordering statically).

Epoch fencing: every record carries the primary *epoch* (term) that
created it.  Failover bumps the cluster epoch and :meth:`BatchLog.fence`s
the old primary's log, after which any append from the stale writer raises
:class:`StaleEpochError` — a zombie primary that wakes up after promotion
cannot extend a log that replication has already moved past.  Epochs are
non-decreasing within a log; a decrease is treated as corruption.

Segments are named ``wal-<startseq>.log``; :meth:`BatchLog.rotate` starts
a new segment (called at checkpoint publish) and :meth:`BatchLog.gc`
deletes segments made redundant by a DURABLE checkpoint.  The reader
tolerates a torn tail (a truncated or CRC-bad final record is discarded);
corruption in the middle of the log — a bad record with a valid record
after it — raises :class:`WalCorruption`, because silently skipping a
record would break replay bit-identity.

Tail reads: :meth:`BatchLog.read` re-parses the whole log; replication
shipping and degraded replay instead keep a :class:`TailCursor` (segment
start, byte offset, last seq) and call :meth:`BatchLog.read_tail`, which
scans only bytes appended since the previous call — O(new bytes), not
O(log).  ``BatchLog.bytes_scanned`` counts bytes parsed by either path so
tests can pin that property.
"""
from __future__ import annotations

import dataclasses
import json
import os
import struct
import zlib
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from repro.launch.trace import count, span

MAGIC = 0x5A51574C
KIND_INGEST = 1
KIND_RETRACT = 2
KIND_EVICT = 3

_HEADER = struct.Struct("<IBIQII")      # magic, kind, epoch, seq, len, crc
_HCRC = struct.Struct("<I")             # crc32 of the header bytes
_HEADER_SIZE = _HEADER.size + _HCRC.size


class WalCorruption(IOError):
    """A WAL record failed validation with valid records after it."""


class StaleEpochError(IOError):
    """A write carried an epoch below the log's fence — the writer was
    deposed by a promotion and must not extend this log."""


def _encode_batch(columns: Dict[str, np.ndarray],
                  valid: np.ndarray) -> bytes:
    cols = {name: np.ascontiguousarray(a) for name, a in columns.items()}
    v = np.ascontiguousarray(np.asarray(valid))
    header = {
        "nrows": int(v.shape[0]),
        "valid_dtype": str(v.dtype),
        "columns": [[name, str(a.dtype)] for name, a in cols.items()],
    }
    hb = json.dumps(header, sort_keys=True).encode()
    parts = [struct.pack("<I", len(hb)), hb, v.tobytes()]
    parts += [cols[name].tobytes() for name, _ in header["columns"]]
    return b"".join(parts)


def _decode_batch(payload: bytes) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    (hlen,) = struct.unpack_from("<I", payload, 0)
    header = json.loads(payload[4:4 + hlen].decode())
    n = header["nrows"]
    off = 4 + hlen
    valid = np.frombuffer(payload, dtype=header["valid_dtype"],
                          count=n, offset=off).copy()
    off += valid.itemsize * n
    columns = {}
    for name, dtype in header["columns"]:
        a = np.frombuffer(payload, dtype=dtype, count=n, offset=off)
        columns[name] = a.copy()
        off += a.itemsize * n
    return columns, valid


class Record:
    """One decoded WAL record."""

    __slots__ = ("kind", "seq", "payload", "epoch")

    def __init__(self, kind: int, seq: int, payload: bytes, epoch: int = 1):
        self.kind = kind
        self.seq = seq
        self.payload = payload
        self.epoch = epoch

    def batch(self) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
        return _decode_batch(self.payload)

    def evict_ttl(self) -> int:
        return int(json.loads(self.payload.decode())["ttl"])


@dataclasses.dataclass
class TailCursor:
    """Resumable position in a WAL: scan only bytes after (seg_start,
    offset), deduplicating by ``last_seq``. A fresh cursor reads the whole
    log; thereafter each :meth:`BatchLog.read_tail` call advances it past
    everything cleanly parsed, so repeated tail reads cost O(new bytes)."""

    seg_start: int = 0
    offset: int = 0
    last_seq: int = 0


def encode_record(rec: Record) -> bytes:
    """Wire/segment encoding of one record — exactly the bytes a segment
    file stores, so shipped spans and local segments are interchangeable."""
    head = _HEADER.pack(MAGIC, rec.kind, rec.epoch, rec.seq,
                        len(rec.payload), zlib.crc32(rec.payload))
    return head + _HCRC.pack(zlib.crc32(head)) + rec.payload


def encode_records(records: Iterable[Record]) -> bytes:
    return b"".join(encode_record(r) for r in records)


def decode_records(data: bytes, offset: int = 0,
                   max_records: Optional[int] = None,
                   ) -> Tuple[List[Record], int, bool]:
    """Incrementally parse records out of ``data`` starting at ``offset``.

    Returns ``(records, end, clean)`` where ``end`` is the byte offset
    just past the last cleanly decoded record.  ``clean=False`` means
    parsing stopped at ``end`` on an incomplete or CRC-bad record (a torn
    tail if nothing valid follows — callers that must distinguish mid-log
    damage run :func:`_scan_rest` over the remainder).  Never raises: this
    is the shared parser for segment files AND shipped byte spans, and a
    truncated ship is routine, not fatal.
    """
    records: List[Record] = []
    off = offset
    while off < len(data):
        if max_records is not None and len(records) >= max_records:
            break
        if off + _HEADER_SIZE > len(data):
            return records, off, False                  # torn header
        magic, kind, epoch, seq, length, crc = _HEADER.unpack_from(data, off)
        (hcrc,) = _HCRC.unpack_from(data, off + _HEADER.size)
        header_ok = (magic == MAGIC
                     and zlib.crc32(data[off:off + _HEADER.size]) == hcrc)
        if not header_ok:
            return records, off, False
        start = off + _HEADER_SIZE
        end = start + length
        if end > len(data):
            return records, off, False                  # torn payload
        payload = data[start:end]
        if zlib.crc32(payload) != crc:
            return records, off, False
        records.append(Record(kind, seq, payload, epoch))
        off = end
    return records, off, True


def _segment_files(directory: str) -> List[Tuple[int, str]]:
    if not os.path.isdir(directory):
        return []
    segs = []
    for f in os.listdir(directory):
        if f.startswith("wal-") and f.endswith(".log"):
            try:
                segs.append((int(f[4:-4]), f))
            except ValueError:
                continue
    return sorted(segs)


def _read_segment(path: str) -> Tuple[List[Record], bool]:
    """Decode one segment. Returns (records, clean); clean=False means a
    torn tail was discarded. Raises WalCorruption for mid-log damage."""
    with open(path, "rb") as f:
        data = f.read()
    records, end, clean = decode_records(data)
    if not clean:
        _scan_rest(path, data, end)                     # raises if mid-log
    return records, clean


def _scan_rest(path: str, data: bytes, off: int) -> None:
    """A record at ``off`` failed validation. If any VALID record exists
    after it the damage is mid-log, not a torn tail: refuse to replay."""
    magic_bytes = struct.pack("<I", MAGIC)
    pos = data.find(magic_bytes, off + 1)
    while pos != -1:
        if pos + _HEADER_SIZE <= len(data):
            (hcrc,) = _HCRC.unpack_from(data, pos + _HEADER.size)
            if zlib.crc32(data[pos:pos + _HEADER.size]) == hcrc:
                raise WalCorruption(
                    f"corrupt WAL record mid-log in {path} at byte {off} "
                    f"(valid record follows at byte {pos}); refusing to "
                    f"replay out of order")
        pos = data.find(magic_bytes, pos + 1)


class BatchLog:
    """Append-only, fsync'd, CRC-protected, epoch-fenced operation
    journal."""

    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        segs = _segment_files(directory)
        self.last_seq = 0
        self.last_epoch = 0             # epoch of the last record on disk
        self.bytes_scanned = 0          # bytes parsed by read()/read_tail()
        self._fence_epoch = 0
        for _, fname in segs:
            recs, _ = _read_segment(os.path.join(directory, fname))
            if recs:
                self.last_seq = max(self.last_seq, recs[-1].seq)
                self.last_epoch = max(self.last_epoch, recs[-1].epoch)
        self.epoch = max(1, self.last_epoch)    # writer epoch for appends
        self._fh = None
        self._dirty = False

    # -- epochs -----------------------------------------------------------
    def set_epoch(self, epoch: int) -> None:
        """Adopt a new (promotion) epoch for records appended from now on.
        Epochs only move forward."""
        if epoch < self.epoch:
            raise ValueError(f"epoch moves forward only: "
                             f"{self.epoch} -> {epoch}")
        self.epoch = int(epoch)

    def fence(self, min_epoch: int) -> None:
        """Revoke write access for any writer below ``min_epoch``.  Called
        on the deposed primary's log at promotion: its in-memory handle
        keeps the old epoch, so every later append raises
        :class:`StaleEpochError` — the zombie cannot diverge the history
        replication already shipped."""
        self._fence_epoch = max(self._fence_epoch, int(min_epoch))

    def set_base(self, seq: int, epoch: int = 0) -> None:
        """Declare that history up to ``seq`` lives in a bootstrap
        snapshot rather than in this log (replica bootstrap): the next
        append continues the PRIMARY's numbering at ``seq + 1``. Only
        legal on an empty log — an existing record already fixes the
        numbering."""
        if self.last_seq != 0 or _segment_files(self.directory):
            raise ValueError(f"set_base on non-empty log {self.directory}")
        self.last_seq = int(seq)
        self.last_epoch = int(epoch)
        if epoch:
            self.epoch = max(self.epoch, int(epoch))

    def _check_fence(self, epoch: int) -> None:
        if epoch < self._fence_epoch:
            raise StaleEpochError(
                f"append at epoch {epoch} rejected: log fenced at epoch "
                f">= {self._fence_epoch} ({self.directory})")

    # -- writing ----------------------------------------------------------
    def _file(self):
        if self._fh is None:
            start = self.last_seq + 1
            path = os.path.join(self.directory, f"wal-{start:012d}.log")
            self._fh = open(path, "ab")
        return self._fh

    def _append(self, kind: int, payload: bytes, sync: bool) -> int:
        self._check_fence(self.epoch)
        rec = Record(kind, self.last_seq + 1, payload, self.epoch)
        n_bytes = self._write(rec, sync)
        count("wal.records")
        count("wal.bytes", n_bytes)
        return rec.seq

    def _write(self, rec: Record, sync: bool) -> int:
        """Write ``rec`` at the end of the log; returns its size in
        bytes."""
        fh = self._file()
        data = encode_record(rec)
        fh.write(data)
        fh.flush()
        self._dirty = True
        if sync:
            self.sync()
        self.last_seq = rec.seq
        self.last_epoch = rec.epoch
        return len(data)

    def append_batch(self, kind: int, columns: Dict[str, np.ndarray],
                     valid: np.ndarray, sync: bool = True) -> int:
        """Journal an ingest/retract batch from HOST numpy columns."""
        if kind not in (KIND_INGEST, KIND_RETRACT):
            raise ValueError(f"append_batch kind must be ingest/retract, "
                             f"got {kind}")
        with span("wal.append"):
            return self._append(kind, _encode_batch(columns, valid), sync)

    def append_evict(self, ttl: int, sync: bool = True) -> int:
        return self._append(KIND_EVICT, json.dumps({"ttl": int(ttl)}).encode(),
                            sync)

    def append_record(self, rec: Record, sync: bool = True) -> int:
        """Append an already-sequenced record verbatim — the replica-side
        durability step for shipped records, which must keep the PRIMARY's
        seq and epoch so the follower log stays a byte-exact suffix copy.
        Enforces seq contiguity and epoch monotonicity; a fenced log
        rejects records below the fence."""
        self._check_fence(rec.epoch)
        if rec.seq != self.last_seq + 1:
            raise WalCorruption(
                f"shipped record seq {rec.seq} does not extend local log "
                f"at seq {self.last_seq} ({self.directory})")
        if rec.epoch < self.last_epoch:
            raise StaleEpochError(
                f"shipped record epoch {rec.epoch} below log epoch "
                f"{self.last_epoch} ({self.directory})")
        self._write(rec, sync)
        return rec.seq

    def sync(self) -> None:
        """fsync the open segment — the durability point for every record
        appended since the last sync. Must complete before the commit
        barrier covering those records acknowledges (ZQL008)."""
        if self._fh is not None and self._dirty:
            with span("wal.fsync"):
                os.fsync(self._fh.fileno())
            self._dirty = False
            count("wal.fsyncs")

    def mark(self) -> Tuple[int, bool, int]:
        """Position token for :meth:`rollback` — taken BEFORE an append
        whose covered operation might still be rejected by the engine."""
        size = 0
        if self._fh is not None:
            self._fh.flush()
            size = self._fh.tell()
        return (self.last_seq, self._fh is not None, size)

    def rollback(self, mark: Tuple[int, bool, int]) -> None:
        """Truncate records appended after ``mark``. Used when the
        operation covered by the append FAILED before its commit barrier
        could acknowledge (e.g. a rejected retraction): the record must
        not survive, or replay would re-raise the same failure — the log
        always equals the applied-operation sequence."""
        seq, was_open, size = mark
        if self.last_seq == seq or self._fh is None:
            return
        if not was_open:
            # the rolled-back record opened this segment: drop the file
            path = self._fh.name
            self._fh.close()
            self._fh = None
            os.remove(path)
        else:
            self._fh.truncate(size)
            self._fh.seek(size)
            os.fsync(self._fh.fileno())
        self._dirty = False
        self.last_seq = seq

    def rotate(self) -> None:
        """Close the current segment; the next append opens a new one.
        Called at checkpoint publish so gc() can drop whole segments."""
        self.sync()
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def gc(self, upto_seq: int) -> None:
        """Delete segments whose every record is <= ``upto_seq`` (i.e. is
        covered by a checkpoint that is already DURABLE on disk)."""
        segs = _segment_files(self.directory)
        for i, (start, fname) in enumerate(segs):
            nxt = segs[i + 1][0] if i + 1 < len(segs) else None
            covered = (nxt is not None and nxt - 1 <= upto_seq)
            if covered:
                os.remove(os.path.join(self.directory, fname))

    def close(self) -> None:
        self.rotate()

    # -- reading ----------------------------------------------------------
    def read(self, after_seq: int = 0) -> List[Record]:
        """All records with seq > ``after_seq``, in order. Tolerates a torn
        tail in the LAST segment only; raises WalCorruption otherwise."""
        segs = _segment_files(self.directory)
        out: List[Record] = []
        for i, (_, fname) in enumerate(segs):
            path = os.path.join(self.directory, fname)
            self.bytes_scanned += os.path.getsize(path)
            recs, clean = _read_segment(path)
            if not clean and i + 1 < len(segs):
                raise WalCorruption(
                    f"torn/corrupt records in non-final WAL segment {path}")
            out.extend(recs)
        prev_seq = prev_epoch = None
        for r in out:
            if prev_seq is not None and r.seq <= prev_seq:
                raise WalCorruption(
                    f"non-monotonic WAL sequence {prev_seq} -> {r.seq} in "
                    f"{self.directory}")
            if prev_epoch is not None and r.epoch < prev_epoch:
                raise WalCorruption(
                    f"decreasing WAL epoch {prev_epoch} -> {r.epoch} in "
                    f"{self.directory}")
            prev_seq, prev_epoch = r.seq, r.epoch
        return [r for r in out if r.seq > after_seq]

    def read_tail(self, cursor: TailCursor,
                  max_records: Optional[int] = None,
                  ) -> Tuple[List[Record], TailCursor]:
        """Records appended since ``cursor``, plus the advanced cursor.

        Scans only bytes past the cursor position: the shipping loop and
        degraded replay call this once per tick, so tail reads must cost
        O(new bytes), not O(log).  A torn final record leaves the cursor
        BEFORE the tear — a later call re-reads it once the remaining
        bytes arrive (an in-flight append mid-flush looks exactly like a
        torn tail).  Mid-log corruption raises :class:`WalCorruption`.
        """
        if self._fh is not None:
            self._fh.flush()
        segs = _segment_files(self.directory)
        out: List[Record] = []
        cur = cursor
        for i, (start, fname) in enumerate(segs):
            if start < cur.seg_start:
                continue                        # fully consumed earlier
            if max_records is not None and len(out) >= max_records:
                break
            path = os.path.join(self.directory, fname)
            offset = cur.offset if start == cur.seg_start else 0
            with open(path, "rb") as f:
                f.seek(offset)
                data = f.read()
            self.bytes_scanned += len(data)
            budget = None if max_records is None else max_records - len(out)
            recs, end, clean = decode_records(data, 0, budget)
            if not clean:
                if i + 1 < len(segs):
                    raise WalCorruption(
                        f"torn/corrupt records in non-final WAL segment "
                        f"{path}")
                _scan_rest(path, data, end)     # raises if mid-log damage
            for r in recs:
                if r.seq <= cur.last_seq:
                    continue                    # re-shipped duplicate
                if out and r.seq != out[-1].seq + 1:
                    raise WalCorruption(
                        f"non-contiguous WAL sequence {out[-1].seq} -> "
                        f"{r.seq} in {path}")
                out.append(r)
            last = out[-1].seq if out else cur.last_seq
            cur = TailCursor(start, offset + end, last)
        return out, cur


def read_log(directory: str, after_seq: int = 0) -> List[Record]:
    """Read records from a WAL directory without opening it for append."""
    log = BatchLog.__new__(BatchLog)
    log.directory = directory
    log._fh = None
    log._dirty = False
    log.last_seq = 0
    log.last_epoch = 0
    log.bytes_scanned = 0
    log._fence_epoch = 0
    return BatchLog.read(log, after_seq)
