"""Durable engine wrapper: WAL + async canonical checkpoints + recovery.

:class:`DurableEngine` wraps either online engine
(:class:`repro.core.online.OnlineEngine` /
:class:`~repro.core.online.PartitionedOnlineEngine`) and makes its state
survive process death with BITWISE-exact recovery semantics:

* every ``ingest``/``retract``/``evict`` is journaled to the write-ahead
  batch log (:mod:`repro.core.wal`) from HOST numpy data before it is
  dispatched — the fsync lands before the operation's commit barrier
  acknowledges (per record in synchronous mode; once per barrier in MVCC
  overlap mode), and the journaling itself never touches a device buffer,
  so the overlap ingest hot path stays 1 dispatch / 0 host syncs;
* :meth:`DurableEngine.checkpoint` snapshots the CANONICAL committed
  state (``OnlineEngine.export_canonical`` — layout-free, key-sorted,
  zero-count groups preserved) and hands the host tree to the
  :class:`repro.checkpoint.ckpt.AsyncSaver`: the disk write overlaps
  subsequent ingests, and the one labeled fetch lives here, never on the
  ingest path;
* :meth:`DurableEngine.recover` restores the newest restorable
  checkpoint (CRC-corrupt steps fall back to older ones, then to an
  empty engine + full-log replay) into a FRESH engine of ANY layout —
  replicated checkpoints restore into partitioned engines at different
  ``n_parts``/device counts via the canonical compaction contract — and
  replays the WAL tail in order through the normal ingest path, so the
  recovered engine's queries are bitwise equal to the never-crashed
  twin's;
* during a staged replay (``degraded_replay=True``) the wrapper reports
  ``degraded=True``: :class:`repro.core.serving.ServingEngine` keeps
  answering from the restored snapshot with results tagged degraded
  until :meth:`replay_step` drains the queue.

Fault-injection hooks (:meth:`_point`) let ``tests/fault_injection.py``
crash the wrapper deterministically at every interesting boundary.
"""
from __future__ import annotations

import json
import os
import zipfile
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.checkpoint import ckpt as ckpt_mod
from repro.core import wal as wal_mod
from repro.core.wal import KIND_EVICT, KIND_INGEST, KIND_RETRACT
from repro.data.columnar import Table
from repro.launch.trace import span

#: contract-lint scoping: WAL-ordering rule ZQL008 applies here.
__engine_owned__ = True


def _pack_snapshot(snap: dict, wal_seq: int) -> dict:
    """Flatten an ``export_canonical`` snapshot into a checkpointable
    pytree of numpy arrays plus a JSON meta blob (stored as a uint8
    array so it rides the same CRC-validated shard)."""
    tree: Dict[str, Any] = {}
    names = list(snap["views"])
    for i, name in enumerate(names):
        v = snap["views"][name]
        ent = {"hi": v["hi"], "lo": v["lo"], "touch": v["touch"],
               "stats": dict(v["stats"])}
        if "keep" in v:
            ent["keep"] = v["keep"]
        tree[f"v{i}"] = ent
    meta = {"wal_seq": int(wal_seq), "view_names": names,
            "fingerprint": snap["fingerprint"],
            "scalars": {k: int(x) for k, x in snap["scalars"].items()},
            "cache": [[t, None if sub is None else
                       [[d, list(bs)] for d, bs in sub]]
                      for t, sub, _ in snap.get("cache", ())],
            "stream": None, "rows": "rows" in snap}
    if "stream" in snap:
        s = snap["stream"]
        meta["stream"] = {"n_batches": int(s["n_batches"]),
                          "capacity": int(s["capacity"])}
        tree["stream"] = {"res": dict(s["res"]), "pri": s["pri"],
                          "n": s["n"], "sums": dict(s["sums"]),
                          "sumsqs": dict(s["sumsqs"])}
    if "rows" in snap:
        tree["rows"] = {"cols": dict(snap["rows"]["cols"]),
                        "valid": snap["rows"]["valid"]}
    for i, (_, _, est) in enumerate(snap.get("cache", ())):
        tree[f"cache{i}"] = {k: np.asarray(x) for k, x in est.items()}
    tree["meta"] = np.frombuffer(
        json.dumps(meta, sort_keys=True).encode(), np.uint8).copy()
    _assert_clean_keys(tree)
    return tree


def _assert_clean_keys(tree, path: str = "") -> None:
    """The npz shard writer folds "/" into "__" and back; a stat/column
    name containing "__" would corrupt that round trip, so refuse it."""
    if not isinstance(tree, dict):
        return
    for k, v in tree.items():
        if "__" in k or "/" in k:
            raise ValueError(
                f"snapshot key {path + k!r} contains '__' or '/' — "
                f"unsupported by the checkpoint shard layout")
        _assert_clean_keys(v, path + k + ".")


def _flatten_tree(tree) -> Dict[str, np.ndarray]:
    """Flatten a packed snapshot to the flat ``a/b/c``-keyed array dict a
    checkpoint restore yields, so in-memory bootstrap hand-off and
    on-disk restore share one :func:`_unpack_snapshot` path."""
    return {key: np.asarray(leaf)
            for key, leaf in ckpt_mod._tree_paths(tree)}


def _unflatten(arrays: Dict[str, np.ndarray], prefix: str) -> dict:
    """Nested dict of every flat-key array under ``prefix/``."""
    out: dict = {}
    pre = prefix + "/"
    for key, a in arrays.items():
        if not key.startswith(pre):
            continue
        parts = key[len(pre):].split("/")
        d = out
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = a
    return out


def _unpack_snapshot(arrays: Dict[str, np.ndarray]) -> Tuple[dict, int]:
    """Inverse of :func:`_pack_snapshot`: (canonical snapshot, wal_seq)."""
    meta = json.loads(bytes(arrays["meta"]).decode())
    views = {}
    for i, name in enumerate(meta["view_names"]):
        ent = _unflatten(arrays, f"v{i}")
        views[name] = ent
    snap = dict(views=views, scalars=meta["scalars"],
                fingerprint=meta["fingerprint"])
    if meta["stream"] is not None:
        s = _unflatten(arrays, "stream")
        s.update(meta["stream"])
        snap["stream"] = s
    if meta["rows"]:
        snap["rows"] = _unflatten(arrays, "rows")
    cache = []
    for i, (t, sub) in enumerate(meta["cache"]):
        frozen = (None if sub is None else
                  tuple((d, tuple(int(b) for b in bs)) for d, bs in sub))
        est = {k: a[()] if getattr(a, "ndim", 0) == 0 else a
               for k, a in _unflatten(arrays, f"cache{i}").items()}
        est["state_version"] = int(est["state_version"])
        cache.append((t, frozen, est))
    snap["cache"] = tuple(cache)
    return snap, int(meta["wal_seq"])


class DurableEngine:
    """WAL + checkpoint/restore wrapper around one online engine.

    Queries and attribute access proxy to the wrapped engine, so a
    ``ServingEngine(DurableEngine(engine, dir))`` serves exactly like
    ``ServingEngine(engine)`` — plus durability and degraded-mode tags.

    directory: holds ``wal/`` (segment files) and ``ckpt/`` (steps).
    saver:     an :class:`~repro.checkpoint.ckpt.AsyncSaver` (own retry
               policy) — a fresh default one if None.
    injector:  optional fault injector with a ``fire(point)`` method
               (``tests/fault_injection.py``); production passes None.
    """

    def __init__(self, engine, directory: str, saver=None, injector=None,
                 keep_last: int = 3, epoch: Optional[int] = None):
        self.engine = engine
        self.directory = directory
        self.wal = wal_mod.BatchLog(os.path.join(directory, "wal"))
        if epoch is not None:
            self.wal.set_epoch(epoch)
        self.ckpt_dir = os.path.join(directory, "ckpt")
        self.saver = saver if saver is not None else ckpt_mod.AsyncSaver()
        self.injector = injector
        self.keep_last = int(keep_last)
        self._ckpt_step = ckpt_mod.latest_step(self.ckpt_dir) or 0
        self._pending_ckpt: Optional[Tuple[int, int]] = None
        self._durable_seq = 0
        self._replay_cursor = wal_mod.TailCursor(last_seq=self.wal.last_seq)
        self.degraded = False

    @property
    def epoch(self) -> int:
        """The primary term stamped into appended WAL records."""
        return self.wal.epoch

    # ------------------------------------------------------ fault points
    def _point(self, name: str) -> None:
        if self.injector is not None:
            self.injector.fire(name)

    def _guard_degraded(self) -> None:
        if self.degraded:
            raise RuntimeError(
                "engine is replaying its WAL (degraded mode): drain "
                "replay_step() before ingesting new batches")

    # ---------------------------------------------------------- mutation
    def ingest(self, batch: Table, retract: bool = False):
        """Validate, journal (WAL append; fsync per record in synchronous
        mode), then dispatch through the wrapped engine. The journal is
        written from host numpy column data BEFORE any device work, so
        overlap-mode steady state stays 1 dispatch / 0 host syncs."""
        with span("durable.ingest", rows=batch.nrows,
                  retract=retract) as sp:
            self._guard_degraded()
            with span("engine.validate"):
                self.engine.validate_batch(batch, retract=retract)
            cols = {c: np.asarray(batch.columns[c])
                    for c in self.engine._row_cols}
            valid = np.asarray(batch.valid)
            overlap = bool(getattr(self.engine, "overlap", False))
            mark = self.wal.mark()
            self._point("wal.pre-append")
            seq = self.wal.append_batch(
                KIND_RETRACT if retract else KIND_INGEST, cols, valid,
                sync=not overlap)
            sp.set(seq=seq)
            self._point("wal.post-append")
            if overlap and (retract or len(self.engine._inflight)
                            >= self.engine.max_inflight):
                # the engine will hit an internal commit barrier inside
                # this ingest: everything journaled so far must be on disk
                # first
                self.wal.sync()
            try:
                rep = self.engine.ingest(batch, retract=retract)
            except ValueError:
                # the engine rejected the operation eagerly (config guard,
                # bad retraction) — its record must not survive, or replay
                # would re-raise the same failure mid-recovery
                self.wal.rollback(mark)
                raise
            self._point("ingest.post-dispatch")
            return rep

    def evict(self, ttl: int):
        self._guard_degraded()
        self._point("wal.pre-append")
        self.wal.append_evict(ttl, sync=True)   # evict is a commit barrier
        self._point("wal.post-append")
        return self.engine.evict(ttl)

    def commit(self):
        """MVCC commit barrier: fsync the journal FIRST, then commit —
        no batch is ever acknowledged before its WAL record is durable
        (lint rule ZQL008 checks this ordering statically)."""
        with span("durable.commit", seq=self.wal.last_seq):
            self.wal.sync()
            self._point("commit.pre")
            with span("engine.commit"):
                out = self.engine.commit()
            self._point("commit.post")
            return out

    # -------------------------------------------------------- checkpoint
    def checkpoint(self, wait: bool = False) -> int:
        """Snapshot the committed canonical state asynchronously.

        Synchronous part: fsync + commit (a checkpoint is a commit
        barrier), ONE labeled host fetch of the committed buffers
        (``export_canonical``), segment rotation. The disk write runs on
        the saver's background thread and overlaps subsequent ingests;
        WAL segments covered by the snapshot are garbage-collected only
        once the NEXT checkpoint call observes the save published (a
        checkpoint that never hit disk keeps its log tail replayable).
        Returns the checkpoint step id."""
        self._guard_degraded()
        self.wal.sync()
        self._finish_pending_ckpt()
        snap = self.engine.export_canonical()    # commits in-flight chain
        wal_seq = self.wal.last_seq
        self.wal.rotate()
        self._ckpt_step += 1
        tree = _pack_snapshot(snap, wal_seq)
        self._point("ckpt.pre-save")
        self.saver.save(tree, self._ckpt_step, self.ckpt_dir,
                        keep_last=self.keep_last)
        self._pending_ckpt = (self._ckpt_step, wal_seq)
        if wait:
            self._finish_pending_ckpt()
        return self._ckpt_step

    def _finish_pending_ckpt(self) -> None:
        if self._pending_ckpt is None:
            return
        step, seq = self._pending_ckpt
        self._pending_ckpt = None
        self.saver.wait()                        # re-raises a failed save
        self._durable_seq = seq
        self.wal.gc(self._durable_seq)

    def export_bootstrap(self) -> Dict[str, np.ndarray]:
        """Replica bootstrap snapshot: the committed canonical state plus
        the WAL seq it covers, flattened to the flat-key array dict a
        checkpoint restore yields.  A follower installs it through the
        identical ``_unpack_snapshot`` / ``install_canonical`` path used
        by crash recovery, so bootstrap inherits the cross-layout bitwise
        restore guarantee; shipping then resumes from the covered seq."""
        self._guard_degraded()
        self.wal.sync()
        snap = self.engine.export_canonical()    # commits in-flight chain
        return _flatten_tree(_pack_snapshot(snap, self.wal.last_seq))

    def close(self) -> None:
        if self._pending_ckpt is not None:
            self._finish_pending_ckpt()
        self.wal.close()

    # ---------------------------------------------------------- recovery
    @classmethod
    def recover(cls, engine, directory: str, degraded_replay: bool = False,
                **kw) -> "DurableEngine":
        """Rebuild from disk into ``engine`` (freshly constructed, ANY
        layout with the same schema fingerprint).

        Restores the newest checkpoint whose CRC validates — corrupt
        steps fall back to older ones, and with no restorable checkpoint
        the whole WAL replays into the empty engine — then replays every
        WAL record with seq > the snapshot's ``wal_seq`` in order through
        the normal ingest path. With ``degraded_replay=True`` the tail is
        queued instead: the wrapper serves from the restored snapshot
        with ``degraded=True`` until :meth:`replay_step` drains it."""
        d = cls(engine, directory, **kw)
        after_seq = 0
        step = ckpt_mod.latest_step(d.ckpt_dir)
        while step is not None:
            try:
                _, arrays = ckpt_mod.restore(d.ckpt_dir, step=step)
                snap, after_seq = _unpack_snapshot(arrays)
                engine.install_canonical(snap)
                break
            except ValueError as e:
                if "schema mismatch" in str(e):
                    raise        # wrong engine config, not disk damage
                older = [s for s in _all_steps(d.ckpt_dir) if s < step]
                step = max(older) if older else None
                after_seq = 0
            except (IOError, OSError, KeyError, zipfile.BadZipFile):
                # CRC-corrupt or torn step: fall back to an older one
                # (a flipped byte inside an npz surfaces as BadZipFile
                # before our own CRC validation even runs)
                older = [s for s in _all_steps(d.ckpt_dir) if s < step]
                step = max(older) if older else None
                after_seq = 0
        if degraded_replay and d.wal.last_seq > after_seq:
            # stage the tail behind a cursor: replay_step() pulls records
            # incrementally (O(new bytes) per pull), serving stays up
            d._replay_cursor = wal_mod.TailCursor(last_seq=after_seq)
            d.degraded = True
        else:
            records = d.wal.read(after_seq=after_seq)
            d._apply_records(records)
            d.engine.commit()
            d._replay_cursor = wal_mod.TailCursor(last_seq=d.wal.last_seq)
        return d

    def _apply_records(self, records) -> None:
        for rec in records:
            self._apply_one(rec)

    def _apply_one(self, rec: wal_mod.Record) -> None:
        if rec.kind == KIND_EVICT:
            self.engine.evict(rec.evict_ttl())
            return
        cols, valid = rec.batch()
        self.engine.ingest(Table.from_numpy(cols, valid),
                           retract=rec.kind == KIND_RETRACT)

    def replay_step(self, n: int = 1) -> int:
        """Apply up to ``n`` staged WAL records (degraded-mode replay);
        returns how many remain. Leaves degraded mode — and commits —
        when the tail drains. Records are pulled through the persistent
        tail cursor, so each step scans only the bytes it consumes."""
        if self.degraded:
            records, self._replay_cursor = self.wal.read_tail(
                self._replay_cursor, max_records=n)
            self._apply_records(records)
            if self._replay_cursor.last_seq >= self.wal.last_seq:
                self.engine.commit()
                self.degraded = False
        return max(0, self.wal.last_seq - self._replay_cursor.last_seq)

    # ----------------------------------------------------------- queries
    # explicit proxies for the serving/query surface (ServingEngine and
    # the tests talk to the wrapper exactly like to a bare engine) ...
    def ate(self, *a, **kw):
        return self.engine.ate(*a, **kw)

    def ate_batch(self, specs):
        return self.engine.ate_batch(specs)

    def cached_estimate(self, *a, **kw):
        return self.engine.cached_estimate(*a, **kw)

    def matched_rows(self, *a, **kw):
        return self.engine.matched_rows(*a, **kw)

    def snapshot_version(self) -> int:
        return self.engine.snapshot_version()

    # ... and a fallback for everything else (treatments, specs, stats()).
    def __getattr__(self, name: str):
        return getattr(self.engine, name)


def _all_steps(directory: str) -> List[int]:
    if not os.path.isdir(directory):
        return []
    out = []
    for d in os.listdir(directory):
        m = ckpt_mod._STEP_RE.match(d)
        if m:
            out.append(int(m.group(1)))
    return sorted(out)
