"""WAL-shipping replication: follower reads, bounded staleness, failover.

One primary :class:`~repro.core.durability.DurableEngine` accepts every
write; N follower :class:`Replica` nodes mirror it by LOG, not by state:

  bootstrap   the primary's canonical cross-layout snapshot
              (``DurableEngine.export_bootstrap``) installs into a fresh
              engine of ANY layout via the same
              ``install_canonical`` path crash recovery uses — the
              follower starts bitwise equal to the primary at the
              snapshot's covered WAL seq;
  ship        :meth:`ReplicatedEngine.ship` streams the primary's WAL
              tail as raw record bytes (one :class:`~repro.core.wal.
              TailCursor` per follower — each tick scans only NEW bytes),
              pure host-side work that never touches a device buffer, so
              primary steady-state ingest stays 1 dispatch / 0 host
              syncs with shipping active;
  verify      a follower CRC-decodes and epoch/contiguity-checks every
              shipped record (:func:`verify_records`) BEFORE journaling
              it to its own log copy and BEFORE applying it (lint rule
              ZQL009 enforces the order statically) — a torn ship
              truncates to the valid prefix and is simply re-shipped;
  apply       verified records replay through the follower engine's
              NORMAL ingest path. Because estimates are deterministic
              functions of canonical group content alone, a replica at
              applied-seq s is bitwise identical to the primary at seq s
              — the lagging-oracle property the differential tests pin.

Bounded-staleness reads: every follower knows the primary's last seq and
its own applied seq; :class:`ReplicationRouter` spreads query waves round
robin across followers within ``max_lag_seqs`` / ``max_lag_secs`` (falling
back to the primary when none qualifies), and every
:class:`~repro.core.serving.ServedQuery` carries ``replica_lag``.

Failover: writes beat a :class:`~repro.runtime.fault_tolerance.
HeartbeatMonitor`; when the primary misses its timeout the monitor plans
a promotion (most durable WAL seq wins, ties to the lowest node id).
Promotion is an epoch CAS: the cluster epoch bumps exactly once — a
second promoter holding the same observed epoch gets
:class:`SplitBrainError` — and the deposed primary's log is FENCED at the
new epoch (:meth:`~repro.core.wal.BatchLog.fence`), so a zombie that
wakes up later has every append rejected with
:class:`~repro.core.wal.StaleEpochError` before any state mutates. The
candidate drains its received-but-unapplied tail, then its directory
(bootstrap checkpoint + shipped log — exactly a ``DurableEngine`` layout)
is re-opened as the new primary at the new epoch. Acknowledged records
the dead primary never shipped are lost, exactly like any asynchronous
log-shipping database: the promoted node equals a never-crashed twin *at
its own applied seq* — never a wrong answer, possibly an older one.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence

from repro.checkpoint import ckpt as ckpt_mod
from repro.core import wal as wal_mod
from repro.core.durability import DurableEngine, _unpack_snapshot
from repro.core.serving import ServedQuery, ServingEngine
from repro.core.wal import StaleEpochError, TailCursor
from repro.data.columnar import Table
from repro.runtime.fault_tolerance import HeartbeatMonitor

#: contract-lint scoping: dispatch/WAL/ship-verify rules apply here.
__engine_owned__ = True


class ReplicationError(RuntimeError):
    """Replication-tier protocol violation."""


class SplitBrainError(ReplicationError):
    """A promotion CAS failed: another node already took the epoch the
    promoter observed — exactly one promotion per epoch may win."""


class PrimaryDownError(ReplicationError):
    """A write arrived while the primary is dead and not yet replaced."""


def verify_records(records: Sequence[wal_mod.Record], max_epoch: int,
                   after_seq: int) -> List[wal_mod.Record]:
    """Gate shipped records before they are journaled or applied.

    Drops records at or below ``after_seq`` (idempotent re-ship after a
    torn delivery), then enforces: seqs contiguous from ``after_seq``,
    epochs non-decreasing, and no epoch above ``max_epoch`` (a record
    from the future means the channel lied about its term). CRC validity
    is already guaranteed by :func:`repro.core.wal.decode_records` — this
    is the second half of the verify-before-apply contract (ZQL009).
    """
    fresh = [r for r in records if r.seq > after_seq]
    prev_seq, prev_epoch = after_seq, 0
    for r in fresh:
        if r.seq != prev_seq + 1:
            raise wal_mod.WalCorruption(
                f"shipped records jump seq {prev_seq} -> {r.seq}; a gap "
                f"cannot be applied without breaking replay bit-identity")
        if r.epoch < prev_epoch:
            raise wal_mod.WalCorruption(
                f"shipped records decrease epoch {prev_epoch} -> "
                f"{r.epoch}")
        if r.epoch > max_epoch:
            raise StaleEpochError(
                f"shipped record at epoch {r.epoch} exceeds channel "
                f"epoch {max_epoch}")
        prev_seq, prev_epoch = r.seq, r.epoch
    return fresh


class Replica:
    """One follower node: a local engine (any layout), a durable copy of
    the shipped log, and apply progress.

    Directory layout is EXACTLY a :class:`DurableEngine`'s (``ckpt/``
    holds the bootstrap snapshot, ``wal/`` the shipped records with the
    primary's seq/epoch preserved), so a crashed follower rebuilds with
    the standard recovery path (:meth:`Replica.recover`) and a promoted
    follower's directory simply re-opens as the new primary's."""

    def __init__(self, engine, directory: str, node_id: int, *,
                 clock: Callable[[], float] = time.monotonic,
                 injector=None):
        self.engine = engine
        self.directory = directory
        self.node_id = node_id
        self.clock = clock
        self.injector = injector
        os.makedirs(directory, exist_ok=True)
        self.wal = wal_mod.BatchLog(os.path.join(directory, "wal"))
        self.ckpt_dir = os.path.join(directory, "ckpt")
        meta = self._load_meta()
        if self.wal.last_seq == 0 and meta.get("bootstrap_seq", 0) > 0:
            self.wal.set_base(meta["bootstrap_seq"],
                              meta.get("bootstrap_epoch", 0))
        #: cluster epoch as this node last learned it
        self.epoch = max(1, self.wal.last_epoch)
        self.applied_seq = 0
        self.primary_seq = 0        # primary's durable seq, as last shipped
        self.shipped_at = clock()   # last successful ship contact
        self.alive = True
        self._pending: List[wal_mod.Record] = []
        self.n_received = 0
        self.n_applied = 0
        self.n_stale_rejects = 0
        self.n_torn_ships = 0

    # -------------------------------------------------------- persistence
    def _meta_path(self) -> str:
        return os.path.join(self.directory, "replica.json")

    def _load_meta(self) -> dict:
        try:
            with open(self._meta_path()) as f:
                return json.load(f)
        except (IOError, OSError, ValueError):
            return {}

    def _save_meta(self, meta: dict) -> None:
        with open(self._meta_path(), "w") as f:
            json.dump(meta, f, sort_keys=True)

    def _point(self, name: str) -> None:
        if self.injector is not None:
            self.injector.fire(name)

    # ---------------------------------------------------------- bootstrap
    def bootstrap(self, arrays: Dict) -> int:
        """Install a primary bootstrap snapshot
        (``DurableEngine.export_bootstrap``) into the fresh local engine
        and persist it as checkpoint step 1, so this node can later
        recover — or be promoted — from its own directory alone.
        Returns the covered WAL seq."""
        snap, seq = _unpack_snapshot(arrays)
        epoch = 0
        if seq > 0:
            self.engine.install_canonical(snap)
            ckpt_mod.save(dict(arrays), 1, self.ckpt_dir, keep_last=2)
            epoch = self.wal.epoch
            self.wal.set_base(seq, epoch)
        self._save_meta({"node_id": self.node_id, "bootstrap_seq": seq,
                         "bootstrap_epoch": epoch})
        self.applied_seq = seq
        return seq

    @classmethod
    def recover(cls, engine, directory: str, node_id: int, *,
                clock: Callable[[], float] = time.monotonic,
                injector=None) -> "Replica":
        """Rebuild a crashed follower from its own directory: restore the
        bootstrap checkpoint, replay the locally journaled shipped log
        through the normal ingest path (both CRC-gated), re-open as a
        caught-up replica at applied = durable seq."""
        d = DurableEngine.recover(engine, directory)
        d.close()
        r = cls(engine, directory, node_id, clock=clock, injector=injector)
        r.applied_seq = r.wal.last_seq
        return r

    # -------------------------------------------------------------- ship
    def receive(self, data: bytes, ship_epoch: int) -> int:
        """Accept one shipped byte span: CRC-decode, verify epoch and
        contiguity, journal the fresh records to the local log (fsync),
        queue them for apply. Returns how many records were accepted.

        A span from a FENCED (stale-epoch) shipper is rejected outright —
        the defense-in-depth twin of the primary-side log fence. A torn
        span (truncated/corrupt suffix) accepts the valid prefix; the
        shipper re-sends the rest next tick."""
        if not self.alive:
            raise ReplicationError(f"replica {self.node_id} is down")
        if ship_epoch < self.epoch:
            self.n_stale_rejects += 1
            raise StaleEpochError(
                f"ship at epoch {ship_epoch} rejected by replica "
                f"{self.node_id} at epoch {self.epoch}")
        records, _, clean = wal_mod.decode_records(data)
        if not clean:
            self.n_torn_ships += 1
        fresh = verify_records(records, max_epoch=ship_epoch,
                               after_seq=self.wal.last_seq)
        for rec in fresh:
            self.wal.append_record(rec, sync=False)
        self.wal.sync()
        self.epoch = max(self.epoch, ship_epoch)
        self._pending.extend(fresh)
        self.primary_seq = max(self.primary_seq, self.wal.last_seq)
        self.shipped_at = self.clock()
        self.n_received += len(fresh)
        return len(fresh)

    # ------------------------------------------------------------- apply
    def apply_step(self, n: Optional[int] = None) -> int:
        """Apply up to ``n`` received records (all, if None) through the
        normal ingest path and commit; returns how many remain queued.
        Records are re-verified against apply progress at this boundary —
        the journal fsync'd them, but epoch/contiguity must still hold
        from ``applied_seq`` (ZQL009)."""
        take = self._pending if n is None else self._pending[:n]
        batch = verify_records(take, max_epoch=self.epoch,
                               after_seq=self.applied_seq)
        done = 0
        try:
            for rec in batch:
                self._point("replica.pre-apply")
                self._apply_one(rec)
                self.applied_seq = rec.seq
                done += 1
                self._point("replica.post-apply")
        finally:
            # trim by seq, not count: a crash mid-batch must leave exactly
            # the unapplied suffix queued for the retry
            self._pending = [r for r in self._pending
                             if r.seq > self.applied_seq]
            if done:
                self.engine.commit()
                self.n_applied += done
        return len(self._pending)

    def _apply_one(self, rec: wal_mod.Record) -> None:
        if rec.kind == wal_mod.KIND_EVICT:
            self.engine.evict(rec.evict_ttl())
            return
        cols, valid = rec.batch()
        self.engine.ingest(Table.from_numpy(cols, valid),
                           retract=rec.kind == wal_mod.KIND_RETRACT)

    def drain(self) -> None:
        """Apply everything received — the promotion prerequisite."""
        self.apply_step(None)

    # ----------------------------------------------------------- queries
    @property
    def replica_lag(self) -> int:
        """How many primary WAL seqs this node's applied state trails —
        the staleness bound the router enforces and every ServedQuery
        reports."""
        return max(0, self.primary_seq - self.applied_seq)

    def fresh(self, now: float, max_lag_seqs: int,
              max_lag_secs: float) -> bool:
        """Within the bounded-staleness envelope: close enough by seqs
        AND heard from the primary recently enough. A partitioned
        follower whose lag *looks* small still goes stale by TIME —
        lag is computed from the last ship, which may itself be old."""
        return (self.replica_lag <= max_lag_seqs
                and (now - self.shipped_at) <= max_lag_secs)

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

    def __getattr__(self, name: str):
        return getattr(self.engine, name)


class ReplicationRouter:
    """Spreads read waves across healthy, staleness-bounded followers.

    Each :meth:`step` picks ONE target node — the next follower (round
    robin) whose :meth:`Replica.fresh` holds, else the primary
    (``n_primary_waves`` counts the fallback) — and drains the queued
    specs through that node's :class:`ServingEngine`, so every wave keeps
    the one-version-per-wave invariant on a single snapshot. Results are
    keyed by router ticket id; every answer carries ``replica_lag``."""

    def __init__(self, cluster: "ReplicatedEngine", n_slots: int = 64,
                 max_queue: Optional[int] = None):
        self.cluster = cluster
        self.n_slots = int(n_slots)
        self.max_queue = max_queue
        self._serving: Dict[int, ServingEngine] = {}
        self._queue: collections.deque = collections.deque()
        self._next_rid = 0
        self._rr = 0
        self.n_replica_waves = 0
        self.n_primary_waves = 0

    def submit(self, spec, deadline: Optional[float] = None) -> int:
        qid = self._next_rid
        self._next_rid += 1
        self._queue.append((qid, spec, deadline))
        return qid

    def pending(self) -> int:
        return len(self._queue)

    @property
    def n_expired(self) -> int:
        return sum(s.n_expired for s in self._serving.values())

    def _serving_for(self, node, node_id: int) -> ServingEngine:
        s = self._serving.get(node_id)
        if s is None or s.engine is not node:
            # first contact, or the node was promoted/recovered since
            s = ServingEngine(node, n_slots=self.n_slots,
                              max_queue=self.max_queue,
                              clock=self.cluster.clock)
            self._serving[node_id] = s
        return s

    def _pick(self):
        now = self.cluster.clock()
        ids = sorted(self.cluster.replicas)
        for k in range(len(ids)):
            nid = ids[(self._rr + k) % len(ids)]
            rep = self.cluster.replicas[nid]
            if rep.alive and rep.fresh(now, self.cluster.max_lag_seqs,
                                       self.cluster.max_lag_secs):
                self._rr = (self._rr + k + 1) % len(ids)
                return nid, rep, True
        return self.cluster.primary_id, self.cluster.primary, False

    def step(self) -> Dict[int, ServedQuery]:
        """Route and serve everything currently queued on one node."""
        if not self._queue:
            return {}
        nid, node, is_replica = self._pick()
        serving = self._serving_for(node, nid)
        tickets: Dict[int, int] = {}
        while self._queue:
            rid, spec, deadline = self._queue.popleft()
            tickets[serving.submit(spec, deadline=deadline)] = rid
        out: Dict[int, ServedQuery] = {}
        while serving.pending():
            if is_replica:
                self.n_replica_waves += 1
            else:
                self.n_primary_waves += 1
            for qid, sq in serving.step().items():
                rid = tickets.pop(qid)
                out[rid] = dataclasses.replace(sq, qid=rid)
        return out

    def serve(self, specs: Sequence,
              deadline: Optional[float] = None) -> Dict[int, ServedQuery]:
        """Submit then drain; returns results keyed by ticket id in
        submit order (expired/shed queries are simply absent)."""
        [self.submit(s, deadline=deadline) for s in specs]
        out: Dict[int, ServedQuery] = {}
        while self.pending():
            out.update(self.step())
        return out


class ReplicatedEngine:
    """Primary + follower tier with WAL shipping and automatic failover.

    ``engines[0]`` becomes the primary (wrapped in a
    :class:`DurableEngine` under ``directory/node0``); each further
    engine — freshly constructed, ANY layout with the same schema
    fingerprint — becomes a follower bootstrapped from the primary's
    canonical snapshot. Writes go through the primary exactly as on an
    unreplicated :class:`DurableEngine` (same journaling, same hot-path
    guarantees) and additionally beat the heartbeat monitor;
    :meth:`ship` / :meth:`apply_all` / :meth:`tick` advance the
    followers; :attr:`router` serves bounded-staleness reads.

    ``clock`` is injectable: tests drive heartbeat timeouts and staleness
    deterministically. ``ship_filter`` (a ``(node_id, bytes) -> bytes``
    hook) lets the chaos harness tear shipped spans in flight."""

    def __init__(self, engines: Sequence, directory: str, *,
                 max_lag_seqs: int = 64, max_lag_secs: float = 5.0,
                 heartbeat_timeout_s: float = 1.0,
                 clock: Callable[[], float] = time.monotonic,
                 injector=None, saver=None, n_slots: int = 64,
                 max_queue: Optional[int] = None):
        engines = list(engines)
        if not engines:
            raise ValueError("ReplicatedEngine needs at least one engine")
        self.directory = directory
        self.clock = clock
        self.injector = injector
        self.saver = saver
        self.max_lag_seqs = int(max_lag_seqs)
        self.max_lag_secs = float(max_lag_secs)
        self.epoch = 1
        self.primary_id = 0
        self.primary = DurableEngine(
            engines[0], os.path.join(directory, "node0"), saver=saver,
            injector=injector, epoch=self.epoch)
        self._primary_dead = False
        self.monitor = HeartbeatMonitor(len(engines),
                                        timeout_s=heartbeat_timeout_s,
                                        clock=clock)
        self.monitor.beat(0, self.primary.wal.last_seq)
        self.replicas: Dict[int, Replica] = {}
        self._cursors: Dict[int, TailCursor] = {}
        for i, eng in enumerate(engines[1:], start=1):
            self._attach_replica(i, eng)
        self.ship_filter: Optional[Callable[[int, bytes], bytes]] = None
        self.n_failovers = 0
        self.router = ReplicationRouter(self, n_slots=n_slots,
                                        max_queue=max_queue)

    # ------------------------------------------------------------ members
    def _attach_replica(self, node_id: int, engine) -> Replica:
        rep = Replica(engine, os.path.join(self.directory,
                                           f"node{node_id}"),
                      node_id, clock=self.clock, injector=self.injector)
        rep.bootstrap(self.primary.export_bootstrap())
        rep.primary_seq = self.primary.wal.last_seq
        self.replicas[node_id] = rep
        self._cursors[node_id] = TailCursor(last_seq=rep.wal.last_seq)
        self.monitor.beat(node_id, rep.wal.last_seq)
        return rep

    def reattach_replica(self, node_id: int, engine) -> Replica:
        """Rejoin a crashed follower: rebuild it from its OWN directory
        (bootstrap checkpoint + locally journaled shipped log) into the
        given fresh engine, then resume shipping from its durable seq."""
        rep = Replica.recover(engine,
                              os.path.join(self.directory,
                                           f"node{node_id}"),
                              node_id, clock=self.clock,
                              injector=self.injector)
        rep.epoch = max(rep.epoch, self.epoch)
        rep.primary_seq = self.primary.wal.last_seq
        self.replicas[node_id] = rep
        self._cursors[node_id] = TailCursor(last_seq=rep.wal.last_seq)
        self.monitor.beat(node_id, rep.wal.last_seq)
        return rep

    def _point(self, name: str) -> None:
        if self.injector is not None:
            self.injector.fire(name)

    def _guard_primary(self) -> None:
        if self._primary_dead:
            raise PrimaryDownError(
                "primary is down and no follower has been promoted yet")

    # -------------------------------------------------------- write path
    # writes proxy to the primary DurableEngine unchanged (journal ->
    # dispatch -> commit-barrier fsync ordering and the hot-path
    # guarantees are its contract), plus a heartbeat per operation.
    def ingest(self, batch: Table, retract: bool = False):
        self._guard_primary()
        rep = self.primary.ingest(batch, retract=retract)
        self.monitor.beat(self.primary_id, self.primary.wal.last_seq)
        return rep

    def evict(self, ttl: int):
        self._guard_primary()
        out = self.primary.evict(ttl)
        self.monitor.beat(self.primary_id, self.primary.wal.last_seq)
        return out

    def commit(self):
        self._guard_primary()
        out = self.primary.commit()
        self.monitor.beat(self.primary_id, self.primary.wal.last_seq)
        return out

    def checkpoint(self, wait: bool = False) -> int:
        self._guard_primary()
        step = self.primary.checkpoint(wait=wait)
        self.monitor.beat(self.primary_id, self.primary.wal.last_seq)
        return step

    # -------------------------------------------------------------- ship
    def ship(self) -> int:
        """Stream the primary's WAL tail to every live follower; host
        bytes only, zero dispatches. Each follower has its own tail
        cursor, advanced past exactly what that follower durably
        accepted — a torn delivery re-ships the suffix next tick.
        Returns total records accepted across followers."""
        self._guard_primary()
        total = 0
        last = self.primary.wal.last_seq
        for nid in sorted(self.replicas):
            rep = self.replicas[nid]
            if not rep.alive:
                continue
            records, moved = self.primary.wal.read_tail(self._cursors[nid])
            data = wal_mod.encode_records(records)
            if self.ship_filter is not None:
                data = self.ship_filter(nid, data)
            self._point("ship.pre-send")
            n = rep.receive(data, self.epoch)
            self._point("ship.post-send")
            if not records or rep.wal.last_seq >= records[-1].seq:
                self._cursors[nid] = moved
            else:
                # partial acceptance (torn span): keep the byte position,
                # bump the dedup floor to what landed durably
                cur = self._cursors[nid]
                self._cursors[nid] = TailCursor(
                    cur.seg_start, cur.offset,
                    max(cur.last_seq, rep.wal.last_seq))
            rep.primary_seq = last
            self.monitor.beat(nid, rep.wal.last_seq)
            total += n
        return total

    def apply_all(self, n: Optional[int] = None) -> int:
        """Advance every live follower's apply by up to ``n`` records
        (all, if None); returns total records still pending."""
        left = 0
        for nid in sorted(self.replicas):
            rep = self.replicas[nid]
            if not rep.alive:
                continue
            left += rep.apply_step(n)
            self.monitor.beat(nid, rep.wal.last_seq)
        return left

    def tick(self) -> Optional[int]:
        """One replication heartbeat: if the monitor declares the primary
        dead, fail over (returns the promoted node id); otherwise ship
        and apply. Liveness and promotion choice come from
        ``HeartbeatMonitor.plan`` — beats carry durable WAL seqs, so the
        plan's candidate IS the most-caught-up live follower."""
        for nid, rep in self.replicas.items():
            if rep.alive:        # live followers beat on every tick
                self.monitor.beat(nid, rep.wal.last_seq)
        plan = self.monitor.plan(primary=self.primary_id)
        if plan.action == "failover":
            return self.failover(plan.promote_to)
        if not self._primary_dead:
            self.ship()
            self.apply_all()
        return None

    # ---------------------------------------------------------- failover
    def kill_primary(self) -> DurableEngine:
        """Chaos hook: simulate primary process death. Writes start
        failing with :class:`PrimaryDownError`; heartbeats stop, so the
        next :meth:`tick` after the timeout fails over. Returns the dead
        handle — the ZOMBIE — so tests can prove its post-promotion
        appends are fenced."""
        zombie = self.primary
        self._primary_dead = True
        return zombie

    def kill_replica(self, node_id: int) -> Replica:
        """Chaos hook: simulate follower process death. It stops
        receiving ships and serving reads until
        :meth:`reattach_replica`."""
        rep = self.replicas[node_id]
        rep.alive = False
        return rep

    def failover(self, candidate: Optional[int] = None) -> int:
        """Promote the most-caught-up live follower (or ``candidate``).
        Returns the new primary's node id."""
        live = [nid for nid, r in sorted(self.replicas.items()) if r.alive]
        if not live:
            raise ReplicationError("no live follower to promote")
        if candidate is None or candidate not in live:
            candidate = max(live,
                            key=lambda nid:
                            (self.replicas[nid].wal.last_seq, -nid))
        return self.promote(candidate, expect_epoch=self.epoch)

    def promote(self, node_id: int, expect_epoch: int) -> int:
        """Epoch-CAS promotion of follower ``node_id``.

        Order matters and each boundary is a chaos crash point:
        fence-then-bump (the old primary's log rejects epochs below the
        new one BEFORE any new history exists), drain (the candidate
        applies its received tail — after this it is bitwise the
        never-crashed twin at its durable seq), then re-open the
        candidate's directory as the new primary at the new epoch.
        Exactly one promoter can win ``expect_epoch``; the rest get
        :class:`SplitBrainError`."""
        if expect_epoch != self.epoch:
            raise SplitBrainError(
                f"promotion CAS failed: observed epoch {expect_epoch}, "
                f"cluster already at {self.epoch}")
        rep = self.replicas[node_id]
        if not rep.alive:
            raise ReplicationError(f"cannot promote dead node {node_id}")
        new_epoch = expect_epoch + 1
        self._point("promote.pre-fence")
        self.primary.wal.fence(new_epoch)   # revoke the zombie's lease
        self.epoch = new_epoch
        self._point("promote.post-fence")
        rep.epoch = new_epoch
        rep.drain()
        self._point("promote.post-drain")
        rep.wal.close()
        self.primary = DurableEngine(rep.engine, rep.directory,
                                     saver=self.saver,
                                     injector=self.injector,
                                     epoch=new_epoch)
        if self.primary.wal.last_seq < rep.applied_seq:
            # nothing was ever shipped to this node: its log is empty and
            # all history lives in its bootstrap snapshot — keep numbering
            self.primary.wal.set_base(rep.applied_seq, new_epoch)
        self.primary_id = node_id
        self._primary_dead = False
        del self.replicas[node_id]
        del self._cursors[node_id]
        last = self.primary.wal.last_seq
        for nid, r in self.replicas.items():
            # fresh cursor on the NEW primary's log: the first ship
            # re-scans it once, the follower dedups by its durable seq
            self._cursors[nid] = TailCursor(last_seq=r.wal.last_seq)
            r.primary_seq = last
            if r.alive:
                # survivors learn the new term NOW, so a zombie's ship at
                # the old epoch is rejected even before the first re-ship
                r.epoch = max(r.epoch, new_epoch)
        self.monitor.beat(node_id, last)
        self.n_failovers += 1
        return node_id

    # ----------------------------------------------------------- queries
    # the primary's full query surface, for writers that read their own
    # writes; bounded-staleness follower reads go through self.router.
    def ate(self, *a, **kw):
        return self.primary.ate(*a, **kw)

    def ate_batch(self, specs):
        return self.primary.ate_batch(specs)

    def cached_estimate(self, *a, **kw):
        return self.primary.cached_estimate(*a, **kw)

    def matched_rows(self, *a, **kw):
        return self.primary.matched_rows(*a, **kw)

    def snapshot_version(self) -> int:
        return self.primary.snapshot_version()

    def __getattr__(self, name: str):
        return getattr(self.primary, name)
