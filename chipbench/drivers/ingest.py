"""Closed-loop durable ingest: one ETL loader that waits for each ack.

Traffic parameters (``chipbench/traffic/<mix>.json``):

* ``setup_rows``: the batches, in hour order, that set-up ingests from
  the start of the relation;
* ``stream_rows``: the flights that follow them, streamed in the window
  in hour order as new rows;
* ``batch_rows``: rows of every batch of the stream;
* ``warmup_batches``: batches of the stream that set-up ingests, which
  compile the window's insert program;
* ``warmup_pairs``: corrections of the first streamed chunk made in
  set-up, which compile the retract program the window needs once the
  stream is used up.

The window sends the stream's next ``batch_rows`` flights, batch after
batch, in hour order. Only once the stream is used up does it go on with
corrections, as the BTS on-time table revises delays: retract a streamed
chunk as last ingested, then ingest it again with its delays revised
(each row's ``delay_revision`` applied, or taken back), chunk after
chunk. Each batch is one ``DurableEngine.ingest`` (journal, fsync, one
dispatch) plus ``commit``; the loader sends the next batch only after
the ack. The window ends at the first ack after ``--seconds``;
``ingest_rows_per_s`` is the rows acknowledged over the time to that ack.

The set-up and stream lengths are chosen so that no view outgrows its
capacity inside the window: growth recompiles the engine's program.

Check: after the window, every view's committed groups, by key (counts,
outcome sums, sums of squares, matched flags) against the float64
reference of the rows acknowledged.
"""
from __future__ import annotations

import os
import time
from typing import Dict, List, Tuple

import numpy as np

from chipbench import gen, reference, system
from chipbench.harness import Checked

#: limits of the numbers compared (PERF.md gives the readings they were
#: set from)
LIMITS = {"views_differing": 0, "exact_mismatch": 0,
          "large_rel_err": 3e-3, "failed_batches": 0}


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.ops: List[Tuple[slice, int, int]] = []   # (rows, version, sign)
        self.window_ops = 0
        self.window_corrections = 0
        self.failed = 0
        self.elapsed = 0.0
        self.dur = None

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        cfg, tr, log = self.cfg, self.traffic, self.ctx.log
        t = time.perf_counter()
        rel = gen.generate(cfg, self.ctx.seed)
        out = cfg["outcome"]
        self.rows = int(tr["batch_rows"])
        self.start = sum(int(r) for r in tr["setup_rows"])
        self.end = self.start + int(tr["stream_rows"])
        n = len(rel[out])
        if self.end > n or self.start % self.rows or self.end % self.rows:
            raise ValueError(f"set-up {self.start} and stream {self.end} "
                             f"rows must be whole batches within {n}")
        # rows past the stream are never sent
        self.rel = {c: a[:self.end] for c, a in rel.items()}
        # version 0: the delays as generated; version 1: revised
        y0 = self.rel[out]
        self.y = (y0, np.maximum(y0 + self.rel["delay_revision"],
                                 0).astype(np.float32))
        self.cols = {c: self.rel[c] for c in system.engine_columns(cfg)
                     if c != out}
        self.present = np.zeros(self.end, bool)
        self.version = np.zeros(self.end, np.int8)
        self.revised = np.zeros(self.end, bool)   # ever at version 1
        log(f"data: {n:,} flights from seed {self.ctx.seed} in "
            f"{time.perf_counter() - t:.1f} s")
        self.dur = system.build(cfg, os.path.join(self.ctx.workdir, "wal"))
        at = 0
        for r in tr["setup_rows"]:
            t = time.perf_counter()
            self._send(slice(at, at + int(r)), 0, +1)
            at += int(r)
            log(f"set-up batch of {int(r):,} rows acked in "
                f"{time.perf_counter() - t:.1f} s")
        self.next = self.start       # first row not yet sent
        self.cursor = 0              # corrections made
        t = time.perf_counter()
        for _ in range(int(tr["warmup_batches"])):
            self._insert_next()
        for _ in range(2 * int(tr["warmup_pairs"])):
            self._correct()
        stats = self.dur.stats()
        self.caps = self._capacities(stats)
        log(f"warm-up: {tr['warmup_batches']} batches, "
            f"{tr['warmup_pairs']} corrections in "
            f"{time.perf_counter() - t:.1f} s; engine {stats}")

    @staticmethod
    def _capacities(stats) -> Dict[str, int]:
        return {view: s["capacity"] for view, s in stats.items()}

    def _send(self, sl: slice, version: int, sign: int) -> None:
        span = self.ctx.span
        out = self.cfg["outcome"]
        with span("batch_build"):
            cols = {c: a[sl] for c, a in self.cols.items()}
            cols[out] = self.y[version][sl]
            tbl = system.batch(cols)
        with span("journal_dispatch"):
            self.dur.ingest(tbl, retract=sign < 0)
        with span("commit"):
            self.dur.commit()
        self.present[sl] = sign > 0
        self.version[sl] = version
        self.revised[sl] |= version == 1
        self.ops.append((sl, version, sign))

    def _insert_next(self) -> None:
        sl = slice(self.next, self.next + self.rows)
        self._send(sl, 0, +1)
        self.next += self.rows

    def _correct(self) -> None:
        """The next correction of a streamed chunk: retract it, or
        re-ingest it revised."""
        n_chunks = (self.next - self.start) // self.rows
        k = self.start // self.rows + (self.cursor // 2) % n_chunks
        sl = slice(k * self.rows, (k + 1) * self.rows)
        if self.present[sl.start]:
            self._send(sl, int(self.version[sl.start]), -1)
        else:
            self._send(sl, 1 - int(self.version[sl.start]), +1)
        self.cursor += 1

    def _next_op(self) -> None:
        if self.next < self.end:
            self._insert_next()
        else:
            self._correct()
            self.window_corrections += 1

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while True:
            try:
                self._next_op()
            except Exception as e:     # a failed batch ends the window
                self.failed += 1
                self.ctx.log(f"batch {self.window_ops} failed: {e!r}")
                break
            self.window_ops += 1
            self.elapsed = time.perf_counter() - t0
            if self.elapsed >= seconds:
                break
        self.ctx.log(f"window: {self.window_ops} batches of {self.rows:,} "
                     f"rows acked in {self.elapsed:.3f} s, "
                     f"{self.window_corrections} of them corrections")

    def end_to_end(self) -> Dict[str, float]:
        if not self.window_ops:
            return {}
        return {"ingest_rows_per_s":
                self.window_ops * self.rows / self.elapsed}

    def counters(self) -> Dict[str, float]:
        return {"batches_acked": self.window_ops,
                "corrections_acked": self.window_corrections}

    # ------------------------------------------------------------- check
    def _reference(self):
        hw = self.next               # every row below was sent once
        groups = reference.Groups(
            self.cfg, {c: a[:hw] for c, a in self.rel.items()})
        y = np.where(self.version[:hw] == 1, self.y[1][:hw],
                     self.y[0][:hw])
        peak = np.where(self.revised[:hw],
                        np.maximum(self.y[0][:hw], self.y[1][:hw]),
                        self.y[0][:hw])
        w = self.present[:hw].astype(np.float64)
        return groups, reference.reference_state(groups, y, w, peak)

    def engine_state(self) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
        views = self.dur.export_canonical()["views"]
        ts = tuple(self.cfg["treatments"])
        return {name: reference.engine_state(v["hi"], v["lo"], v["stats"],
                                             v.get("keep"), ts)
                for name, v in views.items()}

    def check(self) -> Checked:
        stats = self.dur.stats()
        if self._capacities(stats) != self.caps:
            self.ctx.log(f"capacity grew inside the window: {stats}")
        t = time.perf_counter()
        got = self.engine_state()
        _, want = self._reference()
        notes = []
        err = reference.state_error(got, want, notes)
        for line in notes:
            self.ctx.log(f"differs: {line}")
        self.ctx.log(f"reference: {sum(len(w[0]) for w in want.values()):,} "
                     f"groups in {len(want)} views compared in "
                     f"{time.perf_counter() - t:.1f} s")
        err["failed_batches"] = self.failed
        return Checked(attempted=self.window_ops + self.failed,
                       failed=self.failed,
                       compared={k: (float(v), LIMITS[k])
                                 for k, v in err.items()})

    def control(self) -> Dict[str, float]:
        """The numbers compared with the reference in bfloat16 put in the
        program's place."""
        groups, want = self._reference()
        ops = [(sl, self.y[v][sl], float(s)) for sl, v, s in self.ops]
        return reference.state_error(reference.control_state(groups, ops),
                                     want)

    def close(self) -> None:
        if self.dur is not None:
            self.dur.close()
