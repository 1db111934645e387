"""Event assembly: pgoutput row messages → destination events, on either
decode engine.

The apply loop pushes raw row messages here; `flush()` returns the ordered
event list for the destination write.

- CPU engine: each message decodes immediately via the codec oracle
  (reference-architecture per-tuple path, codec/event.rs).
- TPU engine: row-message payloads accumulate as raw bytes in one open run
  per table — the open GROUP, tables in the order of their first row. The
  group is sealed as a whole: each run is framed (native framer), staged
  and decoded in one batch and emitted as one `DecodedBatchEvent`, run
  after run, contiguous in the event list. It seals at a flush, at a
  control event (Relation/Truncate/SchemaChange, and the CPU engine's
  Begin/Commit, stay host-decoded barriers), when one run reaches
  `seal_rows` and when the group reaches the byte seal — the reference's
  per-table batching between barriers (bigquery/core.rs:956-978).

Delivery order (docs/decode-pipeline.md): flushes leave in WAL order and
each covers one contiguous stretch of WAL — a size-bounded flush cuts
between groups, never inside one, so no row is delivered in a later flush
than a row with higher `(commit_lsn, tx_ordinal)`. Inside a flush a
table's rows are in WAL order; the tables of a group are not interleaved
as the WAL interleaved them. Every row carries its own coordinates.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..analysis.annotations import hot_loop
from ..config.pipeline import BatchEngine
from ..models.errors import ErrorKind, EtlError
from ..models.event import DecodedBatchEvent, Event
from ..models.lsn import Lsn
from ..models.schema import ReplicatedTableSchema, TableId
from ..ops.engine import DeviceDecoder
from ..ops.pipeline import DecodePipeline
from ..ops.wal import stage_wal_batch
from ..postgres.codec import event as event_codec
from ..postgres.codec import pgoutput
from ..telemetry import spans
from ..telemetry.metrics import (ETL_ASSEMBLER_SEAL_SECONDS,
                                 ETL_ASSEMBLER_SEALED_ROWS_TOTAL,
                                 ETL_DECODE_CELLS_TOTAL,
                                 ETL_DECODE_DEVICE_KIND_CELLS_TOTAL, registry)


@dataclass
class _Run:
    """One table's open rows, in WAL order: what one seal stages."""

    table_id: TableId
    schema: ReplicatedTableSchema
    #: not the first run of its group — a flush may not cut before it
    joined: bool = False
    payloads: list[bytes] = field(default_factory=list)
    start_lsns: list[int] = field(default_factory=list)
    commit_lsns: list[int] = field(default_factory=list)
    tx_ordinals: list[int] = field(default_factory=list)
    nbytes: int = 0  # size-hint bytes (64/row + payload)


#: seal the open group once a run reaches this many rows. Two effects: decode
#: dispatch starts while the stream keeps flowing (the device/host XLA
#: call overlaps further WAL intake instead of bunching at flush), and
#: staged batches never exceed the 16384-row bucket — so the decode
#: program's (row-bucket, width-signature) key space stays small and a
#: long-running pipeline stops hitting fresh ~0.3s XLA compiles when a
#: backlog drains through ever-larger flushes.
RUN_SEAL_ROWS = 16384

#: backlog growth cap for the dynamic seal (the largest standard row
#: bucket): under sustained backlog the apply loop grows seals toward
#: this so staged batches clear the measured device-routing threshold —
#: the steady-state data plane then decodes on the accelerator instead
#: of capping every run at the host-size bucket (VERDICT r4 #1b).
MEGA_SEAL_ROWS = 262_144


#: distinguishes concurrent assemblers' decode heartbeats (apply loop +
#: table-sync catchup loops each own one)
_ASSEMBLER_SEQ = [0]


class EventAssembler:
    def __init__(self, engine: BatchEngine, monitor=None,
                 decode_window: int = 3, supervisor=None,
                 lag_bytes=None, admission_capacity: int = 0,
                 seal_bytes: int = 0, egress_encoder: "str | None" = None):
        self.engine = engine
        # wire-encoder name (ops/egress.py) the destination consumes —
        # bound into every DeviceDecoder this loop creates so decoded
        # batches carry device-rendered wire buffers (`device_egress`)
        self.egress_encoder = egress_encoder
        # byte seal (0 = off): seal the open group once its size-hint
        # bytes reach this bound (scaled with the dynamic row seal the
        # same ×-factor _scaled_max_bytes uses), so one group — the
        # least a flush can carry — can never exceed the flush sizing:
        # size-bounded flushes then cut between groups and the write
        # window has batches to pipeline. The apply loop passes
        # BatchConfig.max_size_bytes; at typical row widths the
        # 16384-row seal binds first, so decode batch shapes are
        # unchanged.
        self.seal_bytes = seal_bytes
        # fair-admission wiring (ops/pipeline.AdmissionScheduler): this
        # loop's decode pipeline takes one tenant seat on the process-
        # wide scheduler, weighted by `lag_bytes` (the apply loop's
        # received−durable delta — the SlotLagMetrics shape) so a
        # lagging stream wins more batch admissions when several streams
        # share the device set
        self._lag_bytes = lag_bytes
        self._admission_capacity = admission_capacity
        self._events: list[Event] = []
        # per-event (size_bytes, row_events, joined) — lets
        # flush(max_bytes=...) cut a WAL-ordered prefix and keep the
        # remainder's accounting exact (the write window dispatches
        # size-bounded batches instead of one backlog-sized mega write).
        # `joined` marks an event sealed from the same group as the one
        # before it: the cut never falls in front of such an event
        self._meta: list[tuple[int, int, bool]] = []
        # commit watermarks: (n_events_covered, commit_end_lsn) — all
        # events with index < n (counting each open run as the one
        # future event it seals into) belong to commits ending ≤
        # commit_end_lsn, so a prefix flush of ≥ n events may claim
        # durability at that LSN once acked. The apply loop records one
        # per commit boundary (note_commit_end); flush() consumes the
        # covered prefix.
        self._commit_marks: list[tuple[int, int]] = []
        # the open group: one run per table, in first-row order (every
        # run holds at least one row), and its size-hint bytes
        self._group: dict[TableId, _Run] = {}
        self._group_bytes = 0
        # the run _seal_run is to stage: _seal_group hands the group's
        # runs over one at a time (None between seals)
        self._run: _Run | None = None
        self._decoders: dict[TableId, DeviceDecoder] = {}
        # one decode pipeline (worker thread + bounded in-flight window)
        # serves every table this loop assembles; created lazily so the
        # CPU engine never spawns the thread. The monitor shrinks the
        # window to 1 under memory pressure (runtime/backpressure).
        self._monitor = monitor
        self._decode_window = decode_window
        self._supervisor = supervisor  # supervision.Supervisor | None
        _ASSEMBLER_SEQ[0] += 1
        self._seq = _ASSEMBLER_SEQ[0]
        self._pipeline: DecodePipeline | None = None
        # dynamic: the apply loop grows it ×4 (one row bucket per step)
        # under sustained backlog and resets it when the stream idles
        self.seal_rows = RUN_SEAL_ROWS
        self.size_bytes = 0
        # perf_counter_ns of the first push since the assembler was last
        # empty (None while empty): where the apply loop's `flush.fill`
        # span starts. After a size-bounded flush that leaves a remainder
        # it is that flush's own time.
        self.filled_since_ns: int | None = None
        # row (non-control) events in the open window: the apply loop's
        # idle-commit fast flush keys on this — control-only windows
        # (CPU-engine Begin/Commit of unowned-table transactions) must
        # stay on the deadline path or durable progress would be written
        # once per commit instead of once per fill window
        self.row_events = 0

    def __len__(self) -> int:
        """The events a whole-window flush would return."""
        return len(self._events) + len(self._group)

    # -- pushes ---------------------------------------------------------------

    def push_control(self, ev: Event, size_hint: int = 64) -> None:
        """Begin/Commit/Relation/Truncate/SchemaChange — barrier events."""
        if self.filled_since_ns is None:
            self.filled_since_ns = spans.now_ns()
        self._seal_group()
        self._events.append(ev)
        self._meta.append((size_hint, 0, False))
        self.size_bytes += size_hint

    @hot_loop
    def push_raw_row(self, payload: bytes, schema: ReplicatedTableSchema,
                     start_lsn: Lsn, commit_lsn: Lsn,
                     tx_ordinal: int) -> None:
        """TPU fast path: accumulate the raw row-message payload without
        host-side tuple parsing (the framer parses it on the device staging
        path). Callers guarantee payload[0] is I/U/D. @hot_loop: runs once
        per CDC row — a host transfer here caps stream throughput."""
        if self.filled_since_ns is None:
            self.filled_since_ns = spans.now_ns()
        r = self._open_run(schema)
        r.payloads.append(payload)
        r.start_lsns.append(int(start_lsn))
        r.commit_lsns.append(int(commit_lsn))
        r.tx_ordinals.append(tx_ordinal)
        nbytes = 64 + len(payload)
        r.nbytes += nbytes
        self._group_bytes += nbytes
        self.size_bytes += nbytes
        self.row_events += 1
        if len(r.payloads) >= self.seal_rows \
                or (self.seal_bytes
                    and self._group_bytes >= self._scaled_seal_bytes()):
            self._seal_group()

    @hot_loop
    def push_raw_rows(self, payloads: list[bytes],
                      schema: ReplicatedTableSchema, start_lsns: list[int],
                      commit_lsn: int, tx_ordinal0: int) -> int:
        """Bulk form of push_raw_row for a contiguous same-table span (the
        apply loop's drained-window fast path): one call per span, list
        extends instead of per-row pushes. Returns the span's payload
        bytes (the caller's tx_bytes accounting needs the same sum).
        @hot_loop: one call per drained span on the saturated path."""
        if self.filled_since_ns is None:
            self.filled_since_ns = spans.now_ns()
        k = len(payloads)
        if not k:
            return 0
        r = self._open_run(schema)
        if len(r.payloads) + k > self.seal_rows and r.payloads:
            # seal BEFORE extending: overshooting the cap would bump the
            # staged batch into the next (unwarmed) row bucket
            self._seal_group()
            r = self._open_run(schema)
        r.payloads.extend(payloads)
        r.start_lsns.extend(start_lsns)
        r.commit_lsns.extend([commit_lsn] * k)
        r.tx_ordinals.extend(range(tx_ordinal0, tx_ordinal0 + k))
        nbytes = sum(map(len, payloads))
        r.nbytes += 64 * k + nbytes
        self._group_bytes += 64 * k + nbytes
        self.size_bytes += 64 * k + nbytes
        self.row_events += k
        if len(r.payloads) >= self.seal_rows \
                or (self.seal_bytes
                    and self._group_bytes >= self._scaled_seal_bytes()):
            # byte overshoot of at most one span: the seal check runs per
            # span push, so a drained-window span lands whole
            self._seal_group()
        return nbytes

    def _open_run(self, schema: ReplicatedTableSchema) -> _Run:
        """The open run of `schema`'s table, opened where the group has
        none: a row of another table seals nothing. A run whose schema
        object changed starts anew, after the group is sealed."""
        r = self._group.get(schema.id)
        if r is not None and r.schema is not schema:
            self._seal_group()
            r = None
        if r is None:
            r = self._group[schema.id] = _Run(
                table_id=schema.id, schema=schema, joined=bool(self._group))
        return r

    def _scaled_seal_bytes(self) -> int:
        """Byte seal scaled with the dynamic row seal — the same growth
        factor the apply loop's _scaled_max_bytes applies, so backlog
        mega-batching grows flush payloads and run seals in lockstep."""
        return self.seal_bytes * max(1, self.seal_rows // RUN_SEAL_ROWS)

    # -- dynamic seal (backlog mega-batching) ---------------------------------

    def grow_seal(self) -> None:
        """×4 per step = exactly one standard row bucket (16384 → 65536 →
        262144), so growth never lands in an intermediate bucket whose
        decode program would be a wasted compile."""
        if self.seal_rows < MEGA_SEAL_ROWS:
            self.seal_rows = min(self.seal_rows * 4, MEGA_SEAL_ROWS)

    def reset_seal(self) -> None:
        self.seal_rows = RUN_SEAL_ROWS

    def push_row_message(self, msg: pgoutput.LogicalReplicationMessage,
                         payload: bytes, schema: ReplicatedTableSchema,
                         start_lsn: Lsn, commit_lsn: Lsn,
                         tx_ordinal: int) -> None:
        if self.engine is BatchEngine.CPU:
            if isinstance(msg, pgoutput.InsertMessage):
                ev: Event = event_codec.decode_insert(
                    msg, schema, start_lsn, commit_lsn, tx_ordinal)
            elif isinstance(msg, pgoutput.UpdateMessage):
                ev = event_codec.decode_update(
                    msg, schema, start_lsn, commit_lsn, tx_ordinal)
            elif isinstance(msg, pgoutput.DeleteMessage):
                ev = event_codec.decode_delete(
                    msg, schema, start_lsn, commit_lsn, tx_ordinal)
            else:
                raise EtlError(ErrorKind.REPLICATION_MESSAGE_INVALID,
                               f"not a row message: {type(msg).__name__}")
            if self.filled_since_ns is None:
                self.filled_since_ns = spans.now_ns()
            self._events.append(ev)
            self._meta.append((64 + len(payload), 1, False))
            self.size_bytes += 64 + len(payload)
            self.row_events += 1
            return
        # TPU path: defer decode, accumulate raw payloads
        self.push_raw_row(payload, schema, start_lsn, commit_lsn, tx_ordinal)

    # -- flush ----------------------------------------------------------------

    def _seal_group(self) -> None:
        """Seal every open run, in the order of each run's first row, into
        consecutive events: one contiguous stretch of WAL, which
        flush_bounded never cuts."""
        group = self._group
        if not group:
            return
        self._group = {}
        self._group_bytes = 0
        for run in group.values():
            self._run = run
            self._seal_run()

    def _seal_run(self) -> None:
        """Stage and submit `self._run` (one sealed run, one event)."""
        from ..chaos import failpoints

        # chaos site: fires once per sealed run (a decode batch is born)
        failpoints.fail_point(failpoints.ASSEMBLER_SEAL)
        r = self._run
        self._run = None
        decoder = self._decoders.get(r.table_id)
        if decoder is None or decoder.schema is not r.schema:
            # nonblocking: a cold (bucket, specs) program compiles on a
            # background thread while its batches decode on the oracle —
            # a synchronous first-touch build of a wide schema (measured
            # 32s at 120 columns) would wedge the apply loop past the
            # stall deadline and spiral the watchdog into restarts.
            # With a program cache dir configured the cold key usually
            # isn't cold at all: Pipeline.start's prewarm (or the
            # first-touch disk probe in engine._host_fn_ready) loads the
            # previous incarnation's AOT executable, so a warm restart
            # decodes its first flush on the real program, zero builds
            # (ops/program_store.py)
            decoder = DeviceDecoder(r.schema, nonblocking_compile=True,
                                    egress=self.egress_encoder)
            self._decoders[r.table_id] = decoder
        # the batch_id minted here rides the staged batch through every
        # decode span of the run (telemetry/spans.py)
        batch_id = spans.next_batch_id()
        n = len(r.payloads)
        registry.counter_inc(ETL_ASSEMBLER_SEALED_ROWS_TOTAL, n)
        registry.counter_inc(ETL_DECODE_CELLS_TOTAL, n * decoder.n_columns)
        registry.counter_inc(ETL_DECODE_DEVICE_KIND_CELLS_TOTAL,
                             n * decoder.n_device_kind_columns)
        with spans.span("assemble.seal", ETL_ASSEMBLER_SEAL_SECONDS,
                        batch_id=batch_id, rows=n):
            self._stage_and_submit(r, decoder, batch_id)

    def _stage_and_submit(self, r: _Run, decoder: DeviceDecoder,
                          batch_id: int) -> None:
        lens = np.fromiter((len(p) for p in r.payloads), dtype=np.int32,
                           count=len(r.payloads))
        offs = np.zeros(len(r.payloads), dtype=np.int64)
        np.cumsum(lens[:-1], out=offs[1:])
        buf = b"".join(r.payloads)
        n_cols = r.schema.replicated_column_count()
        wal = stage_wal_batch(buf, offs, lens, n_cols)
        if wal.bad_from >= 0:
            raise EtlError(ErrorKind.WAL_DECODE_FAILED,
                           f"malformed row message at run index {wal.bad_from}")
        # pipelined dispatch (ops/pipeline.py): the pack runs on the
        # pipeline's worker thread into a pooled arena and the device
        # decodes (and streams results back) while the apply loop keeps
        # reading WAL; the DecodedBatchEvent resolves the batch lazily
        # when the destination write consumes it, in submit order — the
        # bounded in-flight window caps staged memory across flushes
        if self._pipeline is None:
            hb = None
            if self._supervisor is not None:
                # decode components are observe-only: recovery of a stuck
                # pipeline rides the owning worker's restart, and repeated
                # detections escalate to the host-oracle degrade
                from ..supervision import DECODE_PREFIX

                hb = self._supervisor.register(
                    f"{DECODE_PREFIX}cdc-{self._seq}")
            from ..ops.pipeline import global_admission

            admission = global_admission(
                self._admission_capacity or None).register(
                    f"cdc-{self._seq}", lag_bytes=self._lag_bytes,
                    monitor=self._monitor)
            self._pipeline = DecodePipeline(window=self._decode_window,
                                            monitor=self._monitor,
                                            name="cdc", heartbeat=hb,
                                            admission=admission)
        # publication row-filter eligibility: the fused device filter
        # compacts INSERT-only runs (and the COPY path); runs carrying
        # updates/deletes keep the server-side filtering contract — the
        # U/D row-filter transforms (UPDATE whose old image leaves the
        # filter becomes INSERT, etc.) are walsender semantics the client
        # does not re-implement (docs/decode-pipeline.md)
        from ..models.event import ChangeType

        wal.staged.allow_row_filter = bool(
            wal.old_staged is None
            and (wal.change_types == ChangeType.INSERT).all())
        wal.staged.batch_id = batch_id
        if wal.old_staged is not None:
            wal.old_staged.allow_row_filter = False
            wal.old_staged.batch_id = batch_id
        pending = self._pipeline.submit(decoder, wal.staged)
        old_pending = self._pipeline.submit(decoder, wal.old_staged) \
            if wal.old_staged is not None else None
        self._events.append(DecodedBatchEvent(
            Lsn(r.start_lsns[0]), Lsn(r.commit_lsns[-1]), r.schema,
            pending=pending,
            change_types=wal.change_types,
            commit_lsns=np.asarray(r.commit_lsns, dtype=np.uint64),
            tx_ordinals=np.asarray(r.tx_ordinals, dtype=np.uint64),
            old_pending=old_pending, old_rows=wal.old_rows,
            old_is_key=wal.old_is_key, delete_is_key=wal.delete_is_key,
            batch_id=batch_id,
        ))
        self._meta.append((r.nbytes, len(r.payloads), r.joined))

    def note_commit_end(self, end_lsn: Lsn) -> None:
        """Record a commit watermark: every event assembled SO FAR
        (counting each open run as the one event it seals into) belongs
        to transactions whose commit ends ≤ `end_lsn`. The open runs may
        still grow past the mark, and later tables may join the group
        behind them — the group then carries extra later rows, which
        only makes the covered prefix a superset (claiming durability at
        the mark stays exact; a cut never falls inside the group). The
        apply loop calls this once per commit boundary; flush() consumes
        marks with the prefix they cover."""
        n = len(self._events) + len(self._group)
        lsn = int(end_lsn)
        if self._commit_marks and self._commit_marks[-1][0] == n:
            self._commit_marks[-1] = (n, max(self._commit_marks[-1][1], lsn))
        else:
            self._commit_marks.append((n, lsn))

    def flush(self) -> list[Event]:
        """Seal the open group, return and reset the assembled events
        (the whole window — legacy signature; the apply loop's
        size-bounded dispatch goes through `flush_bounded`)."""
        return self.flush_bounded()[0]

    def flush_bounded(self, max_bytes: "int | None" = None
                      ) -> "tuple[list[Event], Lsn | None, Lsn | None]":
        """Seal the open group and return `(events, covered_commit_end,
        remaining_commit_end)`.

        With `max_bytes=None` (or everything fitting) the whole window
        flushes. Otherwise a WAL-ORDERED PREFIX of whole groups — a
        control event is a group of its own — totalling ≤ max_bytes
        (always at least one group) is returned and the remainder stays
        assembled, so the write window dispatches size-bounded batches a
        backlog can pipeline instead of one backlog-sized mega write.
        The cut never parts a group: its events interleave in the WAL,
        and no row may leave in a later flush than a row with higher
        coordinates (the transactional sinks drop every row at or below
        the last flush's highest).

        `covered_commit_end` is the highest commit watermark whose
        events are ALL inside the returned prefix (None = the flush
        covers no commit boundary — mid-transaction split);
        `remaining_commit_end` is the highest watermark still pending in
        the assembler (None = nothing awaits a future flush)."""
        self._seal_group()
        meta = self._meta
        k = n = len(self._events)
        cum = 0  # bytes of the prefix events[:k], where it is cut
        if max_bytes is not None and self.size_bytes > max_bytes:
            k = 0
            while k < n:
                size = meta[k][0]
                end = k + 1
                while end < n and meta[end][2]:
                    size += meta[end][0]
                    end += 1
                if k and cum + size > max_bytes:
                    break
                cum += size
                k = end
        if k == n:
            events = self._events
            covered = Lsn(self._commit_marks[-1][1]) \
                if self._commit_marks else None
            self._events = []
            self._meta = []
            self._commit_marks = []
            self.size_bytes = 0
            self.row_events = 0
            self.filled_since_ns = None
            return events, covered, None
        events = self._events[:k]
        self._events = self._events[k:]
        self._meta = meta[k:]
        self.size_bytes -= cum
        self.row_events = sum(m[1] for m in self._meta)
        self.filled_since_ns = spans.now_ns()
        covered = None
        while self._commit_marks and self._commit_marks[0][0] <= k:
            covered = Lsn(self._commit_marks.pop(0)[1])
        self._commit_marks = [(m - k, lsn) for m, lsn in self._commit_marks]
        remaining = Lsn(self._commit_marks[-1][1]) \
            if self._commit_marks else None
        return events, covered, remaining

    def close(self) -> None:
        """Stop the decode pipeline's worker (apply-loop teardown).
        Already-flushed DecodedBatchEvents stay resolvable — close only
        fences new submits, and _stage_and_submit re-creates the
        pipeline if a resumed loop reuses this assembler."""
        if self._pipeline is not None:
            self._pipeline.close()
            self._pipeline = None
