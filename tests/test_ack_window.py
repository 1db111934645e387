"""Windowed destination-ack pipeline (ISSUE 14): AckWindow contiguous-
prefix durability, submission chaining, mid-window failure, byte/depth
caps + memory-pressure shrink, the CopyAckWindow bound, the assembler's
commit watermarks + size-bounded flush, window=1 delivery equivalence,
drain-on-shutdown, the K-in-flight chaos crash, and the observed-
signature program-store satellite."""

from __future__ import annotations

import asyncio

import pytest

from etl_tpu.destinations.base import WriteAck
from etl_tpu.models.errors import ErrorKind, EtlError
from etl_tpu.models.lsn import Lsn
from etl_tpu.runtime.ack_window import AckWindow, CopyAckWindow


async def _settle() -> None:
    """Give spawned window tasks a few loop cycles to progress."""
    for _ in range(6):
        await asyncio.sleep(0)


def _submitter(ack, log=None, name=None):
    async def submit():
        if log is not None:
            log.append(name)
        return ack

    return submit


class TestAckWindow:
    async def test_contiguous_prefix_holds_out_of_order_acks(self):
        w = AckWindow(4)
        pairs = [WriteAck.accepted() for _ in range(3)]
        entries = [w.dispatch(_submitter(ack), commit_end_lsn=Lsn(i + 1),
                              n_events=1, nbytes=10)
                   for i, (ack, _) in enumerate(pairs)]
        await _settle()
        # resolve the MIDDLE ack first: nothing may pop (the head is
        # still pending), and durability must never leapfrog
        pairs[1][1].set_result(None)
        await _settle()
        done, failure = w.pop_ready()
        assert done == [] and failure is None
        assert len(w) == 3
        # head resolves: exactly the head pops
        pairs[0][1].set_result(None)
        await _settle()
        done, failure = w.pop_ready()
        # the held-out-of-order entry pops WITH the head the moment the
        # prefix is contiguous
        assert [e.commit_end_lsn for e in done] == [Lsn(1), Lsn(2)]
        assert failure is None
        # tail resolves: window drains fully
        pairs[2][1].set_result(None)
        await _settle()
        done, failure = w.pop_ready()
        assert [e.commit_end_lsn for e in done] == [Lsn(3)]
        assert w.is_empty
        assert entries[0].n_events == 1

    async def test_out_of_order_completion_is_not_actionable(self):
        """Review regression: a successful non-head completion must not
        read as actionable (the select loop would spin against an empty
        pop until the head ack resolves) and its done task must leave
        the pending wait set; a FAILED non-head completion stays
        actionable (fail fast)."""
        w = AckWindow(4)
        pairs = [WriteAck.accepted() for _ in range(3)]
        for i, (ack, _) in enumerate(pairs):
            w.dispatch(_submitter(ack), commit_end_lsn=Lsn(i + 1),
                       n_events=1, nbytes=1)
        await _settle()
        assert not w.any_actionable()
        assert len(w.pending_tasks()) == 3
        pairs[1][1].set_result(None)  # middle resolves first
        await _settle()
        assert w.any_done()
        assert not w.any_actionable()  # held for contiguity: no action
        assert len(w.pending_tasks()) == 2  # done task leaves the waits
        pairs[0][1].set_result(None)  # head resolves: actionable now
        await _settle()
        assert w.any_actionable()
        done, failure = w.pop_ready()
        assert len(done) == 2 and failure is None
        pairs[2][1].set_exception(
            EtlError(ErrorKind.DESTINATION_FAILED, "late fail"))
        await _settle()
        # a FAILED completion is always actionable, head or not
        assert w.any_actionable()
        done, failure = w.pop_ready()
        assert done == [] and isinstance(failure, EtlError)

    async def test_submissions_chain_in_dispatch_order(self):
        w = AckWindow(4)
        log: list = []
        gate = asyncio.Event()
        ack0, fut0 = WriteAck.accepted()
        ack1, fut1 = WriteAck.accepted()

        async def slow_submit():
            log.append("first-start")
            await gate.wait()
            log.append("first-done")
            return ack0

        w.dispatch(slow_submit, n_events=1, nbytes=1)
        w.dispatch(_submitter(ack1, log, "second"), n_events=1, nbytes=1)
        await _settle()
        # the second submission must NOT start until the first returned
        assert log == ["first-start"]
        gate.set()
        await _settle()
        assert log == ["first-start", "first-done", "second"]
        fut0.set_result(None)
        fut1.set_result(None)
        await _settle()
        done, failure = w.pop_ready()
        assert len(done) == 2 and failure is None

    async def test_mid_window_failure_pops_prefix_then_raises(self):
        w = AckWindow(4)
        ack0, fut0 = WriteAck.accepted()
        ack1, fut1 = WriteAck.accepted()
        ack2, fut2 = WriteAck.accepted()
        for i, ack in enumerate((ack0, ack1, ack2)):
            w.dispatch(_submitter(ack), commit_end_lsn=Lsn(i + 1),
                       n_events=1, nbytes=1)
        await _settle()
        fut0.set_result(None)
        fut1.set_exception(EtlError(ErrorKind.DESTINATION_FAILED, "boom"))
        await _settle()
        done, failure = w.pop_ready()
        # the durable prefix surfaces BEFORE the failure so the caller
        # persists it and the restart re-streams only the suffix
        assert [e.commit_end_lsn for e in done] == [Lsn(1)]
        assert isinstance(failure, EtlError)
        assert failure.kind is ErrorKind.DESTINATION_FAILED
        fut2.set_result(None)
        await _settle()

    async def test_failed_submission_fails_successors_without_submitting(
            self):
        w = AckWindow(4)
        log: list = []

        async def failing_submit():
            raise EtlError(ErrorKind.DESTINATION_FAILED, "submit died")

        ack1, fut1 = WriteAck.accepted()
        w.dispatch(failing_submit, n_events=1, nbytes=1)
        w.dispatch(_submitter(ack1, log, "second"), n_events=1, nbytes=1)
        await _settle()
        # the successor must never reach the destination (WAL-order gap)
        assert log == []
        done, failure = w.pop_ready()
        assert done == [] and isinstance(failure, EtlError)

    async def test_depth_and_byte_caps_and_pressure_shrink(self):
        pressure = [False]
        w = AckWindow(3, max_bytes=100,
                      pressure=lambda: pressure[0])
        assert w.can_dispatch(10**9)  # empty window always admits one
        ack0, fut0 = WriteAck.accepted()
        w.dispatch(_submitter(ack0), n_events=1, nbytes=60)
        await _settle()
        assert w.can_dispatch(30)
        assert not w.can_dispatch(50)  # byte cap: 60 + 50 > 100
        ack1, fut1 = WriteAck.accepted()
        w.dispatch(_submitter(ack1), n_events=1, nbytes=30)
        await _settle()
        # memory pressure shrinks the effective depth to 1: nothing
        # more dispatches until the window fully drains
        pressure[0] = True
        assert w.effective_limit() == 1
        assert not w.can_dispatch(1)
        pressure[0] = False
        assert w.can_dispatch(5)  # depth 3, bytes 90+5 <= 100
        ack2, fut2 = WriteAck.accepted()
        w.dispatch(_submitter(ack2), n_events=1, nbytes=5)
        await _settle()
        assert not w.can_dispatch(1)  # depth cap
        for f in (fut0, fut1, fut2):
            f.set_result(None)
        await _settle()
        done, failure = w.pop_ready()
        assert len(done) == 3 and failure is None
        assert w.pending_bytes == 0

    async def test_wait_all_then_drain(self):
        w = AckWindow(4)
        pairs = [WriteAck.accepted() for _ in range(3)]
        for i, (ack, _) in enumerate(pairs):
            w.dispatch(_submitter(ack), commit_end_lsn=Lsn(i + 1),
                       n_events=2, nbytes=1)
        for _, fut in pairs:
            asyncio.get_event_loop().call_later(0.01, fut.set_result, None)
        await asyncio.wait_for(w.wait_all(), 5)
        done, failure = w.pop_ready()
        assert [int(e.commit_end_lsn) for e in done] == [1, 2, 3]
        assert failure is None and w.is_empty

    async def test_event_less_entry_carries_commit_watermark(self):
        w = AckWindow(4)

        async def submit_none():
            return None

        w.dispatch(submit_none, commit_end_lsn=Lsn(9), n_events=0,
                   nbytes=0)
        await _settle()
        done, failure = w.pop_ready()
        assert [e.commit_end_lsn for e in done] == [Lsn(9)]
        assert failure is None


class TestCopyAckWindow:
    async def test_bounds_outstanding_and_preserves_order(self):
        order: list = []

        class TrackedAck(WriteAck):
            __slots__ = ("index",)

            async def wait_durable(self):
                order.append(self.index)
                await super().wait_durable()

        def tracked(i):
            ack, fut = TrackedAck.accepted()
            ack.index = i
            return ack, fut

        w = CopyAckWindow(2)
        pairs = [tracked(i) for i in range(4)]
        for _, fut in pairs:
            fut.set_result(None)
        for i, (ack, _) in enumerate(pairs):
            await w.add(ack)
            assert len(w) <= 2
        await w.drain()
        assert order == [0, 1, 2, 3]  # oldest-first: partition order
        assert len(w) == 0

    async def test_early_error_surfacing(self):
        w = CopyAckWindow(1)
        ok_ack, ok_fut = WriteAck.accepted()
        ok_fut.set_result(None)
        bad_ack, bad_fut = WriteAck.accepted()
        bad_fut.set_exception(
            EtlError(ErrorKind.DESTINATION_FAILED, "copy write died"))
        bad_fut.exception()  # retrieved
        await w.add(bad_ack)
        # the NEXT add must surface the oldest ack's failure — within
        # `limit` batches, not at the end-of-copy barrier
        with pytest.raises(EtlError):
            await w.add(ok_ack)

    async def test_pressure_shrinks_to_serial(self):
        pressure = [True]
        w = CopyAckWindow(4, pressure=lambda: pressure[0])
        for _ in range(3):
            ack, fut = WriteAck.accepted()
            fut.set_result(None)
            await w.add(ack)
            assert len(w) <= 1  # shrunk to 1 outstanding ack
        pressure[0] = False
        for _ in range(3):
            ack, fut = WriteAck.accepted()
            fut.set_result(None)
            await w.add(ack)
        assert len(w) > 1  # pressure lifted: the full window is back


class TestAssemblerWatermarks:
    def _assembler(self):
        from etl_tpu.config.pipeline import BatchEngine
        from etl_tpu.runtime.assembler import EventAssembler

        return EventAssembler(BatchEngine.CPU)

    def _ev(self):
        from etl_tpu.models.event import BeginEvent

        return BeginEvent(Lsn(1), Lsn(2), 0, 0)

    def test_bounded_flush_cuts_prefix_with_covered_watermark(self):
        a = self._assembler()
        a.push_control(self._ev(), size_hint=100)
        a.note_commit_end(Lsn(10))
        a.push_control(self._ev(), size_hint=100)
        a.note_commit_end(Lsn(20))
        a.push_control(self._ev(), size_hint=100)
        events, covered, remaining = a.flush_bounded(max_bytes=100)
        assert len(events) == 1
        assert covered == Lsn(10)  # only commit 10's events are inside
        assert remaining == Lsn(20)  # commit 20 still awaits a flush
        assert a.size_bytes == 200
        events, covered, remaining = a.flush_bounded(max_bytes=None)
        assert len(events) == 2
        assert covered == Lsn(20)
        assert remaining is None
        assert a.size_bytes == 0

    def test_mid_transaction_prefix_covers_no_commit(self):
        a = self._assembler()
        a.push_control(self._ev(), size_hint=100)
        a.push_control(self._ev(), size_hint=100)
        events, covered, remaining = a.flush_bounded(max_bytes=100)
        assert len(events) == 1
        assert covered is None and remaining is None

    def test_event_less_commit_window(self):
        a = self._assembler()
        a.note_commit_end(Lsn(33))
        events, covered, remaining = a.flush_bounded()
        assert events == [] and covered == Lsn(33) and remaining is None

    def test_always_takes_at_least_one_event(self):
        a = self._assembler()
        a.push_control(self._ev(), size_hint=500)
        a.push_control(self._ev(), size_hint=500)
        events, _, _ = a.flush_bounded(max_bytes=1)
        assert len(events) == 1  # a single over-budget event still flushes

    def test_legacy_flush_signature_unchanged(self):
        a = self._assembler()
        a.push_control(self._ev())
        events = a.flush()
        assert isinstance(events, list) and len(events) == 1

    def test_byte_seal_bounds_run_size(self):
        import numpy as np

        from etl_tpu.config.pipeline import BatchEngine
        from etl_tpu.models import (ColumnSchema, Oid, ReplicatedTableSchema,
                                    TableName, TableSchema)
        from etl_tpu.postgres.codec import pgoutput
        from etl_tpu.runtime.assembler import EventAssembler

        rts = ReplicatedTableSchema.with_all_columns(TableSchema(
            7, TableName("public", "t"),
            (ColumnSchema("id", Oid.INT4, nullable=False,
                          primary_key_ordinal=1),)))
        a = EventAssembler(BatchEngine.TPU, seal_bytes=256)
        payload = pgoutput.encode_insert(7, [b"1"])
        for i in range(40):
            a.push_raw_row(payload, rts, Lsn(100 + i), Lsn(9999), i)
        events = a.flush()
        try:
            # one unbounded run would be a single event; the byte seal
            # must have cut it into several ≤ ~256-byte runs
            assert len(events) > 3
            total = sum(len(e.tx_ordinals) for e in events)
            assert total == 40
        finally:
            a.close()


class TestApplyLoopBreakerHold:
    def test_dispatch_blocked_matrix(self):
        from types import SimpleNamespace

        from etl_tpu.runtime.apply_loop import ApplyLoop
        from etl_tpu.supervision.breaker import BreakerState

        class FakeWindow:
            def __init__(self, empty, can):
                self.is_empty = empty
                self._can = can

            def can_dispatch(self, n):
                return self._can

        def ns(empty, can, breaker_state):
            breaker = None if breaker_state is None else \
                SimpleNamespace(state=breaker_state)
            return SimpleNamespace(
                _ack_window=FakeWindow(empty, can),
                destination=SimpleNamespace(breaker=breaker),
                assembler=SimpleNamespace(size_bytes=10),
                _flush_threshold=lambda: 10,
                _breaker_open=lambda s=None: ApplyLoop._breaker_open(
                    SimpleNamespace(destination=SimpleNamespace(
                        breaker=breaker))))

        # window full → blocked regardless of breaker
        assert ApplyLoop._dispatch_blocked(ns(False, False, None))
        # room + closed breaker → dispatch
        assert not ApplyLoop._dispatch_blocked(
            ns(True, True, BreakerState.CLOSED))
        # OPEN breaker + in-flight acks → hold (drain before shedding)
        assert ApplyLoop._dispatch_blocked(
            ns(False, True, BreakerState.OPEN))
        # OPEN breaker + EMPTY window → dispatch (the shed path: the
        # breaker fast-fails the call into worker backoff)
        assert not ApplyLoop._dispatch_blocked(
            ns(True, True, BreakerState.OPEN))


class TestDispatchBlockedByteCap:
    async def test_byte_cap_judges_prospective_flush_not_backlog(self):
        """Review regression: the byte-cap check must see the ≤threshold
        prefix the next flush would actually dispatch — judging the
        whole assembler backlog against the window cap would collapse
        the window to one-in-flight exactly when the backlog is
        largest."""
        from types import SimpleNamespace

        from etl_tpu.runtime.apply_loop import ApplyLoop

        w = AckWindow(4, max_bytes=100)
        ack, fut = WriteAck.accepted()
        w.dispatch(_submitter(ack), n_events=1, nbytes=60)
        await _settle()
        ns = SimpleNamespace(
            _ack_window=w,
            assembler=SimpleNamespace(size_bytes=10**9),  # huge backlog
            destination=SimpleNamespace(breaker=None),
            _flush_threshold=lambda: 30,  # the next flush is ≤ 30 bytes
            _breaker_open=lambda: False)
        # 60 in flight + a 30-byte prospective flush ≤ 100: must dispatch
        assert not ApplyLoop._dispatch_blocked(ns)
        ns._flush_threshold = lambda: 50
        # 60 + 50 > 100: the byte cap legitimately blocks
        assert ApplyLoop._dispatch_blocked(ns)
        fut.set_result(None)
        await _settle()
        w.pop_ready()


ACK_TID = 16395


async def _ack_pipeline(write_window: int, ack_delay_s: float,
                        max_size_bytes: int):
    """(db, store, inner, dest, pipeline): one two-column table streamed
    by the per-tuple engine into a memory sink whose every ack turns
    durable `ack_delay_s` late; started, table READY."""
    from etl_tpu.config import BatchConfig, BatchEngine, PipelineConfig
    from etl_tpu.destinations import (DelayedAckDestination,
                                      MemoryDestination)
    from etl_tpu.models import ColumnSchema, Oid, TableName, TableSchema
    from etl_tpu.models.table_state import TableStateType
    from etl_tpu.postgres.fake import FakeDatabase, FakeSource
    from etl_tpu.runtime import Pipeline
    from etl_tpu.store import NotifyingStore

    db = FakeDatabase()
    db.create_table(TableSchema(
        ACK_TID, TableName("public", "ack_t"),
        (ColumnSchema("id", Oid.INT8, nullable=False,
                      primary_key_ordinal=1),
         ColumnSchema("v", Oid.INT4))))
    db.create_publication("pub", [ACK_TID])
    store = NotifyingStore()
    inner = MemoryDestination()
    dest = DelayedAckDestination(inner, ack_delay_s)
    pipeline = Pipeline(
        config=PipelineConfig(
            pipeline_id=1, publication_name="pub",
            batch=BatchConfig(max_size_bytes=max_size_bytes,
                              max_fill_ms=10,
                              batch_engine=BatchEngine.CPU,
                              write_window=write_window)),
        store=store, destination=dest,
        source_factory=lambda: FakeSource(db))
    await pipeline.start()
    await asyncio.wait_for(
        store.notify_on(ACK_TID, TableStateType.READY), 60)
    return db, store, inner, dest, pipeline


def _delivered_rows(inner) -> list:
    """The sink's row events in arrival order, as (commit lsn, ordinal,
    values): a flat list, so it is the same however flushes were cut."""
    from etl_tpu.models import InsertEvent

    return [(int(e.commit_lsn), e.tx_ordinal, tuple(e.row.values))
            for e in inner.events if isinstance(e, InsertEvent)]


async def _drain_backlog(write_window: int, n_events: int = 300,
                         tx_size: int = 20) -> dict:
    """Commit the whole backlog, then let one pipeline drain it through
    `write_window` against 5 ms-late acks; what was delivered and how
    many acks were ever open at once."""
    from etl_tpu.telemetry.metrics import (
        ETL_DESTINATION_ACK_OVERLAP_SECONDS_TOTAL, registry)

    labels = {"path": "apply"}
    overlap0 = registry.get_counter(
        ETL_DESTINATION_ACK_OVERLAP_SECONDS_TOTAL, labels)
    db, _, inner, dest, pipeline = await _ack_pipeline(
        write_window, 0.005, max_size_bytes=2048)
    for first in range(0, n_events, tx_size):
        tx = db.transaction()
        for i in range(first, min(first + tx_size, n_events)):
            tx.insert(ACK_TID, [str(i), str(i % 97)])
        await tx.commit()
    while len(_delivered_rows(inner)) < n_events or dest.pending:
        assert not pipeline._apply_task.done(), "pipeline stopped early"
        await asyncio.sleep(0.005)
    await pipeline.shutdown_and_wait()
    return {
        "rows": _delivered_rows(inner),
        "max_acks_pending": dest.max_pending,
        "overlap_seconds": registry.get_counter(
            ETL_DESTINATION_ACK_OVERLAP_SECONDS_TOTAL, labels) - overlap0,
    }


class TestEndToEnd:
    async def test_window1_equivalence_and_overlap(self):
        """The same backlog through the default write window and through
        window=1: the same rows in the same order, never more than one
        ack open at window=1, and at the default window at least two
        open at once with overlap recorded."""
        from etl_tpu.config import BatchConfig

        windowed = await _drain_backlog(BatchConfig().write_window)
        serial = await _drain_backlog(1)
        assert len(serial["rows"]) == 300
        assert windowed["rows"] == serial["rows"]
        assert serial["max_acks_pending"] <= 1
        assert windowed["max_acks_pending"] >= 2
        assert windowed["overlap_seconds"] > 0

    async def test_drain_on_shutdown_waits_every_ack(self):
        """Shutdown with acks in flight: the drain must wait them out
        and persist durable progress for the full acked prefix."""
        from etl_tpu.postgres.slots import apply_slot_name

        db, store, inner, dest, pipeline = await _ack_pipeline(
            4, 0.15, max_size_bytes=512)
        last_commit = None
        for t in range(3):
            tx = db.transaction()
            for i in range(8):
                tx.insert(ACK_TID, [str(t * 8 + i + 1), str(i)])
            last_commit = await tx.commit()
        # writes reach the destination quickly; acks are still pending
        # when shutdown begins — the drain must wait them out
        while len(_delivered_rows(inner)) < 24:
            await asyncio.sleep(0.005)
        assert dest.pending >= 1
        await pipeline.shutdown_and_wait()
        assert dest.pending == 0
        durable = await store.get_durable_progress(apply_slot_name(1))
        # the drain consumed every acked entry: durable covers the whole
        # stream (commit END of the last transaction ≥ its commit lsn)
        assert durable is not None and int(durable) >= int(last_commit)

    async def test_chaos_k_inflight_crash(self):
        """The tier-1 chaos gate: hard-kill with ≥ 2 acks in flight,
        zero-loss, dup budget = the window, monotonic durable LSN."""
        from etl_tpu.chaos.ack_window import run_ack_window_crash

        run = await run_ack_window_crash(seed=11)
        assert run.ok, run.describe()
        assert run.acks_in_flight_at_kill >= 2
        assert run.report.stats["max_duplication"] <= \
            run.report.stats["duplication_budget"]


class TestAbandon:
    def test_abandoned_handle_returns_pooled_resources(self):
        """A hard-killed loop's flushed-but-undelivered window entries
        abandon their pending decodes: the staging arena and the decode
        window slot return without the fetch (the leak the chaos probe
        counts)."""
        import time as _time

        from etl_tpu.models import (ColumnSchema, Oid,
                                    ReplicatedTableSchema, TableName,
                                    TableSchema)
        from etl_tpu.ops import DecodePipeline, DeviceDecoder
        from etl_tpu.ops.staging import ARENA_POOL
        from etl_tpu.ops.wal import concat_payloads, stage_wal_batch
        from etl_tpu.postgres.codec import pgoutput

        rts = ReplicatedTableSchema.with_all_columns(TableSchema(
            7, TableName("public", "ab_t"),
            (ColumnSchema("id", Oid.INT4, nullable=False,
                          primary_key_ordinal=1),)))
        payloads = [pgoutput.encode_insert(7, [str(i).encode()])
                    for i in range(128)]
        buf, offs, lens = concat_payloads(payloads)
        staged = stage_wal_batch(buf, offs, lens, 1).staged
        dec = DeviceDecoder(rts, device_min_rows=1 << 30, host_min_rows=0)
        baseline = ARENA_POOL.outstanding
        pipe = DecodePipeline(window=2)
        try:
            handle = pipe.submit(dec, staged)
            deadline = _time.monotonic() + 10
            while not handle._future.done():
                assert _time.monotonic() < deadline
                _time.sleep(0.01)
            assert ARENA_POOL.outstanding > baseline
            handle.abandon()
            assert ARENA_POOL.outstanding == baseline
            assert len(pipe.window) == 0
            with pytest.raises(RuntimeError):
                handle.result()  # post-abandon consumption is forbidden
        finally:
            pipe.close()

    def test_abandon_after_result_is_noop(self):
        from etl_tpu.models import (ColumnSchema, Oid,
                                    ReplicatedTableSchema, TableName,
                                    TableSchema)
        from etl_tpu.ops import DecodePipeline, DeviceDecoder
        from etl_tpu.ops.staging import ARENA_POOL
        from etl_tpu.ops.wal import concat_payloads, stage_wal_batch
        from etl_tpu.postgres.codec import pgoutput

        rts = ReplicatedTableSchema.with_all_columns(TableSchema(
            7, TableName("public", "ab2_t"),
            (ColumnSchema("id", Oid.INT4, nullable=False,
                          primary_key_ordinal=1),)))
        payloads = [pgoutput.encode_insert(7, [str(i).encode()])
                    for i in range(128)]
        buf, offs, lens = concat_payloads(payloads)
        staged = stage_wal_batch(buf, offs, lens, 1).staged
        dec = DeviceDecoder(rts, device_min_rows=1 << 30, host_min_rows=0)
        baseline = ARENA_POOL.outstanding
        pipe = DecodePipeline(window=2)
        try:
            handle = pipe.submit(dec, staged)
            batch = handle.result()
            assert batch.num_rows == 128
            handle.abandon()  # already fetched: no double release
            assert ARENA_POOL.outstanding == baseline
            assert handle.result() is batch  # result stays idempotent
        finally:
            pipe.close()


class TestObservedSignatures:
    def test_record_load_roundtrip_and_corruption(self, tmp_path):
        from etl_tpu.ops import program_store as ps

        ps.reset_for_tests()
        ps.configure(str(tmp_path))
        try:
            key = (256, ((0, "K", 4, 8),), False, None, False, None, True)
            ps.record_observed(key)
            ps.record_observed(key)  # idempotent per process
            assert ps.load_observed() == [key]
            # corruption degrades to empty + deletion, never a crash
            import os

            path = ps._observed_path(str(tmp_path))
            with open(path, "wb") as f:
                f.write(b"garbage")
            assert ps.load_observed() == []
            assert not os.path.exists(path)
        finally:
            ps.configure(None)
            ps.reset_for_tests()

    def test_observed_cap_ages_out_oldest(self, tmp_path):
        from etl_tpu.ops import program_store as ps

        ps.reset_for_tests()
        ps.configure(str(tmp_path))
        try:
            for i in range(ps._OBSERVED_MAX + 5):
                ps.record_observed((i,))
            keys = ps.load_observed()
            assert len(keys) == ps._OBSERVED_MAX
            assert keys[0] == (5,)  # oldest five aged out
            assert keys[-1] == (ps._OBSERVED_MAX + 4,)
        finally:
            ps.configure(None)
            ps.reset_for_tests()

    def test_dispatch_records_host_signature(self, tmp_path):
        """A real host decode records its (canonical layout, row bucket)
        key, and warm_observed_signatures disk-loads it back into the
        in-process cache."""
        from etl_tpu.models import (ColumnSchema, Oid,
                                    ReplicatedTableSchema, TableName,
                                    TableSchema)
        from etl_tpu.ops import program_store as ps
        from etl_tpu.ops.engine import DeviceDecoder, _shared_fn_get
        from etl_tpu.ops.wal import concat_payloads, stage_wal_batch
        from etl_tpu.postgres.codec import pgoutput

        ps.reset_for_tests()
        ps.configure(str(tmp_path))
        try:
            rts = ReplicatedTableSchema.with_all_columns(TableSchema(
                7, TableName("public", "obs_t"),
                (ColumnSchema("id", Oid.INT4, nullable=False,
                              primary_key_ordinal=1),)))
            payloads = [pgoutput.encode_insert(7, [str(i).encode()])
                        for i in range(16)]
            buf, offs, lens = concat_payloads(payloads)
            staged = stage_wal_batch(buf, offs, lens, 1).staged
            dec = DeviceDecoder(rts, device_min_rows=1 << 30,
                                host_min_rows=0)
            dec.decode(staged)  # host path → records the signature
            keys = ps.load_observed()
            assert keys, "host dispatch recorded no observed signature"
            # the recorded key resolves through the shared cache after a
            # warm (memory hit here; a restarted process disk-loads)
            stats = ps.warm_observed_signatures()
            assert stats["observed"] >= 1
            assert stats["observed_ready"] >= 1
            assert _shared_fn_get(keys[-1]) is not None
        finally:
            ps.configure(None)
            ps.reset_for_tests()

    async def test_prewarm_pipeline_folds_observed(self, tmp_path):
        """prewarm_pipeline's stats carry the observed-signature fold,
        even with no stored schemas (the restart-prewarm path)."""
        from etl_tpu.config import BatchConfig
        from etl_tpu.ops import program_store as ps
        from etl_tpu.store import NotifyingStore

        ps.reset_for_tests()
        try:
            cfg = BatchConfig(program_cache_dir=str(tmp_path),
                              prewarm_programs=True)
            stats = await ps.prewarm_pipeline(NotifyingStore(), cfg)
            assert "observed" in stats
            assert stats["observed_missing"] == 0
        finally:
            ps.configure(None)
            ps.reset_for_tests()
