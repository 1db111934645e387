"""The program's own span recorder (etl_tpu/telemetry/spans.py) and the
spans the CDC and copy paths record with it.

Unit cases run against a private ring (monkeypatched module globals); the
end-to-end cases drive the full pipeline — the wire-level fake Postgres
for the CDC path, so the intake spans exist — and read the process-wide
ring from the instant they start.
"""

import asyncio
import collections
import statistics
import threading
import time
import tracemalloc

import numpy as np
import pytest

from etl_tpu.config import (BatchConfig, BatchEngine, PgConnectionConfig,
                            PipelineConfig)
from etl_tpu.config.pipeline import SupervisionConfig, TableSyncCopyConfig
from etl_tpu.destinations import MemoryDestination
from etl_tpu.models import ColumnSchema, Oid, TableName, TableSchema
from etl_tpu.postgres.client import PgReplicationClient
from etl_tpu.postgres.fake import FakeDatabase, FakeSource
from etl_tpu.runtime import Pipeline, TableStateType
from etl_tpu.store import NotifyingStore
from etl_tpu.telemetry import registry, spans
from etl_tpu.testing.fake_pg_server import FakePgServer

TID = 16400

#: every span of the CDC path that any destination records (the ClickHouse
#: ones and `flush.blocked` have cases of their own)
CDC_SPANS = [
    "loop.select_wait", "intake.drain", "intake.segment",
    "apply.frame_walk", "assemble.seal", "flush.fill", "flush.write",
    "flush.ack", "apply.progress_store", "apply.status_update",
    "decode.route", "decode.window_wait", "decode.admission_wait",
    "decode.pack", "decode.dispatch", "decode.handoff_wait",
    "decode.result_wait", "decode.unpack", "monitor.tick"]
COPY_SPANS = ["copy.read_wait", "copy.cut", "copy.stage",
              "copy.decode_wait", "copy.write", "copy.ack_wait"]
#: what the loop thread itself does (or waits in): the records that time a
#: flush's life across callbacks overlap these by design and are left out
LOOP_WORK = ["intake.drain", "intake.segment", "apply.frame_walk",
             "assemble.seal", "apply.progress_store", "apply.status_update",
             "decode.handoff_wait", "decode.result_wait", "decode.unpack",
             "monitor.tick", "supervisor.sweep"]


@pytest.fixture
def ring(monkeypatch):
    """A private 8-slot ring: the process-wide one keeps what other tests
    and pipelines recorded."""
    import itertools

    monkeypatch.setattr(spans, "CAPACITY", 8)
    monkeypatch.setattr(spans, "_ring", [None] * 8)
    monkeypatch.setattr(spans, "_seq", itertools.count())
    return spans


def make_db(rows: int = 0) -> FakeDatabase:
    db = FakeDatabase()
    db.create_table(TableSchema(
        TID, TableName("public", "ledger"),
        (ColumnSchema("id", Oid.INT4, nullable=False, primary_key_ordinal=1),
         ColumnSchema("bid", Oid.INT4),
         ColumnSchema("amount", Oid.INT8))),
        rows=[[str(i), str(i % 7), str(i * 3)] for i in range(rows)])
    db.create_publication("pub", [TID])
    return db


def union_ns(t0, t1, lo, hi) -> int:
    """Length of the union of [t0, t1) intervals clipped to [lo, hi)."""
    iv = sorted((max(a, lo), min(b, hi)) for a, b in zip(t0, t1))
    total, end = 0, lo
    for a, b in iv:
        if b > max(a, end):
            total += b - max(a, end)
            end = b
    return total


class TestRing:
    def test_wraps_at_capacity_and_keeps_the_newest(self, ring):
        for i in range(20):
            ring.record("r", i * 10, i * 10 + 5, batch_id=i + 1)
        assert len(ring._ring) == 8
        snap = ring.snapshot()
        assert snap["id"].tolist() == list(range(13, 21))
        assert snap["t0_ns"].tolist() == [i * 10 for i in range(12, 20)]

    def test_full_ring_stays_under_sixteen_megabytes(self, monkeypatch):
        import itertools

        monkeypatch.setattr(spans, "_seq", itertools.count())
        tracemalloc.start()
        try:
            monkeypatch.setattr(spans, "_ring", [None] * spans.CAPACITY)
            for i in range(spans.CAPACITY + 1000):
                spans.record("m", time.perf_counter_ns(),
                             time.perf_counter_ns(), flush_id=100000 + i,
                             parent=200000 + i)
            size, _peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        assert len(spans._ring) == spans.CAPACITY >= 45_000  # 15 s x 3,000/s
        assert size < 16 << 20

    def test_snapshot_clips_to_the_window(self, ring):
        ring.record("before", 0, 90)
        ring.record("straddles_lo", 90, 110)
        ring.record("inside", 120, 130)
        ring.record("straddles_hi", 190, 210)
        ring.record("after", 200, 300)
        snap = ring.snapshot(100, 200)
        assert snap["name"].tolist() == ["straddles_lo", "inside",
                                         "straddles_hi"]
        assert ring.names() == ["after", "before", "inside",
                                "straddles_hi", "straddles_lo"]
        empty = ring.snapshot(1000, 2000)
        assert len(empty["name"]) == 0 and empty["t0_ns"].dtype == np.int64

    def test_snapshot_merges_threads_in_time_order(self, ring):
        ring.record("main", 30, 40)

        def other():
            ring.record("other", 10, 20)
            ring.record("other", 50, 60)

        t = threading.Thread(target=other)
        t.start()
        t.join()
        snap = ring.snapshot()
        assert snap["name"].tolist() == ["other", "main", "other"]
        assert snap["thread"][0] == snap["thread"][2] == t.ident
        assert snap["thread"][1] == threading.get_ident()

    async def test_appends_from_two_threads_and_the_loop_lose_nothing(
            self, monkeypatch):
        import itertools

        n = 5000
        monkeypatch.setattr(spans, "_ring", [None] * spans.CAPACITY)
        monkeypatch.setattr(spans, "_seq", itertools.count())

        def worker(tag: str):
            for i in range(n):
                with spans.span(tag, batch_id=i + 1):
                    pass

        jobs = [asyncio.to_thread(worker, "w1"),
                asyncio.to_thread(worker, "w2")]
        pending = asyncio.gather(*jobs)
        for i in range(n):
            spans.record("loop", i, i + 1, flush_id=i + 1)
            if i % 500 == 0:
                await asyncio.sleep(0)
        await pending
        snap = spans.snapshot()
        for tag in ("w1", "w2", "loop"):
            ids = snap["id"][snap["name"] == tag]
            assert sorted(ids.tolist()) == list(range(1, n + 1)), tag

    def test_more_threads_than_cores_lose_no_record_and_no_observation(
            self, monkeypatch):
        """Stress, time-bounded: 12 threads on a 10 us switch interval
        append and observe while this thread folds; a lost ring slot or a
        doubly folded batch would break the counts."""
        import itertools
        import sys

        workers, each = 12, 2000
        name = "etl_test_stress_seconds"
        monkeypatch.setattr(spans, "_ring", [None] * spans.CAPACITY)
        monkeypatch.setattr(spans, "_seq", itertools.count())
        monkeypatch.setattr(spans, "_PENDING_MAX", 64)
        before = registry.get_histogram(name)[0]

        def worker(k: int):
            for i in range(each):
                spans.record("stress", i, i + 1000, name,
                             batch_id=k * each + i + 1)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=worker, args=(k,))
                       for k in range(workers)]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 60
            while any(t.is_alive() for t in threads):
                spans.fold()
                assert time.monotonic() < deadline
            for t in threads:
                t.join(timeout=10)
                assert not t.is_alive()
        finally:
            sys.setswitchinterval(interval)
        ids = spans.snapshot()["id"]
        assert sorted(ids.tolist()) == list(range(1, workers * each + 1))
        assert registry.get_histogram(name)[0] - before == workers * each

    def test_span_with_a_series_moves_the_histogram(self, ring):
        before = registry.get_histogram("etl_test_span_seconds")
        with ring.span("timed", "etl_test_span_seconds"):
            time.sleep(0.002)
        ring.record("given", 0, 3_000_000, "etl_test_span_seconds")
        count, total = registry.get_histogram("etl_test_span_seconds")
        assert count - before[0] == 2
        assert 0.005 <= total - before[1] < 0.5
        snap = ring.snapshot()
        timed = snap["name"] == "timed"
        assert (snap["t1_ns"] - snap["t0_ns"])[timed][0] >= 2_000_000

    def test_observations_wait_for_a_read_or_a_full_list(self, ring,
                                                         monkeypatch):
        name = "etl_test_deferred_seconds"
        monkeypatch.setattr(spans, "_PENDING_MAX", 3)
        spans.fold()
        ring.record("a", 0, 1_000_000, name)
        ring.record("a", 0, 1_000_000, name)
        assert name not in registry._histograms  # nothing observed yet
        ring.record("a", 0, 1_000_000, name)  # the third folds all three
        assert registry._histograms[name][()].count == 3
        ring.record("a", 0, 2_000_000, name)
        # any read of the registry settles what is pending
        assert registry.get_histogram(name) == (4, pytest.approx(0.005))
        assert name + "_count 4" in registry.render_prometheus()

    def test_dropped_span_leaves_nothing(self, ring):
        before = registry.get_histogram("etl_test_drop_seconds")
        with ring.span("dropped", "etl_test_drop_seconds") as sp:
            sp.drop()
        assert len(ring.snapshot()["name"]) == 0
        assert registry.get_histogram("etl_test_drop_seconds") == before

    def test_span_records_when_the_body_raises(self, ring):
        with pytest.raises(ValueError):
            with ring.span("raises", batch_id=7):
                raise ValueError("x")
        snap = ring.snapshot()
        assert snap["name"].tolist() == ["raises"]
        assert snap["id"].tolist() == [7]

    @pytest.mark.parametrize("ids,want", [
        ({}, (0, 0)),
        ({"batch_id": 5, "rows": 100}, (5, 0)),
        ({"flush_id": 9, "parent": 5}, (9, 5)),
        ({"partition": 3, "mode": "host"}, (0, 0)),
    ])
    def test_which_keywords_are_the_identifiers(self, ring, ids, want):
        with ring.span("s", **ids):
            pass
        snap = ring.snapshot()
        assert (int(snap["id"][0]), int(snap["parent"][0])) == want

    def test_span_outside_a_profiler_session_is_cheap(self):
        import jax.profiler  # noqa: F401 — the annotation path is live

        costs = []
        for _ in range(10_000):
            t0 = time.perf_counter_ns()
            with spans.span("cost", batch_id=1):
                pass
            costs.append(time.perf_counter_ns() - t0)
        # generous: ~1 us here; the budget in docs/OPERATIONS.md is 3 us
        assert statistics.median(costs) < 5_000

    def test_ids_are_unique_and_rising(self):
        a, b = spans.next_batch_id(), spans.next_batch_id()
        f, g = spans.next_flush_id(), spans.next_flush_id()
        assert b == a + 1 and g == f + 1


class TestDevicePrograms:
    def _specs(self):
        from etl_tpu.models.pgtypes import CellKind

        return ((0, CellKind.I32, 12, 0), (1, CellKind.I64, 20, 0))

    def test_decode_program_is_named_and_scoped(self):
        from etl_tpu.ops import engine

        _fn, _avals, lowered = engine.lower_program(self._specs(), 256)
        text = lowered.as_text(debug_info=True)
        assert "module @jit_etl_decode " in text
        assert "jit_fn" not in text
        for scope in ("gather", "parse_i32", "parse_i64", "bitpack"):
            assert f"/{scope}" in text or f"{scope}/" in text, scope

    def test_filtered_program_is_named_and_compacts(self):
        from etl_tpu.analysis.ir import catalog
        from etl_tpu.ops import engine

        _name, schema = catalog.filtered_schema()
        dec = catalog._decoder(schema)
        _fn, _avals, lowered = engine.lower_program(
            dec._host_specs(), 256, pred=dec._row_filter)
        text = lowered.as_text(debug_info=True)
        assert "module @jit_etl_decode_filter " in text
        assert "compact" in text

    def test_pallas_program_is_named(self):
        from etl_tpu.ops import engine

        _fn, _avals, lowered = engine.lower_program(
            self._specs(), 256, use_pallas=True)
        assert "module @jit_etl_decode_pallas " in lowered.as_text()

    def test_egress_program_is_named_and_scoped(self):
        from etl_tpu.ops import egress

        _fn, _avals, lowered = egress.lower_egress_program(
            self._specs(), "tsv", 256)
        text = lowered.as_text(debug_info=True)
        assert "module @jit_etl_egress " in text
        assert "render" in text


# ---------------------------------------------------------------------------
# end to end
# ---------------------------------------------------------------------------


def wire_pipeline(server, destination, **batch):
    conn = PgConnectionConfig(host="127.0.0.1", port=server.port,
                              name="postgres", username="etl")
    store = NotifyingStore()
    pipeline = Pipeline(
        config=PipelineConfig(
            pipeline_id=25, publication_name="pub", pg_connection=conn,
            batch=BatchConfig(batch_engine=BatchEngine.TPU,
                              **{"max_fill_ms": 40, **batch})),
        store=store, destination=destination,
        source_factory=lambda: PgReplicationClient(conn))
    return pipeline, store


async def commit_rows(db, first: int, n: int) -> None:
    async with db.transaction() as tx:
        for i in range(first, first + n):
            tx.insert(TID, [str(i), str(i % 7), str(i * 3)])


async def until(predicate, timeout: float = 60.0) -> None:
    deadline = time.monotonic() + timeout
    while not predicate():
        assert time.monotonic() < deadline, "timed out"
        await asyncio.sleep(0.02)


def inserted(dest) -> int:
    return sum(1 for e in dest.events if type(e).__name__ == "InsertEvent")


class TestCdcPathSpans:
    """The wire-level fake Postgres into a memory destination, as
    tests/test_wire_client.py builds it: 500-row transactions (the
    benchmark's size), so runs route to the host program once it has
    compiled."""

    ROWS = 500

    async def _run(self, n_tx: int = 20):
        db = make_db()
        server = FakePgServer(db, keepalive_interval_s=0.05)
        await server.start()
        dest = MemoryDestination()
        pipeline, store = wire_pipeline(server, dest)
        try:
            await pipeline.start()
            await asyncio.wait_for(
                store.notify_on(TID, TableStateType.READY), 30)
            # the first runs decode on the oracle while the host program
            # compiles in the background: wait for one that packs
            sent = 0
            warm_from = spans.now_ns()
            while "decode.pack" not in \
                    spans.snapshot(warm_from)["name"].tolist():
                await commit_rows(db, sent, self.ROWS)
                sent += self.ROWS
                await until(lambda: inserted(dest) >= sent)
                assert sent < 400 * self.ROWS, "the host program never ran"
            lo = spans.now_ns()
            drains = registry.get_histogram("etl_intake_drain_seconds")[0]
            frames = registry.get_counter("etl_intake_frames_total")
            for _ in range(n_tx):
                await commit_rows(db, sent, self.ROWS)
                sent += self.ROWS
                await until(lambda: inserted(dest) >= sent)
            # the status update that covers the last flush
            await asyncio.sleep(0.15)
            hi = spans.now_ns()
            counts = {
                "drains": registry.get_histogram(
                    "etl_intake_drain_seconds")[0] - drains,
                "frames": registry.get_counter(
                    "etl_intake_frames_total") - frames}
        finally:
            await pipeline.shutdown_and_wait()
            await server.stop()
        return spans.snapshot(lo, hi), lo, hi, counts

    async def test_every_cdc_span_appears_and_none_is_per_frame(self):
        snap, _lo, _hi, counts = await self._run()
        seen = collections.Counter(snap["name"].tolist())
        assert not [n for n in CDC_SPANS if not seen[n]], seen
        # 20 transactions of 502 frames each: thousands of frames, and the
        # intake spans count drains
        assert counts["frames"] >= 20 * (self.ROWS + 2)
        assert seen["intake.drain"] <= counts["drains"] + 1
        assert seen["intake.segment"] <= seen["intake.drain"]
        assert seen["intake.drain"] < counts["frames"] / 20
        # per flush, not per row: every name stays far below the row count
        per_tx = sum(seen[n] for n in CDC_SPANS
                     if n not in ("loop.select_wait", "monitor.tick")) / 20
        assert per_tx <= 40, seen

    async def test_loop_thread_spans_cover_its_busy_time(self):
        """Between two selects the apply loop's task runs: at least nine
        tenths of that time lies inside a span the loop thread recorded.
        (The fake server and the producer share the thread in this test,
        but they run while the apply loop waits in its select.)"""
        snap, lo, hi, _ = await self._run()
        sel = snap["name"] == "loop.select_wait"
        loop_thread = collections.Counter(
            snap["thread"][sel].tolist()).most_common(1)[0][0]
        mine = snap["thread"] == loop_thread
        t0, t1 = snap["t0_ns"], snap["t1_ns"]
        waiting = union_ns(t0[sel & mine], t1[sel & mine], lo, hi)
        work = np.isin(snap["name"], LOOP_WORK) & mine
        either = union_ns(t0[(sel | work) & mine], t1[(sel | work) & mine],
                          lo, hi)
        busy = (hi - lo) - waiting
        unspanned = (hi - lo) - either
        assert busy > 0
        assert unspanned <= 0.10 * busy, (unspanned, busy)

    async def test_a_flush_carries_its_ids(self):
        snap, _lo, _hi, _ = await self._run(n_tx=5)
        by = {n: snap["name"] == n for n in
              ("flush.fill", "flush.write", "flush.ack", "assemble.seal",
               "decode.pack", "decode.unpack", "apply.status_update")}
        seals = set(snap["id"][by["assemble.seal"]].tolist())
        fills = snap["id"][by["flush.fill"]].tolist()
        assert len(fills) >= 5 and 0 not in seals
        for flush_id in set(fills):
            assert flush_id in snap["id"][by["flush.write"]].tolist()
            assert flush_id in snap["id"][by["flush.ack"]].tolist()
        # flush.fill names the batches it consumed as parent, and those
        # are batches a seal minted and the decode spans carry
        parents = set(snap["parent"][by["flush.fill"]].tolist())
        assert parents <= seals and parents
        assert parents <= set(snap["id"][by["decode.unpack"]].tolist())
        assert set(snap["id"][by["decode.pack"]].tolist()) <= seals
        # fill -> write -> ack are one chain in time
        for flush_id in set(fills):
            fill_end = snap["t1_ns"][by["flush.fill"]
                                     & (snap["id"] == flush_id)].max()
            write = by["flush.write"] & (snap["id"] == flush_id)
            ack = by["flush.ack"] & (snap["id"] == flush_id)
            assert snap["t0_ns"][write][0] <= fill_end
            assert snap["t0_ns"][ack][0] == snap["t1_ns"][write][0]
        assert set(snap["id"][by["apply.status_update"]].tolist()) \
            & set(fills)


class TestCopyPathSpans:
    async def test_a_parallel_copy_records_every_copy_span(self):
        db = make_db(rows=6000)
        dest = MemoryDestination()
        store = NotifyingStore()
        pipeline = Pipeline(
            config=PipelineConfig(
                pipeline_id=26, publication_name="pub",
                batch=BatchConfig(batch_engine=BatchEngine.TPU,
                                  max_size_bytes=16 * 1024, max_fill_ms=40),
                table_sync_copy=TableSyncCopyConfig(
                    max_connections=2, partitions_per_connection=2,
                    rows_per_partition_target=1500)),
            store=store, destination=dest,
            source_factory=lambda: FakeSource(db))
        lo = spans.now_ns()
        rows = registry.get_counter("etl_table_copy_rows_total")
        try:
            await pipeline.start()
            await asyncio.wait_for(
                store.notify_on(TID, TableStateType.READY), 60)
        finally:
            await pipeline.shutdown_and_wait()
        snap = spans.snapshot(lo)
        seen = collections.Counter(snap["name"].tolist())
        assert not [n for n in COPY_SPANS if not seen[n]], seen
        assert registry.get_counter("etl_table_copy_rows_total") - rows \
            == 6000
        # a span per chunk and stage, never per row or per socket read
        chunks = seen["copy.cut"]
        assert 2 <= chunks <= 200
        assert seen["copy.read_wait"] == chunks
        assert seen["copy.stage"] <= chunks
        # every chunk's spans carry its batch_id
        staged = set(snap["id"][snap["name"] == "copy.stage"].tolist())
        assert 0 not in staged
        for name in ("copy.decode_wait", "copy.write"):
            assert set(snap["id"][snap["name"] == name].tolist()) == staged
        for series in ("etl_copy_read_wait_seconds", "etl_copy_cut_seconds",
                       "etl_copy_stage_seconds",
                       "etl_copy_decode_wait_seconds",
                       "etl_copy_write_seconds",
                       "etl_copy_ack_wait_seconds"):
            assert registry.get_histogram(series)[0] > 0, series


class TestBlockedAndLoopSeries:
    async def test_a_full_window_records_flush_blocked(self):
        """One write in flight at a time into a destination that acks
        20 ms late: commits that arrive meanwhile are due and held."""
        from etl_tpu.destinations import DelayedAckDestination

        db = make_db()
        dest = DelayedAckDestination(MemoryDestination(), 0.02)
        store = NotifyingStore()
        pipeline = Pipeline(
            config=PipelineConfig(
                pipeline_id=27, publication_name="pub",
                batch=BatchConfig(batch_engine=BatchEngine.TPU,
                                  max_fill_ms=20, write_window=1)),
            store=store, destination=dest,
            source_factory=lambda: FakeSource(db))
        lo = spans.now_ns()
        blocked = registry.get_counter(
            "etl_apply_dispatch_blocked_seconds_total")
        try:
            await pipeline.start()
            await asyncio.wait_for(
                store.notify_on(TID, TableStateType.READY), 30)
            for k in range(12):
                await commit_rows(db, k * 10, 10)
                await asyncio.sleep(0.005)
            await until(lambda: inserted(dest.inner) >= 120)
        finally:
            await pipeline.shutdown_and_wait()
        snap = spans.snapshot(lo)
        held = snap["name"] == "flush.blocked"
        assert held.any()
        assert set(snap["id"][held].tolist()) \
            <= set(snap["id"][snap["name"] == "flush.write"].tolist())
        seconds = registry.get_counter(
            "etl_apply_dispatch_blocked_seconds_total") - blocked
        ring_s = float((snap["t1_ns"] - snap["t0_ns"])[held].sum()) / 1e9
        assert seconds == pytest.approx(ring_s, rel=1e-6) and seconds > 0

    async def test_the_monitor_tick_reports_loop_lag_and_loop_cpu(self):
        from etl_tpu.config.pipeline import MemoryBackpressureConfig
        from etl_tpu.runtime.backpressure import MemoryMonitor

        monitor = MemoryMonitor(
            MemoryBackpressureConfig(refresh_interval_ms=10),
            limit_bytes=1 << 40)
        lag = registry.get_histogram("etl_event_loop_lag_seconds")
        cpu = registry.get_counter("etl_loop_thread_cpu_seconds_total")
        lo = spans.now_ns()
        monitor.start()
        try:
            await asyncio.sleep(0.03)
            # hold the loop thread: the next wake-up is ~50 ms late and the
            # thread burns that much CPU
            t_end = time.thread_time() + 0.06
            while time.thread_time() < t_end:
                pass
            await asyncio.sleep(0.03)
        finally:
            await monitor.stop()
        count, total = registry.get_histogram("etl_event_loop_lag_seconds")
        assert count - lag[0] >= 3
        assert total - lag[1] >= 0.03
        burned = registry.get_counter(
            "etl_loop_thread_cpu_seconds_total") - cpu
        assert burned >= 0.04
        ticks = spans.snapshot(lo)
        assert (ticks["name"] == "monitor.tick").sum() >= 3

    async def test_two_monitors_count_the_loop_threads_cpu_once(self):
        from etl_tpu.config.pipeline import MemoryBackpressureConfig
        from etl_tpu.runtime.backpressure import MemoryMonitor

        cfg = MemoryBackpressureConfig(refresh_interval_ms=10)
        monitors = [MemoryMonitor(cfg, limit_bytes=1 << 40)
                    for _ in range(2)]
        for m in monitors:
            m.start()
        await asyncio.sleep(0.02)
        cpu = registry.get_counter("etl_loop_thread_cpu_seconds_total")
        t0 = time.thread_time()
        try:
            t_end = time.thread_time() + 0.05
            while time.thread_time() < t_end:
                pass
            await asyncio.sleep(0.03)
        finally:
            for m in monitors:
                await m.stop()
        spent = time.thread_time() - t0
        counted = registry.get_counter(
            "etl_loop_thread_cpu_seconds_total") - cpu
        assert 0.04 <= counted <= spent + 0.01


class TestClickHouseSpans:
    async def test_render_request_and_egress_fetch(self):
        from etl_tpu.destinations.clickhouse import (ClickHouseConfig,
                                                     ClickHouseDestination)
        from etl_tpu.destinations.util import DestinationRetryPolicy
        from etl_tpu.testing.fake_http import RecordingHttpServer

        http = RecordingHttpServer()
        await http.start()
        db = make_db()
        dest = ClickHouseDestination(
            ClickHouseConfig(url=http.url(), database="etl"),
            DestinationRetryPolicy(max_attempts=3, initial_delay_s=0.01,
                                   max_delay_s=0.05))
        store = NotifyingStore()
        pipeline = Pipeline(
            config=PipelineConfig(
                pipeline_id=28, publication_name="pub",
                batch=BatchConfig(batch_engine=BatchEngine.TPU,
                                  max_fill_ms=20),
                # the supervision wrapper does not forward the
                # destination's `egress_encoder` (PERF.md section 7): only
                # an unsupervised pipeline runs the egress program
                supervision=SupervisionConfig(enabled=False)),
            store=store, destination=dest,
            source_factory=lambda: FakeSource(db))
        lo = spans.now_ns()
        requests = registry.get_histogram("etl_clickhouse_request_seconds")
        try:
            await pipeline.start()
            await asyncio.wait_for(
                store.notify_on(TID, TableStateType.READY), 30)
            sent = 0

            def names():
                return set(spans.snapshot(lo)["name"].tolist())

            # the egress program compiles in the background: batches ship
            # without wire buffers until it is there
            while "decode.egress_fetch" not in names():
                await commit_rows(db, sent, 200)
                sent += 200
                await asyncio.sleep(0.1)
                assert sent < 600 * 200, "the egress program never ran"
        finally:
            await pipeline.shutdown_and_wait()
            await http.stop()
        snap = spans.snapshot(lo)
        seen = collections.Counter(snap["name"].tolist())
        assert seen["ch.render"] and seen["ch.request"] >= seen["ch.render"]
        assert registry.get_histogram(
            "etl_clickhouse_request_seconds")[0] - requests[0] \
            == seen["ch.request"]
        assert registry.get_histogram(
            "etl_clickhouse_render_seconds")[0] >= seen["ch.render"]
        assert registry.get_histogram(
            "etl_decode_egress_fetch_seconds")[0] >= 1


class TestRetiredSeries:
    def test_device_decode_seconds_is_gone(self):
        from etl_tpu.telemetry import metrics

        assert not hasattr(metrics, "ETL_DEVICE_DECODE_SECONDS")
        assert "etl_device_decode_seconds" not in \
            registry.render_prometheus()

    async def test_a_deduplicated_restream_counts_its_rows(self):
        """etl_exactly_once_dedup_rows_total was documented and never
        emitted: the transactional memory sink now counts the rows it
        refuses, by mode."""
        from etl_tpu.destinations import TransactionalMemoryDestination
        from etl_tpu.destinations.base import CommitRange
        from etl_tpu.models import InsertEvent, Lsn, TableRow
        from etl_tpu.models import ReplicatedTableSchema

        schema = ReplicatedTableSchema.with_all_columns(
            make_db().tables[TID].schema)
        events = [InsertEvent(Lsn(0x100), Lsn(0x100), i, schema,
                              TableRow([i, 1, 2])) for i in range(3)]
        sink = TransactionalMemoryDestination()

        def counted(mode):
            return registry.get_counter("etl_exactly_once_dedup_rows_total",
                                        {"mode": mode})

        stream, replay = counted("stream"), counted("replay")
        commit = CommitRange.from_events(events, commit_end_lsn=Lsn(0x200))
        await sink.write_event_batches_committed(events, commit)
        await sink.write_event_batches_committed(events, commit)
        assert counted("stream") - stream == 3 == sink.dedup_skipped_rows
        again = CommitRange.from_events(events, replay=True)
        await sink.write_event_batches_committed(events, again)
        await sink.write_event_batches_committed(events, again)
        assert counted("replay") - replay == 3 == sink.replay_skipped_rows
