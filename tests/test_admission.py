"""Fair batch-admission scheduler tests (ops/pipeline.AdmissionScheduler):
stride-scheduling fairness math, lag weighting, starvation aging, the
bypass liveness valve, memory-pressure capacity, ticket reclamation on
close, and the DecodePipeline integration (N pipelines sharing one
device set stay byte-identical to serial decode and leak nothing)."""

import threading
import time

import pytest

from etl_tpu.models import Oid
from etl_tpu.ops import stage_tuples
from etl_tpu.ops.engine import DeviceDecoder
from etl_tpu.ops.pipeline import AdmissionScheduler, DecodePipeline
from etl_tpu.telemetry.metrics import (
    ETL_DECODE_ADMISSION_BYPASS_GRANTS_TOTAL,
    ETL_DECODE_ADMISSION_GRANTS_TOTAL,
    ETL_DECODE_ADMISSION_STARVATION_GRANTS_TOTAL,
    ETL_DECODE_ADMISSION_WAIT_SECONDS, registry)
from tests.test_ops_decode import (assert_batches_equal, make_schema,
                                   tuples_from_texts)

MB64 = 64 * 1024 * 1024


def _drain_grant(sched, tenant):
    """Apply one grant's bookkeeping the way _acquire does (fairness-math
    unit tests drive _pick directly so thread timing can't blur the
    stride arithmetic)."""
    sched._vt = max(sched._vt, tenant._pass)
    tenant._pass += sched.STRIDE / sched._weight(tenant)
    tenant._grants += 1


class TestSchedulerUnits:
    def test_acquire_release_counts(self):
        s = AdmissionScheduler(2)
        t = s.register("a")
        t.acquire()
        assert s.in_flight == 1 and t.held == 1
        t.release()
        assert s.in_flight == 0 and t.held == 0

    def test_release_without_hold_is_noop(self):
        s = AdmissionScheduler(1)
        t = s.register("a")
        t.release()
        assert s.in_flight == 0

    def test_stride_split_proportional_to_lag_weight(self):
        # B lags 7×64MB → weight 8; over 90 contended grants the stride
        # invariant gives B eight grants for each of A's (±1)
        s = AdmissionScheduler(1, starvation_s=999.0)
        a = s.register("a", lag_bytes=lambda: 0)
        b = s.register("b", lag_bytes=lambda: 7 * MB64)
        now = time.monotonic()
        a._wait_since = now
        b._wait_since = now
        for _ in range(90):
            picked = s._pick(now)
            assert picked is not None and not picked[1]
            _drain_grant(s, picked[0])
        assert 9 <= a._grants <= 11
        assert a._grants + b._grants == 90

    def test_zero_lag_tenant_never_locked_out(self):
        # even against an infinitely-lagging tenant, the weight clamp
        # keeps A's share at 1/max_weight — not zero
        s = AdmissionScheduler(1, starvation_s=999.0, max_weight=16.0)
        a = s.register("a", lag_bytes=lambda: 0)
        b = s.register("b", lag_bytes=lambda: float("inf"))
        now = time.monotonic()
        a._wait_since = now
        b._wait_since = now
        for _ in range(64):
            _drain_grant(s, s._pick(now)[0])
        assert a._grants >= 3  # 64/16 = 4 expected, ±1

    def test_starvation_aging_overrides_weight(self):
        s = AdmissionScheduler(1, starvation_s=0.05)
        a = s.register("a", lag_bytes=lambda: 0)
        b = s.register("b", lag_bytes=lambda: 100 * MB64)
        t0 = time.monotonic()
        a._wait_since = t0
        b._wait_since = t0
        # before the deadline: weight wins — after the cold-start tie is
        # broken, b's tiny stride keeps it ahead of a for a long run
        _drain_grant(s, s._pick(t0 + 0.01)[0])
        for _ in range(10):
            picked, starved = s._pick(t0 + 0.01)
            assert picked is b and not starved
            _drain_grant(s, picked)
        # past the deadline both are starved: FIFO among starved; tie on
        # wait_since resolves deterministically and the grant is flagged
        a._wait_since = t0
        b._wait_since = t0 + 0.001
        picked, starved = s._pick(t0 + 0.2)
        assert picked is a and starved

    def test_bad_lag_provider_degrades_to_weight_one(self):
        s = AdmissionScheduler(1)

        def boom():
            raise RuntimeError("lag reader died")

        t = s.register("a", lag_bytes=boom)
        assert s._weight(t) == 1.0

    def test_blocked_acquire_wakes_on_release(self):
        s = AdmissionScheduler(1)
        a = s.register("a")
        b = s.register("b")
        a.acquire()
        granted = threading.Event()

        def waiter():
            b.acquire()
            granted.set()

        th = threading.Thread(target=waiter, daemon=True)
        th.start()
        time.sleep(0.08)
        assert not granted.is_set(), "capacity 1 must block the second"
        a.release()
        assert granted.wait(2.0)
        b.release()
        th.join(2.0)
        assert s.in_flight == 0

    def test_bypass_valve_overshoots_capacity(self):
        before = registry.get_counter(
            ETL_DECODE_ADMISSION_BYPASS_GRANTS_TOTAL, {"pipeline": "b"})
        s = AdmissionScheduler(1)
        a = s.register("a")
        b = s.register("b")
        a.acquire()
        b.acquire(bypass=lambda: True)  # demanded consumer: no deadlock
        assert s.in_flight == 2  # overshoot, accounted symmetrically
        assert registry.get_counter(
            ETL_DECODE_ADMISSION_BYPASS_GRANTS_TOTAL,
            {"pipeline": "b"}) == before + 1
        a.release()
        b.release()
        assert s.in_flight == 0

    def test_memory_pressure_shrinks_capacity_to_one(self):
        class FakeMonitor:
            pressure = True

        s = AdmissionScheduler(4)
        s.register("a", monitor=FakeMonitor())
        assert s.effective_capacity == 1

    def test_close_reclaims_held_tickets_and_deregisters(self):
        s = AdmissionScheduler(4)
        a = s.register("a")
        b = s.register("b")
        a.acquire()
        a.acquire()
        b.acquire()
        assert s.in_flight == 3
        a.close()
        assert s.in_flight == 1 and a.held == 0 and a.closed
        a.release()  # late release from a drained handle: no-op
        assert s.in_flight == 1
        with pytest.raises(RuntimeError):
            a.acquire()
        b.close()
        assert s.in_flight == 0
        assert s.stats()["tenants"] == {}

    def test_grant_telemetry_observed(self):
        g0 = registry.get_counter(ETL_DECODE_ADMISSION_GRANTS_TOTAL,
                                  {"pipeline": "telem"})
        h0, _ = registry.get_histogram(ETL_DECODE_ADMISSION_WAIT_SECONDS,
                                       {"pipeline": "telem"})
        s = AdmissionScheduler(2)
        t = s.register("telem")
        t.acquire()
        t.release()
        assert registry.get_counter(ETL_DECODE_ADMISSION_GRANTS_TOTAL,
                                    {"pipeline": "telem"}) == g0 + 1
        h1, _ = registry.get_histogram(ETL_DECODE_ADMISSION_WAIT_SECONDS,
                                       {"pipeline": "telem"})
        assert h1 == h0 + 1

    def test_starvation_grant_counted_end_to_end(self):
        # threaded: A hogs the only slot long enough for B to age out,
        # then B's grant must be flagged as a starvation grant
        c0 = registry.get_counter(
            ETL_DECODE_ADMISSION_STARVATION_GRANTS_TOTAL,
            {"pipeline": "slow"})
        s = AdmissionScheduler(1, starvation_s=0.05)
        a = s.register("hog", lag_bytes=lambda: 100 * MB64)
        b = s.register("slow", lag_bytes=lambda: 0)
        a.acquire()
        done = threading.Event()

        def waiter():
            b.acquire()
            done.set()

        th = threading.Thread(target=waiter, daemon=True)
        th.start()
        time.sleep(0.12)  # b ages past the starvation deadline
        a.release()
        assert done.wait(2.0)
        th.join(2.0)
        b.release()
        assert registry.get_counter(
            ETL_DECODE_ADMISSION_STARVATION_GRANTS_TOTAL,
            {"pipeline": "slow"}) == c0 + 1


def _staged_batch(n=128):
    return stage_tuples(
        tuples_from_texts([[str(i + 1), str(i * 3)] for i in range(n)]), 2)


class TestPipelineIntegration:
    def test_two_pipelines_share_capacity_byte_identical(self):
        schema = make_schema([Oid.INT4, Oid.INT8])
        # host route for every batch (host_min_rows=0): each dispatch
        # takes a ticket on the shared scheduler
        dec = DeviceDecoder(schema, host_min_rows=0)
        serial = [dec.decode(_staged_batch()) for _ in range(4)]
        s = AdmissionScheduler(1)  # maximum contention between the two
        pa = DecodePipeline(window=2, name="tenant-a",
                            admission=s.register("tenant-a"))
        pb = DecodePipeline(window=2, name="tenant-b",
                            admission=s.register("tenant-b"))
        try:
            ha = [pa.submit(dec, _staged_batch()) for _ in range(4)]
            hb = [pb.submit(dec, _staged_batch()) for _ in range(4)]
            for want, h in zip(serial, ha):
                assert_batches_equal(h.result(), want)
            for want, h in zip(serial, hb):
                assert_batches_equal(h.result(), want)
        finally:
            pa.close()
            pb.close()
        assert s.in_flight == 0
        assert s.stats()["tenants"] == {}
        ga = registry.get_counter(ETL_DECODE_ADMISSION_GRANTS_TOTAL,
                                  {"pipeline": "tenant-a"})
        gb = registry.get_counter(ETL_DECODE_ADMISSION_GRANTS_TOTAL,
                                  {"pipeline": "tenant-b"})
        assert ga >= 4 and gb >= 4

    def test_close_with_undrained_handles_releases_tickets(self):
        schema = make_schema([Oid.INT4, Oid.INT8])
        dec = DeviceDecoder(schema, host_min_rows=0)
        s = AdmissionScheduler(2)
        pipe = DecodePipeline(window=3, name="abandon",
                              admission=s.register("abandon"))
        handles = [pipe.submit(dec, _staged_batch()) for _ in range(3)]
        # drain ONE handle first so the worker is provably past pack/
        # dispatch for it — the rest are left undrained at close time
        assert handles[0].result().num_rows == 128
        pipe.close()  # reclaim with undrained handles outstanding
        assert s.in_flight == 0
        # handles already packed/dispatched stay resolvable after close;
        # their late releases into the closed tenant are no-ops
        for h in handles[1:]:
            try:
                assert h.result().num_rows == 128
            except RuntimeError:
                pass  # queued behind the close: fails fast by contract
        assert s.in_flight == 0

    async def test_chaos_multi_pipeline_crash_one_stream(self):
        """The multi-pipeline chaos scenario (chaos/multi.py): two full
        pipelines share the admission scheduler at capacity 2, one is
        hard-killed mid-stream and restarted. The survivor must deliver
        its whole remaining workload DURING the outage (stranded tickets
        would choke it), invariants must hold for both streams, and the
        scheduler must drain without leaking tickets or tenants."""
        from etl_tpu.chaos.multi import run_multi_pipeline_scenario

        run = await run_multi_pipeline_scenario(seed=7)
        assert run.ok, run.describe()
        assert run.survivor_txs_during_outage >= 1
        assert run.scheduler_drained
        assert len(run.restarts) == 1 and run.restarts[0].kind == "crash"

    async def test_two_full_pipelines_take_tickets_and_drain(self):
        """Two whole `Pipeline`s (fake walsender -> apply loop -> decode
        -> sink) on the process-wide scheduler: both end states verify,
        the streams really took admission tickets (the 512-row
        transactions of `giant_tx` cross the host-program threshold;
        oracle-routed flushes hold no capacity), and after shutdown the
        scheduler holds no ticket and no tenant."""
        import asyncio

        from etl_tpu.chaos.runner import TracingDestination
        from etl_tpu.config import (BatchConfig, BatchEngine,
                                    PipelineConfig)
        from etl_tpu.models.table_state import TableStateType
        from etl_tpu.ops import engine
        from etl_tpu.ops.pipeline import (global_admission,
                                          reset_global_admission)
        from etl_tpu.postgres.fake import FakeSource
        from etl_tpu.runtime import Pipeline
        from etl_tpu.store import NotifyingStore
        from etl_tpu.workloads import WorkloadGenerator, get_profile

        reset_global_admission()
        streams = []
        for i, name in enumerate(("insert_heavy", "giant_tx")):
            gen = WorkloadGenerator(get_profile(name), seed=7 + i)
            db = gen.build_db()
            store, dest = NotifyingStore(), TracingDestination()
            pipeline = Pipeline(
                config=PipelineConfig(
                    pipeline_id=i + 1, publication_name="pub",
                    batch=BatchConfig(max_fill_ms=30,
                                      batch_engine=BatchEngine.TPU)),
                store=store, destination=dest,
                source_factory=lambda db=db: FakeSource(db))
            streams.append((gen, db, store, dest, pipeline))

        async def produce_and_verify(gen, db, dest, pipeline, ops):
            base = gen.row_ops
            while gen.row_ops - base < ops:
                await gen.run_tx(db)
            while not gen.delivered(dest):
                assert not pipeline._apply_task.done(), "stream stopped"
                await asyncio.sleep(0.05)

        started = []
        try:
            for gen, db, store, dest, pipeline in streams:
                await pipeline.start()
                started.append(pipeline)
                for tid in gen.table_ids:
                    await asyncio.wait_for(
                        store.notify_on(tid, TableStateType.READY), 60)
            # a cold program serves its first batches from the oracle
            # while it builds on a background thread: warm both streams,
            # then count grants over traffic the built programs decode
            await asyncio.gather(*(
                produce_and_verify(g, db, d, p, 60)
                for g, db, _, d, p in streams))
            while engine.background_compiles_inflight():
                await asyncio.sleep(0.05)
            grants0 = registry.sum_counter(ETL_DECODE_ADMISSION_GRANTS_TOTAL)
            await asyncio.gather(*(
                produce_and_verify(g, db, d, p, 1500)
                for g, db, _, d, p in streams))
            grants = registry.sum_counter(
                ETL_DECODE_ADMISSION_GRANTS_TOTAL) - grants0
            tenants = global_admission().stats()["tenants"]
        finally:
            for pipeline in started:
                await pipeline.shutdown_and_wait()
        assert grants > 0
        assert len(tenants) >= 2
        drained = global_admission().stats()
        assert drained["in_flight"] == 0 and not drained["tenants"]

    def test_oracle_route_takes_no_ticket(self):
        schema = make_schema([Oid.INT4, Oid.INT8])
        # default thresholds: a 4-row batch routes to the oracle
        dec = DeviceDecoder(schema)
        s = AdmissionScheduler(1)
        tenant = s.register("oracle-t")
        pipe = DecodePipeline(window=2, name="oracle-t", admission=tenant)
        try:
            g0 = registry.get_counter(ETL_DECODE_ADMISSION_GRANTS_TOTAL,
                                      {"pipeline": "oracle-t"})
            h = pipe.submit(dec, _staged_batch(4))
            assert h.result().num_rows == 4
            assert registry.get_counter(
                ETL_DECODE_ADMISSION_GRANTS_TOTAL,
                {"pipeline": "oracle-t"}) == g0
        finally:
            pipe.close()
        assert s.in_flight == 0
