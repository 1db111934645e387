"""Three-stage decode pipeline tests (ops/pipeline.py) + its satellites:
multiple in-flight pendings per decoder, byte-identical pipelined vs
serial output, fallback fixup with a second batch in flight, the LRU
program cache, mesh row-capacity padding, arena reuse, the in-flight
window's backpressure behavior."""

import threading
import time
import types
from collections import OrderedDict

import numpy as np
import pytest

from etl_tpu.models import Oid
from etl_tpu.ops import (ARENA_POOL, DecodePipeline, DeviceDecoder,
                         StagingArenaPool, stage_tuples)
from etl_tpu.ops import engine as engine_mod
from etl_tpu.runtime.backpressure import InFlightWindow
from tests.test_ops_decode import (assert_batches_equal, decode_both,
                                   make_schema, tuples_from_texts)

OIDS = [Oid.INT8, Oid.INT4, Oid.FLOAT8, Oid.DATE, Oid.TEXT]


def _rows(n, start=0):
    return [[str((i * 7919) % 2**62 - 2**61), str(i % 97), f"{i}.25",
             "2024-05-01", f"note-{i}"] for i in range(start, start + n)]


def _stage(rows):
    return stage_tuples(tuples_from_texts(rows), len(rows[0]))


class TestPipelinedVsSerial:
    def test_byte_identical_output(self):
        schema = make_schema(OIDS)
        dec = DeviceDecoder(schema, device_min_rows=0)
        batches = [_rows(200, k * 1000) for k in range(4)]
        serial = [dec.decode(_stage(r)) for r in batches]
        pipe = DecodePipeline(window=3)
        try:
            handles = [pipe.submit(dec, _stage(r)) for r in batches]
            for h, s in zip(handles, serial):
                assert_batches_equal(h.result(), s)
        finally:
            pipe.close()

    def test_result_is_idempotent(self):
        schema = make_schema([Oid.INT4])
        dec = DeviceDecoder(schema, device_min_rows=0)
        pipe = DecodePipeline(window=2)
        try:
            h = pipe.submit(dec, _stage([["7"]] * 100))
            assert h.result() is h.result()
        finally:
            pipe.close()

    def test_oracle_route_through_pipeline(self):
        # tiny batch routes to the per-row oracle: no window slot, no
        # stage work, same output as serial decode
        schema = make_schema(OIDS)
        dec = DeviceDecoder(schema)  # production thresholds
        rows = _rows(dec.host_min_rows - 1)
        pipe = DecodePipeline(window=2)
        try:
            batch = pipe.submit(dec, _stage(rows)).result()
            assert_batches_equal(batch, dec.decode(_stage(rows)))
            assert pipe.in_flight == 0
        finally:
            pipe.close()

    def test_submit_after_close_raises(self):
        pipe = DecodePipeline(window=1)
        pipe.close()
        with pytest.raises(RuntimeError):
            pipe.submit(DeviceDecoder(make_schema([Oid.INT4])),
                        _stage([["1"]]))


class TestMultipleInFlight:
    def test_out_of_order_results(self):
        """Three batches in flight; resolve newest-first. Each handle's
        completion is independent, and the window's liveness valve keeps
        the worker from deadlocking against its own consumer."""
        schema = make_schema(OIDS)
        dec = DeviceDecoder(schema, device_min_rows=0)
        batches = [_rows(150, k * 500) for k in range(3)]
        expected = [dec.decode(_stage(r)) for r in batches]
        pipe = DecodePipeline(window=3)
        try:
            handles = [pipe.submit(dec, _stage(r)) for r in batches]
            for h, e in zip(reversed(handles), reversed(expected)):
                assert_batches_equal(h.result(), e)
        finally:
            pipe.close()

    def test_out_of_order_with_window_one_no_deadlock(self):
        """window=1 and the consumer demands the SECOND batch first — the
        worker must overshoot the window (bypass) instead of deadlocking
        (the old_batch-before-batch consumption shape)."""
        schema = make_schema([Oid.INT4])
        dec = DeviceDecoder(schema, device_min_rows=0)
        pipe = DecodePipeline(window=1)
        try:
            h1 = pipe.submit(dec, _stage([[str(i)] for i in range(100)]))
            h2 = pipe.submit(dec, _stage([[str(i + 500)]
                                          for i in range(100)]))
            assert h2.result().columns[0].data[3] == 503
            assert h1.result().columns[0].data[3] == 3
        finally:
            pipe.close()

    def test_serial_decode_async_out_of_order(self):
        # the non-pipelined API keeps the same property: N pendings per
        # decoder, resolvable in any order
        schema = make_schema([Oid.INT4, Oid.TEXT])
        dec = DeviceDecoder(schema, device_min_rows=0)
        p1 = dec.decode_async(_stage([[str(i), f"a{i}"] for i in range(64)]))
        p2 = dec.decode_async(_stage([[str(i + 90), f"b{i}"]
                                      for i in range(64)]))
        b2 = p2.result()
        b1 = p1.result()
        assert b1.columns[0].data[5] == 5
        assert b2.columns[0].data[5] == 95
        assert b2.columns[1].value(5) == "b5"

    def test_fallback_fixup_with_second_batch_in_flight(self):
        """Batch 1 carries CPU-fallback rows (BC date, 17-digit float);
        batch 2 is dispatched before batch 1 resolves. The oracle fixup of
        batch 1 must patch exactly its own rows — pooled arenas and the
        shared fn cache must not bleed state across in-flight batches."""
        oids = [Oid.FLOAT8, Oid.DATE]
        rows1 = [[f"{i}.5", "2024-01-02"] for i in range(120)]
        rows1[7] = ["0.12345678901234567", "0044-03-15 BC"]  # both fall back
        rows2 = [[f"{i}.25", "2023-06-15"] for i in range(120)]
        _, cpu1 = decode_both(oids, rows1)
        _, cpu2 = decode_both(oids, rows2)
        schema = make_schema(oids)
        dec = DeviceDecoder(schema, device_min_rows=0)
        pipe = DecodePipeline(window=2)
        try:
            h1 = pipe.submit(dec, _stage(rows1))
            h2 = pipe.submit(dec, _stage(rows2))
            # resolve the clean batch FIRST so batch 1's fixup runs while
            # nothing shields it from cross-batch state
            assert_batches_equal(h2.result(), cpu2)
            assert_batches_equal(h1.result(), cpu1)
        finally:
            pipe.close()

    def test_overlap_recorded(self):
        """Pack of batch N+1 concurrent with batch N in flight must show
        up in the pipeline's overlap accounting."""
        schema = make_schema(OIDS)
        dec = DeviceDecoder(schema, device_min_rows=0)
        pipe = DecodePipeline(window=3)
        try:
            handles = [pipe.submit(dec, _stage(_rows(400, k * 400)))
                       for k in range(5)]
            for h in handles:
                h.result()
            stats = pipe.stats()
            assert stats["completed"] == 5
            assert stats["pack_seconds_total"] > 0
            assert stats["overlap_seconds_total"] > 0
        finally:
            pipe.close()

    def test_stage_histograms_observed(self):
        """One run through the pipeline leaves observations in each of
        the three stage series (pack / dispatch / fetch) — the series
        every per-layer reading of the decode stage is computed from."""
        from etl_tpu.telemetry.metrics import (ETL_DECODE_DISPATCH_SECONDS,
                                               ETL_DECODE_FETCH_SECONDS,
                                               ETL_DECODE_PACK_SECONDS,
                                               registry)

        series = (ETL_DECODE_PACK_SECONDS, ETL_DECODE_DISPATCH_SECONDS,
                  ETL_DECODE_FETCH_SECONDS)
        before = [registry.get_histogram(n)[0] for n in series]
        dec = DeviceDecoder(make_schema(OIDS), device_min_rows=0)
        pipe = DecodePipeline(window=2)
        try:
            for h in [pipe.submit(dec, _stage(_rows(300, k * 300)))
                      for k in range(3)]:
                h.result()
        finally:
            pipe.close()
        for name, n0 in zip(series, before):
            assert registry.get_histogram(name)[0] >= n0 + 3, name

    def test_failed_fetch_is_permanent(self):
        """A fetch failure released the arena already — retrying result()
        must re-raise the SAME error, not re-complete from a pool buffer
        another batch may have dirtied (code-review finding)."""
        from etl_tpu.models.errors import EtlError

        schema = make_schema([Oid.INT4])
        dec = DeviceDecoder(schema, device_min_rows=0)
        pipe = DecodePipeline(window=2)
        try:
            # out-of-range INT4: device flags the row, the oracle fixup
            # raises a typed error at completion (the fetch stage)
            h = pipe.submit(dec, _stage([["99999999999"], ["5"]] * 50))
            with pytest.raises(EtlError) as first:
                h.result()
            with pytest.raises(EtlError) as second:
                h.result()
            assert second.value is first.value
        finally:
            pipe.close()

    def test_close_with_abandoned_handles_does_not_leak_worker(self):
        """A failed consumer abandons its handles without draining; close()
        must still run the worker down (window bypass + fail-fast on
        queued jobs) instead of leaking the thread and queued batches."""
        schema = make_schema(OIDS)
        dec = DeviceDecoder(schema, device_min_rows=0)
        pipe = DecodePipeline(window=1)
        handles = [pipe.submit(dec, _stage(_rows(120, k * 200)))
                   for k in range(5)]
        pipe.close()  # nobody ever calls result()
        pipe._worker.join(timeout=30)
        assert not pipe._worker.is_alive()
        # handles are all resolved: dispatched ones complete, queued ones
        # fail fast — none hang a late consumer
        outcomes = []
        for h in handles:
            try:
                outcomes.append(h.result() is not None)
            except RuntimeError:
                outcomes.append("closed")
        assert all(o is True or o == "closed" for o in outcomes)

    def test_error_delivered_at_result(self):
        schema = make_schema([Oid.INT4])
        dec = DeviceDecoder(schema, device_min_rows=0)
        pipe = DecodePipeline(window=2)
        try:
            bad = _stage([["1", "x"]])  # 2 cols vs 1-col schema
            h = pipe.submit(dec, bad)
            with pytest.raises(ValueError):
                h.result()
            # the window slot was returned on failure: a fresh submit
            # still completes
            ok = pipe.submit(dec, _stage([["5"]] * 80)).result()
            assert ok.columns[0].data[0] == 5
        finally:
            pipe.close()


class TestSharedFnCacheLRU:
    def test_hits_refresh_recency(self, monkeypatch):
        monkeypatch.setattr(engine_mod, "_SHARED_FN_CACHE", OrderedDict())
        monkeypatch.setattr(engine_mod, "_SHARED_FN_CACHE_MAX", 3)
        for k in ("k1", "k2", "k3"):
            engine_mod._shared_fn_put(k, lambda: k)
        assert engine_mod._shared_fn_get("k1") is not None  # refresh k1
        engine_mod._shared_fn_put("k4", lambda: "k4")  # evicts k2, NOT k1
        assert list(engine_mod._SHARED_FN_CACHE) == ["k3", "k1", "k4"]
        assert engine_mod._shared_fn_get("k2") is None

    def test_eviction_is_bounded(self, monkeypatch):
        monkeypatch.setattr(engine_mod, "_SHARED_FN_CACHE", OrderedDict())
        monkeypatch.setattr(engine_mod, "_SHARED_FN_CACHE_MAX", 2)
        for i in range(10):
            engine_mod._shared_fn_put(f"k{i}", lambda: None)
        assert len(engine_mod._SHARED_FN_CACHE) == 2


class TestMeshCapacityPadding:
    def test_odd_mesh_size_engages_and_matches(self):
        """A 3-device mesh does not divide the 1024-row bucket; the pack
        stage pads capacity to 1026 so sharded dispatch engages instead of
        silently falling back — output identical to the single-device
        program."""
        import jax
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:3]), axis_names=("sp",))
        oids = [Oid.INT4, Oid.TEXT]
        rows = [[str(i), f"v-{i}"] for i in range(300)]  # 1024 bucket
        schema = make_schema(oids)
        staged = _stage(rows)
        assert staged.row_capacity % mesh.size != 0  # the fixed case
        dec = DeviceDecoder(schema, device_min_rows=0, mesh=mesh,
                            mesh_min_rows=0)
        assert dec._use_mesh(staged.row_capacity)
        batch = dec.decode(staged)
        serial = DeviceDecoder(schema, device_min_rows=0,
                               mesh=None).decode(_stage(rows))
        assert_batches_equal(batch, serial)
        # the program really ran on the mesh at the padded capacity
        mesh_keys = [k for k in dec._fn_cache if k[3] is not None]
        assert mesh_keys and mesh_keys[0][0] == 1026

    def test_divisible_bucket_unpadded(self):
        from etl_tpu.ops.staging import bucket_rows, pad_to_multiple

        assert pad_to_multiple(1024, 8) == 1024
        assert pad_to_multiple(1024, 3) == 1026
        assert pad_to_multiple(1026, 3) == 1026  # idempotent
        assert bucket_rows(300) == 1024


class TestStagingArenas:
    def test_reuse_round_trip(self):
        pool = StagingArenaPool(max_per_bucket=2)
        lease = pool.lease()
        a = lease.take((64, 32), np.uint8)
        lease.release()
        lease2 = pool.lease()
        b = lease2.take((64, 32), np.uint8)
        assert b is a  # the same buffer came back
        c = lease2.take((64, 32), np.uint8)
        assert c is not a
        lease2.release()
        assert pool.stats()["free_arrays"] == 2

    def test_pool_bound(self):
        pool = StagingArenaPool(max_per_bucket=1)
        leases = [pool.lease() for _ in range(3)]
        for lease in leases:
            lease.take((8, 8), np.uint8)
        for lease in leases:
            lease.release()
        assert pool.stats()["free_arrays"] == 1  # excess dropped

    def test_pipeline_reuses_arenas(self):
        from etl_tpu.telemetry.metrics import (
            ETL_STAGING_ARENA_REQUESTS_TOTAL, registry)

        pool = StagingArenaPool()
        schema = make_schema([Oid.INT4])
        dec = DeviceDecoder(schema, device_min_rows=0)
        pipe = DecodePipeline(window=1, arena_pool=pool)
        hits0 = registry.get_counter(ETL_STAGING_ARENA_REQUESTS_TOTAL,
                                     {"result": "hit"})
        try:
            # window=1 serializes: batch 2 packs after batch 1's arena is
            # back in the pool — guaranteed reuse hit
            for k in range(3):
                pipe.submit(dec, _stage([[str(i + k)] for i in
                                         range(100)])).result()
        finally:
            pipe.close()
        hits1 = registry.get_counter(ETL_STAGING_ARENA_REQUESTS_TOTAL,
                                     {"result": "hit"})
        assert hits1 > hits0

    def test_dirty_arena_cannot_leak_between_batches(self):
        """Pack into an arena, then pack a SHORTER-valued batch into the
        same arena: the second decode must not see the first batch's
        bytes (C packers zero-pad every field to its width)."""
        schema = make_schema([Oid.INT8])
        dec = DeviceDecoder(schema, device_min_rows=0)
        pool = StagingArenaPool()
        pipe = DecodePipeline(window=1, arena_pool=pool)
        try:
            wide = [[str(10**17 + i)] for i in range(100)]  # 18-digit
            short = [[str(i)] for i in range(100)]  # 1-2 digit
            assert_batches_equal(pipe.submit(dec, _stage(wide)).result(),
                                 dec.decode(_stage(wide)))
            assert_batches_equal(pipe.submit(dec, _stage(short)).result(),
                                 dec.decode(_stage(short)))
        finally:
            pipe.close()


class TestInFlightWindow:
    def test_limit_enforced_and_released(self):
        w = InFlightWindow(2)
        w.acquire()
        w.acquire()
        assert len(w) == 2
        acquired = threading.Event()

        def third():
            w.acquire()
            acquired.set()

        t = threading.Thread(target=third, daemon=True)
        t.start()
        time.sleep(0.05)
        assert not acquired.is_set()  # blocked at the limit
        w.release()
        assert acquired.wait(2.0)
        t.join(2.0)

    def test_pressure_shrinks_to_one(self):
        monitor = types.SimpleNamespace(pressure=True)
        w = InFlightWindow(4, monitor)
        assert w.effective_limit == 1
        monitor.pressure = False
        assert w.effective_limit == 4

    def test_bypass_overrides_limit(self):
        w = InFlightWindow(1)
        w.acquire()
        w.acquire(bypass=lambda: True)  # liveness valve: overshoot
        assert len(w) == 2

    def test_rejects_zero_limit(self):
        with pytest.raises(ValueError):
            InFlightWindow(0)
