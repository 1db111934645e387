"""Runtime unit tests: state machine, slots, copy planning, stores,
destinations (reference strategy: in-module unit tests, SURVEY §4.1)."""

import asyncio

import pytest

from etl_tpu.config import PipelineConfig, RetryConfig
from etl_tpu.models import (ColumnSchema, EtlError, Lsn, Oid,
                            ReplicatedTableSchema, RetryKind, TableName,
                            TableSchema)
from etl_tpu.postgres.slots import (apply_slot_name, parse_slot_name,
                                    slots_for_pipeline, table_sync_slot_name)
from etl_tpu.runtime.copy import plan_copy_partitions
from etl_tpu.runtime.state import TableState, TableStateType
from etl_tpu.store import MemoryStore


class TestTableState:
    def test_happy_path_transitions(self):
        st = TableState.init()
        seq = [TableState.data_sync(), TableState.finished_copy(),
               TableState.sync_wait(Lsn(1)), TableState.catchup(Lsn(2)),
               TableState.sync_done(Lsn(3)), TableState.ready()]
        for nxt in seq:
            st = st.transition_to(nxt)
        assert st.type is TableStateType.READY

    def test_invalid_transition_rejected(self):
        with pytest.raises(EtlError):
            TableState.init().transition_to(TableState.ready())
        with pytest.raises(EtlError):
            TableState.ready().transition_to(TableState.data_sync())

    def test_error_and_rollback_from_any_state(self):
        for st in [TableState.init(), TableState.catchup(Lsn(1)),
                   TableState.ready()]:
            assert st.can_transition_to(TableStateType.ERRORED)
            assert st.can_transition_to(TableStateType.INIT)

    def test_serialization_roundtrip(self):
        for st in [TableState.init(), TableState.finished_copy(),
                   TableState.sync_done(Lsn("AB/CD")), TableState.ready(),
                   TableState.errored("boom", solution="fix it",
                                      retry_policy=RetryKind.MANUAL,
                                      retry_attempts=3)]:
            assert TableState.from_json(st.to_json()) == st

    def test_memory_only_states_not_serializable(self):
        for st in [TableState.sync_wait(Lsn(1)), TableState.catchup(Lsn(2))]:
            with pytest.raises(EtlError):
                st.to_json()

    async def test_memory_store_rejects_memory_only(self):
        store = MemoryStore()
        with pytest.raises(EtlError):
            await store.update_table_state(1, TableState.sync_wait(Lsn(1)))


class TestSlots:
    def test_names(self):
        assert apply_slot_name(7) == "supabase_etl_apply_7"
        assert table_sync_slot_name(7, 16384) == \
            "supabase_etl_table_sync_7_16384"

    def test_parse(self):
        p = parse_slot_name("supabase_etl_apply_12")
        assert p.pipeline_id == 12 and p.is_apply
        p = parse_slot_name("supabase_etl_table_sync_12_99")
        assert (p.pipeline_id, p.table_id) == (12, 99)
        assert parse_slot_name("someone_elses_slot") is None
        assert parse_slot_name("supabase_etl_apply_xyz") is None

    def test_filter_for_pipeline(self):
        names = ["supabase_etl_apply_1", "supabase_etl_apply_2",
                 "supabase_etl_table_sync_1_5", "other"]
        assert slots_for_pipeline(names, 1) == \
            ["supabase_etl_apply_1", "supabase_etl_table_sync_1_5"]

    def test_length_limit(self):
        with pytest.raises(EtlError):
            table_sync_slot_name(10**40, 10**40)


class TestCopyPlanning:
    def cfg(self):
        return PipelineConfig(pipeline_id=1, publication_name="p")

    def test_small_table_single_partition(self):
        parts = plan_copy_partitions(100, 2, self.cfg())
        assert len(parts) <= 2
        assert sum(p.estimated_rows for p in parts) <= 100 + len(parts)

    def test_partition_count_math(self):
        # 10M rows / 250k target = 40 partitions (> 4×4 floor)
        parts = plan_copy_partitions(10_000_000, 100_000, self.cfg())
        assert len(parts) == 40
        # page ranges tile [0, heap_pages) exactly
        ordered = sorted(parts, key=lambda p: p.start_page)
        assert ordered[0].start_page == 0
        for a, b in zip(ordered, ordered[1:]):
            assert a.end_page == b.start_page
        assert ordered[-1].end_page is None

    def test_clamped_to_max_partitions(self):
        parts = plan_copy_partitions(10**9, 10**6, self.cfg())
        assert len(parts) == 1024

    def test_largest_first(self):
        parts = plan_copy_partitions(1_000_000, 101, self.cfg())
        sizes = [p.estimated_rows for p in parts]
        assert sizes == sorted(sizes, reverse=True)

    def test_empty_stats(self):
        parts = plan_copy_partitions(0, 0, self.cfg())
        assert len(parts) == 1 and parts[0].start_page == 0


class TestRetryConfig:
    def test_backoff(self):
        r = RetryConfig(max_attempts=5, initial_delay_ms=100,
                        max_delay_ms=1000, backoff_factor=2.0)
        assert [r.delay_ms(i) for i in range(5)] == [100, 200, 400, 800, 1000]


class TestMemoryStoreContracts:
    async def test_progress_monotonic(self):
        store = MemoryStore()
        assert await store.update_durable_progress("k", Lsn(100))
        assert not await store.update_durable_progress("k", Lsn(50))
        assert await store.get_durable_progress("k") == Lsn(100)
        assert await store.update_durable_progress("k", Lsn(100))  # equal ok

    async def test_schema_versioning(self):
        store = MemoryStore()
        s = TableSchema(5, TableName("p", "t"),
                        (ColumnSchema("a", Oid.INT4),))
        s2 = TableSchema(5, TableName("p", "t"),
                         (ColumnSchema("a", Oid.INT4),
                          ColumnSchema("b", Oid.TEXT)))
        r1 = ReplicatedTableSchema.with_all_columns(s)
        r2 = ReplicatedTableSchema.with_all_columns(s2)
        await store.store_table_schema(r1, 10)
        await store.store_table_schema(r2, 20)
        assert (await store.get_table_schema(5)).table_schema == s2
        assert (await store.get_table_schema(5, at_snapshot=15)) \
            .table_schema == s
        assert (await store.get_table_schema(5, at_snapshot=5)) is None
        # prune keeps the version still needed for snapshot 20
        removed = await store.prune_schema_versions(5, 25)
        assert removed == 1
        assert await store.get_schema_versions(5) == [20]


class TestReplicatorStoreConfig:
    def test_postgres_store_connection_overrides_merge(self):
        """store.connection overrides merge ONTO the source connection
        (per-field), convert secrets/tls through the loader, and reject
        unknown keys — review r2 findings on the raw-constructor path."""
        import asyncio
        import dataclasses

        from etl_tpu.config.load import Secret
        from etl_tpu.config.pipeline import PgConnectionConfig

        from etl_tpu.replicator import store_connection_from_doc as merge

        base = PgConnectionConfig(host="src-db", port=6000, name="app",
                                  username="etl", password=Secret("pw"))
        merged = merge(base, {"name": "etl_state"})
        assert merged.host == "src-db" and merged.port == 6000
        assert merged.name == "etl_state"
        assert merged.password == "pw"  # inherited, still wrapped
        merged2 = merge(base, {"password": "other",
                               "tls": {"enabled": True}})
        assert isinstance(merged2.password, Secret)
        assert merged2.tls.enabled is True  # typed, not a dict

        from etl_tpu.models.errors import EtlError
        import pytest as _pytest
        with _pytest.raises(EtlError):
            merge(base, {"host": "x", "bogus_key": 1})


class TestAssemblerBulkPush:
    """push_raw_rows (the drained-window span path) must be byte-equivalent
    to N push_raw_row calls — same runs, ordinals, size accounting."""

    def _schema(self):
        from etl_tpu.models import ReplicatedTableSchema, TableName, TableSchema
        return ReplicatedTableSchema.with_all_columns(TableSchema(
            7, TableName("public", "t"),
            (ColumnSchema("id", Oid.INT4, nullable=False,
                          primary_key_ordinal=1),)))

    def test_bulk_equals_single(self):
        from etl_tpu.config.pipeline import BatchEngine
        from etl_tpu.postgres.codec import pgoutput
        from etl_tpu.runtime.assembler import EventAssembler

        schema = self._schema()
        payloads = [pgoutput.encode_insert(7, [str(i).encode()])
                    for i in range(10)]
        a1 = EventAssembler(BatchEngine.TPU)
        for i, p in enumerate(payloads):
            a1.push_raw_row(p, schema, Lsn(100 + i), Lsn(500), i)
        a2 = EventAssembler(BatchEngine.TPU)
        nbytes = a2.push_raw_rows(payloads, schema,
                                  [100 + i for i in range(10)], 500, 0)
        assert nbytes == sum(len(p) for p in payloads)
        assert a1.size_bytes == a2.size_bytes
        r1, r2 = a1._group[schema.id], a2._group[schema.id]
        assert r1.payloads == r2.payloads
        assert r1.start_lsns == r2.start_lsns
        assert r1.commit_lsns == r2.commit_lsns
        assert list(r1.tx_ordinals) == list(r2.tx_ordinals)

    def test_bulk_seals_on_schema_change(self):
        from etl_tpu.config.pipeline import BatchEngine
        from etl_tpu.models import (ReplicatedTableSchema, TableName,
                                    TableSchema)
        from etl_tpu.postgres.codec import pgoutput
        from etl_tpu.runtime.assembler import EventAssembler

        s1 = self._schema()
        s2 = ReplicatedTableSchema.with_all_columns(TableSchema(
            8, TableName("public", "u"),
            (ColumnSchema("id", Oid.INT4, nullable=False,
                          primary_key_ordinal=1),)))
        a = EventAssembler(BatchEngine.TPU)
        a.push_raw_rows([pgoutput.encode_insert(7, [b"1"])], s1, [1], 10, 0)
        a.push_raw_rows([pgoutput.encode_insert(8, [b"2"])], s2, [2], 10, 1)
        events = a.flush()
        assert len(events) == 2  # two sealed DecodedBatchEvents


class TestIdentityPreservingTableCache:
    def test_equal_schema_keeps_object(self):
        from etl_tpu.models import (ReplicatedTableSchema, TableName,
                                    TableSchema)
        from etl_tpu.runtime.table_cache import SharedTableCache

        def make():
            return ReplicatedTableSchema.with_all_columns(TableSchema(
                7, TableName("public", "t"),
                (ColumnSchema("id", Oid.INT4, nullable=False,
                              primary_key_ordinal=1),)))

        cache = SharedTableCache()
        a = make()
        cache.set(a)
        cache.set(make())  # equal but not identical (RELATION re-send)
        assert cache.get(7) is a, \
            "equal re-set must preserve identity (decoder/jit reuse)"
        changed = ReplicatedTableSchema.with_all_columns(TableSchema(
            7, TableName("public", "t"),
            (ColumnSchema("id", Oid.INT8, nullable=False,
                          primary_key_ordinal=1),)))
        cache.set(changed)
        assert cache.get(7) is changed  # real change replaces


class TestPreencodedInserts:
    def test_wal_identical_to_plain_insert(self):
        import asyncio as _a

        from etl_tpu.models import TableName, TableSchema
        from etl_tpu.postgres.codec import pgoutput
        from etl_tpu.postgres.fake import FakeDatabase

        def mk_db():
            db = FakeDatabase()
            db.create_table(TableSchema(
                16384, TableName("public", "t"),
                (ColumnSchema("id", Oid.INT4, nullable=False,
                              primary_key_ordinal=1),)))
            db.create_publication("pub", [16384])
            return db

        async def run():
            db1, db2 = mk_db(), mk_db()
            tx = db1.transaction(xid=9)
            for i in range(3):
                tx.insert(16384, [str(i)])
            lsn1 = await tx.commit()
            tx = db2.transaction(xid=9)
            for i in range(3):
                tx.insert_preencoded(
                    16384, pgoutput.encode_insert(16384, [str(i).encode()]),
                    [str(i)])
            lsn2 = await tx.commit()
            assert int(lsn1) == int(lsn2)
            assert [int(lsn) for lsn, *_ in db1.wal] \
                == [int(lsn) for lsn, *_ in db2.wal]
            for (l1, p1, t1, r1), (l2, p2, t2, r2) in zip(db1.wal, db2.wal):
                if p1[:1] in (b"I", b"R"):
                    assert p1 == p2
                    assert t1 == t2 and r1 == r2
                else:  # BEGIN/COMMIT embed wall-clock timestamps
                    assert p1[:1] == p2[:1]
            # table state advanced identically
            assert db1.tables[16384].rows == db2.tables[16384].rows

        asyncio.run(run())


class TestDynamicSeal:
    """Backlog mega-batching (VERDICT r4 #1b): the seal grows one row
    bucket per step toward MEGA_SEAL_ROWS and resets to the latency size."""

    def _schema(self):
        from etl_tpu.models import ReplicatedTableSchema, TableName, TableSchema
        return ReplicatedTableSchema.with_all_columns(TableSchema(
            7, TableName("public", "t"),
            (ColumnSchema("id", Oid.INT4, nullable=False,
                          primary_key_ordinal=1),)))

    def test_grow_and_reset_steps_are_row_buckets(self):
        from etl_tpu.config.pipeline import BatchEngine
        from etl_tpu.ops.staging import ROW_BUCKETS
        from etl_tpu.runtime.assembler import (MEGA_SEAL_ROWS, RUN_SEAL_ROWS,
                                               EventAssembler)

        a = EventAssembler(BatchEngine.TPU)
        assert a.seal_rows == RUN_SEAL_ROWS
        seen = [a.seal_rows]
        for _ in range(5):
            a.grow_seal()
            seen.append(a.seal_rows)
        # monotone, capped, and every step lands exactly on a standard
        # bucket (an off-bucket seal would compile a wasted program)
        assert seen[-1] == MEGA_SEAL_ROWS
        assert all(s in ROW_BUCKETS for s in seen)
        assert seen == sorted(seen)
        a.reset_seal()
        assert a.seal_rows == RUN_SEAL_ROWS

    def test_grown_seal_accumulates_past_default(self):
        from etl_tpu.config.pipeline import BatchEngine
        from etl_tpu.postgres.codec import pgoutput
        from etl_tpu.runtime.assembler import RUN_SEAL_ROWS, EventAssembler

        schema = self._schema()
        a = EventAssembler(BatchEngine.TPU)
        a.grow_seal()
        n = RUN_SEAL_ROWS + 8
        payloads = [pgoutput.encode_insert(7, [b"1"])] * n
        a.push_raw_rows(payloads, schema, list(range(n)), 999, 0)
        # the run is still OPEN (one future DecodedBatchEvent, not two)
        assert len(a._group[schema.id].payloads) == n

    def test_scaled_flush_threshold_tracks_seal(self):
        from etl_tpu.config import BatchConfig, PipelineConfig
        from etl_tpu.config.pipeline import BatchEngine
        from etl_tpu.runtime.apply_loop import ApplyLoop
        from etl_tpu.runtime.assembler import EventAssembler

        loop = ApplyLoop.__new__(ApplyLoop)
        loop.config = PipelineConfig(
            pipeline_id=1, publication_name="p",
            batch=BatchConfig(max_size_bytes=1000))
        loop.assembler = EventAssembler(BatchEngine.TPU)
        assert loop._scaled_max_bytes() == 1000
        loop.assembler.grow_seal()
        assert loop._scaled_max_bytes() == 4000
        loop.assembler.grow_seal()
        assert loop._scaled_max_bytes() == 16000
        loop.assembler.reset_seal()
        assert loop._scaled_max_bytes() == 1000


class TestAutotuneModel:
    """Measured device routing (VERDICT r4 #1a)."""

    def test_crossover_math(self):
        from etl_tpu.ops.autotune import _FLOOR_ROWS, DeviceCostModel

        # host: 1M col-rows/s; link: 100MB/s with 10ms fixed cost.
        # schema: 2 dense cols, 50B/row → host 2µs/row, link 0.5µs/row
        # → margin 1.5µs/row → crossover ≈ 6667 rows
        m = DeviceCostModel(fixed_s=0.010, bytes_per_s=100e6,
                            host_col_rows_per_s=1e6, backend="tpu")
        got = m.device_min_rows(n_dense=2, bytes_per_row=50.0,
                                default=131_072)
        assert _FLOOR_ROWS <= got <= 7000
        assert got == int(0.010 / (2 / 1e6 - 50 / 100e6)) + 1

    def test_slow_link_keeps_default(self):
        from etl_tpu.ops.autotune import DeviceCostModel

        # slow link: 40MB/s, 50B/row → 1.25µs/row link vs
        # 0.5µs/row host → the device never wins on throughput;
        # routing keeps the static default
        m = DeviceCostModel(fixed_s=0.050, bytes_per_s=40e6,
                            host_col_rows_per_s=4e6, backend="tpu")
        assert m.device_min_rows(2, 50.0, default=131_072) == 131_072

    def test_floor_guards_lucky_probe(self):
        from etl_tpu.ops.autotune import _FLOOR_ROWS, DeviceCostModel

        m = DeviceCostModel(fixed_s=1e-6, bytes_per_s=1e12,
                            host_col_rows_per_s=1e5, backend="tpu")
        assert m.device_min_rows(4, 60.0, default=131_072) == _FLOOR_ROWS

    def test_no_dense_columns_keeps_default(self):
        from etl_tpu.ops.autotune import DeviceCostModel

        m = DeviceCostModel(fixed_s=0.01, bytes_per_s=1e8,
                            host_col_rows_per_s=1e6, backend="tpu")
        assert m.device_min_rows(0, 0.0, default=77) == 77

    def test_cpu_backend_measures_none_and_default_resolves(self):
        import etl_tpu.ops.autotune as at

        # conftest pins JAX_PLATFORMS=cpu → no separate accelerator
        at._MEASURED = None
        try:
            assert at.measure() is None
            assert at.resolve_device_min_rows(4, 60.0, 131_072) == 131_072
        finally:
            at._MEASURED = None
