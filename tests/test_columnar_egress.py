"""Columnar fetch-to-wire egress (ISSUE 6).

Three layers of coverage:

  1. `ColumnarBatch.to_arrow()` / `from_cells` / `concat` round-trips
     across every CellKind — numeric precision, timestamp µs exactness,
     tz handling, bytea, NULL validity bitmaps, empty batches, and a
     120-column wide schema.
  2. The vectorized CDC metadata builders (`_CHANGE_TYPE` /
     `_CHANGE_SEQUENCE_NUMBER` as batch numpy ops) against the per-row
     f-string reference.
  3. PARITY: the columnar destination encoders produce BYTE-IDENTICAL
     wire payloads to the legacy row path on the same events —
     end-to-end through the real ClickHouse/BigQuery HTTP surfaces and
     the lake catalog, plus the zero-TableRow guarantee on the hot path
     and the sequential_batch_program ordering/coalescing/fallback
     semantics the seam rests on.
"""

from __future__ import annotations

import asyncio
import datetime as dt
import json
import uuid

import numpy as np
import pyarrow as pa
import pytest

from etl_tpu.destinations import bq_proto
from etl_tpu.destinations.base import (CoalescedBatch, Destination, WriteAck,
                                       batch_event_columnar_ok,
                                       expand_batch_events,
                                       sequential_batch_program)
from etl_tpu.destinations.util import (CHANGE_SEQUENCE_COLUMN,
                                       CHANGE_TYPE_COLUMN, change_type_arrow,
                                       change_type_batch, hex16_arrow,
                                       sequence_number_arrow,
                                       sequence_number_batch)
from etl_tpu.models import (ColumnSchema, ColumnarBatch, Oid,
                            ReplicatedTableSchema, TableName, TableSchema)
from etl_tpu.models.cell import JSON_NULL, PgInterval, PgNumeric, TOAST_UNCHANGED
from etl_tpu.models.event import (ChangeType, DecodedBatchEvent, InsertEvent,
                                  TruncateEvent)
from etl_tpu.models.lsn import Lsn
from etl_tpu.models.table_row import Column, TableRow, rows_constructed


def _schema(cols, tid=41001, name="egress"):
    return ReplicatedTableSchema.with_all_columns(TableSchema(
        tid, TableName("public", name), tuple(cols)))


def _kinds_schema():
    return _schema((
        ColumnSchema("pk", Oid.INT8, nullable=False, primary_key_ordinal=1),
        ColumnSchema("b", Oid.BOOL),
        ColumnSchema("i2", Oid.INT2),
        ColumnSchema("i4", Oid.INT4),
        ColumnSchema("f4", Oid.FLOAT4),
        ColumnSchema("f8", Oid.FLOAT8),
        ColumnSchema("num", Oid.NUMERIC),
        ColumnSchema("d", Oid.DATE),
        ColumnSchema("t", Oid.TIME),
        ColumnSchema("ts", Oid.TIMESTAMP),
        ColumnSchema("tstz", Oid.TIMESTAMPTZ),
        ColumnSchema("u", Oid.UUID),
        ColumnSchema("js", Oid.JSONB),
        ColumnSchema("by", Oid.BYTEA),
        ColumnSchema("s", Oid.TEXT),
    ))


def _kinds_rows(n=8):
    rows = []
    for i in range(n):
        rows.append(TableRow([
            i,
            bool(i % 2) if i % 5 else None,
            (i - 3) * 7 if i % 4 else None,
            -i * 1000 if i % 3 else None,
            i * 0.5,
            i * 1.25e10,
            PgNumeric("123456789012345678901234567890.%09d" % i),
            dt.date(2024, 5, (i % 28) + 1),
            dt.time(12, 34, 56, i),
            dt.datetime(2024, 5, 1, 1, 2, 3, 100000 + i),
            dt.datetime(2031, 12, 31, 23, 59, 59, 999990 + (i % 10),
                        tzinfo=dt.timezone.utc),
            uuid.UUID(int=i + 7),
            {"k": i} if i % 2 else JSON_NULL,
            b"\x00\xffbytes-%d" % i,
            "str-%d\twith\ttabs" % i if i % 2 else None,
        ]))
    return rows


def _engine_batch_event(n=64, tid=41002, start=0):
    """An engine-shaped DecodedBatchEvent (dense ints + Arrow strings)
    through the REAL staging + decode path — what the apply loop hands
    the destination in production."""
    from etl_tpu.ops.engine import DeviceDecoder
    from etl_tpu.ops.wal import concat_payloads, stage_wal_batch
    from etl_tpu.postgres.codec.pgoutput import encode_insert

    schema = _schema((
        ColumnSchema("id", Oid.INT8, nullable=False, primary_key_ordinal=1),
        ColumnSchema("v", Oid.INT4),
        ColumnSchema("note", Oid.TEXT)), tid=tid, name=f"t{tid}")
    payloads = [encode_insert(tid, [str(start + i).encode(),
                                    str(i % 97).encode(),
                                    b"note-%d" % (start + i)])
                for i in range(n)]
    buf, offs, lens = concat_payloads(payloads)
    wal = stage_wal_batch(buf, offs, lens, 3)
    batch = DeviceDecoder(schema).decode(wal.staged)
    ev = DecodedBatchEvent(
        Lsn(start + 1), Lsn(start + n), schema,
        change_types=np.zeros(n, dtype=np.int8),
        commit_lsns=np.arange(start, start + n, dtype=np.uint64) + 0x1000,
        tx_ordinals=np.arange(n, dtype=np.uint64),
        batch=batch)
    return schema, ev


# ---------------------------------------------------------------------------
# 1. to_arrow / from_cells / concat round trips
# ---------------------------------------------------------------------------


class TestToArrowRoundTrip:
    def test_every_kind_round_trips(self):
        schema = _kinds_schema()
        rows = _kinds_rows()
        batch = ColumnarBatch.from_rows(schema, rows)
        rb = batch.to_arrow()
        assert rb.num_rows == len(rows)
        got = rb.to_pydict()
        for i, row in enumerate(rows):
            vals = dict(zip([c.name for c in schema.replicated_columns],
                            row.values))
            assert got["pk"][i] == vals["pk"]
            assert got["b"][i] == vals["b"]
            assert got["i2"][i] == vals["i2"]
            assert got["i4"][i] == vals["i4"]
            assert got["f4"][i] == pytest.approx(vals["f4"])
            assert got["f8"][i] == vals["f8"]
            # NUMERIC: exact pg text at any precision
            assert got["num"][i] == vals["num"].pg_text()
            assert got["d"][i] == vals["d"]
            assert got["t"][i] == vals["t"]
            # timestamps: µs exactness, tz attached only for tstz
            assert got["ts"][i] == vals["ts"]
            assert got["ts"][i].microsecond == vals["ts"].microsecond
            assert got["tstz"][i] == vals["tstz"]
            assert got["tstz"][i].utcoffset() == dt.timedelta(0)
            assert got["u"][i] == str(vals["u"])
            expect_js = "null" if vals["js"] is JSON_NULL \
                else json.dumps(vals["js"])
            assert got["js"][i] == expect_js
            assert got["by"][i] == vals["by"]
            assert got["s"][i] == vals["s"]

    def test_null_validity_bitmaps(self):
        schema = _schema((ColumnSchema("a", Oid.INT4),
                          ColumnSchema("s", Oid.TEXT)))
        rows = [TableRow([None, None]), TableRow([1, "x"]),
                TableRow([None, "y"]), TableRow([2, None])]
        rb = ColumnarBatch.from_rows(schema, rows).to_arrow()
        assert rb.column(0).to_pylist() == [None, 1, None, 2]
        assert rb.column(1).to_pylist() == [None, "x", "y", None]
        assert rb.column(0).null_count == 2

    def test_empty_batch(self):
        schema = _kinds_schema()
        rb = ColumnarBatch.from_rows(schema, []).to_arrow()
        assert rb.num_rows == 0
        assert rb.num_columns == len(schema.replicated_columns)

    def test_wide_schema_120_columns(self):
        kinds = [Oid.INT8, Oid.FLOAT8, Oid.TEXT, Oid.NUMERIC,
                 Oid.TIMESTAMPTZ, Oid.BOOL]
        cols = [ColumnSchema(f"c{i}", kinds[i % len(kinds)])
                for i in range(120)]
        schema = _schema(tuple(cols), name="wide")
        rng = np.random.RandomState(5)

        def val(j, i):
            if rng.rand() < 0.15:
                return None
            k = kinds[j % len(kinds)]
            if k == Oid.INT8:
                return int(rng.randint(-10**9, 10**9))
            if k == Oid.FLOAT8:
                return float(rng.rand())
            if k == Oid.TEXT:
                return f"v{j}-{i}"
            if k == Oid.NUMERIC:
                return PgNumeric(f"{i}.{j:03d}")
            if k == Oid.TIMESTAMPTZ:
                return dt.datetime(2024, 1, 1, i % 24, 0, 0, j,
                                   tzinfo=dt.timezone.utc)
            return bool((i + j) % 2)

        rows = [TableRow([val(j, i) for j in range(120)]) for i in range(40)]
        batch = ColumnarBatch.from_rows(schema, rows)
        rb = batch.to_arrow()
        assert rb.num_columns == 120 and rb.num_rows == 40
        # spot-check full value equality through Column.value
        for j in (0, 59, 119):
            col = batch.columns[j]
            kind = schema.replicated_columns[j].kind
            arrow_vals = rb.column(j).to_pylist()
            for i in range(40):
                v = col.value(i)
                if isinstance(v, PgNumeric):
                    v = v.pg_text()
                assert arrow_vals[i] == v

    def test_from_cells_equals_from_rows(self):
        schema = _kinds_schema()
        rows = _kinds_rows(12)
        a = ColumnarBatch.from_rows(schema, rows)
        cells = [[r.values[j] for r in rows]
                 for j in range(len(schema.replicated_columns))]
        b = ColumnarBatch.from_cells(schema, cells, len(rows))
        for ca, cb in zip(a.columns, b.columns):
            assert np.array_equal(ca.validity, cb.validity)
            for i in range(a.num_rows):
                assert ca.value(i) == cb.value(i)

    def test_concat_dense_arrow_and_object(self):
        _, ev1 = _engine_batch_event(16, start=0)
        _, ev2 = _engine_batch_event(16, start=16)
        merged = ColumnarBatch.concat([ev1.batch, ev2.batch])
        assert merged.num_rows == 32
        for i in range(16):
            for ca, cb in zip(merged.columns, ev1.batch.columns):
                assert ca.value(i) == cb.value(i)
            for ca, cb in zip(merged.columns, ev2.batch.columns):
                assert ca.value(16 + i) == cb.value(i)
        # object columns (NUMERIC) concat too
        schema = _kinds_schema()
        b1 = ColumnarBatch.from_rows(schema, _kinds_rows(4))
        b2 = ColumnarBatch.from_rows(schema, _kinds_rows(6))
        m = ColumnarBatch.concat([b1, b2])
        assert m.num_rows == 10
        assert m.columns[6].value(9) == b2.columns[6].value(5)


# ---------------------------------------------------------------------------
# 2. vectorized CDC metadata
# ---------------------------------------------------------------------------


class TestVectorizedCdcMetadata:
    def test_sequence_numbers_match_fstring_reference(self):
        lsns = np.array([0, 1, 0xDEADBEEF, 2**64 - 1, 2**40],
                        dtype=np.uint64)
        txos = np.array([0, 7, 2**63, 1, 42], dtype=np.uint64)
        ords = np.array([0, 1, 2, 3, 2**32], dtype=np.uint64)
        got = sequence_number_batch(lsns, txos, ords)
        for i in range(len(lsns)):
            ref = (f"{int(lsns[i]):016x}/{int(txos[i]):016x}/"
                   f"{int(ords[i]):016x}")
            assert got[i].decode() == ref
        assert sequence_number_arrow(lsns, txos, ords).to_pylist() == \
            [g.decode() for g in got]

    def test_sequence_matches_event_key(self):
        from etl_tpu.models.event import EventSequenceKey

        key = EventSequenceKey(Lsn(0x1234), 9)
        got = sequence_number_batch(np.array([0x1234], dtype=np.uint64),
                                    np.array([9], dtype=np.uint64),
                                    np.array([3], dtype=np.uint64))
        assert got[0].decode() == key.with_ordinal(3)

    def test_change_type_labels(self):
        cts = np.array([0, 1, 2, 0, 2])
        assert change_type_batch(cts).tolist() == \
            [b"UPSERT", b"UPSERT", b"DELETE", b"UPSERT", b"DELETE"]
        assert change_type_arrow(cts).to_pylist() == \
            ["UPSERT", "UPSERT", "DELETE", "UPSERT", "DELETE"]

    def test_hex16_arrow(self):
        vals = np.array([0, 255, 2**64 - 1], dtype=np.uint64)
        assert hex16_arrow(vals).to_pylist() == \
            [f"{int(v):016x}" for v in vals]


# ---------------------------------------------------------------------------
# 3. sequential_batch_program semantics
# ---------------------------------------------------------------------------


class TestSequentialBatchProgram:
    def test_coalesces_consecutive_same_table(self):
        schema, ev1 = _engine_batch_event(8, tid=41011)
        _, ev2 = _engine_batch_event(8, tid=41011, start=8)
        # force identical schema object (same-table run condition)
        ev2.schema = schema
        ops = list(sequential_batch_program([ev1, ev2]))
        assert [op[0] for op in ops] == ["batch"]
        cb = ops[0][2]
        assert isinstance(cb, CoalescedBatch) and cb.num_rows == 16
        assert cb.commit_lsns.tolist() == \
            ev1.commit_lsns.tolist() + ev2.commit_lsns.tolist()

    def test_splits_at_table_change_and_barriers(self):
        schema_a, ev_a = _engine_batch_event(4, tid=41012)
        schema_b, ev_b = _engine_batch_event(4, tid=41013)
        trunc = TruncateEvent(Lsn(5), Lsn(6), 0, 0, (schema_a,))
        ops = list(sequential_batch_program([ev_a, trunc, ev_b]))
        assert [op[0] for op in ops] == ["batch", "truncate", "batch"]
        assert ops[0][1].id == schema_a.id and ops[2][1].id == schema_b.id

    def test_old_tuple_batches_fall_back_to_rows_in_place(self):
        schema, simple = _engine_batch_event(4, tid=41014)
        _, complex_ev = _engine_batch_event(2, tid=41014, start=4)
        complex_ev.schema = schema
        # attach an old image: expand_batch_events semantics required
        complex_ev.old_rows = np.array([0], dtype=np.int64)
        complex_ev.old_is_key = np.array([False])
        complex_ev._old_batch = complex_ev.batch
        complex_ev.change_types = np.array([1, 0], dtype=np.int8)
        assert not batch_event_columnar_ok(complex_ev)
        ops = list(sequential_batch_program([simple, complex_ev]))
        assert [op[0] for op in ops] == ["batch", "rows"]
        # WAL order preserved: the batch run precedes the row fallback
        assert ops[0][2].num_rows == 4 and len(ops[1][2]) == 2

    def test_toast_batches_fall_back(self):
        schema = _schema((ColumnSchema("a", Oid.INT4),
                          ColumnSchema("s", Oid.TEXT)), tid=41015)
        rows = [TableRow([1, TOAST_UNCHANGED])]
        batch = ColumnarBatch.from_rows(schema, rows)
        ev = DecodedBatchEvent(
            Lsn(1), Lsn(2), schema,
            change_types=np.array([1], dtype=np.int8),
            commit_lsns=np.array([2], dtype=np.uint64),
            tx_ordinals=np.array([0], dtype=np.uint64), batch=batch)
        assert not batch_event_columnar_ok(ev)

    def test_per_row_events_take_rows_path(self):
        schema = _schema((ColumnSchema("a", Oid.INT4),), tid=41016)
        evs = [InsertEvent(Lsn(1), Lsn(2), i, schema, TableRow([i]))
               for i in range(3)]
        ops = list(sequential_batch_program(evs))
        assert [op[0] for op in ops] == ["rows"]
        assert len(ops[0][2]) == 3


# ---------------------------------------------------------------------------
# 4. encoder parity: columnar == legacy row path, byte for byte
# ---------------------------------------------------------------------------


def _retry_fast():
    from etl_tpu.destinations.util import DestinationRetryPolicy

    return DestinationRetryPolicy(max_attempts=2, initial_delay_s=0.01,
                                  max_delay_s=0.02)


class TestBqProtoParity:
    def test_encode_batch_identical_to_encode_row_all_kinds(self):
        schema = _kinds_schema()
        batch = ColumnarBatch.from_rows(schema, _kinds_rows(32))
        n = batch.num_rows
        cts = np.array([0 if i % 3 else 2 for i in range(n)])
        lsns = np.arange(n, dtype=np.uint64) + 2**40
        txos = np.arange(n, dtype=np.uint64)
        ords = np.arange(n, dtype=np.uint64)
        labels = change_type_batch(cts).tolist()
        seqs = sequence_number_batch(lsns, txos, ords)
        got = bq_proto.encode_batch(schema, batch, labels, seqs)
        want = [bq_proto.encode_row(
            schema, [c.value(i) for c in batch.columns],
            labels[i].decode(), seqs[i].decode()) for i in range(n)]
        assert got == want

    def test_encode_batch_identical_on_engine_batch(self):
        schema, ev = _engine_batch_event(128)
        n = len(ev)
        labels = change_type_batch(ev.change_types).tolist()
        seqs = sequence_number_batch(ev.commit_lsns, ev.tx_ordinals,
                                     np.arange(n, dtype=np.uint64))
        got = bq_proto.encode_batch(schema, ev.batch, labels, seqs)
        want = [bq_proto.encode_row(
            schema, [c.value(i) for c in ev.batch.columns],
            labels[i].decode(), seqs[i].decode()) for i in range(n)]
        assert got == want

    def test_dense_timestamptz_specials_raise_like_row_path(self):
        from etl_tpu.models.errors import EtlError

        schema = _schema((ColumnSchema("ts", Oid.TIMESTAMPTZ),), tid=41017)
        col = Column(schema.replicated_columns[0],
                     np.array([2**63 - 1], dtype=np.int64),
                     np.array([True]))
        batch = ColumnarBatch(schema, [col])
        with pytest.raises(EtlError):
            bq_proto.encode_batch(schema, batch, [b"UPSERT"],
                                  [b"0" * 50])


class TestClickHouseWireParity:
    async def test_cdc_bodies_byte_identical(self):
        from etl_tpu.destinations.clickhouse import (ClickHouseConfig,
                                                     ClickHouseDestination)
        from etl_tpu.testing.fake_http import RecordingHttpServer

        schema, ev1 = _engine_batch_event(32, tid=41021)
        _, ev2 = _engine_batch_event(16, tid=41021, start=32)
        ev2.schema = schema
        ev2.change_types = np.array([2] * 8 + [0] * 8, dtype=np.int8)
        events = [ev1, ev2]

        async def run(method):
            server = RecordingHttpServer()
            await server.start()
            try:
                d = ClickHouseDestination(
                    ClickHouseConfig(url=server.url(), database="etl"),
                    _retry_fast())
                await d.startup()
                await getattr(d, method)(events)
                await d.shutdown()
                return [r.body for r in server.requests
                        if "INSERT INTO" in r.query.get("query", "")]
            finally:
                await server.stop()

        legacy = await run("write_events")
        columnar = await run("write_event_batches")
        assert legacy and b"".join(legacy) == b"".join(columnar)

    def test_ancient_timestamps_render_identically(self):
        """Year < 1000 regression: glibc strftime('%Y') drops the zero
        padding, np.datetime_as_string keeps it — both paths must emit
        the padded form ClickHouse parses."""
        from etl_tpu.destinations.clickhouse import (_column_texts,
                                                     render_value)

        schema = _schema((ColumnSchema("ts", Oid.TIMESTAMP),
                          ColumnSchema("tstz", Oid.TIMESTAMPTZ)), tid=41027)
        rows = [TableRow([dt.datetime(99, 12, 31, 1, 2, 3, 4),
                          dt.datetime(7, 1, 2, 0, 0, 0, 0,
                                      tzinfo=dt.timezone.utc)])]
        batch = ColumnarBatch.from_rows(schema, rows)
        for col in batch.columns:
            bulk = _column_texts(col)[0]
            row = render_value(col.value(0), col.schema.kind)
            assert bulk == row, (bulk, row)
            assert str(bulk).startswith(("0099-", "0007-"))

    async def test_copy_bodies_byte_identical(self):
        from etl_tpu.destinations.clickhouse import (ClickHouseConfig,
                                                     ClickHouseDestination)
        from etl_tpu.testing.fake_http import RecordingHttpServer

        schema = _kinds_schema()
        batch = ColumnarBatch.from_rows(schema, _kinds_rows(16))

        async def run(method):
            server = RecordingHttpServer()
            await server.start()
            try:
                d = ClickHouseDestination(
                    ClickHouseConfig(url=server.url(), database="etl"),
                    _retry_fast())
                await d.startup()
                await getattr(d, method)(schema, batch)
                await d.shutdown()
                return [r.body for r in server.requests
                        if "INSERT INTO" in r.query.get("query", "")]
            finally:
                await server.stop()

        assert await run("write_table_rows") == await run("write_table_batch")


class TestBigQueryWireParity:
    async def _bq(self):
        from etl_tpu.testing.fake_bq import StorageWriteFake
        from etl_tpu.testing.fake_http import RecordingHttpServer

        server = RecordingHttpServer()
        await server.start()
        fake = StorageWriteFake()
        server.responders.append(fake)
        return server, fake

    async def test_cdc_rows_byte_identical(self):
        from etl_tpu.destinations.bigquery import (BigQueryConfig,
                                                   BigQueryDestination)

        schema, ev1 = _engine_batch_event(32, tid=41022)
        _, ev2 = _engine_batch_event(16, tid=41022, start=32)
        ev2.schema = schema
        ev2.change_types = np.array([2] * 8 + [0] * 8, dtype=np.int8)
        events = [ev1, ev2]

        async def run(method):
            server, fake = await self._bq()
            try:
                d = BigQueryDestination(
                    BigQueryConfig(project_id="p", dataset_id="ds",
                                   base_url=server.url()), _retry_fast())
                await d.startup()
                ack = await getattr(d, method)(events)
                await ack.wait_durable()
                await d.shutdown()
                return [req.serialized_rows for _, req, _ in fake.appends]
            finally:
                await server.stop()

        legacy = await run("write_events")
        columnar = await run("write_event_batches")
        assert legacy and legacy == columnar

    async def test_copy_rows_byte_identical(self):
        from etl_tpu.destinations.bigquery import (BigQueryConfig,
                                                   BigQueryDestination)

        schema, ev = _engine_batch_event(24, tid=41023)

        async def run(method):
            server, fake = await self._bq()
            try:
                d = BigQueryDestination(
                    BigQueryConfig(project_id="p", dataset_id="ds",
                                   base_url=server.url()), _retry_fast())
                await d.startup()
                ack = await getattr(d, method)(schema, ev.batch)
                await ack.wait_durable()
                await d.shutdown()
                return [req.serialized_rows for _, req, _ in fake.appends]
            finally:
                await server.stop()

        assert await run("write_table_rows") == await run("write_table_batch")


class TestLakeParity:
    async def test_cdc_content_identical(self, tmp_path):
        import pyarrow.parquet as pq

        from etl_tpu.destinations.lake import LakeConfig, LakeDestination

        schema, ev1 = _engine_batch_event(32, tid=41024)
        _, ev2 = _engine_batch_event(16, tid=41024, start=32)
        ev2.schema = schema
        ev2.change_types = np.array([2] * 8 + [0] * 8, dtype=np.int8)
        events = [ev1, ev2]

        async def run(method, sub):
            d = LakeDestination(LakeConfig(str(tmp_path / sub)))
            await d.startup()
            await getattr(d, method)(events)
            db = d._catalog()
            tables = []
            for (path,) in db.execute(
                    "SELECT path FROM lake_files WHERE kind='cdc'"):
                tables.append(pq.read_table(path))
            current = d.read_current(schema.id)
            await d.shutdown()
            return tables, current

        legacy_files, legacy_current = await run("write_events", "legacy")
        col_files, col_current = await run("write_event_batches", "col")
        assert len(legacy_files) == len(col_files) == 1
        assert legacy_files[0].equals(col_files[0])
        assert legacy_current.sort_by("id").equals(col_current.sort_by("id"))

    async def test_replay_dedup_carries_over(self, tmp_path):
        from etl_tpu.destinations.lake import LakeConfig, LakeDestination

        schema, ev = _engine_batch_event(8, tid=41025)
        d = LakeDestination(LakeConfig(str(tmp_path / "dedup")))
        await d.startup()
        await d.write_event_batches([ev])
        n1 = d.current_cdc_file_count(schema.id)
        await d.write_event_batches([ev])  # redelivery: max_seq ≤ watermark
        assert d.current_cdc_file_count(schema.id) == n1
        await d.shutdown()


class TestIcebergRbParity:
    def test_record_batch_identical(self):
        schema, ev = _engine_batch_event(24, tid=41026)
        cb = CoalescedBatch([ev])
        n = cb.num_rows
        # columnar rb (what _write_cdc_batch builds)
        rb_col = cb.batch.to_arrow()
        rb_col = rb_col.append_column(CHANGE_TYPE_COLUMN,
                                      change_type_arrow(cb.change_types))
        rb_col = rb_col.append_column(
            CHANGE_SEQUENCE_COLUMN,
            sequence_number_arrow(cb.commit_lsns, cb.tx_ordinals,
                                  np.arange(n, dtype=np.uint64)))
        # legacy rb (what _write_cdc_run builds from expanded rows)
        evs = expand_batch_events([ev])
        rows = [e.row for e in evs]
        types = ["UPSERT"] * n
        seqs = [e.sequence_key.with_ordinal(i) for i, e in enumerate(evs)]
        rb_row = ColumnarBatch.from_rows(schema, rows).to_arrow()
        rb_row = rb_row.append_column(CHANGE_TYPE_COLUMN,
                                      pa.array(types, pa.string()))
        rb_row = rb_row.append_column(CHANGE_SEQUENCE_COLUMN,
                                      pa.array(seqs, pa.string()))
        assert rb_col.equals(rb_row)


# ---------------------------------------------------------------------------
# 5. seam plumbing: shims, wrappers, zero row materialization
# ---------------------------------------------------------------------------


class TestSeamPlumbing:
    async def test_default_shim_passes_events_through(self):
        captured = {}

        class RowOnly(Destination):
            async def startup(self):
                return None

            async def write_table_rows(self, schema, batch):
                captured["copy"] = batch
                return WriteAck.durable()

            async def write_events(self, events):
                captured["events"] = events
                return WriteAck.durable()

            async def drop_table(self, table_id, schema=None):
                return None

            async def truncate_table(self, table_id):
                return None

        schema, ev = _engine_batch_event(4, tid=41031)
        d = RowOnly()
        await d.write_event_batches([ev])
        assert captured["events"] == [ev]  # identity passthrough
        await d.write_table_batch(schema, ev.batch)
        assert captured["copy"] is ev.batch

    async def test_fault_wrapper_applies_row_scripts_to_batch_seam(self):
        from etl_tpu.destinations.memory import (FaultAction,
                                                 FaultInjectingDestination,
                                                 FaultKind,
                                                 MemoryDestination)
        from etl_tpu.models.errors import EtlError

        schema, ev = _engine_batch_event(4, tid=41032)
        d = FaultInjectingDestination(MemoryDestination())
        d.script("write_events", FaultAction(FaultKind.REJECT))
        with pytest.raises(EtlError):
            await d.write_event_batches([ev])
        # after the scripted fault drains, the batch seam lands rows
        await d.write_event_batches([ev])
        assert len(d.inner.events) == 4
        d.script("write_table_rows", FaultAction(FaultKind.REJECT))
        with pytest.raises(EtlError):
            await d.write_table_batch(schema, ev.batch)

    async def test_supervised_wrapper_routes_to_inner_batch_seam(self):
        from etl_tpu.supervision.destination import SupervisedDestination

        calls = []

        class Spy(Destination):
            async def startup(self):
                return None

            async def write_table_rows(self, schema, batch):
                calls.append("rows")
                return WriteAck.durable()

            async def write_events(self, events):
                calls.append("events")
                return WriteAck.durable()

            async def write_table_batch(self, schema, batch):
                calls.append("batch")
                return WriteAck.durable()

            async def write_event_batches(self, events):
                calls.append("event_batches")
                return WriteAck.durable()

            async def drop_table(self, table_id, schema=None):
                return None

            async def truncate_table(self, table_id):
                return None

        schema, ev = _engine_batch_event(4, tid=41033)
        d = SupervisedDestination(Spy(), timeout_s=5.0)
        await d.write_event_batches([ev])
        await d.write_table_batch(schema, ev.batch)
        assert calls == ["event_batches", "batch"]

    async def test_zero_row_materialization_on_columnar_paths(self, tmp_path):
        from etl_tpu.destinations.clickhouse import (ClickHouseConfig,
                                                     ClickHouseDestination)
        from etl_tpu.destinations.lake import LakeConfig, LakeDestination
        from etl_tpu.testing.fake_http import RecordingHttpServer

        schema, ev = _engine_batch_event(64, tid=41034)
        server = RecordingHttpServer()
        await server.start()
        try:
            ch = ClickHouseDestination(
                ClickHouseConfig(url=server.url(), database="etl"),
                _retry_fast())
            await ch.startup()
            lake = LakeDestination(LakeConfig(str(tmp_path / "zero")))
            await lake.startup()
            before = rows_constructed()
            await ch.write_event_batches([ev])
            await ch.write_table_batch(schema, ev.batch)
            await lake.write_event_batches([ev])
            labels = change_type_batch(ev.change_types).tolist()
            seqs = sequence_number_batch(
                ev.commit_lsns, ev.tx_ordinals,
                np.arange(len(ev), dtype=np.uint64))
            bq_proto.encode_batch(schema, ev.batch, labels, seqs)
            assert rows_constructed() == before, \
                "columnar egress constructed TableRows on the hot path"
            await ch.shutdown()
            await lake.shutdown()
        finally:
            await server.stop()

    async def test_streamed_cdc_constructs_no_rows(self):
        """The whole streamed path — fake walsender -> apply loop ->
        assembler -> decode engine -> a sink that resolves batches —
        builds ZERO TableRows once the table's program is warm: decoded
        batches reach the destination columnar."""
        from etl_tpu.config import (BatchConfig, BatchEngine,
                                    PipelineConfig)
        from etl_tpu.models.table_state import TableStateType
        from etl_tpu.ops import engine
        from etl_tpu.postgres.fake import FakeDatabase, FakeSource
        from etl_tpu.runtime import Pipeline
        from etl_tpu.store import NotifyingStore
        from etl_tpu.telemetry.metrics import (
            ETL_DECODE_ROUTED_ORACLE_ROWS_TOTAL, registry)

        class ResolvingSink(Destination):
            rows = 0

            async def startup(self):
                return None

            async def write_table_rows(self, schema, batch):
                return WriteAck.durable()

            async def write_events(self, events):
                for e in events:
                    if isinstance(e, DecodedBatchEvent):
                        self.rows += e.batch.num_rows  # resolves it
                    elif isinstance(e, InsertEvent):
                        self.rows += 1
                return WriteAck.durable()

            async def drop_table(self, table_id, schema=None):
                return None

            async def truncate_table(self, table_id):
                return None

        tid = 41037
        db = FakeDatabase()
        db.create_table(TableSchema(
            tid, TableName("public", "stream_t"),
            (ColumnSchema("id", Oid.INT8, nullable=False,
                          primary_key_ordinal=1),
             ColumnSchema("v", Oid.INT4),
             ColumnSchema("note", Oid.TEXT))))
        db.create_publication("pub", [tid])
        store, dest = NotifyingStore(), ResolvingSink()
        # a long fill window: flushes are cut at commits, so every flush
        # of a 200-row transaction stages into the same row bucket
        pipeline = Pipeline(
            config=PipelineConfig(
                pipeline_id=1, publication_name="pub",
                batch=BatchConfig(max_fill_ms=2000,
                                  batch_engine=BatchEngine.TPU)),
            store=store, destination=dest,
            source_factory=lambda: FakeSource(db))

        async def commit_and_deliver(first: int) -> None:
            want = dest.rows + 200
            tx = db.transaction()
            for i in range(first, first + 200):
                tx.insert(tid, [str(i), str(i % 97), "note-%d" % i])
            await tx.commit()
            while dest.rows < want:
                assert not pipeline._apply_task.done(), "stream stopped"
                await asyncio.sleep(0.01)

        await pipeline.start()
        try:
            await asyncio.wait_for(
                store.notify_on(tid, TableStateType.READY), 60)
            # the cold program builds on a background thread while the
            # per-row oracle (which does build rows) serves the batch
            await commit_and_deliver(1000)
            while engine.background_compiles_inflight():
                await asyncio.sleep(0.02)
            before = rows_constructed()
            oracle0 = registry.get_counter(
                ETL_DECODE_ROUTED_ORACLE_ROWS_TOTAL)
            for k in range(2, 6):
                await commit_and_deliver(k * 1000)
            assert registry.get_counter(
                ETL_DECODE_ROUTED_ORACLE_ROWS_TOTAL) == oracle0, \
                "the measured transactions were not decoded by the engine"
            assert rows_constructed() == before, \
                "the streamed CDC path constructed TableRows"
        finally:
            await pipeline.shutdown_and_wait()

    async def test_memory_shim_still_expands(self):
        from etl_tpu.destinations.memory import MemoryDestination

        _, ev = _engine_batch_event(8, tid=41035)
        d = MemoryDestination()
        before = rows_constructed()
        await d.write_event_batches([ev])
        assert len(d.events) == 8
        assert rows_constructed() > before  # the compatibility shim works


# ---------------------------------------------------------------------------
# 6. columnar COPY parse (runtime/copy.py:177 round-trip kill)
# ---------------------------------------------------------------------------


class TestCopyColumnarParse:
    def test_parse_chunk_columns_matches_row_parse(self):
        from etl_tpu.postgres.codec.copy_text import (parse_copy_chunk_columns,
                                                      parse_copy_row)

        oids = [int(Oid.INT8), int(Oid.TEXT), int(Oid.FLOAT8)]
        lines = [b"1\thello\t1.5", b"2\t\\N\t-3.25",
                 b"3\ttab\\there\t\\N", b""]
        chunk = b"\n".join(lines) + b"\n"
        cells, n = parse_copy_chunk_columns(chunk, oids)
        assert n == 3
        rows = [parse_copy_row(line, oids) for line in lines if line]
        for j in range(3):
            assert cells[j] == [r.values[j] for r in rows]

    def test_columnar_parse_constructs_no_rows(self):
        from etl_tpu.postgres.codec.copy_text import parse_copy_chunk_columns

        oids = [int(Oid.INT8), int(Oid.TEXT)]
        chunk = b"".join(b"%d\tv-%d\n" % (i, i) for i in range(100))
        before = rows_constructed()
        cells, n = parse_copy_chunk_columns(chunk, oids)
        schema = _schema((ColumnSchema("a", Oid.INT8),
                          ColumnSchema("b", Oid.TEXT)), tid=41036)
        batch = ColumnarBatch.from_cells(schema, cells, n)
        assert batch.num_rows == 100
        assert rows_constructed() == before

    def test_field_count_mismatch_raises(self):
        from etl_tpu.models.errors import EtlError
        from etl_tpu.postgres.codec.copy_text import parse_copy_chunk_columns

        with pytest.raises(EtlError):
            parse_copy_chunk_columns(b"1\t2\t3\n", [int(Oid.INT4)])


# ---------------------------------------------------------------------------
# Snowpipe NDJSON columnar encoder (ISSUE 12 satellite — the last
# destination off the row path)
# ---------------------------------------------------------------------------


class TestSnowpipeNdjsonParity:
    """encode_batch_ndjson must be byte-identical to the row path's
    `json.dumps(_doc(...), separators=(",", ":"), ensure_ascii=False,
    allow_nan=False) + "\\n"` on every kind and escape case."""

    @staticmethod
    def _reference_lines(schema, batch, ops, seqs):
        from etl_tpu.destinations.bigquery import encode_value
        from etl_tpu.destinations.snowflake import (CDC_OPERATION_COLUMN,
                                                    CDC_SEQUENCE_COLUMN)

        lines = []
        for i in range(batch.num_rows):
            doc = {c.schema.name: encode_value(c.value(i), c.schema.kind)
                   for c in batch.columns}
            doc[CDC_OPERATION_COLUMN] = \
                ops if isinstance(ops, str) else ops[i]
            doc[CDC_SEQUENCE_COLUMN] = \
                seqs if isinstance(seqs, str) else seqs[i]
            lines.append((json.dumps(doc, separators=(",", ":"),
                                     ensure_ascii=False, allow_nan=False)
                          + "\n").encode())
        return lines

    def test_every_kind_byte_identical(self):
        from etl_tpu.destinations.snowflake import (encode_batch_ndjson,
                                                    offset_token_batch)

        schema = _kinds_schema()
        batch = ColumnarBatch.from_rows(schema, _kinds_rows(12))
        seqs = offset_token_batch(
            np.arange(12, dtype=np.uint64) + (1 << 33),
            np.arange(12, dtype=np.uint64))
        got = encode_batch_ndjson(schema, batch, "insert", seqs)
        assert got == self._reference_lines(schema, batch, "insert", seqs)

    def test_engine_batch_byte_identical(self):
        """The production shape: dense ints + Arrow strings straight off
        the decode engine, mixed op labels."""
        from etl_tpu.destinations.snowflake import (encode_batch_ndjson,
                                                    offset_token_batch)

        schema, ev = _engine_batch_event(n=96, tid=41050)
        cb = CoalescedBatch([ev])
        labels = ["insert" if i % 3 else "update" for i in range(96)]
        seqs = offset_token_batch(cb.commit_lsns, cb.tx_ordinals)
        got = encode_batch_ndjson(schema, cb.batch, labels, seqs)
        assert got == self._reference_lines(schema, cb.batch, labels, seqs)

    def test_unicode_and_escape_cases(self):
        from etl_tpu.destinations.snowflake import encode_batch_ndjson

        schema = _schema((ColumnSchema("s", Oid.TEXT),), tid=41051)
        texts = ['plain', 'quote " inside', 'back\\slash', 'tab\tnl\n',
                 'ctrl\x01\x1f', 'emoji 🚀 café', ' ls  ps',
                 None, '']
        rows = [TableRow([t]) for t in texts]
        batch = ColumnarBatch.from_rows(schema, rows)
        got = encode_batch_ndjson(schema, batch, "insert", "0" * 33)
        assert got == self._reference_lines(schema, batch, "insert",
                                            "0" * 33)

    def test_nonfinite_float_raises_like_row_path(self):
        from etl_tpu.destinations.snowflake import encode_batch_ndjson
        from etl_tpu.models.errors import EtlError

        schema = _schema((ColumnSchema("f", Oid.FLOAT8),), tid=41052)
        batch = ColumnarBatch.from_rows(
            schema, [TableRow([1.5]), TableRow([float("nan")])])
        with pytest.raises(EtlError):
            encode_batch_ndjson(schema, batch, "insert", "0" * 33)
        # the row path refuses the same batch (allow_nan=False)
        with pytest.raises(ValueError):
            json.dumps({"f": float("nan")}, allow_nan=False)

    def test_offset_token_batch_matches_scalar(self):
        from etl_tpu.destinations.snowflake import offset_token_batch
        from etl_tpu.destinations.snowpipe import offset_token

        lsns = [0, 1, 0xdeadbeef, (1 << 64) - 1]
        ords = [0, 7, 123456789, (1 << 40) + 3]
        assert offset_token_batch(lsns, ords) == \
            [offset_token(l, o) for l, o in zip(lsns, ords)]

    def test_push_encoded_line_equals_push_row(self):
        pytest.importorskip("zstandard")
        from etl_tpu.destinations.snowflake import (CDC_OPERATION_COLUMN,
                                                    CDC_SEQUENCE_COLUMN,
                                                    encode_batch_ndjson)
        from etl_tpu.destinations.snowpipe import RowBatchBuilder

        schema = _kinds_schema()
        batch = ColumnarBatch.from_rows(schema, _kinds_rows(8))
        seq = "0" * 16 + "/" + "0" * 16
        row_builder = RowBatchBuilder()
        for i in range(batch.num_rows):
            from etl_tpu.destinations.bigquery import encode_value

            doc = {c.schema.name: encode_value(c.value(i), c.schema.kind)
                   for c in batch.columns}
            doc[CDC_OPERATION_COLUMN] = "insert"
            doc[CDC_SEQUENCE_COLUMN] = seq
            row_builder.push_row(doc, seq)
        col_builder = RowBatchBuilder()
        for line in encode_batch_ndjson(schema, batch, "insert", seq):
            col_builder.push_encoded_line(line, seq)
        a, b = row_builder.finish(), col_builder.finish()
        assert [(x.data, x.row_count, x.start_offset, x.end_offset)
                for x in a] == \
            [(x.data, x.row_count, x.start_offset, x.end_offset)
             for x in b]

    def test_hot_loop_marked(self):
        """etl-lint rule 13 territory: the encoder is @hot_loop so row
        materialization can never creep into it unnoticed."""
        from etl_tpu.analysis.annotations import HOT_LOOP_ATTR
        from etl_tpu.destinations import snowflake

        assert getattr(snowflake.encode_batch_ndjson, HOT_LOOP_ATTR, False)
        assert getattr(snowflake._column_json_texts, HOT_LOOP_ATTR, False)
