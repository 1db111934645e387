"""Device-resident egress (ISSUE 17): the fused wire-encoding stage.

Byte-identity is the whole contract — the device encoder is only
allowed to exist because its bytes are indistinguishable from the host
columnar encoders on every destination format. Covered here:

  1. the egress plan (renderable-kind selection, width table, the
     EGRESS_MAX_COLS guard);
  2. device program vs numpy host twins per renderable CellKind,
     single-device AND on the forced 8-shard mesh;
  3. destination fast paths vs their columnar oracles: ClickHouse TSV,
     Snowpipe NDJSON, BigQuery proto DATE cells, the Arrow fixed-width
     string helpers — with NULL bitmaps, specials-driven fallback rows
     (untrusted overrides), tab/escape-laden strings, and both the
     copy and CDC shapes;
  4. the engine seam: `ColumnarBatch.device_egress` attach on the host
     dispatch route, encoder-dependent field selection, config gating,
     and `DeviceEgress.concat` all-or-nothing merging.
"""

from __future__ import annotations

import datetime as dt

import numpy as np
import pyarrow as pa
import pytest

from etl_tpu.destinations import bq_proto
from etl_tpu.destinations.base import CoalescedBatch
from etl_tpu.destinations.clickhouse import (render_batch_tsv_columnar,
                                             render_batch_tsv_fast)
from etl_tpu.destinations.snowflake import (encode_batch_ndjson,
                                            encode_batch_ndjson_fast,
                                            offset_token_batch)
from etl_tpu.destinations.util import (change_type_batch,
                                       fixed_width_string_arrow, hex16_arrow,
                                       sequence_number_arrow,
                                       sequence_number_batch,
                                       sequence_number_buffer,
                                       string_array_from_fixed)
from etl_tpu import native
from etl_tpu.models import (ColumnSchema, ColumnarBatch, Oid,
                            ReplicatedTableSchema, TableName, TableSchema)
from etl_tpu.models.cell import JSON_NULL, TOAST_UNCHANGED, PgNumeric
from etl_tpu.models.event import ChangeType, DecodedBatchEvent
from etl_tpu.models.lsn import Lsn
from etl_tpu.models.table_row import CellKind, TableRow
from etl_tpu.ops import egress as eg


def _schema(cols, tid=43001, name="dev_egress"):
    return ReplicatedTableSchema.with_all_columns(TableSchema(
        tid, TableName("public", name), tuple(cols)))


def _kinds_schema(tid=43001):
    return _schema((
        ColumnSchema("pk", Oid.INT8, nullable=False, primary_key_ordinal=1),
        ColumnSchema("b", Oid.BOOL),
        ColumnSchema("i2", Oid.INT2),
        ColumnSchema("i4", Oid.INT4),
        ColumnSchema("f4", Oid.FLOAT4),
        ColumnSchema("f8", Oid.FLOAT8),
        ColumnSchema("num", Oid.NUMERIC),
        ColumnSchema("d", Oid.DATE),
        ColumnSchema("ts", Oid.TIMESTAMP),
        ColumnSchema("tstz", Oid.TIMESTAMPTZ),
        ColumnSchema("js", Oid.JSONB),
        ColumnSchema("s", Oid.TEXT),
    ), tid=tid)


def _kinds_rows(n=16):
    rows = []
    for i in range(n):
        rows.append(TableRow([
            (i - n // 2) * 123456789,
            bool(i % 2) if i % 5 else None,
            (i - 3) * 7 if i % 4 else None,
            -i * 1000 if i % 3 else None,
            i * 0.5,
            i * 1.25e10 if i % 6 else None,
            PgNumeric("9" * 20 + ".%05d" % i),
            dt.date(2024, 5, (i % 28) + 1) if i % 7 else None,
            dt.datetime(2024, 5, 1, 1, 2, 3, 100000 + i),
            dt.datetime(2031, 12, 31, 23, 59, 59, 999990 + (i % 10),
                        tzinfo=dt.timezone.utc),
            {"k": i} if i % 2 else JSON_NULL,
            "str-%d\twith\ttabs\nand\\back" % i if i % 2 else None,
        ]))
    return rows


def _specials_rows(n=8):
    """Rows whose temporal values force the oracle-fallback path
    (infinity / out-of-text-range sentinels never ride device text)."""
    rows = _kinds_rows(n)
    vals = list(rows[2].values)
    vals[7] = dt.date.max            # DATE beyond the render range
    rows[2] = TableRow(vals)
    vals = list(rows[5].values)
    vals[8] = dt.datetime.max        # TIMESTAMP at the sentinel edge
    rows[5] = TableRow(vals)
    return rows


def _toast_rows(n=16):
    """Rows of an UPDATE that left its TOASTed columns alone: the string
    and the JSON cell of every third row are unchanged-TOAST, which the
    wire renders as NULL."""
    rows = _kinds_rows(n)
    for i in range(0, n, 3):
        vals = list(rows[i].values)
        vals[10] = vals[11] = TOAST_UNCHANGED
        rows[i] = TableRow(vals)
    return rows


def _accounts_schema(tid=43010):
    return _schema((
        ColumnSchema("aid", Oid.INT4, nullable=False, primary_key_ordinal=1),
        ColumnSchema("bid", Oid.INT4),
        ColumnSchema("abalance", Oid.INT4),
        ColumnSchema("filler", Oid.BPCHAR, modifier=88)),
        tid=tid, name="pgbench_accounts")


def _accounts_values(n=500):
    """`pgbench_accounts` rows as the benchmark's CDC cells send them."""
    return [[str(i + 1).encode(), str(i % 10 + 1).encode(),
             str((i * 7919) % 2_000_000_000 - 10**9).encode(), b" " * 84]
            for i in range(n)]


def _untrusted(dev, rows):
    """`dev` with `rows` marked as the decode's fallback rows are: their
    device bytes may hold anything and must not reach the wire."""
    fields = {}
    for j, (buf, lens) in dev.fields.items():
        buf = np.array(buf, copy=True)
        buf[rows] = ord("?")
        fields[j] = (buf, lens)
    return eg.DeviceEgress(dev.encoder, dev.n_rows, fields,
                           np.asarray(rows, dtype=np.int64))


@pytest.fixture(params=["native", "numpy"])
def assembly(request, monkeypatch):
    """Both branches of `ops/egress.assemble_rows` / `int_text_fixed`: the
    C pass, and the numpy bodies of a process whose native library did
    not build (the framer and the pack then take their fallbacks too)."""
    if request.param == "native":
        if not native.native_available():
            pytest.skip(f"no native library: {native._build_error}")
    else:
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_build_error", "test: no compiler")
    return request.param


def _native_rows():
    from etl_tpu.telemetry.metrics import (
        ETL_EGRESS_NATIVE_ASSEMBLED_ROWS_TOTAL, registry)

    return registry.get_counter(ETL_EGRESS_NATIVE_ASSEMBLED_ROWS_TOTAL)


def _decoded_event(schema, batch, start=0):
    n = batch.num_rows
    return DecodedBatchEvent(
        Lsn(start + 1), Lsn(start + n), schema,
        change_types=np.array([int(ChangeType.DELETE) if i % 5 == 4
                               else int(ChangeType.INSERT)
                               for i in range(n)], dtype=np.int8),
        commit_lsns=np.arange(start, start + n, dtype=np.uint64) + 0x1000,
        tx_ordinals=np.arange(n, dtype=np.uint64),
        batch=batch)


def _engine_batch(schema, values_rows, egress=None, **decoder_kw):
    """A ColumnarBatch through the REAL staging + decode + egress path.
    `values_rows` are per-row lists of wire texts (bytes) or None."""
    from etl_tpu.ops.engine import DeviceDecoder
    from etl_tpu.ops.wal import concat_payloads, stage_wal_batch
    from etl_tpu.postgres.codec.pgoutput import encode_insert

    payloads = [encode_insert(schema.id, vals) for vals in values_rows]
    buf, offs, lens = concat_payloads(payloads)
    wal = stage_wal_batch(buf, offs, lens,
                          len(schema.replicated_columns))
    dec = DeviceDecoder(schema, egress=egress, **decoder_kw)
    return dec.decode(wal.staged)


def _int_schema(tid=43002):
    return _schema((
        ColumnSchema("id", Oid.INT8, nullable=False, primary_key_ordinal=1),
        ColumnSchema("v", Oid.INT4),
        ColumnSchema("flag", Oid.BOOL),
        ColumnSchema("d", Oid.DATE),
        ColumnSchema("note", Oid.TEXT)), tid=tid, name=f"t{tid}")


def _int_values(n=64, start=0):
    out = []
    for i in range(n):
        out.append([
            str(start + i - n // 3).encode(),
            str((i * 37) % 211 - 100).encode() if i % 7 else None,
            (b"t" if i % 2 else b"f") if i % 5 else None,
            b"2024-0%d-1%d" % ((i % 9) + 1, i % 10),
            b"note-%d" % i if i % 3 else None,
        ])
    return out


# ---------------------------------------------------------------------------
# 1. the egress plan
# ---------------------------------------------------------------------------


class TestEgressPlan:
    def test_tsv_selects_renderable_kinds_only(self):
        specs = tuple((j, k, 4, 32) for j, k in enumerate((
            CellKind.I64, CellKind.BOOL, CellKind.F64, CellKind.DATE,
            CellKind.TIMESTAMP, CellKind.STRING)))
        plan = eg.plan_for_specs(specs, eg.ENCODER_TSV)
        assert plan is not None
        assert plan.slots == (0, 1, 3, 4)
        assert plan.kinds == (CellKind.I64, CellKind.BOOL, CellKind.DATE,
                              CellKind.TIMESTAMP)
        assert plan.total_width == 20 + 5 + 10 + 26

    def test_json_excludes_temporals(self):
        specs = ((0, CellKind.I32, 4, 32), (1, CellKind.DATE, 4, 32),
                 (2, CellKind.TIMESTAMP, 8, 64))
        plan = eg.plan_for_specs(specs, eg.ENCODER_JSON)
        assert plan is not None and plan.slots == (0,)

    def test_no_renderable_fields_is_none(self):
        specs = ((0, CellKind.F32, 4, 32), (1, CellKind.STRING, 4, 32))
        assert eg.plan_for_specs(specs, eg.ENCODER_TSV) is None
        assert eg.plan_for_specs((), eg.ENCODER_TSV) is None
        assert eg.plan_for_specs(specs, "nope") is None

    def test_too_wide_schema_is_none(self):
        specs = tuple((j, CellKind.I32, 4, 32)
                      for j in range(eg.EGRESS_MAX_COLS + 1))
        assert eg.plan_for_specs(specs, eg.ENCODER_TSV) is None

    def test_budget_contract_matches_program_outputs(self):
        from etl_tpu.analysis.ir import contracts
        from etl_tpu.ops.egress import lower_egress_program

        specs = ((0, CellKind.I64, 8, 64), (1, CellKind.DATE, 4, 32))
        _fn, _avals, lowered = lower_egress_program(
            specs, eg.ENCODER_TSV, 256)
        import jax

        out_avals = jax.tree_util.tree_leaves(lowered.out_info)
        plan = eg.plan_for_specs(specs, eg.ENCODER_TSV)
        assert contracts.check_egress_output_budget(
            out_avals, 256, plan.total_width, len(plan.slots)) == []
        # a shrunk budget must fire
        assert contracts.check_egress_output_budget(
            out_avals, 256, plan.total_width - 10, 0)


# ---------------------------------------------------------------------------
# 2. device program vs host twins
# ---------------------------------------------------------------------------


class TestDeviceVsHostTwins:
    """The decode engine is the honest packer: decode real wire text,
    then compare the attached device buffers against the numpy twins on
    the decoded dense columns."""

    def _egress_fields(self, encoder):
        schema = _int_schema()
        vals = _int_values(64)
        batch = _engine_batch(schema, vals, egress=encoder)
        dev = batch.device_egress
        assert dev is not None and dev.encoder == encoder
        assert dev.untrusted.size == 0
        return batch, dev

    def test_tsv_fields_match_twins(self):
        batch, dev = self._egress_fields(eg.ENCODER_TSV)
        for j, col in enumerate(batch.columns):
            kind = col.schema.kind
            pair = dev.field(j)
            if kind is CellKind.STRING:
                assert pair is None
                continue
            assert pair is not None, (j, kind)
            buf, lens = pair
            data = np.asarray(col.data)
            if kind in (CellKind.I64, CellKind.I32, CellKind.I16,
                        CellKind.U32):
                twin = eg.int_text_fixed(data)
            elif kind is CellKind.BOOL:
                twin = eg.bool_text_fixed(data)
            elif kind is CellKind.DATE:
                twin = eg.date_text_fixed(data)
            else:
                continue
            tbuf, tlens = twin
            valid = np.asarray(col.validity, dtype=bool)
            assert np.array_equal(np.asarray(lens)[valid], tlens[valid])
            for i in np.flatnonzero(valid):
                assert bytes(buf[i, :lens[i]]) == bytes(tbuf[i, :tlens[i]])

    def test_json_fields_exclude_dates(self):
        _batch, dev = self._egress_fields(eg.ENCODER_JSON)
        kinds = {j for j in dev.fields}
        schema = _int_schema()
        date_j = [j for j, c in enumerate(schema.replicated_columns)
                  if c.name == "d"][0]
        text_j = [j for j, c in enumerate(schema.replicated_columns)
                  if c.name == "note"][0]
        assert date_j not in kinds and text_j not in kinds

    def test_timestamp_twin_matches_device_on_mesh(self):
        """Full-width coverage on the forced 8-shard mesh: TIMESTAMP is
        the widest render (26B); the mesh program must produce the
        same bytes as the single-device one and the host twin."""
        import jax
        from jax.sharding import Mesh

        if len(jax.devices()) < 8:
            pytest.skip("needs the forced 8-device CPU backend")
        schema = _schema((
            ColumnSchema("id", Oid.INT8, nullable=False,
                         primary_key_ordinal=1),
            ColumnSchema("ts", Oid.TIMESTAMP)), tid=43005, name="mts")
        vals = [[str(i).encode(),
                 b"2024-05-01 01:02:03.%06d" % (i * 999983 % 1000000)]
                for i in range(64)]
        mesh = Mesh(np.array(jax.devices()[:8]), axis_names=("sp",))
        b_single = _engine_batch(schema, vals, egress=eg.ENCODER_TSV)
        b_mesh = _engine_batch(schema, vals, egress=eg.ENCODER_TSV,
                               device_min_rows=0, mesh=mesh,
                               mesh_min_rows=0)
        for b in (b_single, b_mesh):
            dev = b.device_egress
            assert dev is not None
            buf, lens = dev.field(1)
            micros = np.asarray(b.columns[1].data)
            tbuf, tlens = eg.timestamp_text_fixed(micros)
            assert np.array_equal(np.asarray(lens), tlens)
            for i in range(len(vals)):
                assert bytes(np.asarray(buf)[i, :lens[i]]) \
                    == bytes(tbuf[i, :tlens[i]]), i
        # and the mesh bytes equal the single-device bytes
        bs, ls = b_single.device_egress.field(1)
        bm, lm = b_mesh.device_egress.field(1)
        assert np.array_equal(np.asarray(ls), np.asarray(lm))
        assert np.array_equal(np.asarray(bs), np.asarray(bm))


# ---------------------------------------------------------------------------
# 3. destination byte identity
# ---------------------------------------------------------------------------


@pytest.mark.usefixtures("assembly")
class TestClickHouseTsvIdentity:
    def _seqs(self, n):
        lsns = np.arange(n, dtype=np.uint64) + 0x2000
        ords = np.arange(n, dtype=np.uint64)
        zeros = np.zeros(n, dtype=np.uint64)
        seq_buf = sequence_number_buffer(lsns, zeros, ords)
        seq_strs = [s.decode() for s in sequence_number_batch(
            lsns, zeros, ords)]
        return seq_buf, seq_strs

    @pytest.mark.parametrize("rows_fn", [_kinds_rows, _specials_rows,
                                         _toast_rows])
    def test_copy_shape_identity(self, rows_fn, assembly):
        schema = _kinds_schema()
        batch = ColumnarBatch.from_rows(schema, rows_fn())
        n = batch.num_rows
        seq_buf, seq_strs = self._seqs(n)
        oracle = render_batch_tsv_columnar(schema, batch, "UPSERT",
                                           seq_strs)
        before = _native_rows()
        fast, used = render_batch_tsv_fast(schema, batch, "UPSERT",
                                           seq_buf)
        assert used is False  # host twins, no device buffers attached
        assert fast == oracle
        # the branch the parameter names is the branch that ran
        assert (_native_rows() > before) == (assembly == "native")

    @pytest.mark.parametrize("n", [1, 500, 2_000])
    def test_pgbench_accounts_identity(self, n):
        """The benchmark's ClickHouse cell: three int4 columns and a
        char(84), as a 500-row transaction and as coalesced writes."""
        schema = _accounts_schema()
        batch = _engine_batch(schema, _accounts_values(n))
        cts = np.full(n, int(ChangeType.INSERT), dtype=np.int8)
        ct_arr = change_type_batch(cts)
        seq_buf, seq_strs = self._seqs(n)
        oracle = render_batch_tsv_columnar(
            schema, batch, [c.decode() for c in ct_arr.tolist()], seq_strs)
        fast, used = render_batch_tsv_fast(schema, batch, ct_arr, seq_buf)
        assert used is False and fast == oracle

    def test_untrusted_rows_take_the_oracle_line(self):
        schema = _int_schema()
        batch = _engine_batch(schema, _int_values(64),
                              egress=eg.ENCODER_TSV)
        dev = _untrusted(batch.device_egress, [0, 7, 8, 63])
        seq_buf, seq_strs = self._seqs(64)
        oracle = render_batch_tsv_columnar(schema, batch, "UPSERT",
                                           seq_strs)
        fast, used = render_batch_tsv_fast(schema, batch, "UPSERT",
                                           seq_buf, egress=dev)
        assert used is True and b"?" not in fast
        assert fast == oracle

    def test_cdc_shape_identity(self):
        schema = _kinds_schema()
        batch = ColumnarBatch.from_rows(schema, _kinds_rows())
        n = batch.num_rows
        cts = np.array([int(ChangeType.DELETE) if i % 4 == 3
                        else int(ChangeType.INSERT) for i in range(n)],
                       dtype=np.int8)
        ct_arr = change_type_batch(cts)
        ct_strs = [c.decode() for c in ct_arr.tolist()]
        seq_buf, seq_strs = self._seqs(n)
        oracle = render_batch_tsv_columnar(schema, batch, ct_strs,
                                           seq_strs)
        fast, _ = render_batch_tsv_fast(schema, batch, ct_arr, seq_buf)
        assert fast == oracle

    def test_device_egress_identity_and_counted(self):
        schema = _int_schema()
        batch = _engine_batch(schema, _int_values(64),
                              egress=eg.ENCODER_TSV)
        assert batch.device_egress is not None
        n = batch.num_rows
        seq_buf, seq_strs = self._seqs(n)
        oracle = render_batch_tsv_columnar(schema, batch, "UPSERT",
                                           seq_strs)
        fast, used = render_batch_tsv_fast(schema, batch, "UPSERT",
                                           seq_buf,
                                           egress=batch.device_egress)
        assert used is True
        assert fast == oracle


def _numeric_schema(tid=43020):
    return _schema((
        ColumnSchema("pk", Oid.INT8, nullable=False, primary_key_ordinal=1),
        ColumnSchema("num", Oid.NUMERIC),
        ColumnSchema("s", Oid.TEXT)), tid=tid, name="numerics")


def _numeric_batch(texts, toast=(), storage="lazy"):
    """(pk, num, s) with the NUMERIC column as the decoder hands it over:
    `lazy` — its exact Postgres text in an Arrow array with
    `lazy_text_oid` set; `sliced` — the same behind a non-zero Arrow
    offset; `objects` — a list of parsed values, as the per-row oracle
    and `_cpu_fixup` leave it. `toast` rows are unchanged-TOAST cells."""
    from etl_tpu.models.table_row import Column
    from etl_tpu.postgres.codec.text import parse_cell_text

    schema = _numeric_schema()
    cols = schema.replicated_columns
    n = len(texts)
    valid = np.array([t is not None for t in texts], dtype=np.bool_)
    unchanged = None
    if toast:
        unchanged = np.zeros(n, dtype=np.bool_)
        unchanged[list(toast)] = True
        valid &= ~unchanged
    if storage == "objects":
        num = Column(cols[1], [None if t is None
                               else parse_cell_text(t, Oid.NUMERIC)
                               for t in texts], valid, unchanged)
    else:
        arr = pa.array(texts, type=pa.string())
        if storage == "sliced":
            arr = pa.array(["0.5"] + list(texts), type=pa.string()).slice(1)
            assert arr.offset == 1
        num = Column(cols[1], arr, valid, unchanged,
                     lazy_text_oid=Oid.NUMERIC)
    return schema, ColumnarBatch(schema, [
        Column(cols[0], np.arange(n, dtype=np.int64) - 3,
               np.ones(n, dtype=np.bool_)),
        num,
        Column(cols[2], pa.array(["s%d" % i for i in range(n)],
                                 type=pa.string()),
               np.ones(n, dtype=np.bool_))])


# name -> (the column's texts, unchanged-TOAST rows, storage, the cells
# that go value by value)
_NUMERIC_CASES = {
    "plain": (["1.00", "12.34", "100", "4242.4200"], (), "lazy", 0),
    "negatives": (["-1.50", "-9999999999.99", "-7"], (), "lazy", 0),
    "zero": (["0"], (), "lazy", 0),
    "zero_with_scale": (["0.0000", "1.0"], (), "lazy", 0),
    "negative_zero": (["-0.00", "-0", "-0.5"], (), "lazy", 0),
    "one_digit": (["7", "0", "5", "-3"], (), "lazy", 0),
    "hundreds_of_digits": (["9" * 400 + "." + "1" * 300, "-" + "8" * 500,
                            "0." + "0" * 200 + "1"], (), "lazy", 0),
    "nulls": (["1.5", None, "2.5", None, None], (), "lazy", 0),
    "toast_unchanged": (["1.5", "2.5", "3.5", None], (1, 3), "lazy", 0),
    "nan": (["1.0", "NaN", "2.0"], (), "lazy", 1),
    "infinity": (["Infinity", "3"], (), "lazy", 1),
    "negative_infinity": (["3", "-Infinity"], (), "lazy", 1),
    "exponent": (["1e5", "1E+2", "2.5e-3", "10"], (), "lazy", 3),
    "plus_sign": (["+1", "1"], (), "lazy", 1),
    "leading_zeros": (["007", "00", "00.5", "-01", "0.5", "10"], (),
                      "lazy", 4),
    "whitespace": ([" 1", "1 ", "1"], (), "lazy", 2),
    "bare_point": ([".5", "5.", "-.5", "0.5"], (), "lazy", 3),
    "underscore_and_unicode_digits": (["1_0", "\u0663", "10"], (), "lazy",
                                      2),
    "specials_beside_nulls": ([None, "NaN", None, "-0.10", "+2", None],
                              (), "lazy", 2),
    "empty_batch": ([], (), "lazy", 0),
    "all_null": ([None] * 5, (), "lazy", 0),
    "sliced_arrow": (["1.00", None, "NaN", "-2.50"], (), "sliced", 3),
    "object_list": (["1.00", None, "NaN", "-2.50"], (3,), "objects", 2),
}


def _boxed_cells():
    from etl_tpu.telemetry.metrics import (
        ETL_CLICKHOUSE_BOXED_CELLS_TOTAL,
        ETL_CLICKHOUSE_RENDERED_CELLS_TOTAL, registry)

    return (registry.get_counter(ETL_CLICKHOUSE_RENDERED_CELLS_TOTAL),
            registry.get_counter(ETL_CLICKHOUSE_BOXED_CELLS_TOTAL))


@pytest.mark.usefixtures("assembly")
class TestClickHouseNumericVerbatim:
    """A NUMERIC column that arrives as the decoder's exact Postgres text
    is the TSV field as it stands; only a cell whose spelling
    `PgNumeric.pg_text()` could change is parsed (PR 37)."""

    def _both(self, schema, batch, cdc=False):
        n = batch.num_rows
        lsns = np.arange(n, dtype=np.uint64) + 0x3000
        zeros = np.zeros(n, dtype=np.uint64)
        seq_buf = sequence_number_buffer(lsns, zeros, zeros)
        seqs = [s.decode() for s in sequence_number_batch(lsns, zeros,
                                                          zeros)]
        labels = ct = "UPSERT"
        if cdc:
            ct = change_type_batch(np.array(
                [int(ChangeType.DELETE) if i % 3 == 2
                 else int(ChangeType.INSERT) for i in range(n)],
                dtype=np.int8))
            labels = [c.decode() for c in ct.tolist()]
        rendered, boxed = _boxed_cells()
        fast, used = render_batch_tsv_fast(schema, batch, ct, seq_buf)
        after = _boxed_cells()
        assert used is False
        assert fast == render_batch_tsv_columnar(schema, batch, labels,
                                                 seqs)
        assert after[0] - rendered == n * len(batch.columns)
        return fast, after[1] - boxed

    @pytest.mark.parametrize("case", list(_NUMERIC_CASES))
    def test_identity_and_boxed_cells(self, case):
        texts, toast, storage, by_value = _NUMERIC_CASES[case]
        schema, batch = _numeric_batch(texts, toast, storage)
        fast, boxed = self._both(schema, batch)
        assert boxed == by_value
        fields = [line.split(b"\t")[1] for line in fast.split(b"\n")[:-1]]
        assert len(fields) == len(texts)
        for i, (t, f) in enumerate(zip(texts, fields)):
            if t is None or i in toast:
                assert f == b"\\N"
            elif by_value == 0:
                assert f == t.encode()  # the decoder's bytes, verbatim
        _, boxed = self._both(schema, batch, cdc=True)
        assert boxed == by_value

    def test_one_nan_in_a_bulk_boxes_one_cell(self):
        n = 16_384
        texts = ["%d.%02d" % (i * 37 % 10_000, i % 100) for i in range(n)]
        schema, batch = _numeric_batch(texts)
        _, boxed = self._both(schema, batch)
        assert boxed == 0
        texts[9_999] = "NaN"
        schema, batch = _numeric_batch(texts)
        fast, boxed = self._both(schema, batch)
        assert boxed == 1  # not 16,384
        assert fast.split(b"\n")[9_999].split(b"\t")[1] == b"NaN"

    def test_malformed_text_is_refused_as_before(self):
        """An empty string or a stray sign is no number: the parse that
        refuses it today still sees it (by value), on both renders."""
        from etl_tpu.models.errors import EtlError

        for bad in ("", "-", "1-2", "1.2.3", "--1", "."):
            schema, batch = _numeric_batch(["1.0", bad])
            seq_buf = sequence_number_buffer(*[np.zeros(2, np.uint64)] * 3)
            with pytest.raises(EtlError, match="invalid numeric"):
                render_batch_tsv_fast(schema, batch, "UPSERT", seq_buf)
            with pytest.raises(EtlError, match="invalid numeric"):
                render_batch_tsv_columnar(schema, batch, "UPSERT",
                                          ["x", "y"])

    def test_the_predicate_against_the_parse(self):
        """Seeded random cells over the alphabet and beyond it: a row the
        predicate leaves verbatim re-spells to itself, and every row spelt
        as `numeric_out` spells is left verbatim."""
        import random
        import re
        from decimal import Decimal

        from etl_tpu.destinations.clickhouse import (_arrow_text_buffers,
                                                     _numeric_rows_by_value)

        rng = random.Random(37)
        canonical = re.compile(r"-?(0|[1-9][0-9]*)(\.[0-9]+)?")
        for alphabet in ("0123456789.-", "0123456789.-+eE Na_"):
            texts = [None if rng.random() < 0.1 else "".join(
                rng.choice(alphabet) for _ in range(rng.randint(0, 6)))
                for _ in range(4_000)]
            arr = pa.array(texts, type=pa.string())
            valid = np.array([t is not None for t in texts])
            by_value = set(_numeric_rows_by_value(
                *_arrow_text_buffers(arr, len(texts)), valid).tolist())
            for i, t in enumerate(texts):
                if t is None:
                    assert i not in by_value
                elif canonical.fullmatch(t):
                    assert i not in by_value, t
                    assert format(Decimal(t), "f") == t
                else:
                    assert i in by_value, t


@pytest.mark.usefixtures("assembly")
class TestSnowflakeNdjsonIdentity:
    def _labels_seqs(self, n):
        labels = ["delete" if i % 4 == 3 else "insert" for i in range(n)]
        seqs = offset_token_batch(
            np.arange(n, dtype=np.uint64) + 0x3000,
            np.arange(n, dtype=np.uint64))
        return labels, list(seqs)

    @pytest.mark.parametrize("rows_fn", [_kinds_rows, _specials_rows,
                                         _toast_rows])
    def test_host_twin_identity(self, rows_fn, assembly):
        schema = _kinds_schema()
        batch = ColumnarBatch.from_rows(schema, rows_fn())
        labels, seqs = self._labels_seqs(batch.num_rows)
        oracle = encode_batch_ndjson(schema, batch, labels, seqs)
        before = _native_rows()
        fast, used = encode_batch_ndjson_fast(schema, batch, labels,
                                              seqs)
        assert used is False
        assert fast == oracle
        assert (_native_rows() > before) == (assembly == "native")

    def test_pgbench_accounts_identity(self):
        schema = _accounts_schema()
        batch = _engine_batch(schema, _accounts_values(500))
        labels, seqs = self._labels_seqs(500)
        oracle = encode_batch_ndjson(schema, batch, labels, seqs)
        fast, _ = encode_batch_ndjson_fast(schema, batch, labels, seqs)
        assert fast == oracle

    def test_untrusted_rows_take_the_oracle_line(self):
        schema = _int_schema()
        batch = _engine_batch(schema, _int_values(64),
                              egress=eg.ENCODER_JSON)
        dev = _untrusted(batch.device_egress, [0, 7, 8, 63])
        labels, seqs = self._labels_seqs(64)
        oracle = encode_batch_ndjson(schema, batch, labels, seqs)
        fast, used = encode_batch_ndjson_fast(schema, batch, labels, seqs,
                                              egress=dev)
        assert used is True and not any(b"?" in line for line in fast)
        assert fast == oracle

    def test_device_egress_identity(self):
        schema = _int_schema()
        batch = _engine_batch(schema, _int_values(64),
                              egress=eg.ENCODER_JSON)
        assert batch.device_egress is not None
        labels, seqs = self._labels_seqs(batch.num_rows)
        oracle = encode_batch_ndjson(schema, batch, labels, seqs)
        fast, used = encode_batch_ndjson_fast(
            schema, batch, labels, seqs, egress=batch.device_egress)
        assert used is True
        assert fast == oracle

    def test_non_finite_float_still_rejected(self):
        schema = _schema((
            ColumnSchema("pk", Oid.INT8, nullable=False,
                         primary_key_ordinal=1),
            ColumnSchema("f", Oid.FLOAT8)), tid=43009, name="nf")
        batch = ColumnarBatch.from_rows(
            schema, [TableRow([1, float("inf")])])
        from etl_tpu.models.errors import EtlError

        with pytest.raises(EtlError):
            encode_batch_ndjson_fast(schema, batch, "insert", "0/0")


class TestBqProtoIdentity:
    def test_date_cells_identical_with_egress(self):
        schema = _int_schema()
        batch = _engine_batch(schema, _int_values(64),
                              egress=eg.ENCODER_TSV)
        assert batch.device_egress is not None
        n = batch.num_rows
        cts = [b"UPSERT"] * n
        seqs = sequence_number_batch(
            np.arange(n, dtype=np.uint64), np.zeros(n, dtype=np.uint64),
            np.zeros(n, dtype=np.uint64))
        oracle = bq_proto.encode_batch(schema, batch, cts, seqs)
        fast = bq_proto.encode_batch(schema, batch, cts, seqs,
                                     egress=batch.device_egress)
        assert fast == oracle


class TestArrowHelpers:
    def test_fixed_width_matches_sequence_arrow(self):
        n = 37
        lsns = np.arange(n, dtype=np.uint64) + 7
        ords = np.arange(n, dtype=np.uint64) * 3
        zeros = np.zeros(n, dtype=np.uint64)
        buf = sequence_number_buffer(lsns, zeros, ords)
        got = fixed_width_string_arrow(buf)
        want = sequence_number_arrow(lsns, zeros, ords)
        assert got.equals(want)

    def test_hex16_matches_format(self):
        vals = np.array([0, 1, 0xDEADBEEF, 2**63], dtype=np.uint64)
        assert hex16_arrow(vals).to_pylist() \
            == [f"{int(v):016x}" for v in vals]

    def test_string_array_from_fixed_variable_lens(self):
        schema = _int_schema()
        batch = _engine_batch(schema, _int_values(64),
                              egress=eg.ENCODER_TSV)
        buf, lens = batch.device_egress.field(0)
        got = string_array_from_fixed(np.asarray(buf), np.asarray(lens))
        want = pa.array([bytes(np.asarray(buf)[i, :lens[i]]).decode()
                         for i in range(len(lens))], pa.string())
        assert got.equals(want)

    def test_string_array_from_fixed_empty(self):
        got = string_array_from_fixed(
            np.zeros((0, 4), dtype=np.uint8), np.zeros(0, dtype=np.int32))
        assert len(got) == 0


# ---------------------------------------------------------------------------
# 4. the engine seam
# ---------------------------------------------------------------------------


class TestEngineAttach:
    def test_no_egress_configured_attaches_nothing(self):
        schema = _int_schema()
        batch = _engine_batch(schema, _int_values(64))
        assert batch.device_egress is None

    def test_encoder_field_selection(self):
        schema = _int_schema()
        tsv = _engine_batch(schema, _int_values(64),
                            egress=eg.ENCODER_TSV)
        js = _engine_batch(schema, _int_values(64),
                           egress=eg.ENCODER_JSON)
        assert set(tsv.device_egress.fields) == {0, 1, 2, 3}
        assert set(js.device_egress.fields) == {0, 1, 2}

    def test_take_drops_device_buffers(self):
        schema = _int_schema()
        batch = _engine_batch(schema, _int_values(64),
                              egress=eg.ENCODER_TSV)
        sub = batch.take(np.array([1, 3, 5]))
        assert sub.device_egress is None  # buffers are positional

    def test_assembler_threads_encoder_from_destination(self):
        import inspect

        from etl_tpu.runtime.assembler import EventAssembler

        params = inspect.signature(EventAssembler.__init__).parameters
        assert "egress_encoder" in params
        assert params["egress_encoder"].default is None

    def test_batch_config_gate_defaults_on(self):
        from etl_tpu.config.pipeline import BatchConfig

        assert BatchConfig().device_egress is True

    def test_destinations_declare_encoders(self):
        from etl_tpu.destinations.base import Destination
        from etl_tpu.destinations.bigquery import BigQueryDestination
        from etl_tpu.destinations.clickhouse import ClickHouseDestination
        from etl_tpu.destinations.snowflake import SnowflakeDestination

        assert Destination.egress_encoder is None
        assert ClickHouseDestination.egress_encoder == "tsv"
        assert SnowflakeDestination.egress_encoder == "json"
        assert BigQueryDestination.egress_encoder == "tsv"


class TestDeviceEgressConcat:
    def _dev(self, start=0):
        schema = _int_schema()
        return _engine_batch(schema, _int_values(64, start=start),
                             egress=eg.ENCODER_TSV).device_egress

    def test_concat_merges_offsets(self):
        a, b = self._dev(0), self._dev(100)
        merged = eg.DeviceEgress.concat([a, b])
        assert merged is not None
        assert merged.n_rows == a.n_rows + b.n_rows
        buf, lens = merged.field(0)
        ab, al = a.field(0)
        assert np.array_equal(buf[:a.n_rows], ab)
        assert np.array_equal(lens[:a.n_rows], al)

    def test_concat_all_or_nothing(self):
        a = self._dev()
        assert eg.DeviceEgress.concat([a, None]) is None
        assert eg.DeviceEgress.concat([]) is None
        other = eg.DeviceEgress("json", a.n_rows, dict(a.fields),
                                a.untrusted)
        assert eg.DeviceEgress.concat([a, other]) is None

    def test_coalesced_batch_carries_merged_egress(self):
        schema = _int_schema()
        b1 = _engine_batch(schema, _int_values(64, start=0),
                           egress=eg.ENCODER_TSV)
        b2 = _engine_batch(schema, _int_values(64, start=200),
                           egress=eg.ENCODER_TSV)
        ev1, ev2 = _decoded_event(schema, b1), _decoded_event(
            schema, b2, start=64)
        cb = CoalescedBatch([ev1, ev2])
        assert cb.egress is not None
        assert cb.egress.n_rows == 128
