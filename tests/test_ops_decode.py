"""TPU decode engine tests: differential against the CPU oracle.

Strategy (SURVEY §4.4 adapted): generate random typed values, render them to
Postgres text with the test renderers, decode via DeviceDecoder, compare
bit-for-bit with the CPU codec path. Runs on the CPU backend (conftest
forces JAX_PLATFORMS=cpu); the same jitted code runs on TPU unchanged.
"""

import datetime as dt
import math
import random
import string

import numpy as np
import pytest

from etl_tpu.models import (ColumnSchema, ColumnarBatch, Oid, PgNumeric,
                            ReplicatedTableSchema, TableName, TableRow,
                            TableSchema)
from etl_tpu.ops import (DeviceDecoder, stage_copy_chunk, stage_tuples)
from etl_tpu.postgres.codec import encode_copy_row, parse_copy_row
from etl_tpu.postgres.codec.pgoutput import (TUPLE_NULL, TUPLE_TEXT,
                                             TUPLE_UNCHANGED_TOAST, TupleData)

rng = random.Random(42)


def make_schema(cols):
    return ReplicatedTableSchema.with_all_columns(TableSchema(
        1, TableName("public", "t"),
        tuple(ColumnSchema(f"c{i}", oid) for i, oid in enumerate(cols))))


def tuples_from_texts(rows):
    out = []
    for r in rows:
        kinds = [TUPLE_NULL if v is None else TUPLE_TEXT for v in r]
        vals = [None if v is None else v.encode() for v in r]
        out.append(TupleData(kinds, vals))
    return out


def decode_both(col_oids, text_rows):
    """Decode text rows via device engine and CPU oracle; return both."""
    schema = make_schema(col_oids)
    staged = stage_tuples(tuples_from_texts(text_rows), len(col_oids))
    # device_min_rows=0: differential tests must exercise the device path
    # (the production default routes small batches to the CPU oracle, which
    # would make this comparison vacuous)
    dev_batch = DeviceDecoder(schema, device_min_rows=0).decode(staged)
    cpu_rows = [
        TableRow([None if v is None else
                  __import__("etl_tpu.postgres.codec.text",
                             fromlist=["parse_cell_text"]).parse_cell_text(v, oid)
                  for v, oid in zip(r, col_oids)])
        for r in text_rows
    ]
    cpu_batch = ColumnarBatch.from_rows(schema, cpu_rows)
    return dev_batch, cpu_batch


def assert_batches_equal(dev: ColumnarBatch, cpu: ColumnarBatch):
    assert dev.num_rows == cpu.num_rows
    for dcol, ccol in zip(dev.columns, cpu.columns):
        np.testing.assert_array_equal(dcol.validity, ccol.validity,
                                      err_msg=f"validity {dcol.schema.name}")
        if dcol.is_dense:
            d = np.where(dcol.validity, dcol.data, 0)
            c = np.where(ccol.validity, ccol.data, 0)
            if np.issubdtype(d.dtype, np.floating):
                np.testing.assert_array_equal(
                    d.view(np.uint32 if d.dtype == np.float32 else np.uint64),
                    c.view(np.uint32 if c.dtype == np.float32 else np.uint64),
                    err_msg=f"float bits {dcol.schema.name}")
            else:
                np.testing.assert_array_equal(d, c,
                                              err_msg=f"col {dcol.schema.name}")
        else:
            for i in range(dev.num_rows):
                if dcol.validity[i]:
                    dv, cv = dcol.value(i), ccol.value(i)
                    if (isinstance(dv, PgNumeric) and dv.is_nan()
                            and isinstance(cv, PgNumeric) and cv.is_nan()):
                        continue
                    assert dv == cv, \
                        f"{dcol.schema.name}[{i}]: {dv!r} != {cv!r}"


class TestIntDecode:
    def test_pgbench_like(self):
        rows = [[str(i + 1), str(rng.randrange(1, 11)),
                 str(rng.randrange(-10**9, 10**9)), "padding" * 3]
                for i in range(100)]
        dev, cpu = decode_both([Oid.INT4, Oid.INT4, Oid.INT4, Oid.TEXT], rows)
        assert_batches_equal(dev, cpu)

    def test_int_extremes(self):
        rows = [["-32768", "-2147483648", "-9223372036854775808"],
                ["32767", "2147483647", "9223372036854775807"],
                ["0", "-0", "+5"],
                [None, "1", None]]
        dev, cpu = decode_both([Oid.INT2, Oid.INT4, Oid.INT8], rows)
        assert_batches_equal(dev, cpu)

    def test_random_int8(self):
        rows = [[str(rng.randrange(-2**63, 2**63))] for _ in range(500)]
        dev, cpu = decode_both([Oid.INT8], rows)
        assert_batches_equal(dev, cpu)

    def test_garbage_falls_back(self):
        # invalid int text: CPU oracle raises, device flags; engine fixup
        # re-raises through the oracle — so feed values that *parse* under
        # the oracle but not on device: none exist for ints; instead check
        # ok-flag fallback via a float in an int column raising cleanly
        from etl_tpu.models.errors import EtlError
        with pytest.raises(EtlError):
            decode_both([Oid.INT4], [["12.5"]])


class TestBoolDecode:
    def test_bools(self):
        rows = [["t"], ["f"], [None], ["t"]]
        dev, cpu = decode_both([Oid.BOOL], rows)
        assert_batches_equal(dev, cpu)


class TestFloatDecode:
    def test_simple(self):
        rows = [["1.5", "-0.25"], ["100", "2.5e10"], ["-1e-5", "0"],
                ["NaN", "Infinity"], [None, "-Infinity"]]
        dev, cpu = decode_both([Oid.FLOAT8, Oid.FLOAT4], rows)
        assert_batches_equal(dev, cpu)

    def test_random_fixed_precision(self):
        # ≤15 sig digits: device fast path, bit-identical to strtod
        rows = [[f"{rng.uniform(-1e6, 1e6):.6f}"] for _ in range(300)]
        dev, cpu = decode_both([Oid.FLOAT8], rows)
        assert_batches_equal(dev, cpu)

    def test_17_digit_shortest_roundtrip_falls_back(self):
        # full-precision doubles exceed the 15-digit fast path → CPU fixup,
        # still bit-exact
        rows = [[repr(rng.uniform(-1, 1))] for _ in range(50)]
        rows += [["1.7976931348623157e308"], ["5e-324"], ["2.2250738585072014e-308"]]
        dev, cpu = decode_both([Oid.FLOAT8], rows)
        assert_batches_equal(dev, cpu)


class TestDateTimeDecode:
    def test_dates(self):
        rows = [["2024-02-29"], ["1970-01-01"], ["0001-01-01"],
                ["9999-12-31"], [None], ["2000-03-01"]]
        dev, cpu = decode_both([Oid.DATE], rows)
        assert_batches_equal(dev, cpu)

    def test_random_dates(self):
        rows = [[(dt.date(1900, 1, 1)
                  + dt.timedelta(days=rng.randrange(0, 80000))).isoformat()]
                for _ in range(300)]
        dev, cpu = decode_both([Oid.DATE], rows)
        assert_batches_equal(dev, cpu)

    def test_bc_date_falls_back(self):
        rows = [["0044-03-15 BC"], ["2024-01-01"]]
        dev, cpu = decode_both([Oid.DATE], rows)
        assert_batches_equal(dev, cpu)

    def test_times(self):
        rows = [["00:00:00"], ["23:59:59.999999"], ["12:30:15.5"],
                ["01:02:03.123"], [None]]
        dev, cpu = decode_both([Oid.TIME], rows)
        assert_batches_equal(dev, cpu)

    def test_timestamps(self):
        rows = [["2024-05-01 12:34:56"], ["2024-05-01 12:34:56.789123"],
                ["1970-01-01 00:00:00"], ["2262-04-11 23:47:16.854775"],
                [None], ["1900-01-01 06:00:00.1"]]
        dev, cpu = decode_both([Oid.TIMESTAMP], rows)
        assert_batches_equal(dev, cpu)

    def test_timestamptz(self):
        rows = [["2024-05-01 12:34:56+02"], ["2024-05-01 12:34:56.789-05:30"],
                ["2024-01-01 00:00:00+00"], ["1995-06-15 10:00:00.25+09:30:30"],
                [None]]
        dev, cpu = decode_both([Oid.TIMESTAMPTZ], rows)
        assert_batches_equal(dev, cpu)

    def test_random_timestamps(self):
        rows = []
        for _ in range(200):
            base = dt.datetime(1950, 1, 1) + dt.timedelta(
                seconds=rng.randrange(0, 4 * 10**9),
                microseconds=rng.randrange(0, 10**6))
            rows.append([base.isoformat(sep=" ")])
        dev, cpu = decode_both([Oid.TIMESTAMP], rows)
        assert_batches_equal(dev, cpu)


class TestObjectColumns:
    def test_text_numeric_uuid_json(self):
        rows = [
            ["hello", "12.340", "a0eebc99-9c0b-4ef8-bb6d-6bb9bd380a11",
             '{"k": 1}'],
            [None, "NaN", None, "[1,2]"],
            ["unicode-é", "-99999999999999999999.5", None, "null"],
        ]
        dev, cpu = decode_both([Oid.TEXT, Oid.NUMERIC, Oid.UUID, Oid.JSONB],
                               rows)
        assert_batches_equal(dev, cpu)
        assert isinstance(dev.columns[1].value(0), PgNumeric)

    def test_numeric_f64_mode(self):
        schema = make_schema([Oid.NUMERIC])
        staged = stage_tuples(tuples_from_texts([["12.5"], ["-3"]]), 1)
        batch = DeviceDecoder(schema, numeric_mode="f64", device_min_rows=0).decode(staged)
        assert batch.columns[0].is_dense
        np.testing.assert_array_equal(batch.columns[0].data, [12.5, -3.0])


class TestToastAndNulls:
    def test_toast_passthrough(self):
        schema = make_schema([Oid.INT4, Oid.TEXT])
        tup = TupleData([TUPLE_TEXT, TUPLE_UNCHANGED_TOAST], [b"5", None])
        batch = DeviceDecoder(schema, device_min_rows=0).decode(stage_tuples([tup], 2))
        assert batch.columns[0].data[0] == 5
        assert not batch.columns[1].validity[0]
        assert batch.columns[1].is_toast_unchanged(0)

    def test_all_null_row(self):
        dev, cpu = decode_both([Oid.INT4, Oid.DATE], [[None, None], ["1", "2020-01-01"]])
        assert_batches_equal(dev, cpu)


class TestCopyStaging:
    def test_copy_chunk_roundtrip(self):
        lines = []
        expected = []
        for i in range(50):
            texts = [str(i), f"name-{i}" if i % 3 else None, f"{i}.25"]
            lines.append(encode_copy_row(texts))
            expected.append(texts)
        chunk = b"\n".join(lines) + b"\n"
        staged = stage_copy_chunk(chunk, 3)
        assert staged.n_rows == 50
        assert len(staged.cpu_fallback_rows) == 0
        schema = make_schema([Oid.INT4, Oid.TEXT, Oid.FLOAT8])
        batch = DeviceDecoder(schema, device_min_rows=0).decode(staged)
        for i, texts in enumerate(expected):
            assert batch.columns[0].data[i] == i
            if texts[1] is None:
                assert not batch.columns[1].validity[i]
            else:
                assert batch.columns[1].value(i) == texts[1]

    def test_copy_chunk_with_escapes(self):
        lines = [encode_copy_row(["1", "plain"]),
                 encode_copy_row(["2", "tab\there"]),
                 encode_copy_row(["3", None])]
        staged = stage_copy_chunk(b"\n".join(lines) + b"\n", 2)
        assert list(staged.cpu_fallback_rows) == [1]
        schema = make_schema([Oid.INT4, Oid.TEXT])
        batch = DeviceDecoder(schema, device_min_rows=0).decode(staged)
        assert batch.columns[1].value(1) == "tab\there"
        assert not batch.columns[1].validity[2]

    def test_copy_chunk_ragged_raises(self):
        from etl_tpu.models.errors import EtlError
        with pytest.raises(EtlError):
            stage_copy_chunk(b"1\t2\n3\n", 2)

    def test_against_cpu_copy_parser(self):
        oids = [Oid.INT8, Oid.TEXT, Oid.NUMERIC, Oid.DATE]
        lines, cpu_rows = [], []
        for i in range(64):
            texts = [str(rng.randrange(-10**12, 10**12)),
                     "".join(rng.choice(string.printable[:60]) for _ in range(10)),
                     f"{rng.randrange(0, 10**6)}.{rng.randrange(0, 100):02d}",
                     (dt.date(2000, 1, 1) + dt.timedelta(days=i)).isoformat()]
            line = encode_copy_row(texts)
            lines.append(line)
            cpu_rows.append(parse_copy_row(line, oids))
        staged = stage_copy_chunk(b"\n".join(lines) + b"\n", 4)
        schema = make_schema(oids)
        dev = DeviceDecoder(schema, device_min_rows=0).decode(staged)
        cpu = ColumnarBatch.from_rows(schema, cpu_rows)
        assert_batches_equal(dev, cpu)


class TestBuckets:
    def test_jit_cache_reuse_across_sizes(self):
        schema = make_schema([Oid.INT4])
        dec = DeviceDecoder(schema, device_min_rows=0)
        for n in (3, 100, 250):  # all inside the 256 bucket
            # constant digit count: same (row-bucket, widths, bit-widths)
            # signature across batch sizes must reuse one compiled program
            staged = stage_tuples(
                tuples_from_texts([[str(100 + i)] for i in range(n)]), 1)
            batch = dec.decode(staged)
            assert list(batch.columns[0].data) == [100 + i for i in range(n)]
        assert len(dec._fn_cache) == 1

    def test_jit_cache_bit_width_buckets_are_even(self):
        # value-width drift (1→2 digits) must NOT recompile: bit widths
        # bucket to even character counts
        schema = make_schema([Oid.INT4])
        dec = DeviceDecoder(schema, device_min_rows=0)
        for hi in (9, 99):
            staged = stage_tuples(
                tuples_from_texts([[str(hi)] for _ in range(8)]), 1)
            assert dec.decode(staged).columns[0].data[0] == hi
        assert len(dec._fn_cache) == 1

    def test_oversized_field_falls_back(self):
        schema = make_schema([Oid.TEXT, Oid.INT4])
        big = "x" * 5000
        staged = stage_tuples(tuples_from_texts([[big, "7"]]), 2)
        batch = DeviceDecoder(schema, device_min_rows=0).decode(staged)
        assert batch.columns[0].value(0) == big
        assert batch.columns[1].data[0] == 7


class TestReviewRegressions:
    def test_int_overflow_errors_not_wraps(self):
        # out-of-range values for the declared type are corrupt data: the
        # device flags them and the CPU fixup raises a typed error instead
        # of silently shipping a wrapped/truncated integer
        from etl_tpu.models.errors import ErrorKind, EtlError
        for oid, text in [(Oid.INT4, "99999999999"), (Oid.INT2, "70000"),
                          (Oid.INT8, "9223372036854775808")]:
            with pytest.raises(EtlError) as ei:
                decode_both([oid], [[text], ["5"]])
            assert ei.value.kind is ErrorKind.ROW_CONVERSION_FAILED

    def test_int_boundaries_exact(self):
        dev, cpu = decode_both(
            [Oid.INT2, Oid.INT4, Oid.INT8],
            [["-32768", "-2147483648", "-9223372036854775808"],
             ["32767", "2147483647", "9223372036854775807"]])
        assert_batches_equal(dev, cpu)

    def test_numeric_f64_to_arrow(self):
        schema = make_schema([Oid.NUMERIC])
        staged = stage_tuples(tuples_from_texts([["12.5"], [None]]), 1)
        batch = DeviceDecoder(schema, numeric_mode="f64", device_min_rows=0).decode(staged)
        rb = batch.to_arrow()
        assert rb.column(0).to_pylist() == [12.5, None]
        assert batch.to_rows()[0].values[0] == 12.5

    def test_json_null_to_arrow(self):
        schema = make_schema([Oid.JSONB])
        staged = stage_tuples(tuples_from_texts(
            [["null"], [None], ['{"a": 1}']]), 1)
        batch = DeviceDecoder(schema, device_min_rows=0).decode(staged)
        rb = batch.to_arrow()
        assert rb.column(0).to_pylist() == ["null", None, '{"a": 1}']

    def test_binary_tuple_rejected(self):
        from etl_tpu.models.errors import EtlError, ErrorKind
        from etl_tpu.postgres.codec.pgoutput import TUPLE_BINARY
        tup = TupleData([TUPLE_BINARY], [b"\x00\x00\x00\x05"])
        with pytest.raises(EtlError) as ei:
            stage_tuples([tup], 1)
        assert ei.value.kind is ErrorKind.UNSUPPORTED_TYPE


class TestPallasKernel:
    """The Pallas program (interpret mode on CPU) must agree with the XLA
    program bit-for-bit; on TPU a lowering Mosaic rejects raises
    (chip_smoke.py compiles every kind there)."""

    def test_pallas_matches_xla(self):
        oids = [Oid.INT4, Oid.INT8, Oid.DATE, Oid.TIMESTAMPTZ]
        # tz forms cover every _parse_tz_at branch: hours-only, :MM,
        # :MM:SS, and negative offsets (PG renders IST as +05:30)
        tzs = ["+0{h}", "-0{h}", "+0{h}:30", "-0{h}:30", "+0{h}:30:15"]
        rows = []
        for i in range(256):
            tz = tzs[i % len(tzs)].format(h=i % 9)
            rows.append([str(i - 128), str(rng.randrange(-2**62, 2**62)),
                         f"20{i % 100:02d}-03-{1 + i % 28:02d}",
                         f"2024-05-01 12:{i % 60:02d}:33.25{tz}"])
        schema = make_schema(oids)
        staged = stage_tuples(tuples_from_texts(rows), len(oids))
        a = DeviceDecoder(schema, device_min_rows=0).decode(staged)
        b = DeviceDecoder(schema, use_pallas=True, device_min_rows=0).decode(staged)
        assert_batches_equal(a, b)

    def test_pallas_matches_xla_float_time_bool(self):
        """The lane-packed kernel's float/time/bool paths against XLA —
        including exponent forms, fractional-second runs, and specials
        (which fall to the CPU oracle identically on both engines)."""
        oids = [Oid.BOOL, Oid.INT2, Oid.FLOAT4, Oid.FLOAT8, Oid.TIME,
                Oid.TIMESTAMP]
        rows = []
        floats = ["1.5", "-0.25", "3e4", "-2.5E-3", "0.0001", "12345.678",
                  "NaN", "Infinity", "-Infinity", "1e30", "7", "-0"]
        # 1e300 only on FLOAT8: the FLOAT4 cpu-fixup cast would emit a
        # numpy overflow RuntimeWarning (inf result, parity unaffected)
        floats8 = floats[:-3] + ["1e300"] + floats[-2:]
        for i in range(256):
            rows.append([
                "t" if i % 2 else "f",
                str(i - 128),
                floats[i % len(floats)],
                floats8[(i + 5) % len(floats8)],
                f"{i % 24:02d}:{i % 60:02d}:{(i * 7) % 60:02d}"
                + ("" if i % 3 == 0 else f".{i % 1_000_000:06d}"[:1 + i % 7]),
                f"19{i % 100:02d}-11-{1 + i % 28:02d} "
                f"{i % 24:02d}:00:{i % 60:02d}",
            ])
        schema = make_schema(oids)
        staged = stage_tuples(tuples_from_texts(rows), len(oids))
        a = DeviceDecoder(schema, device_min_rows=0).decode(staged)
        b = DeviceDecoder(schema, use_pallas=True,
                          device_min_rows=0).decode(staged)
        assert_batches_equal(a, b)


class TestWideOkWords:
    def test_35_dense_columns_both_programs(self):
        """32-62 dense columns use two ok words; the XLA and Pallas
        programs must agree on layout (reviewed failure)."""
        oids = [Oid.INT4] * 35
        rows = [[str(i * 100 + j) for j in range(35)] for i in range(64)]
        schema = make_schema(oids)
        staged = stage_tuples(tuples_from_texts(rows), 35)
        a = DeviceDecoder(schema, device_min_rows=0).decode(staged)
        b = DeviceDecoder(schema, use_pallas=True,
                          device_min_rows=0).decode(staged)
        assert_batches_equal(a, b)
        for j in (0, 30, 31, 34):
            assert a.columns[j].data[5] == 500 + j

    def test_lazy_text_consistent_after_fixup(self):
        """A single fallback row must not change other rows' value types
        (reviewed failure: fixup densified lazy text without parsing)."""
        rows = [["1.25", "2024-01-01"], ["3.50", "0044-03-15 BC"]]
        dev, cpu = decode_both([Oid.NUMERIC, Oid.DATE], rows)
        assert isinstance(dev.columns[0].value(0), PgNumeric)
        assert isinstance(dev.columns[0].value(1), PgNumeric)
        assert_batches_equal(dev, cpu)


class TestBitpackTransport:
    """The packed uint32 transport (ops/bitpack.py) must roundtrip exactly
    at every type's extremes and never corrupt silently (ok=1 implies the
    value fits its bit budget)."""

    def test_extreme_values_roundtrip(self):
        dev, cpu = decode_both(
            [Oid.INT2, Oid.INT4, Oid.INT8, Oid.FLOAT8],
            [["-32768", "-2147483648", "-9223372036854775808", "-1.5e22"],
             ["32767", "2147483647", "9223372036854775807", "1e-22"],
             ["0", "0", "0", "-0"]])
        assert_batches_equal(dev, cpu)

    def test_long_mantissa_falls_back_not_truncates(self):
        # 21-digit mantissa, 15 significant digits: the device limbs hold
        # only 18 digits — must fall back to the CPU oracle, not silently
        # drop the high digits (parse_float n_mant <= 18 guard)
        dev, cpu = decode_both(
            [Oid.FLOAT8],
            [["123456789012345000000"], ["0.000000000000000012345"],
             ["999999999999999000000000"], ["1.5"]])
        assert_batches_equal(dev, cpu)

    def test_oversized_tz_offset_falls_back(self):
        # tz hh > 15 would overflow the 29-bit packed ms budget; the device
        # must flag the row so the CPU oracle re-decodes it — surfacing a
        # typed INVALID_DATA error (the oracle rejects ±24h+ offsets), not
        # a silently bit-truncated timestamp
        from etl_tpu.models.errors import EtlError

        with pytest.raises(EtlError):
            decode_both([Oid.TIMESTAMPTZ], [["2024-01-01 00:00:00+75"]])
        dev, cpu = decode_both(
            [Oid.TIMESTAMPTZ],
            [["2024-01-01 00:00:00+09"],
             ["2024-06-15 23:59:59.999999-15:59:59"]])
        assert_batches_equal(dev, cpu)

    def test_timestamptz_extreme_valid_offsets(self):
        dev, cpu = decode_both(
            [Oid.TIMESTAMPTZ],
            [["0001-01-01 00:00:00+15:59:59"],
             ["9999-12-31 23:59:59.999999-15:59:59"]])
        assert_batches_equal(dev, cpu)

    def test_layout_saturation_stops_recompiles(self):
        from etl_tpu.ops.bitpack import layout_for_specs, saturation_width
        from etl_tpu.models.pgtypes import CellKind

        # widths past saturation must produce identical layouts
        for kind in (CellKind.I32, CellKind.I64, CellKind.TIMESTAMPTZ,
                     CellKind.DATE, CellKind.F64, CellKind.BOOL):
            sat = saturation_width(kind)
            a = layout_for_specs(((0, kind, 64, sat),))
            b = layout_for_specs(((0, kind, 64, sat),))
            assert a == b and a.n_words >= 1


class TestWalOldTuplesAtScale:
    def test_large_batch_old_tuple_mapping(self):
        """Device-scale WAL batch with mixed I/U/D and old/key tuples:
        stage_wal_batch must map old tuples to row positions and mark
        delete kinds exactly; the decoded old batch must match the CPU
        oracle (VERDICT r1 item 2 at the device path, not just e2e)."""
        import numpy as np

        from etl_tpu.ops import DeviceDecoder
        from etl_tpu.ops.wal import concat_payloads, stage_wal_batch
        from etl_tpu.postgres.codec import pgoutput

        schema = make_schema([Oid.INT4, Oid.TEXT])
        payloads = []
        kinds = []  # (change, has_old, old_is_key) per row
        r = random.Random(5)
        for i in range(9000):
            c = r.random()
            if c < 0.5:
                payloads.append(pgoutput.encode_insert(
                    1, [str(i).encode(), f"v{i}".encode()]))
                kinds.append(("I", False, False))
            elif c < 0.7:  # update with key tuple (PK change)
                payloads.append(pgoutput.encode_update(
                    1, [str(i).encode(), f"n{i}".encode()],
                    key_values=[str(i - 1).encode(), None]))
                kinds.append(("U", True, True))
            elif c < 0.8:  # update with full old tuple
                payloads.append(pgoutput.encode_update(
                    1, [str(i).encode(), f"n{i}".encode()],
                    old_values=[str(i - 1).encode(), f"o{i}".encode()]))
                kinds.append(("U", True, False))
            elif c < 0.9:  # plain update
                payloads.append(pgoutput.encode_update(
                    1, [str(i).encode(), f"n{i}".encode()]))
                kinds.append(("U", False, False))
            else:  # delete, alternating K/O
                full = i % 2 == 0
                payloads.append(pgoutput.encode_delete(
                    1, [str(i).encode(), f"d{i}".encode() if full else None],
                    full_old=full))
                kinds.append(("D", False, full))
        buf, offs, lens = concat_payloads(payloads)
        wal = stage_wal_batch(buf, offs, lens, 2)
        assert wal.bad_from < 0
        n = len(kinds)
        assert wal.staged.n_rows == n

        # delete_is_key marks exactly the 'K' deletes
        expect_dk = np.array([k == "D" and not key_or_full
                              for k, _, key_or_full in kinds])
        np.testing.assert_array_equal(wal.delete_is_key, expect_dk)

        # old_rows maps exactly the updates that carried a tuple
        expect_old = [i for i, (k, has_old, _) in enumerate(kinds)
                      if k == "U" and has_old]
        np.testing.assert_array_equal(wal.old_rows, expect_old)
        expect_is_key = np.array(
            [kinds[i][2] for i in expect_old])
        np.testing.assert_array_equal(wal.old_is_key, expect_is_key)

        # decode BOTH batches on the device path; values line up by row
        dec = DeviceDecoder(schema, device_min_rows=0)
        main = dec.decode(wal.staged)
        old = dec.decode(wal.old_staged)
        for j, i in enumerate(expect_old):
            assert old.columns[0].data[j] == i - 1
            if not wal.old_is_key[j]:
                assert old.columns[1].value(j) == f"o{i}"
            else:
                assert not old.columns[1].validity[j]
        # main batch: deletes carry the old/key tuple as the row
        for i, (k, _, full) in enumerate(kinds):
            if k == "D":
                assert main.columns[0].data[i] == i


class TestVeryWideTables:
    def test_100_dense_columns_stay_on_device(self):
        """Wide tables: all 100 int columns decode as DEVICE columns (the
        previous 62-column cap spilled the tail to per-row host objects)."""
        oids = [Oid.INT8 if i % 2 else Oid.INT4 for i in range(100)]
        schema = make_schema(oids)
        dec = DeviceDecoder(schema, device_min_rows=0)
        assert len(dec._dense) == 100, "wide dense columns spilled"
        rows = [[str((i * 97 + c) % 10**6) for c in range(100)]
                for i in range(300)]
        dev, cpu = decode_both(oids, rows)
        assert_batches_equal(dev, cpu)

    def test_260_dense_columns_spill_tail_only(self):
        oids = [Oid.INT4] * 260
        schema = make_schema(oids)
        dec = DeviceDecoder(schema)
        assert len(dec._dense) == 250
        assert len(dec._object) == 10
        # small batch routes to the oracle (no 260-col program compile);
        # spilled columns must still come back correct
        staged = stage_tuples(tuples_from_texts(
            [[str(i + c) for c in range(260)] for i in range(5)]), 260)
        batch = dec.decode(staged)
        assert batch.columns[259].value(2) == 261


class TestHostVectorPath:
    """CDC-sized batches (host_min_rows ≤ n < device_min_rows) run the SAME
    XLA program on the host CPU backend with a data-INDEPENDENT signature
    (engine._HOST_WIDTH fixed gather widths) — one compile per schema, no
    per-row oracle pass. Differential against the oracle, plus the
    signature-stability property the streaming throughput depends on."""

    OIDS = [Oid.INT8, Oid.INT4, Oid.FLOAT8, Oid.DATE, Oid.TIMESTAMPTZ,
            Oid.TEXT]

    def _rows(self, n, start=0):
        out = []
        for i in range(start, start + n):
            out.append([str((i * 7919) % 2**62 - 2**61), str(i % 97),
                        f"{i}.25", "2024-05-01",
                        "2024-05-01 12:34:56.789+05:30", f"note-{i}"])
        return out

    def test_host_path_matches_oracle(self):
        schema = make_schema(self.OIDS)
        dec = DeviceDecoder(schema)  # production thresholds
        rows = self._rows(500)
        staged = stage_tuples(tuples_from_texts(rows), len(self.OIDS))
        assert staged.n_rows >= dec.host_min_rows < dec.device_min_rows
        batch = dec.decode(staged)
        # routing proof: the host program ran (a jit fn was cached with
        # host=True) — not the per-row oracle
        assert any(key[-1] for key in dec._fn_cache), "host path not taken"
        from etl_tpu.postgres.codec.text import parse_cell_text
        cpu_rows = [TableRow([None if v is None else parse_cell_text(v, oid)
                              for v, oid in zip(r, self.OIDS)])
                    for r in rows]
        assert_batches_equal(batch, ColumnarBatch.from_rows(schema, cpu_rows))

    def test_signature_stable_across_field_lengths(self):
        """Two batches with different max field lengths must NOT compile two
        programs — drifting widths once recompiled per transaction and
        collapsed streaming throughput 60×."""
        schema = make_schema(self.OIDS)
        dec = DeviceDecoder(schema)
        short = [["1", "2", "3.5", "2024-01-02",
                  "2024-01-02 03:04:05+00", "a"]] * 100
        long = [["-9223372036854775808", "-2147483648",
                 "-1.7976931348623157e+308", "2024-12-31",
                 "2024-12-31 23:59:59.999999+15:59:59", "b" * 300]] * 100
        dec.decode(stage_tuples(tuples_from_texts(short), len(self.OIDS)))
        n_after_first = len(dec._fn_cache)
        dec.decode(stage_tuples(tuples_from_texts(long), len(self.OIDS)))
        assert len(dec._fn_cache) == n_after_first == 1

    def test_oversize_fields_fall_back_correctly(self):
        """Fields wider than the fixed host gather width (BC dates, huge
        numerics-as-float) take the oracle fallback row-wise, exactly."""
        oids = [Oid.INT8, Oid.DATE]
        rows = [[str(i), "2024-05-01"] for i in range(120)]
        rows[7] = [str(2**62), "0044-03-15 BC"]  # BC: oracle-only form
        schema = make_schema(oids)
        dec = DeviceDecoder(schema)
        batch = dec.decode(stage_tuples(tuples_from_texts(rows), 2))
        from etl_tpu.models.table_row import _to_dense
        from etl_tpu.models.pgtypes import CellKind
        from etl_tpu.postgres.codec.text import parse_cell_text
        # BC date: exact DAYS via the oracle fallback (text repr normalizes)
        assert batch.columns[1].data[7] == _to_dense(
            CellKind.DATE, parse_cell_text("0044-03-15 BC", Oid.DATE))
        assert batch.columns[0].value(7) == 2**62
        assert batch.columns[0].value(119) == 119

    def test_below_host_min_uses_oracle(self):
        schema = make_schema(self.OIDS)
        dec = DeviceDecoder(schema)
        rows = self._rows(dec.host_min_rows - 1)
        batch = dec.decode(stage_tuples(tuples_from_texts(rows),
                                        len(self.OIDS)))
        assert not dec._fn_cache  # oracle path: nothing compiled
        assert batch.columns[1].value(3) == 3 % 97
