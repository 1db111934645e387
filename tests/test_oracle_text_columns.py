"""The per-row oracle's text columns (`ops/engine.DeviceDecoder._assemble`).

A CDC run under `DeviceDecoder.HOST_MIN_ROWS` goes to the per-row oracle.
Its staged bytes carry no COPY escapes, so its text kinds (STRING, NUMERIC
and the other lazy kinds) are gathered whole as the Arrow text the host
program's runs hold, and the CPU fixup parses only the dense cells. Here
the same runs of every TPC-C table's shapes go through both routes and are
held cell by cell to each other and to the text parser; a COPY chunk with
an escaped row shows that masking and fixup still apply there; and
`etl_decode_fixup_cells_total` counts the cells the fixup parsed."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import oplog  # noqa: E402
from source import table_schema  # noqa: E402

import etl_tpu.native as native  # noqa: E402
from etl_tpu.models import ReplicatedTableSchema  # noqa: E402
from etl_tpu.models.cell import TOAST_UNCHANGED  # noqa: E402
from etl_tpu.ops.engine import DeviceDecoder  # noqa: E402
from etl_tpu.ops.staging import stage_copy_chunk  # noqa: E402
from etl_tpu.ops.wal import concat_payloads, stage_wal_batch  # noqa: E402
from etl_tpu.postgres.codec import pgoutput  # noqa: E402
from etl_tpu.postgres.codec.copy_text import unescape_copy_field  # noqa: E402
from etl_tpu.postgres.codec.text import parse_cell_text  # noqa: E402
from etl_tpu.telemetry.metrics import (  # noqa: E402
    ETL_DECODE_FIXUP_CELLS_TOTAL, registry)

with open(os.path.join(BENCH, "configs", "tpcc-w4-null.json")) as _f:
    TABLES = {t["name"].split(".")[1]: t
              for t in oplog.tables_of(json.load(_f))}
INTS = ["7", "-2147483648", "2147483647", "0", "42"]
STAMPS = ["2024-01-01 00:00:00", "1999-12-31 23:59:59.999999",
          "2024-02-29 12:34:56.007013"]
VARCHARS = ["naïve – ünïcödé ✓", "", "O'Brien & co", "x" * 500]
CHARS = ["ab", "é", "", "zz"]  # padded to the column's length, as sent
RUN_ROWS = 12  # under HOST_MIN_ROWS: one staged batch, one row bucket


def _numerics(c):
    """NUMERIC texts at both ends of the column's precision, NaN and a
    zero with its scale (`numeric(4,4)` has no digit before the point)."""
    whole, frac = "9" * (c["precision"] - c["scale"]), "9" * c["scale"]
    return [f"-{whole or '0'}.{frac}", "NaN", "0." + "0" * c["scale"],
            f"{whole or '0'}.{frac}", "-0." + "0" * (c["scale"] - 1) + "1"]


def _text(c, r: int) -> bytes:
    t = c["type"]
    if t == "int4":
        v = INTS[r % len(INTS)]
    elif t == "numeric":
        v = _numerics(c)[r % 5]
    elif t == "timestamp":
        v = STAMPS[r % len(STAMPS)]
    elif t == "varchar":
        v = VARCHARS[r % len(VARCHARS)][:c["text_bytes"]]
    else:
        assert t == "bpchar"
        v = CHARS[r % len(CHARS)][:c["text_bytes"]].ljust(c["text_bytes"])
    return v.encode()


def _run(table, n_rows: int):
    """(pgoutput payloads, each row's cells as the wire has them: bytes,
    None for NULL, TOAST_UNCHANGED). Rows cycle through an insert, an
    update with every non-key column NULL, an update whose non-key text
    columns are unchanged TOAST, and a delete with a `K` key image."""
    tid, cols = int(table["id"]), table["columns"]
    keys = set(oplog.key_indices(table))
    payloads, rows = [], []
    for r in range(n_rows):
        cells = [_text(c, r) for c in cols]
        shape = r % 4
        if shape == 1:
            cells = [v if j in keys else None for j, v in enumerate(cells)]
        elif shape == 2:
            cells = [TOAST_UNCHANGED if j not in keys
                     and c["type"] == "varchar" else v
                     for j, (c, v) in enumerate(zip(cols, cells))]
        elif shape == 3:
            cells = [v if j in keys else None for j, v in enumerate(cells)]
        wire = [None if v is TOAST_UNCHANGED else v for v in cells]
        if shape == 0:
            payloads.append(pgoutput.encode_insert(tid, wire))
        elif shape == 3:
            payloads.append(pgoutput.encode_delete(tid, wire))
        else:
            kinds = [pgoutput.TUPLE_UNCHANGED_TOAST if v is TOAST_UNCHANGED
                     else pgoutput.TUPLE_NULL if v is None
                     else pgoutput.TUPLE_TEXT for v in cells]
            payloads.append(pgoutput.encode_update(tid, wire,
                                                   new_kinds=kinds))
        rows.append(cells)
    return payloads, rows


def _schema(table):
    return ReplicatedTableSchema.with_all_columns(table_schema(table))


def _decoders(schema):
    """(oracle, host program): the same schema, routed by row count."""
    return (DeviceDecoder(schema, host_min_rows=1 << 30,
                          device_min_rows=1 << 30, mesh=None),
            DeviceDecoder(schema, host_min_rows=1, device_min_rows=1 << 30,
                          mesh=None))


def _decode(decoder, staged, route):
    batch = decoder.decode(staged)
    assert staged.route == route
    return batch


def _expected(schema, rows):
    """Each cell as the text parser reads the wire's bytes."""
    oids = [c.type_oid for c in schema.replicated_columns]
    return [[v if v is None or v is TOAST_UNCHANGED
             else parse_cell_text(v.decode("utf-8"), oids[j])
             for j, v in enumerate(row)] for row in rows]


def _same(a, b) -> bool:
    # repr: NaN is not equal to itself, and a value's type is part of it
    return type(a) is type(b) and repr(a) == repr(b)


def _assert_cells(batch, expected):
    assert batch.num_rows == len(expected)
    for j, col in enumerate(batch.columns):
        for i, row in enumerate(expected):
            got = col.value(i)
            assert _same(got, row[j]), (col.schema.name, i, got, row[j])


def _fixup_cells() -> float:
    return registry.get_counter(ETL_DECODE_FIXUP_CELLS_TOTAL)


@pytest.mark.parametrize("name", sorted(TABLES))
@pytest.mark.parametrize("n_rows", [1, RUN_ROWS])
@pytest.mark.parametrize("gather", ["native", "numpy"])
def test_oracle_and_host_runs_agree_cell_by_cell(name, n_rows, gather,
                                                 monkeypatch):
    if gather == "numpy":
        # a process without the native library: the numpy twin of the
        # gather (and of the framer)
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_build_error", "test: no compiler")
    else:
        assert native.native_available(), native._build_error
    table = TABLES[name]
    schema = _schema(table)
    payloads, rows = _run(table, n_rows)
    buf, offs, lens = concat_payloads(payloads)
    staged = stage_wal_batch(buf, offs, lens, len(table["columns"])).staged
    assert not staged.copy_escapes
    oracle, host = _decoders(schema)
    by_oracle = _decode(oracle, staged, "oracle")
    by_host = _decode(host, staged, "host")
    expected = _expected(schema, rows)
    _assert_cells(by_oracle, expected)
    _assert_cells(by_host, expected)
    for a, b in zip(by_oracle.columns, by_host.columns):
        # the same storage on both routes: numpy for the dense kinds, the
        # decoder's Arrow text (NUMERIC unparsed) for the text kinds
        assert (a.is_dense, a.is_arrow, a.lazy_text_oid) \
            == (b.is_dense, b.is_arrow, b.lazy_text_oid)
        assert a.is_dense or a.is_arrow
        assert a.data.equals(b.data) if a.is_arrow \
            else np.array_equal(a.data[a.validity], b.data[b.validity])
        assert np.array_equal(a.validity, b.validity)
        assert (a.toast_unchanged is None) == (b.toast_unchanged is None)


def _customer_copy_chunk():
    """Three `customer` rows as COPY text: plain; `c_data` with a tab and
    a backslash escaped; `c_middle` and `c_since` as \\N. (rows of cells,
    chunk)."""
    cols = TABLES["customer"]["columns"]
    rows = [[_text(c, 0) for c in cols] for _ in range(3)]
    at = [c["name"] for c in cols].index
    rows[1][at("c_data")] = b"tab\\there \\\\ back"
    rows[2][at("c_middle")] = rows[2][at("c_since")] = None
    chunk = b"".join(b"\t".join(b"\\N" if v is None else v for v in row)
                     + b"\n" for row in rows)
    return rows, chunk


@pytest.mark.parametrize("route", ["oracle", "host"])
def test_a_copy_chunk_with_escapes_keeps_its_masking_and_fixup(route):
    table = TABLES["customer"]
    schema = _schema(table)
    rows, chunk = _customer_copy_chunk()
    staged = stage_copy_chunk(chunk, len(table["columns"]))
    assert staged.copy_escapes and staged.cpu_fallback_rows.tolist() == [1]
    decoder = _decoders(schema)[route == "host"]
    before = _fixup_cells()
    batch = _decode(decoder, staged, route)
    unescaped = [[None if v is None else unescape_copy_field(v) for v in row]
                 for row in rows]
    _assert_cells(batch, _expected(schema, unescaped))
    at = [c["name"] for c in table["columns"]].index
    assert batch.columns[at("c_data")].value(1) == "tab\there \\ back"
    # the escaped row is parsed in every column: the text columns the
    # fixup patched are no longer Arrow, the unescaped rows' dense cells
    # came from the host program where it ran
    text = [j for j, c in enumerate(table["columns"])
            if c["type"] not in ("int4", "timestamp")]
    assert not any(batch.columns[j].is_arrow for j in text)
    cells = sum(v is not None for v in rows[1])
    if route == "host":
        assert _fixup_cells() - before == cells
    else:
        assert _fixup_cells() - before == sum(
            v is not None for row in rows for v in row)


def test_fixup_cells_count_the_dense_cells_of_an_oracle_run():
    table = TABLES["customer"]
    schema = _schema(table)
    dense = [c for c in table["columns"] if c["type"] in ("int4",
                                                          "timestamp")]
    assert len(dense) == 6 and len(table["columns"]) == 21
    payloads, rows = _run(table, 1)
    assert all(v is not None for v in rows[0])
    buf, offs, lens = concat_payloads(payloads)
    staged = stage_wal_batch(buf, offs, lens, 21).staged
    oracle, host = _decoders(schema)
    before = _fixup_cells()
    _decode(oracle, staged, "oracle")
    assert _fixup_cells() - before == 6
    # NULL and unchanged-TOAST cells are not parsed: the update with every
    # non-key column NULL counts its three key cells
    payloads, _ = _run(table, 2)
    buf, offs, lens = concat_payloads(payloads[1:])
    before = _fixup_cells()
    _decode(oracle, stage_wal_batch(buf, offs, lens, 21).staged, "oracle")
    assert _fixup_cells() - before == 3
    # a host-routed run whose every cell the program parsed: no fixup
    payloads, _ = _run(table, RUN_ROWS)
    buf, offs, lens = concat_payloads(payloads)
    before = _fixup_cells()
    _decode(host, stage_wal_batch(buf, offs, lens, 21).staged, "host")
    assert _fixup_cells() - before == 0
