"""The bulk renderers agree with the program's own codecs: every type's
text, every tuple kind ('t', 'n', 'u'), both old-image kinds ('K', 'O')
under both replica identities, and COPY text with its NULLs and escapes."""

import datetime as dt
import json
import os
import sys
from decimal import Decimal

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import oplog  # noqa: E402
import wire  # noqa: E402
from oplog import DELETE, INSERT, UPDATE, Col  # noqa: E402

SEED = 2**31 + 11
EPOCH = dt.datetime(1970, 1, 1)


def py_text(column: dict, v) -> str:
    """One value's text as a server prints it, by plain Python."""
    kind = column["type"]
    if kind == "bool":
        return "t" if v else "f"
    if kind in ("int2", "int4", "int8"):
        return str(int(v))
    if kind == "float8":
        special = {"inf": "Infinity", "-inf": "-Infinity", "nan": "NaN"}
        r = repr(float(v))
        return special.get(r, r[:-2] if r.endswith(".0") else r)
    if kind == "numeric":
        scale = int(column.get("scale", 0))
        return f"{Decimal(int(v)).scaleb(-scale):.{scale}f}"
    if kind == "date":
        return (dt.date(1970, 1, 1) + dt.timedelta(days=int(v))).isoformat()
    if kind in ("timestamp", "timestamptz"):
        t = EPOCH + dt.timedelta(microseconds=int(v))
        text = t.strftime("%Y-%m-%d %H:%M:%S")
        if t.microsecond:
            text += (".%06d" % t.microsecond).rstrip("0")
        return text + ("+00" if kind == "timestamptz" else "")
    text = bytes(v).decode()
    return text.ljust(oplog.char_width(column)) if kind == "bpchar" else text


def rows_of(block, valid, n):
    return [bytes(block[i] if valid is None else block[i][valid[i]])
            for i in range(n)]


def seeded(kind: str, rng, n: int):
    """Random values of a type with its extremes in front."""
    if kind == "bool":
        return np.array([True, False] + list(rng.integers(0, 2, n) == 1))
    if kind in ("int2", "int4", "int8"):
        bits = {"int2": 15, "int4": 31, "int8": 63}[kind]
        lo, hi = -(1 << bits), (1 << bits) - 1
        ext = [lo, hi, 0, -1, 1, 9, 10, 99_999, 100_000, -100_000]
        rnd = rng.integers(lo, hi, n, dtype=np.int64, endpoint=True)
        small = rng.integers(-1000, 1000, n)
        return np.array([v for v in ext if lo <= v <= hi]
                        + list(rnd) + list(small), dtype=np.int64)
    if kind == "float8":
        return np.array([0.0, -0.0, 1.0, -1.5, 1e20, 1.5e-7, 1e300,
                         float("inf"), float("-inf"), float("nan"),
                         0.1, 2.0**53] + list(rng.normal(0, 1e6, n)))
    if kind == "numeric":
        return np.array([0, 1, -1, 5, -5, 99, -99, 100, -100, 10**15,
                         -10**15, 123_456_789] + list(
            rng.integers(-10**12, 10**12, n)), dtype=np.int64)
    if kind == "date":  # 0001-01-01 .. 9999-12-31, and days before 2000
        return np.array([-719_162, 2_932_896, 0, -1, 10_956, 10_957]
                        + list(rng.integers(-30_000, 30_000, n)),
                        dtype=np.int32)
    if kind in ("timestamp", "timestamptz"):
        return np.array([0, -1, 1, 500_000, -500_000, 946_684_800_000_000,
                         946_684_799_999_999, -2_208_988_800_000_000,
                         1_700_000_000_120_000, 86_399_999_999]
                        + list(rng.integers(-3 * 10**15, 3 * 10**15, n))
                        + list(rng.integers(0, 10**9, n) * 1000),
                        dtype=np.int64)
    texts = [b"", b" ", b"plain", b"tab\tin", b"back\\slash", b"nl\nin",
             b"cr\rin", b"\\N", b"\\.", b"\x08\x0c\x0b", b"caf\xc3\xa9",
             b"trailing  "]
    if kind == "bpchar":
        texts = [t for t in texts if not t.endswith(b" ")]
    return np.array(texts + [b"v%d" % x for x in rng.integers(0, 10**9, n)])


COLUMNS = [
    {"name": "b", "type": "bool"}, {"name": "i2", "type": "int2"},
    {"name": "i4", "type": "int4"}, {"name": "i8", "type": "int8"},
    {"name": "f8", "type": "float8"},
    {"name": "n2", "type": "numeric", "precision": 18, "scale": 2},
    {"name": "n7", "type": "numeric", "precision": 18, "scale": 7},
    {"name": "n0", "type": "numeric", "precision": 18, "scale": 0},
    {"name": "c", "type": "bpchar", "text_bytes": 16},
    {"name": "vc", "type": "varchar", "text_bytes": 16},
    {"name": "tx", "type": "text"}, {"name": "d", "type": "date"},
    {"name": "ts", "type": "timestamp"},
    {"name": "tz", "type": "timestamptz"}]


@pytest.mark.parametrize("column", COLUMNS, ids=lambda c: c["name"])
def test_text_of_a_type_is_the_server_s_and_the_codec_reads_it(column):
    from etl_tpu.postgres.codec.text import parse_cell_text

    from source import table_schema

    values = seeded(column["type"], np.random.default_rng(SEED), 200)
    n = len(values)
    block, valid, length = wire.text_block(column, values, n)
    texts = rows_of(block, valid, n)
    assert texts == [py_text(column, v).encode() for v in values]
    if length is not None:
        assert length.tolist() == [len(t) for t in texts]
    oid = table_schema({"name": "public.t", "id": 1, "columns": [
        dict(column, key=True)]}).columns[0].type_oid
    assert wire.TYPE_OIDS[column["type"]] == oid
    for text, v in zip(texts[:40], values[:40]):
        got = parse_cell_text(text.decode(), oid)
        kind = column["type"]
        if kind == "float8":
            assert got == float(v) or (got != got and v != v)
        elif kind == "numeric":
            assert Decimal(got.pg_text()) == Decimal(int(v)).scaleb(
                -int(column.get("scale", 0)))
        elif kind == "date":
            assert got == dt.date(1970, 1, 1) + dt.timedelta(days=int(v))
        elif kind in ("timestamp", "timestamptz"):
            assert got.replace(tzinfo=None) == \
                EPOCH + dt.timedelta(microseconds=int(v))
        elif kind in ("bool", "int2", "int4", "int8"):
            assert got == v
        else:
            assert got == text.decode()


def test_a_shared_value_renders_as_every_row_s():
    column = {"name": "filler", "type": "bpchar", "modifier": 88}
    block, valid, length = wire.text_block(column, b"", 3)
    assert rows_of(block, valid, 3) == [b" " * 84] * 3


def test_copy_rows_are_copy_text_with_nulls_and_escapes():
    from etl_tpu.postgres.codec.copy_text import encode_copy_row

    rng = np.random.default_rng(SEED)
    table = {"name": "public.t", "id": 7, "columns": COLUMNS}
    n = 60
    cols, nulls = [], []
    for j, c in enumerate(COLUMNS):
        values = np.resize(seeded(c["type"], rng, 50), n)
        null = rng.integers(0, 4, n) == 0 if j % 2 else None
        cols.append(Col(values, null))
        nulls.append(null)
    blob, off = wire.render_copy_rows(table, cols, n)
    blob = bytes(blob)
    for i in range(n):
        line = encode_copy_row([
            None if nulls[j] is not None and nulls[j][i]
            else py_text(c, cols[j].values[i])
            for j, c in enumerate(COLUMNS)]) + b"\n"
        assert blob[off[i]:off[i + 1]] == \
            b"d" + (len(line) + 4).to_bytes(4, "big") + line, i


def fixture(kind: str = "backlog"):
    data = os.path.join(HERE, "data")
    with open(os.path.join(data, "fixture-null.json")) as f:
        config = json.load(f)
    with open(os.path.join(data, f"fixture-{kind}.json")) as f:
        traffic = json.load(f)
    traffic.update(traffic["rehearsal"])
    traffic["generator"] = dict(traffic["generator"], bulk_rows=40,
                                bulk_every_transactions=7, snapshot_rows=90,
                                backlog_transactions_per_second=10)
    gen = oplog.load_generator(
        config, os.path.join(data, "fixture-null.json"))
    return config, traffic, gen


def test_frames_of_every_kind_are_the_codec_s_own():
    """The fixture's stream — both tables and every kind of change inside
    one transaction — frame by frame against `pgoutput.encode_*`."""
    from etl_tpu.postgres.codec import pgoutput

    config, traffic, gen = fixture()
    tables = oplog.tables_of(config)
    stream = gen.stream(config, traffic, SEED, 2.0)
    kinds = wire.old_kinds(tables, stream)
    local = stream.local_index()
    relations = [wire.relation_payload(t) for t in tables]
    n_tx = len(stream.layout.rows)
    bufs, payload_bytes = wire.render_transactions(
        tables, stream, kinds, local, 0, n_tx, 1_700_000_000_000_000,
        relations)
    assert len(set(stream.layout.rows.tolist())) > 3  # unequal lengths
    seen, total, e = set(), 0, 0
    for k, buf in enumerate(bufs):
        at = ordinal = 0
        tables_here = set()
        while at < len(buf):
            assert buf[at:at + 1] == b"d"
            n = int.from_bytes(buf[at + 1:at + 5], "big")
            frame = pgoutput.decode_replication_frame(buf[at + 5:at + 1 + n])
            at += 1 + n
            head = frame.payload[:1]
            if head == b"B":
                msg = pgoutput.decode_logical_message(frame.payload)
                assert int(msg.final_lsn) == stream.layout.commit_lsn[k]
            elif head == b"C":
                msg = pgoutput.decode_logical_message(frame.payload)
                assert int(msg.end_lsn) == stream.layout.end_lsn[k]
            elif head == b"R":
                msg = pgoutput.decode_logical_message(frame.payload)
                table = next(t for t in tables if t["id"] == msg.relation_id)
                assert chr(msg.replica_identity) == table["replica_identity"]
                assert [c.name for c in msg.columns if c.flags & 1] == [
                    table["columns"][i]["name"]
                    for i in sorted(oplog.key_indices(table))]
            else:
                t, op, kind = int(stream.table[e]), int(stream.op[e]), \
                    int(kinds[e])
                table, ev = tables[t], stream.events[t]
                i = e if local is None else int(local[e])

                def image(cols, only=None):
                    values, marks = [], []
                    for j, (c, col) in enumerate(zip(table["columns"], cols)):
                        if only is not None and j not in only:
                            values.append(None)
                            marks.append(pgoutput.TUPLE_NULL)
                        elif col.unchanged is not None and col.unchanged[i]:
                            values.append(None)
                            marks.append(pgoutput.TUPLE_UNCHANGED_TOAST)
                        elif col.null is not None and col.null[i]:
                            values.append(None)
                            marks.append(pgoutput.TUPLE_NULL)
                        else:
                            values.append(py_text(c, col.values[i]).encode())
                            marks.append(pgoutput.TUPLE_TEXT)
                    return values, marks

                keys = set(oplog.key_indices(table))
                if op == INSERT:
                    want = pgoutput.encode_insert(table["id"], *image(ev.new))
                elif op == UPDATE:
                    new, marks = image(ev.new)
                    want = pgoutput.encode_update(
                        table["id"], new,
                        old_values=image(ev.old)[0] if kind == ord("O")
                        else None,
                        key_values=image(ev.old, keys)[0]
                        if kind == ord("K") else None, new_kinds=marks)
                else:
                    want = pgoutput.encode_delete(
                        table["id"], image(
                            ev.old, keys if kind == ord("K") else None)[0],
                        full_old=kind == ord("O"))
                assert frame.payload == want, (e, chr(op), chr(kind or 32))
                assert int(frame.start_lsn) == \
                    stream.layout.begin_lsn[k] + 8 * (ordinal + 1)
                seen.add((t, op, kind, ))
                tables_here.add(t)
                total += len(frame.payload)
                ordinal += 1
                e += 1
        assert ordinal == stream.layout.rows[k]
        if k > 9:
            assert tables_here == {0, 1}  # interleaved in one transaction
    assert total == payload_bytes and e == len(stream.op)
    # every kind of change: inserts; updates with no old image, a key
    # image, a whole old row; deletes with a key image and a whole row
    assert seen == {(0, INSERT, 0), (1, INSERT, 0), (0, UPDATE, ord("O")),
                    (1, UPDATE, 0), (1, UPDATE, ord("K")),
                    (0, DELETE, ord("O")), (1, DELETE, ord("K"))}
    note = stream.events[0].new[8]
    assert note.unchanged.any() and stream.events[1].new[2].null.any()


def test_the_generator_keeps_its_contract():
    """It imports nothing of the program, and the same seed gives the same
    log."""
    config, traffic, gen = fixture("paced")
    before = set(sys.modules)
    a = gen.stream(config, traffic, SEED, 1.0)
    b = gen.stream(config, traffic, SEED, 1.0)
    assert not {m for m in set(sys.modules) - before
                if m.startswith(("etl_tpu", "jax"))}
    assert (a.table == b.table).all() and (a.op == b.op).all()
    assert (a.events[0].new[4].values == b.events[0].new[4].values).all()
    c = gen.stream(config, traffic, SEED + 1, 1.0)
    assert (a.events[0].new[4].values != c.events[0].new[4].values).any()
    with open(os.path.join(os.path.dirname(HERE), "deployments",
                           "pgbench_accounts.py")) as f:
        source = f.read()
    assert "etl_tpu" not in source.split('"""', 2)[2]
