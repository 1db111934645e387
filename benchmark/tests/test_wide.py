"""Wide rows as files: JSON is a type the yardstick knows and compares by
value, a configuration with a type it cannot compare is refused when it
loads, `Coded` text renders the bytes an `S` array does without padding
the log, and no render holds more than `wire.RENDER_BYTES` in matrices at
once — while the benchmark's own cells render in the chunks they did."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import oplog  # noqa: E402
import reference  # noqa: E402
import wire  # noqa: E402
from oplog import INSERT, UPDATE, Col, Coded, Pool  # noqa: E402

SEED = 2**31 + 11
DATA = os.path.join(HERE, "data")


# ---------------------------------------------------------------------------
# one definition of how a column compares
# ---------------------------------------------------------------------------


def test_a_type_with_no_comparison_is_refused_when_the_configuration_loads(
        tmp_path):
    table = {"name": "public.t", "id": 5, "columns": [
        {"name": "id", "type": "int8", "key": True},
        {"name": "tag", "type": "uuid"}]}
    with pytest.raises(ValueError, match="public.t.tag: type 'uuid'"):
        oplog.tables_of({"tables": [table]})
    with open(os.path.join(DATA, "fixture-null.json")) as f:
        config = json.load(f)
    config["tables"][1]["columns"][4]["type"] = "uuid"
    path = tmp_path / "fixture-null.json"
    path.write_text(json.dumps(config))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "pgbench-s10-null.backlog-drain", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--config-file", str(path)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout == ""
    assert "public.fx_items.seen: type 'uuid' has no comparison" \
        in out.stderr


def test_every_type_has_its_comparison_and_a_text_form():
    assert set(oplog.COMPARISONS.values()) == {"typed", "numeric", "text",
                                               "json"}
    for kind in oplog.COMPARISONS:
        assert kind in wire.TYPE_OIDS
        assert kind in wire.TEXT_BLOCKS or oplog.as_text({"type": kind})


@pytest.mark.parametrize("a,b,equal", [
    ("1.50", "1.5", True), ("15e-1", "1.5", True), ("1", "1.01", False),
    ("-0", "0", True), ("0.10", "0.1", True), ("1e2", "100", True),
    ("1e2", '"1e2"', False), ('{"a": 1, "b": [1, 2]}', '{"b":[1,2],"a":1}',
                             True),
    ("[1, 2]", "[2, 1]", False), ('"caf\\u00e9"', '"café"', True),
    ("null", '"null"', False), ("true", "1", False), ("0", "false", False),
    ('{"a": {"b": null}}', '{"a": {}}', False),
    ("12345678901234567890.5", "12345678901234567890.50", True),
    ("12345678901234567890.5", "12345678901234567890.4", False),
])
def test_json_compares_by_value(a, b, equal):
    assert (reference.json_text_spelling(a)
            == reference.json_text_spelling(b)) is equal


def test_a_parsed_value_spells_as_its_text():
    """What a host parsed (a string is a JSON string; floats at their
    shortest repr) spells as the text it came from."""
    for text in ('{"z": [1, 2.5, "x"], "a": null}', '"plain"', "100",
                 "1e2", "-1.5e-7", "true", "null", '["\\t\\n\\\\"]'):
        assert reference.json_spelling(json.loads(text)) \
            == reference.json_text_spelling(text), text
    assert reference.json_spelling("1e2") != reference.json_spelling(100)
    assert reference.json_text_spelling("café é").startswith(
        reference.NOT_JSON)
    assert reference.json_text_spelling("NaN").startswith(reference.NOT_JSON)


@pytest.mark.parametrize("coded", [False, True])
def test_the_reference_holds_json_by_value_and_json_null_apart(coded):
    column = {"name": "j", "type": "jsonb", "nullable": True}
    texts = [b'{"a": 1, "b": [1, 2]}', b"null", b"0.10", b'"x"', b"null"]
    values = Coded([0, 1, 2, 3, 1], Pool(texts[:4])) if coded \
        else np.array(texts)
    want = Col(values, null=np.array([False, False, False, False, True]))
    spell = reference.json_text_spelling
    have = [spell('{"b":[1,2],"a":1.0}'), spell("null"), spell("1e-1"),
            spell('"x"'), None]
    null = np.array([False, False, False, False, True])
    assert not reference._differs(column, (have, null, None), want, None,
                                  5).any()
    # JSON null is not SQL NULL, either way; another value is another value
    bad = reference._differs(
        column, ([None, have[1], spell("0.11"), spell("x1"), spell("null")],
                 np.array([True, False, False, False, False]), None),
        want, None, 5)
    assert bad.tolist() == [True, False, True, True, True]


# ---------------------------------------------------------------------------
# coded text and JSON on the wire
# ---------------------------------------------------------------------------


def test_json_text_is_the_generator_s_and_the_codec_reads_it():
    from etl_tpu.postgres.codec.text import parse_cell_text

    from source import table_schema

    texts = np.array([b'{"a": 1}', b'"tab\\there"', b"1e2", b"null",
                      b'"caf\xc3\xa9"', b"[1, 2.5]"])
    for kind, oid in (("json", 114), ("jsonb", 3802)):
        column = {"name": "j", "type": kind}
        block, valid, length = wire.text_block(column, texts, len(texts))
        rows = [bytes(block[i][valid[i]]) for i in range(len(texts))]
        assert rows == texts.tolist()
        assert wire.TYPE_OIDS[kind] == oid == table_schema(
            {"name": "public.t", "id": 1,
             "columns": [dict(column, key=True)]}).columns[0].type_oid
        for text in rows:
            got = parse_cell_text(text.decode(), oid)
            assert reference.json_spelling(
                None if type(got).__name__ == "JsonNull" else got) \
                == reference.json_text_spelling(text)


def _wide_pool():
    rng = np.random.default_rng(SEED)
    values = [b"", b"short", b"tab\tand\\back", b"caf\xc3\xa9",
              b"\\N", b"x" * 1024, b"y" * 1025]
    for size in (2048, 5000, 70_000):
        values.append(bytes(rng.integers(32, 127, size, dtype=np.uint8))
                      + b"\t\n\\" + "é".encode())
    return Pool(values)


@pytest.mark.parametrize("kind", ["text", "varchar", "bpchar", "jsonb"])
def test_coded_text_renders_as_its_s_array(kind):
    """Frames and COPY rows of Coded values, long ones spliced, are the
    bytes of the same values held as an `S` array."""
    pool = _wide_pool()
    if kind == "jsonb":
        pool = Pool([json.dumps(v.decode("utf-8", "replace")).encode()
                     for v in pool.values])
    column = {"name": "v", "type": kind, "text_bytes": 70_100}
    table = {"name": "public.w", "id": 9, "replica_identity": "f",
             "columns": [{"name": "id", "type": "int4", "key": True},
                         column, dict(column, name="w", nullable=True)]}
    n = 300
    rng = np.random.default_rng(SEED)
    codes = rng.integers(0, len(pool), (2, n))
    null = rng.integers(0, 5, n) == 0
    unchanged = rng.integers(0, 4, n) == 0
    plain = np.array(pool.values, dtype="S")

    def cols(values):
        return [Col(np.arange(n, dtype=np.int32)),
                Col(values(0), unchanged=unchanged),
                Col(values(1), null=null)]

    coded = cols(lambda j: Coded(codes[j], pool))
    padded = cols(lambda j: plain[codes[j]])
    lsns = 8 * np.arange(n, dtype=np.int64)
    for op in (INSERT, UPDATE):
        a = wire.render_change_frames(table, op, ord("O") if op == UPDATE
                                      else 0, coded, coded, lsns, lsns, 0)
        b = wire.render_change_frames(table, op, ord("O") if op == UPDATE
                                      else 0, padded, padded, lsns, lsns, 0)
        assert bytes(a[0]) == bytes(b[0])
        assert a[1].tolist() == b[1].tolist()
        assert a[2].tolist() == b[2].tolist()
    a = wire.render_copy_rows(table, [c.pick(slice(None)) for c in coded], n)
    b = wire.render_copy_rows(table, padded, n)
    assert bytes(a[0]) == bytes(b[0]) and a[1].tolist() == b[1].tolist()


def test_coded_copy_rows_are_copy_text():
    from etl_tpu.postgres.codec.copy_text import encode_copy_row

    pool = _wide_pool()
    n = 40
    codes = np.arange(n) % len(pool)
    null = np.arange(n) % 6 == 5
    table = {"name": "public.w", "id": 9, "columns": [
        {"name": "id", "type": "int4", "key": True},
        {"name": "v", "type": "text", "nullable": True}]}
    blob, off = wire.render_copy_rows(
        table, [Col(np.arange(n, dtype=np.int32)),
                Col(Coded(codes, pool), null=null)], n)
    blob = bytes(blob)
    for i in range(n):
        line = encode_copy_row([str(i), None if null[i] else
                                pool.values[codes[i]].decode()]) + b"\n"
        assert blob[off[i]:off[i + 1]] == \
            b"d" + (len(line) + 4).to_bytes(4, "big") + line


def test_a_coded_log_holds_four_bytes_a_row():
    pool = _wide_pool()
    col = Col(Coded(np.arange(1_000_000) % len(pool), pool))
    assert col.values.codes.nbytes == 4_000_000
    picked = col.pick(np.array([9, 19]))
    assert isinstance(picked.values, Coded) and picked.values.pool is pool
    assert np.asarray(picked.values).tolist() == [pool.values[9]] * 2
    assert oplog.n_rows([col]) == 1_000_000


# ---------------------------------------------------------------------------
# the byte budget
# ---------------------------------------------------------------------------


def _wide_table(width: int, columns: int = 10):
    """A table whose rows are `width` bytes of text, over `columns`
    varchar columns, and a log of `n` rows of it."""
    w = width // columns
    return {"name": "public.wide", "id": 11, "replica_identity": "f",
            "columns": [{"name": "id", "type": "int8", "key": True}] + [
                {"name": f"c{j}", "type": "varchar", "text_bytes": w}
                for j in range(columns)]}


def _wide_stream(table: dict, n_tx: int, seed: int = SEED):
    """Transactions of two inserts and an update each, rows 30 KB wide."""
    rng = np.random.default_rng(seed)
    w = table["columns"][1]["text_bytes"]
    n = 3 * n_tx

    def image(shift):
        return [Col(np.arange(n, dtype=np.int64))] + [
            Col(np.array([(b"%07d\t\\" % (i + shift) * (w // 9 + 1))[:w]
                          for i in rng.integers(0, 10**6, n)], dtype="S"))
            for _ in table["columns"][1:]]

    op = np.tile(np.array([INSERT, INSERT, UPDATE], np.uint8), n_tx)
    return oplog.Stream(oplog.TxLayout.build([3] * n_tx),
                        np.zeros(n, np.uint8), op,
                        {0: oplog.TableEvents(image(0), image(1))})


def test_at_30_kb_a_chunk_renders_in_slices_under_the_budget():
    table = _wide_table(30_000)
    stream = _wide_stream(table, 1)
    bound = wire._change_bound(table, UPDATE, ord("O"),
                               stream.events[0].new, stream.events[0].old,
                               None)
    assert 60_000 <= bound < 62_000  # the new row and the whole old one
    rows = wire._slice_rows(bound)
    import source

    assert rows < source.RENDER_CHUNK_ROWS
    assert 4 * rows * bound <= wire.RENDER_BYTES


def test_the_largest_matrix_stays_under_the_budget(monkeypatch):
    """At 30 KB a row, rendered with a budget that cuts a chunk of
    transactions (inserts and updates with the whole old row interleaved)
    and a COPY into slices: every row matrix with its mask stays under it
    (a render makes one at a time: several kinds of change are rendered
    one after another and joined by runs), and the bytes are those of one
    unsliced render."""
    table = _wide_table(30_000)
    stream = _wide_stream(table, 40)
    kinds = wire.old_kinds([table], stream)
    whole = wire.render_transactions([table], stream, kinds, None, 0, 40, 0)
    copy = wire.render_copy_rows(table, stream.events[0].new, 120)
    budget = 8 << 20
    monkeypatch.setattr(wire, "RENDER_BYTES", budget)
    held = []
    rows_matrix = wire._rows_matrix

    def watched(parts, n, scratch=True):
        mat, mask = rows_matrix(parts, n, scratch)
        held.append(mat.nbytes + mask.nbytes)
        return mat, mask

    monkeypatch.setattr(wire, "_rows_matrix", watched)
    sliced = wire.render_transactions([table], stream, kinds, None, 0, 40, 0)
    assert len(held) > 2 and max(held) <= budget
    assert sliced == whole
    held.clear()
    sliced = wire.render_copy_rows(table, stream.events[0].new, 120)
    assert len(held) > 1 and max(held) <= budget
    assert bytes(sliced[0]) == bytes(copy[0])
    assert sliced[1].tolist() == copy[1].tolist()


def _cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    return [(w["name"], files[w["config"]], w["traffic"])
            for w in bench["workloads"]]


@pytest.mark.parametrize("name,config,traffic", _cells(),
                         ids=[c[0] for c in _cells()])
def test_the_cells_render_in_the_chunks_they_did(name, config, traffic):
    """No kind of change or COPY of the benchmark's cells is cut by the
    budget: a chunk of RENDER_CHUNK_ROWS events and its largest
    transaction, and a COPY chunk, render at once, as before it."""
    import source

    path = os.path.join(os.path.dirname(BENCH), config)
    with open(path) as f:
        doc = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    largest = max(int(mix.get("bulk_rows", 0)),
                  int(mix.get("generator", {}).get("bulk_rows", 0)),
                  int(mix.get("transaction_rows", 0)))
    # the widths are the log's and the snapshot's: rehearsal sizes hold
    # the same column widths as the cell's own
    doc.update(doc.get("rehearsal", {}))
    mix.update(mix.get("rehearsal", {}))
    gen = oplog.load_generator(doc, path)
    tables = oplog.tables_of(doc)
    stream = gen.stream(doc, mix, SEED, 2.0)
    if stream is not None:
        kinds = wire.old_kinds(tables, stream)
        group = (stream.table.astype(np.int64) << 16) \
            | (stream.op.astype(np.int64) << 8) | kinds
        for t, op, old_kind in map(wire._unkey, np.unique(group).tolist()):
            ev = stream.events[t]
            assert wire._slice_rows(wire._change_bound(
                tables[t], op, old_kind, ev.new, ev.old, None)) \
                >= source.RENDER_CHUNK_ROWS + largest
    snapshot = gen.snapshot(doc, mix, SEED)
    for table in tables:
        cols = snapshot.get(int(table["id"]))
        if cols and oplog.n_rows(cols):
            assert wire._slice_rows(5 + sum(
                1 + max(2, wire.width_bound(c, col.values, None, copy=True))
                for c, col in zip(table["columns"], cols))) \
                >= source.COPY_CHUNK_ROWS


# ---------------------------------------------------------------------------
# the wide fixture
# ---------------------------------------------------------------------------


def _docs(kind: str = "backlog"):
    path = os.path.join(DATA, "docs-null.json")
    with open(path) as f:
        config = json.load(f)
    with open(os.path.join(DATA, f"docs-{kind}.json")) as f:
        traffic = json.load(f)
    traffic.update(traffic["rehearsal"])
    traffic["generator"] = dict(traffic["generator"], bulk_rows=40,
                                bulk_every_transactions=7, snapshot_rows=90,
                                backlog_transactions_per_second=10)
    return config, traffic, oplog.load_generator(config, path)


def test_the_wide_fixture_keeps_the_generator_s_contract():
    """It imports nothing of the program; the same seed gives the same log;
    the body is Coded, long in about one row of eight, and unchanged TOAST
    only where an update keeps a long one."""
    config, traffic, gen = _docs()
    before = set(sys.modules)
    a = gen.stream(config, traffic, SEED, 2.0)
    b = gen.stream(config, traffic, SEED, 2.0)
    assert not {m for m in set(sys.modules) - before
                if m.startswith(("etl_tpu", "jax"))}
    assert len(config["tables"][0]["columns"]) == 100
    body = a.events[0].new[-1]
    assert isinstance(body.values, Coded)
    assert (body.values.codes == b.events[0].new[-1].values.codes).all()
    long = body.values.pool.lengths[body.values.codes] > 2048
    assert 0.05 < long.mean() < 0.25
    assert body.unchanged.any() and not (body.unchanged & ~long).any()
    assert (a.op[body.unchanged] == UPDATE).all()
    assert {int(x) for x in np.unique(a.op)} == {INSERT, UPDATE,
                                                 oplog.DELETE}
    c = gen.stream(config, traffic, SEED + 1, 2.0)
    assert (body.values.codes != c.events[0].new[-1].values.codes).any()


def test_the_wide_fixture_s_frames_are_the_codec_s_own():
    """Every change of the wide fixture's stream, frame by frame, against
    `pgoutput.encode_*`: the whole old row of every update and delete, the
    long bodies spliced in, `u` where an update keeps a long body."""
    from etl_tpu.postgres.codec import pgoutput

    config, traffic, gen = _docs()
    tables = oplog.tables_of(config)
    table = tables[0]
    stream = gen.stream(config, traffic, SEED, 2.0)
    kinds = wire.old_kinds(tables, stream)
    n_tx = len(stream.layout.rows)
    bufs, _ = wire.render_transactions(tables, stream, kinds, None, 0, n_tx,
                                       1_700_000_000_000_000)
    texts = [wire.text_block(c, col.values, len(stream.op))
             for c, col in zip(table["columns"], stream.events[0].new)]
    olds = [wire.text_block(c, col.values, len(stream.op))
            for c, col in zip(table["columns"], stream.events[0].old)]

    def image(cols, blocks, i):
        values, marks = [], []
        for col, (block, valid, _) in zip(cols, blocks):
            if col.unchanged is not None and col.unchanged[i]:
                values.append(None)
                marks.append(pgoutput.TUPLE_UNCHANGED_TOAST)
            elif col.null is not None and col.null[i]:
                values.append(None)
                marks.append(pgoutput.TUPLE_NULL)
            else:
                values.append(bytes(np.asarray(col.values[i:i + 1])[0])
                              if isinstance(col.values, Coded) else
                              bytes(block[i] if valid is None
                                    else block[i][valid[i]]))
                marks.append(pgoutput.TUPLE_TEXT)
        return values, marks

    e, seen = 0, set()
    for buf in bufs:
        at = 0
        while at < len(buf):
            n = int.from_bytes(buf[at + 1:at + 5], "big")
            frame = pgoutput.decode_replication_frame(buf[at + 5:at + 1 + n])
            at += 1 + n
            if frame.payload[:1] in (b"B", b"C", b"R"):
                continue
            op = int(stream.op[e])
            new, marks = image(stream.events[0].new, texts, e)
            old = image(stream.events[0].old, olds, e)[0]
            if op == INSERT:
                want = pgoutput.encode_insert(table["id"], new)
            elif op == UPDATE:
                want = pgoutput.encode_update(table["id"], new,
                                              old_values=old,
                                              new_kinds=marks)
            else:
                want = pgoutput.encode_delete(table["id"], old,
                                              full_old=True)
            assert frame.payload == want, e
            seen.add((op, pgoutput.TUPLE_UNCHANGED_TOAST in marks))
            e += 1
    assert e == len(stream.op)
    assert seen == {(INSERT, False), (UPDATE, False), (UPDATE, True),
                    (oplog.DELETE, False)}
