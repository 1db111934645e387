"""The TPC-C stream into ClickHouse (`benchmark/configs/
tpcc-w4-clickhouse.json`, the cell `tpcc-w4-clickhouse.standard-mix-drain`)
on the CPU: the configuration is `tpcc-w4-null.json` with another sink and
nothing else; every shape the stream holds — NUMERIC at both ends of its
precision, padded `char(n)`, `varchar(500)`, TIMESTAMP, NULLs in fixed and
in text columns, an `Update` with no old tuple, a `Delete` with a `K` image
of a four-part key — comes out of the fast TSV render byte for byte as the
per-value render has it; the two cell counters count what they say; the DDL,
the change label and the sequence key are what `docs/destinations.md`
tabulates; and one rehearsal of the cell through `benchmark/run.py` into
`benchmark/sink.py` ends `correct: true` with every new metric printing."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import oplog  # noqa: E402
import wire  # noqa: E402
from source import table_schema  # noqa: E402

from etl_tpu.destinations.clickhouse import (  # noqa: E402
    ClickHouseConfig, ClickHouseDestination, ClickHouseEngine,
    create_table_sql, render_batch_tsv_columnar, render_batch_tsv_fast)
from etl_tpu.destinations.util import (  # noqa: E402
    DestinationRetryPolicy, change_type_batch, sequence_number_batch,
    sequence_number_buffer)
from etl_tpu.models import ChangeType, ReplicatedTableSchema  # noqa: E402
from etl_tpu.ops.engine import DeviceDecoder  # noqa: E402
from etl_tpu.ops.wal import concat_payloads, stage_wal_batch  # noqa: E402
from etl_tpu.postgres.codec import pgoutput  # noqa: E402
from etl_tpu.telemetry.metrics import (  # noqa: E402
    ETL_CLICKHOUSE_BOXED_CELLS_TOTAL, ETL_CLICKHOUSE_RENDERED_CELLS_TOTAL,
    registry)

CONFIG_PATH = os.path.join(BENCH, "configs", "tpcc-w4-clickhouse.json")
NULL_CONFIG_PATH = os.path.join(BENCH, "configs", "tpcc-w4-null.json")
TRAFFIC_PATH = os.path.join(BENCH, "traffic", "standard-mix-drain.json")
CELL = "tpcc-w4-clickhouse.standard-mix-drain"
NULL_CELL = "tpcc-w4-null.standard-mix-drain"
NEW_METRICS = ("tpcc_ch_render_s_per_mrow", "ch_boxed_cell_share_pct",
               "ch_requests_per_flush_mean", "ch_request_ms_mean")
SEED = 2147483659
I, U, D = oplog.INSERT, oplog.UPDATE, oplog.DELETE
NUMERIC_COLUMNS = {"warehouse": 2, "district": 2, "customer": 4,
                   "new_order": 0, "orders": 0, "order_line": 1, "stock": 0,
                   "item": 1}


def _load(path):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the files
# ---------------------------------------------------------------------------


def test_the_configuration_is_the_null_one_with_another_sink():
    mine, null = _load(CONFIG_PATH), _load(NULL_CONFIG_PATH)
    assert list(mine) == list(null)
    differ = {k for k in null if mine[k] != null[k]}
    assert differ == {"name", "source", "destination", "assumed"}
    assert {k for k in null["assumed"]
            if mine["assumed"][k] != null["assumed"][k]} == {"affinity"}
    assert list(mine["assumed"]) == list(null["assumed"])
    assert mine["name"] == "tpcc-w4-clickhouse"
    assert mine["destination"] == {"type": "clickhouse",
                                   "database": "default"}
    pgbench = _load(os.path.join(BENCH, "configs",
                                 "pgbench-s10-clickhouse.json"))
    assert mine["assumed"]["affinity"] == pgbench["assumed"]["affinity"]
    assert mine["destination"] == pgbench["destination"]
    assert len(mine["source"]) <= 200 and "\n" not in mine["source"]
    assert "go-tpc" in mine["source"] and "ClickHouse" in mine["source"]
    assert mine["reduced"] == ["warehouses"] and mine["warehouses"] == 4
    assert "hundreds" in mine["reduced_why"]["warehouses"]
    assert mine["guarantee"] == "at-least-once"
    assert mine["pipeline"]["batch"] == {}


def test_the_cell_is_in_the_benchmark_s_lists():
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry == [{"name": CELL, "config": "tpcc-w4-clickhouse",
                      "traffic": "standard-mix-drain", "chips": 1,
                      "why": entry[0]["why"]}]
    assert len(entry[0]["why"]) <= 200
    config = [c for c in bench["configs"]
              if c["name"] == "tpcc-w4-clickhouse"][0]
    assert config["file"] == "benchmark/configs/tpcc-w4-clickhouse.json"
    assert config["source"] == _load(CONFIG_PATH)["source"]
    assert config["reduced"] == ["warehouses"]
    # the last entries of their lists: nothing that was there has moved
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "tpcc-w4-clickhouse"
    # PR 36's four in a row; the lists below are the benchmark as PR 36
    # left it (later PRs append after them)
    names = [m["name"] for m in bench["per_layer"]]
    end = names.index(NEW_METRICS[-1]) + 1
    assert names[end - len(NEW_METRICS):end] == list(NEW_METRICS)

    def listed(cell):
        return {m["name"] for m in bench["per_layer"][:end]
                if cell in m.get("workloads", [])}

    mine, null = listed(CELL), listed(NULL_CELL)
    pgbench = listed("pgbench-s10-clickhouse.backlog-drain")
    # what the null cell lists (but the share that reads 0 by construction),
    # what the other ClickHouse cell lists, and the four new ones
    assert mine == (null - {"tpcc_table_switch_seal_share_pct"}) \
        | {n for n in pgbench if n.startswith("ch_")} | set(NEW_METRICS)
    assert {n for n in pgbench if n.startswith("ch_")} == {
        "ch_tsv_render_busy_pct", "ch_sink_service_pct",
        "ch_render_s_per_mrow", "ch_render_native_row_share_pct"}
    for name in NEW_METRICS:
        assert [m["workloads"] for m in bench["per_layer"]
                if m["name"] == name] == [[CELL]]
        assert _load(os.path.join(BENCH, "metrics", name + ".json"))[
            "reader"] == "counter_ratio"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["cdc_events_per_s"]["workloads"]
    assert (e2e["cdc_events_per_s"]["bound"], e2e["setup_s"]["bound"]) \
        == (0.08, 0.25)


# ---------------------------------------------------------------------------
# the render: the fast path against the per-value one
# ---------------------------------------------------------------------------


def _rehearsal_files():
    config, traffic = _load(CONFIG_PATH), _load(TRAFFIC_PATH)
    config.update(config["rehearsal"])
    traffic.update(traffic["rehearsal"])
    return config, traffic


@pytest.fixture(scope="module")
def deployment():
    config, traffic = _rehearsal_files()
    gen = oplog.load_generator(config, CONFIG_PATH)
    tables = oplog.tables_of(config)
    return {"tables": {t["name"].split(".")[1]: (i, t)
                       for i, t in enumerate(tables)},
            "all": tables,
            "stream": gen.stream(config, traffic, SEED, 2.0),
            "snapshot": gen.snapshot(config, traffic, SEED)}


def _schema(table):
    return ReplicatedTableSchema.with_all_columns(table_schema(table))


def _decoded(table, payloads):
    """(batch, change types) of pgoutput payloads through the real staging
    and decode, as the assembler's sealed run goes."""
    buf, offs, lens = concat_payloads(payloads)
    wal = stage_wal_batch(buf, offs, lens, len(table["columns"]))
    return DeviceDecoder(_schema(table)).decode(wal.staged), wal.change_types


def _seeded(deployment, name, op, n=300):
    """`n` of the generator's events of one kind on one table, rendered as
    the source renders them (the item table, which no transaction writes,
    from its snapshot rows)."""
    t, table = deployment["tables"][name]
    stream = deployment["stream"]
    if name == "item":
        cols, old, old_kind = deployment["snapshot"][int(table["id"])], \
            None, 0
        rows = np.resize(np.arange(oplog.n_rows(cols)), n)
    else:
        ev = stream.events[t]
        kinds = wire.old_kinds(deployment["all"], stream)
        mine = np.flatnonzero((stream.table == t) & (stream.op == op))
        assert len(mine), (name, op)
        old_kind = int(kinds[mine[0]])
        rows = np.resize(stream.local_index()[mine], n)
        cols, old = ev.new, ev.old
    zeros = np.zeros(n, dtype=np.int64)
    blob, offsets, payload_len = wire.render_change_frames(
        table, op, old_kind, [c.pick(rows) for c in cols],
        [c.pick(rows) for c in old] if old_kind else None, zeros, zeros, 0,
        None)
    head = 5 + 25  # CopyData header + XLogData header
    wal = stage_wal_batch(blob, offsets[:n] + head,
                          payload_len[:n].astype(np.int32),
                          len(table["columns"]))
    assert wal.old_staged is None  # no old tuple but a delete's key image
    return table, DeviceDecoder(_schema(table)).decode(wal.staged), \
        wal.change_types


def _both_renders(table, batch, change_types):
    n = batch.num_rows
    lsns = np.arange(n, dtype=np.uint64) + 0x5000
    ords = np.arange(n, dtype=np.uint64) % 7
    zeros = np.zeros(n, dtype=np.uint64)
    labels = change_type_batch(change_types)
    seq_buf = sequence_number_buffer(lsns, ords, zeros)
    seqs = [s.decode() for s in sequence_number_batch(lsns, ords, zeros)]
    schema = _schema(table)
    plain = render_batch_tsv_columnar(
        schema, batch, [c.decode() for c in labels.tolist()], seqs)
    fast, used_device = render_batch_tsv_fast(schema, batch, labels, seq_buf)
    assert used_device is False
    return fast, plain


def _cells():
    return (registry.get_counter(ETL_CLICKHOUSE_RENDERED_CELLS_TOTAL),
            registry.get_counter(ETL_CLICKHOUSE_BOXED_CELLS_TOTAL))


SEEDED = [("warehouse", U), ("district", U), ("customer", U),
          ("new_order", I), ("new_order", D), ("orders", I), ("orders", U),
          ("order_line", I), ("order_line", U), ("stock", U), ("item", I)]


@pytest.mark.parametrize("name,op", SEEDED,
                         ids=[f"{n}-{chr(o)}" for n, o in SEEDED])
def test_seeded_batches_render_byte_identical_and_are_counted(
        deployment, name, op):
    table, batch, change_types = _seeded(deployment, name, op)
    n, n_cols = batch.num_rows, len(table["columns"])
    assert set(change_types.tolist()) == {
        {I: int(ChangeType.INSERT), U: int(ChangeType.UPDATE),
         D: int(ChangeType.DELETE)}[op]}
    rendered, boxed = _cells()
    fast, plain = _both_renders(table, batch, change_types)
    assert fast == plain
    lines = fast.split(b"\n")
    assert len(lines) == n + 1 and lines[-1] == b""
    assert all(line.count(b"\t") == n_cols + 1 for line in lines[:-1])
    label = b"DELETE" if op == D else b"UPSERT"
    assert all(line.split(b"\t")[-2] == label for line in lines[:-1])
    # one increment a column: every cell counted, none boxed — the
    # generator spells NUMERIC as `numeric_out` does, which goes verbatim
    # (PR 37), and no text of its needs an escape
    after = _cells()
    assert after[0] - rendered == n * n_cols
    assert after[1] == boxed
    numeric = [j for j, c in enumerate(table["columns"])
               if c["type"] == "numeric"]
    assert len(numeric) == NUMERIC_COLUMNS[name]
    assert all(batch.columns[j].is_arrow
               and batch.columns[j].lazy_text_oid is not None
               for j in numeric)
    # the NULLs the specification has, as \N
    if (name, op) == ("orders", I):
        at = [c["name"] for c in table["columns"]].index("o_carrier_id")
        assert all(line.split(b"\t")[at] == b"\\N" for line in lines[:-1])
    if (name, op) == ("order_line", I):
        at = [c["name"] for c in table["columns"]].index("ol_delivery_d")
        assert any(line.split(b"\t")[at] == b"\\N" for line in lines[:-1])


def _texts(table, **values):
    """One row's wire texts: `values` by column name, a filler by type for
    the rest."""
    filler = {"int4": b"7", "numeric": b"1.00", "timestamp":
              b"2024-01-01 00:00:00", "bpchar": b"ab", "varchar": b"xy"}
    out = []
    for c in table["columns"]:
        v = values.get(c["name"], filler[c["type"]])
        if c["type"] == "bpchar" and v is not None:
            v = v.ljust(int(c["text_bytes"]))  # as the server pads it
        out.append(v)
    return out


def _edge_rows(deployment, name):
    """Hand-made rows of the shapes a seed may never draw: (table, pgoutput
    payloads, each row's wire texts)."""
    _, table = deployment["tables"][name]
    tid = int(table["id"])
    if name == "customer":
        rows = [
            _texts(table, c_credit_lim=b"9999999999.99",
                   c_balance=b"-9999999999.99", c_discount=b"0.9999",
                   c_ytd_payment=b"0.00", c_data=b"x" * 500),
            _texts(table, c_credit_lim=b"0.01", c_balance=b"-0.01",
                   c_discount=b"0.0000", c_ytd_payment=b"10.00",
                   c_data=None, c_since=None, c_middle=None),
            _texts(table, c_data=b"tab\there \\ and\nnewline",
                   c_first=b"", c_payment_cnt=None),
        ]
        return table, [pgoutput.encode_update(tid, r) for r in rows], rows
    if name == "warehouse":
        rows = [_texts(table, w_tax=b"0.0000", w_ytd=b"9999999999.99"),
                _texts(table, w_tax=b"0.9999", w_ytd=b"-9999999999.99"),
                _texts(table, w_tax=b"0.2000", w_ytd=None, w_name=None)]
        return table, [pgoutput.encode_update(tid, r) for r in rows], rows
    assert name == "order_line"
    keys = {c["name"] for i, c in enumerate(table["columns"])
            if i in oplog.key_indices(table)}
    rows = [_texts(table, ol_amount=b"9999.99", ol_delivery_d=None),
            _texts(table, ol_amount=b"0.00",
                   ol_delivery_d=b"2024-01-01 00:00:00.007013"),
            # a `K` image of the four-part key: every other column absent
            [b"7" if c["name"] in keys else None for c in table["columns"]]]
    return table, [pgoutput.encode_insert(tid, rows[0]),
                   pgoutput.encode_update(tid, rows[1]),
                   pgoutput.encode_delete(tid, rows[2])], rows


def _boxed_cells(table, rows) -> int:
    """The cells of `rows` that `_column_piece_tsv` renders value by value,
    whichever route decoded them — the per-row oracle of a run under
    `DeviceDecoder.HOST_MIN_ROWS` keeps NUMERIC and text columns as the
    decoder's Arrow text, as the host program does: no NUMERIC cell spelt
    as `numeric_out` spells (every one here), and of the text cells those
    of a column in which some text needs a TSV escape."""
    boxed = 0
    for j, c in enumerate(table["columns"]):
        cells = [r[j] for r in rows if r[j] is not None]
        if c["type"] in ("bpchar", "varchar") and any(
                ch in v for v in cells for ch in b"\t\n\r\\"):
            boxed += len(cells)
    return boxed


@pytest.mark.parametrize("route,times", [("oracle", 1), ("host", 30)])
@pytest.mark.parametrize("name", ["customer", "warehouse", "order_line"])
def test_edge_rows_render_byte_identical(deployment, name, route, times):
    """Three rows as a transaction's run holds them (under 64 rows: the
    per-row oracle decodes them), and the same rows thirty times over (the
    host program does, as in a coalesced flush)."""
    table, payloads, texts = _edge_rows(deployment, name)
    payloads, texts = payloads * times, texts * times
    assert (len(payloads) < DeviceDecoder.HOST_MIN_ROWS) \
        == (route == "oracle")
    batch, change_types = _decoded(table, payloads)
    names = [c["name"] for c in table["columns"]]
    text_column = batch.columns[[c["type"] for c in table["columns"]]
                                .index("bpchar")]
    assert text_column.is_arrow
    rendered, boxed = _cells()
    fast, plain = _both_renders(table, batch, change_types)
    assert fast == plain
    lines = [line.split(b"\t") for line in fast.split(b"\n")[:-1]]
    after = _cells()
    assert after[0] - rendered == len(payloads) * len(names)
    assert after[1] - boxed == _boxed_cells(table, texts)
    at = names.index
    if name == "customer":
        assert lines[0][at("c_credit_lim")] == b"9999999999.99"
        assert lines[0][at("c_balance")] == b"-9999999999.99"
        assert lines[0][at("c_discount")] == b"0.9999"
        assert lines[1][at("c_discount")] == b"0.0000"
        assert lines[1][at("c_balance")] == b"-0.01"
        assert lines[0][at("c_data")] == b"x" * 500
        # NULL in a text, a fixed (timestamp, int4) and a char column
        assert lines[1][at("c_data")] == lines[1][at("c_since")] \
            == lines[1][at("c_middle")] == lines[2][at("c_payment_cnt")] \
            == b"\\N"
        assert lines[2][at("c_data")] == b"tab\\there \\\\ and\\nnewline"
        assert lines[2][at("c_first")] == b""
        assert lines[0][at("c_middle")] == b"ab"  # char(2), full
        assert lines[0][at("c_phone")] == b"ab" + b" " * 14  # char(16)
        if route == "host":
            # the one column with a text that needs an escape, value by
            # value for all its rows (one of the three is NULL); none of
            # the four NUMERIC cells a row
            assert after[1] - boxed == times * 2
    elif name == "warehouse":
        assert [line[at("w_tax")] for line in lines[:3]] \
            == [b"0.0000", b"0.9999", b"0.2000"]
        assert lines[2][at("w_ytd")] == lines[2][at("w_name")] == b"\\N"
        if route == "host":
            assert after[1] == boxed  # no NUMERIC cell, no text cell
    else:
        assert [line[-2] for line in lines[:3]] == [b"UPSERT", b"UPSERT",
                                                    b"DELETE"]
        assert lines[0][at("ol_delivery_d")] == b"\\N"
        assert lines[1][at("ol_delivery_d")] == b"2024-01-01 00:00:00.007013"
        assert lines[0][at("ol_amount")] == b"9999.99"
        assert lines[0][at("ol_dist_info")] == b"ab" + b" " * 22
        keys = oplog.key_indices(table)
        assert [f for i, f in enumerate(lines[2][:len(names)])
                if i not in keys] == [b"\\N"] * (len(names) - len(keys))
        assert [lines[2][i] for i in keys] == [b"7"] * 4
        if route == "host":
            assert after[1] == boxed


def test_a_pgbench_accounts_batch_boxes_nothing():
    """The accepted ClickHouse cell's shape: three int4 and a char(84)."""
    table = _load(os.path.join(
        BENCH, "configs", "pgbench-s10-clickhouse.json"))["table"]
    rows = [[str(i + 1).encode(), str(i % 10 + 1).encode(),
             str((i * 7919) % 2_000_000_000 - 10**9).encode(), b" " * 84]
            for i in range(500)]
    batch, change_types = _decoded(table, [
        pgoutput.encode_insert(int(table["id"]), r) for r in rows])
    rendered, boxed = _cells()
    fast, plain = _both_renders(table, batch, change_types)
    assert fast == plain
    after = _cells()
    assert after[0] - rendered == 500 * 4
    assert after[1] == boxed
    # and the per-layer metric reads 0 there, not nothing
    sys.path.insert(0, os.path.join(BENCH, "readers"))
    import counter_ratio
    params = _load(os.path.join(
        BENCH, "metrics", "ch_boxed_cell_share_pct.json"))["params"]
    window = {ETL_CLICKHOUSE_RENDERED_CELLS_TOTAL: after[0] - rendered,
              ETL_CLICKHOUSE_BOXED_CELLS_TOTAL: after[1] - boxed}
    assert counter_ratio.read({"window": window}, params) == 0.0
    # at the parent neither counter exists: both read 0 and nothing prints
    assert counter_ratio.read({"window": dict.fromkeys(window, 0.0)},
                              params) is None


# ---------------------------------------------------------------------------
# what ClickHouse receives: DDL, change label, sequence key
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(NUMERIC_COLUMNS))
def test_ddl_of_each_table(deployment, name):
    _, table = deployment["tables"][name]
    sql = create_table_sql("default", "public_" + name.replace("_", "__"),
                           _schema(table),
                           ClickHouseEngine.REPLACING_MERGE_TREE)
    keys = oplog.key_indices(table)
    ch_type = {"int4": "Int32", "numeric": "String", "bpchar": "String",
               "varchar": "String", "timestamp": "DateTime64(6)"}
    for i, c in enumerate(table["columns"]):
        kind = ch_type[c["type"]]
        # a key-image DELETE carries \N in every column outside the key
        want = kind if i in keys else f"Nullable({kind})"
        assert f"`{c['name']}` {want}" in sql, c["name"]
    # the key's columns in the table's column order, not in the key's own
    # (`ol_w_id` is the first part of order_line's key and its third column)
    order = ", ".join(f"`{table['columns'][i]['name']}`" for i in sorted(keys))
    assert sql.endswith(f"ORDER BY ({order})")
    assert "ReplacingMergeTree(`_CHANGE_SEQUENCE_NUMBER`)" in sql
    assert "`_CHANGE_TYPE` String" in sql


async def test_update_without_old_tuple_and_key_image_delete_on_the_wire(
        deployment):
    """Through the assembler and `write_event_batches`, as the pipeline
    goes: one HTTP INSERT per table, the rows in WAL order, UPSERT for an
    insert and for an update with no old tuple, DELETE with the key image
    alone, the sequence key `{commit_lsn}/{tx_ordinal}/{0}` in hex."""
    from etl_tpu.config.pipeline import BatchEngine
    from etl_tpu.models.lsn import Lsn
    from etl_tpu.runtime.assembler import EventAssembler
    from etl_tpu.testing.fake_http import RecordingHttpServer

    ol_table, ol_rows, _ = _edge_rows(deployment, "order_line")
    w_table, w_rows, _ = _edge_rows(deployment, "warehouse")
    ol, wh = _schema(ol_table), _schema(w_table)
    server = RecordingHttpServer()
    await server.start()
    a = EventAssembler(BatchEngine.TPU)
    try:
        # one transaction that interleaves two tables, then a second one
        a.push_raw_row(ol_rows[0], ol, Lsn(0x100), Lsn(0x1A0), 0)
        a.push_raw_row(w_rows[0], wh, Lsn(0x110), Lsn(0x1A0), 1)
        a.push_raw_row(ol_rows[1], ol, Lsn(0x120), Lsn(0x1A0), 2)
        a.push_raw_row(ol_rows[2], ol, Lsn(0x200), Lsn(0x2B0), 0)
        events = a.flush()
        d = ClickHouseDestination(
            ClickHouseConfig(url=server.url(), database="default"),
            DestinationRetryPolicy(max_attempts=2, initial_delay_s=0.01,
                                   max_delay_s=0.02))
        await d.startup()
        ack = await d.write_event_batches(events)
        assert ack.is_durable
        await d.shutdown()
    finally:
        a.close()
        await server.stop()
    inserts = [(r.query["query"], r.body) for r in server.requests
               if r.query.get("query", "").startswith("INSERT INTO")]
    assert [q.split(" (")[0] for q, _ in inserts] == [
        "INSERT INTO `default`.`public_order__line`",
        "INSERT INTO `default`.`public_warehouse`"]
    assert inserts[0][0].endswith(
        "`_CHANGE_TYPE`, `_CHANGE_SEQUENCE_NUMBER`) FORMAT TabSeparated")
    lines = [line.split(b"\t") for line in inserts[0][1].split(b"\n")[:-1]]
    assert [line[-2:] for line in lines] == [
        [b"UPSERT", b"%016x/%016x/%016x" % (0x1A0, 0, 0)],
        [b"UPSERT", b"%016x/%016x/%016x" % (0x1A0, 2, 0)],
        [b"DELETE", b"%016x/%016x/%016x" % (0x2B0, 0, 0)]]
    assert lines[2][:10].count(b"\\N") == 6
    (warehouse,) = [line.split(b"\t")
                    for line in inserts[1][1].split(b"\n")[:-1]]
    assert warehouse[-2:] == [b"UPSERT",
                              b"%016x/%016x/%016x" % (0x1A0, 1, 0)]


WRITTEN = [("warehouse", U), ("district", U), ("customer", U),
           ("new_order", I), ("orders", I), ("order_line", I), ("stock", U),
           ("item", I)]


@pytest.mark.parametrize("name,op", WRITTEN, ids=[n for n, _ in WRITTEN])
async def test_each_table_through_both_write_paths(deployment, name, op):
    """What ClickHouse is sent for a table's rows by the CDC write and by
    the start-up copy's: the per-value render's bytes, with every NUMERIC
    cell taken from the decoder's text as it stands (nothing boxed)."""
    from etl_tpu.models.event import DecodedBatchEvent
    from etl_tpu.models.lsn import Lsn
    from etl_tpu.testing.fake_http import RecordingHttpServer

    table, batch, change_types = _seeded(deployment, name, op, n=200)
    schema, n = _schema(table), batch.num_rows
    lsns = np.arange(n, dtype=np.uint64) // 10 + 0x7000
    ords = np.arange(n, dtype=np.uint64) % 10
    zeros = np.zeros(n, dtype=np.uint64)
    event = DecodedBatchEvent(Lsn(0x7000), Lsn(int(lsns[-1])), schema,
                              change_types=change_types, commit_lsns=lsns,
                              tx_ordinals=ords, batch=batch)
    server = RecordingHttpServer()
    await server.start()
    d = ClickHouseDestination(
        ClickHouseConfig(url=server.url(), database="default"),
        DestinationRetryPolicy(max_attempts=2, initial_delay_s=0.01,
                               max_delay_s=0.02))
    rendered, boxed = _cells()
    try:
        await d.startup()
        assert (await d.write_event_batches([event])).is_durable
        assert (await d.write_table_batch(schema, batch)).is_durable
        await d.shutdown()
    finally:
        await server.stop()
    after = _cells()
    cdc, copy = [r.body for r in server.requests
                 if r.query.get("query", "").startswith("INSERT INTO")]
    labels = [c.decode() for c in change_type_batch(change_types).tolist()]
    assert cdc == render_batch_tsv_columnar(
        schema, batch, labels, [s.decode() for s in sequence_number_batch(
            lsns, ords, zeros)])
    assert copy == render_batch_tsv_columnar(
        schema, batch, "UPSERT", [s.decode() for s in sequence_number_batch(
            zeros, zeros, np.arange(n, dtype=np.uint64))])
    assert after[0] - rendered == 2 * n * len(table["columns"])
    assert after[1] == boxed
    at = [j for j, c in enumerate(table["columns"])
          if c["type"] == "numeric"]
    assert len(at) == NUMERIC_COLUMNS[name]
    for body in (cdc, copy):
        for line in body.split(b"\n")[:-1]:
            fields = line.split(b"\t")
            assert all(fields[j].replace(b".", b"").replace(b"-", b"")
                       .isdigit() for j in at)


# ---------------------------------------------------------------------------
# the cell, rehearsed
# ---------------------------------------------------------------------------


def test_the_cell_rehearses_correct_with_its_metrics(tmp_path):
    # the cell's own mix with five times its rehearsal backlog, as
    # tests/test_tpcc_deployment.py rehearses the null cell (ROADMAP C14)
    traffic = _load(TRAFFIC_PATH)
    traffic["rehearsal"]["backlog_events_per_second"] = 100_000
    mix = tmp_path / "standard-mix-drain.json"
    mix.write_text(json.dumps(traffic))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(SEED), "--seconds", "2", "--trace", "1",
         "--rehearse", "--traffic-file", str(mix)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert set(line["checks"]) == {
        "missing_rows", "wrong_rows", "unknown_rows", "misattributed_rows",
        "state_mismatch_rows", "backlog_exhausted"}
    assert all(v == {"value": 0, "limit": 0}
               for v in line["checks"].values())
    m = {k: v["value"] for k, v in line["metrics"].items()}
    for name in NEW_METRICS + (
            "ch_render_s_per_mrow", "ch_render_native_row_share_pct",
            "ch_sink_service_pct", "ch_tsv_render_busy_pct",
            "tpcc_rows_per_flush_mean", "tpcc_runs_per_transaction_mean",
            "drain_dispatch_blocked_pct", "pipeline_ready_s"):
        assert "rehearsal." + name in m, sorted(m)
    assert "rehearsal.tpcc_table_switch_seal_share_pct" not in m
    # no NUMERIC cell spelt as `numeric_out` spells goes value by value,
    # and every route — the per-row oracle's small runs too — hands its
    # NUMERIC and text columns over as Arrow text: what could still box is
    # a text that needs a TSV escape, which the generator never draws
    assert 0 <= m["rehearsal.ch_boxed_cell_share_pct"] < 0.05
    # a flush is one INSERT per table it holds rows of: more than the one
    # of a single-table stream, at most the eight published tables
    # (ROADMAP C16: a flush that holds two sealed groups sends more)
    assert 1 < m["rehearsal.ch_requests_per_flush_mean"] <= 8
    assert m["rehearsal.ch_request_ms_mean"] > 0
    assert m["rehearsal.ch_render_native_row_share_pct"] == 100
    # per row of every route: the accepted `ch_render_s_per_mrow` leaves the
    # oracle's rows out of its denominator (in a rehearsal, whose flushes
    # are large, the oracle takes next to none and the two read alike)
    assert m["rehearsal.tpcc_ch_render_s_per_mrow"] > 0
    assert m["rehearsal.ch_render_s_per_mrow"] > 0
