"""The TPC-C deployment (`benchmark/deployments/tpcc.py`,
`benchmark/configs/tpcc-w4-null.json`, `benchmark/traffic/
standard-mix-drain.json`) at rehearsal size on the CPU: the generator's log
is a TPC-C history (mix, NURand, statement order, NULLs, determinism, the
specification's consistency conditions after a replay), the three counters
the cell's per-layer metrics read count what they say, and one rehearsal of
the cell through `benchmark/run.py` ends `correct: true`."""

import hashlib
import json
import os
import subprocess
import sys
from collections import Counter

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import oplog  # noqa: E402

CONFIG_PATH = os.path.join(BENCH, "configs", "tpcc-w4-null.json")
TRAFFIC_PATH = os.path.join(BENCH, "traffic", "standard-mix-drain.json")
CELL = "tpcc-w4-null.standard-mix-drain"
SEEDS = (1, 2147483659, 4294967311)
SECONDS = 8.0
I, U, D = oplog.INSERT, oplog.UPDATE, oplog.DELETE
NEW_ORDER = [("district", U), ("orders", I), ("new_order", I), ("stock", U),
             ("order_line", I)]
PAYMENT = [("warehouse", U), ("district", U), ("customer", U)]
DELIVERY = [("new_order", D), ("orders", U), ("order_line", U),
            ("customer", U)]
BULK = [("order_line", I)]


def _files(rehearse: bool = True):
    with open(CONFIG_PATH) as f:
        config = json.load(f)
    with open(TRAFFIC_PATH) as f:
        traffic = json.load(f)
    if rehearse:
        config.update(config["rehearsal"])
        traffic.update(traffic["rehearsal"])
    return config, traffic


@pytest.fixture(scope="module")
def gen():
    return oplog.load_generator(_files()[0], CONFIG_PATH)


class History:
    """One seed's snapshot and log at rehearsal size, with each
    transaction's events as (table name, op, {column: value or None})."""

    def __init__(self, gen, seed: int):
        self.config, self.traffic = _files()
        self.tables = oplog.tables_of(self.config)
        self.names = [t["name"].split(".")[1] for t in self.tables]
        self.snapshot = gen.snapshot(self.config, self.traffic, seed)
        self.stream = gen.stream(self.config, self.traffic, seed, SECONDS)
        local = self.stream.local_index()
        self.events = []
        images = {}
        for t, ev in self.stream.events.items():
            images[t] = self._rows(self.tables[t], ev.new)
        for e in range(len(self.stream.table)):
            t = int(self.stream.table[e])
            self.events.append((self.names[t], int(self.stream.op[e]),
                                images[t][int(local[e])]))
        starts = self.stream.layout.starts
        self.transactions = [self.events[starts[k]:starts[k + 1]]
                             for k in range(len(self.stream.layout.rows))]

    @staticmethod
    def _rows(table: dict, cols: list) -> list:
        n = oplog.n_rows(cols)
        listed = []
        for column, col in zip(table["columns"], cols):
            values = [col.values] * n if isinstance(col.values, bytes) \
                else col.values.tolist()
            if col.null is not None:
                values = [None if null else v
                          for v, null in zip(values, col.null.tolist())]
            listed.append(values)
        names = [c["name"] for c in table["columns"]]
        return [dict(zip(names, row)) for row in zip(*listed)]

    def loaded(self, name: str) -> list:
        table = self.tables[self.names.index(name)]
        return self._rows(table, self.snapshot[int(table["id"])])

    @staticmethod
    def runs(transaction: list) -> list:
        """The transaction's same-table runs: [(table, op, rows), ...]."""
        out = []
        for table, op, _ in transaction:
            if out and out[-1][:2] == (table, op):
                out[-1] = (table, op, out[-1][2] + 1)
            else:
                out.append((table, op, 1))
        return out

    def kind_of(self, transaction: list) -> str:
        first = transaction[0][:2]
        bulk = int(self.traffic["generator"]["bulk_rows"])
        return {("district", U): "new_order", ("warehouse", U): "payment",
                ("new_order", D): "delivery"}.get(
            first, "bulk" if len(transaction) == bulk else "?")


@pytest.fixture(scope="module")
def histories(gen):
    return {seed: History(gen, seed) for seed in SEEDS}


# ---------------------------------------------------------------------------
# the files
# ---------------------------------------------------------------------------


def test_the_configuration_holds_the_published_tables():
    config, _ = _files(rehearse=False)
    shapes = {t["name"]: (len(t["columns"]), len(oplog.key_indices(t)))
              for t in config["tables"]}
    assert shapes == {
        "public.warehouse": (9, 1), "public.district": (11, 2),
        "public.customer": (21, 3), "public.new_order": (3, 3),
        "public.orders": (8, 3), "public.order_line": (10, 4),
        "public.stock": (17, 2), "public.item": (5, 1)}
    assert all(t["replica_identity"] == "d" for t in config["tables"])
    assert config["warehouses"] == 4 and config["reduced"] == ["warehouses"]
    assert len(config["source"]) <= 200
    for key in ("types", "statement_order", "bulk_inserts", "nurand_c",
                "affinity", "batch_config"):
        assert key in config["assumed"], key
    assert config["pipeline"]["batch"] == {}
    by_name = {c["name"]: c for t in config["tables"] for c in t["columns"]}
    assert (by_name["c_data"]["type"], by_name["c_data"]["text_bytes"]) \
        == ("varchar", 500)
    assert (by_name["w_tax"]["precision"], by_name["w_tax"]["scale"]) == (4, 4)
    assert (by_name["ol_amount"]["precision"],
            by_name["ol_amount"]["scale"]) == (6, 2)
    assert by_name["o_carrier_id"]["nullable"] \
        and by_name["ol_delivery_d"]["nullable"]


def test_the_traffic_file_carries_the_issue_s_numbers():
    _, traffic = _files(rehearse=False)
    g = traffic["generator"]
    assert traffic["kind"] == "backlog"
    assert g["mix"] == {"new_order": 45, "payment": 43, "order_status": 4,
                        "delivery": 4, "stock_level": 4}
    assert (g["nurand_customer_a"], g["nurand_item_a"]) == (1023, 8191)
    assert (g["bulk_every_transactions"], g["bulk_rows"]) == (1000, 16384)
    assert (g["new_order_rollback"], g["remote_line_share"],
            g["remote_payment_share"]) == (0.01, 0.01, 0.15)
    assert (traffic["backlog_events_per_second"], traffic["warmup_seconds"],
            traffic["close_cap_seconds"], traffic["trace_seconds"]) \
        == (100000, 3, 15, 10)


def test_the_cell_is_in_the_benchmark_s_lists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w for w in bench["workloads"] if w["name"] == CELL][0]["chips"] == 1
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    drains = {m["name"] for m in bench["per_layer"]
              if m["name"].startswith("drain_")}
    assert drains <= listed
    assert {"compiles_in_window", "pipeline_ready_s"} <= listed
    mine = sorted(n for n in listed if n.startswith("tpcc_"))
    # the cell's seven, two CPU twins (seal, the oracle's unpack) and the
    # cells the fixup parses per oracle row
    assert len(mine) == 10
    for name in mine:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            assert json.load(f)["reader"] == "counter_ratio"


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("a,x,y", [(1023, 1, 3000), (8191, 1, 100000),
                                   (255, 0, 999), (15, 1, 30)])
def test_nurand_stays_in_range_and_is_skewed(gen, a, x, y):
    rng = np.random.default_rng(5)
    n = 400_000
    v = gen.nurand(rng, a, x, y, 7, n)
    assert v.min() >= x and v.max() <= y
    counts = np.sort(np.bincount(v - x, minlength=y - x + 1))[::-1]
    # non-uniform: the busiest tenth of the keys takes far more than a
    # tenth of the draws
    assert counts[:max(1, len(counts) // 10)].sum() > 0.2 * n


@pytest.mark.parametrize("seed", SEEDS)
def test_mix_shares(histories, seed):
    h = histories[seed]
    kinds = Counter(h.kind_of(tx) for tx in h.transactions)
    assert "?" not in kinds
    drawn = kinds["new_order"] / (0.45 * 0.99)
    assert kinds["payment"] / drawn == pytest.approx(0.43, rel=0.08)
    assert kinds["delivery"] / drawn == pytest.approx(0.04, rel=0.25)
    every = h.traffic["generator"]["bulk_every_transactions"]
    assert kinds["bulk"] == int(drawn) // every \
        or abs(kinds["bulk"] - drawn / every) <= 1


@pytest.mark.parametrize("seed", SEEDS)
def test_statement_order_and_runs_per_transaction(histories, seed):
    h = histories[seed]
    want = {"new_order": NEW_ORDER, "payment": PAYMENT, "delivery": DELIVERY,
            "bulk": BULK}
    n_runs = 0
    for tx in h.transactions:
        runs = h.runs(tx)
        kind = h.kind_of(tx)
        assert [r[:2] for r in runs] == want[kind], (kind, runs)
        n_runs += len(runs)
        if kind == "new_order":
            lines = runs[3][2]
            assert 5 <= lines <= 15 and runs[4][2] == lines
            assert [r[2] for r in runs[:3]] == [1, 1, 1]
            order = tx[1][2]
            assert order["o_ol_cnt"] == lines
            assert [e[2]["ol_number"] for e in tx[3 + lines:]] \
                == list(range(1, lines + 1))
        elif kind == "delivery":
            taken = runs[0][2]
            assert 1 <= taken <= 10
            assert runs[1][2] == taken and runs[3][2] == taken
            assert runs[2][2] == sum(e[2]["o_ol_cnt"]
                                     for e in tx[taken:2 * taken])
    # the cell's metric: about four same-table runs a transaction
    assert n_runs / len(h.transactions) == pytest.approx(4.0, abs=0.15)


@pytest.mark.parametrize("seed", SEEDS)
def test_nulls_where_the_specification_has_them(histories, seed):
    h = histories[seed]
    seen = Counter()
    for table, op, row in h.events:
        if table == "orders":
            assert (row["o_carrier_id"] is None) == (op == I)
            seen["orders", op] += 1
        elif table == "order_line" and row["ol_w_id"] <= h.config["warehouses"]:
            assert (row["ol_delivery_d"] is None) == (op == I)
            seen["order_line", op] += 1
    assert min(seen["orders", I], seen["orders", U],
               seen["order_line", I], seen["order_line", U]) > 0
    undelivered = h.config["orders_per_district"] \
        - h.config["undelivered_orders_per_district"]
    for row in h.loaded("orders"):
        assert (row["o_carrier_id"] is None) == (row["o_id"] > undelivered)
    for row in h.loaded("order_line"):
        assert (row["ol_delivery_d"] is None) == (row["ol_o_id"] > undelivered)
    assert len(h.loaded("new_order")) == h.config["warehouses"] * 10 \
        * h.config["undelivered_orders_per_district"]


@pytest.mark.parametrize("seed", SEEDS)
def test_keys_are_in_range_and_updates_keep_them(histories, seed):
    h = histories[seed]
    c, w = h.config, h.config["warehouses"]
    for table, op, row in h.events:
        if table == "customer":
            assert 1 <= row["c_id"] <= c["customers_per_district"]
            assert 1 <= row["c_d_id"] <= 10 and 1 <= row["c_w_id"] <= w
        elif table == "stock":
            assert 1 <= row["s_i_id"] <= c["items"] and 1 <= row["s_w_id"] <= w
            assert 10 <= row["s_quantity"] <= 100
    for t, ev in h.stream.events.items():
        # replica identity default and no key change: no old image, but for
        # the delete's key
        assert (ev.old is not None) == (h.names[t] == "new_order")


def _digest(stream) -> str:
    h = hashlib.sha256()
    h.update(stream.table.tobytes() + stream.op.tobytes()
             + stream.layout.rows.tobytes())
    for t in sorted(stream.events):
        for col in stream.events[t].new:
            h.update(col.values if isinstance(col.values, bytes)
                     else np.ascontiguousarray(col.values).tobytes())
            if col.null is not None:
                h.update(col.null.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_log_and_equal_work(gen, histories, seed):
    h = histories[seed]
    again = gen.stream(h.config, h.traffic, seed, SECONDS)
    assert _digest(again) == _digest(h.stream)
    other = histories[SEEDS[(SEEDS.index(seed) + 1) % len(SEEDS)]]
    assert _digest(other.stream) != _digest(h.stream)
    # every seed draws the same number of transactions; what reaches the
    # WAL of them differs by the draw alone
    assert gen.drawn_transactions(h.traffic, SECONDS) == \
        gen.drawn_transactions(other.traffic, SECONDS)
    assert len(h.transactions) == pytest.approx(len(other.transactions),
                                                rel=0.05)
    assert len(h.events) == pytest.approx(len(other.events), rel=0.08)
    # the backlog holds what the traffic file asks for
    want = h.traffic["backlog_events_per_second"] * (
        h.traffic["warmup_seconds"] + SECONDS + 1)
    assert len(h.events) == pytest.approx(want, rel=0.1)


def _replayed(h: History) -> dict:
    """The tables after the log is replayed over the snapshot: {table:
    {key: row}}."""
    state = {}
    for name, table in zip(h.names, h.tables):
        keys = [table["columns"][i]["name"] for i in oplog.key_indices(table)]
        state[name] = ({tuple(r[k] for k in keys): r for r in h.loaded(name)},
                       keys)
    for name, op, row in h.events:
        rows, keys = state[name]
        key = tuple(row[k] for k in keys)
        if op == D:
            del rows[key]
        elif op == I:
            assert key not in rows, (name, key)
            rows[key] = row
        else:
            assert key in rows, (name, key)
            rows[key] = row
    return {name: rows for name, (rows, _) in state.items()}


CONDITIONS = ("w_ytd_is_sum_of_d_ytd", "next_o_id_is_max_o_id",
              "new_order_ids_contiguous", "ol_cnt_is_count_of_lines")


@pytest.mark.parametrize("condition", CONDITIONS)
@pytest.mark.parametrize("seed", SEEDS)
def test_consistency_conditions_after_replay(histories, seed, condition):
    """TPC-C 3.3.2.1 to 3.3.2.4, over the published warehouses."""
    h = histories[seed]
    if not hasattr(h, "state"):
        h.state = _replayed(h)
    s, w_n = h.state, h.config["warehouses"]
    districts = [(w, d) for w in range(1, w_n + 1) for d in range(1, 11)]
    if condition == "w_ytd_is_sum_of_d_ytd":
        for w in range(1, w_n + 1):
            assert s["warehouse"][(w,)]["w_ytd"] == sum(
                s["district"][(w, d)]["d_ytd"] for d in range(1, 11))
        assert any(r["w_ytd"] != 30_000_000 for r in s["warehouse"].values())
    elif condition == "next_o_id_is_max_o_id":
        for w, d in districts:
            top = s["district"][(w, d)]["d_next_o_id"] - 1
            assert top == max(k[2] for k in s["orders"] if k[:2] == (w, d))
            waiting = [k[2] for k in s["new_order"] if k[:2] == (w, d)]
            assert not waiting or top == max(waiting)
    elif condition == "new_order_ids_contiguous":
        for w, d in districts:
            waiting = [k[2] for k in s["new_order"] if k[:2] == (w, d)]
            assert not waiting \
                or max(waiting) - min(waiting) + 1 == len(waiting)
    else:
        lines = Counter(k[:2] for k in s["order_line"] if k[0] <= w_n)
        for w, d in districts:
            assert lines[(w, d)] == sum(
                r["o_ol_cnt"] for k, r in s["orders"].items()
                if k[:2] == (w, d))


@pytest.mark.parametrize("seed", SEEDS)
def test_balances_and_carriers_after_replay(histories, seed):
    """What 3.3.2.5 to 3.3.2.7 hold together: an order has a carrier exactly
    where it no longer waits in new_order and its lines are stamped; a
    customer's balance is what was delivered less what was paid."""
    h = histories[seed]
    if not hasattr(h, "state"):
        h.state = _replayed(h)
    s = h.state
    for key, order in s["orders"].items():
        assert (order["o_carrier_id"] is None) == (key in s["new_order"])
    for key, line in s["order_line"].items():
        if key[0] <= h.config["warehouses"]:
            assert (line["ol_delivery_d"] is None) \
                == (s["orders"][key[:3]]["o_carrier_id"] is None)
    for c in s["customer"].values():
        assert c["c_payment_cnt"] >= 1 and c["c_delivery_cnt"] >= 0
        assert len(c["c_data"]) <= 500
    assert any(c["c_data"].split(b"|")[0].count(b" ") == 5
               for c in s["customer"].values() if c["c_credit"] == b"BC")


# ---------------------------------------------------------------------------
# the program's counters
# ---------------------------------------------------------------------------


def _counters():
    from etl_tpu.telemetry.metrics import (
        ETL_ASSEMBLER_SEAL_SECONDS, ETL_ASSEMBLER_SEALED_ROWS_TOTAL,
        ETL_ASSEMBLER_TABLE_SWITCH_SEALS_TOTAL, ETL_DECODE_CELLS_TOTAL,
        ETL_DECODE_DEVICE_KIND_CELLS_TOTAL,
        ETL_DECODE_DEVICE_PARSED_CELLS_TOTAL, registry)

    return {"switch": registry.sum_counter(
                ETL_ASSEMBLER_TABLE_SWITCH_SEALS_TOTAL),
            "seals": registry.sum_histogram(ETL_ASSEMBLER_SEAL_SECONDS)[0],
            "rows": registry.sum_counter(ETL_ASSEMBLER_SEALED_ROWS_TOTAL),
            "cells": registry.sum_counter(ETL_DECODE_CELLS_TOTAL),
            "device_kind": registry.sum_counter(
                ETL_DECODE_DEVICE_KIND_CELLS_TOTAL),
            "device_parsed": registry.sum_counter(
                ETL_DECODE_DEVICE_PARSED_CELLS_TOTAL)}


def _schemas():
    from etl_tpu.models import (ColumnSchema, Oid, ReplicatedTableSchema,
                                TableName, TableSchema)

    def schema(tid, name, oids):
        return ReplicatedTableSchema.with_all_columns(TableSchema(
            tid, TableName("public", name), tuple(
                ColumnSchema(f"c{i}", oid, nullable=i > 0,
                             primary_key_ordinal=1 if i == 0 else None)
                for i, oid in enumerate(oids))))

    # one of three columns, and two of two, are of a device-parsed kind
    return (schema(7, "mixed", (Oid.INT4, Oid.NUMERIC, Oid.VARCHAR)),
            schema(8, "ints", (Oid.INT4, Oid.TIMESTAMP)))


# (the pushes, as "a"/"b" rows, "|" a control event, "B" a two-row bulk
# push of b), then what the counters must have moved by. Since PR 34 a
# table has one open run however the pushes interleave ("ababab" seals
# two runs of three rows), a row of another table seals nothing (`switch`
# stays 0) and a control event is still a barrier
STREAMS = [
    ("aab", {"switch": 0, "seals": 2, "rows": 3, "cells": 8,
             "device_kind": 4}),
    ("ababab", {"switch": 0, "seals": 2, "rows": 6, "cells": 15,
                "device_kind": 9}),
    ("aa|aa", {"switch": 0, "seals": 2, "rows": 4, "cells": 12,
               "device_kind": 4}),
    ("aaaa", {"switch": 0, "seals": 1, "rows": 4, "cells": 12,
              "device_kind": 4}),
    ("aBBa", {"switch": 0, "seals": 2, "rows": 6, "cells": 14,
              "device_kind": 10}),
    ("a|b", {"switch": 0, "seals": 2, "rows": 2, "cells": 5,
             "device_kind": 3}),
]


@pytest.mark.parametrize("pushes,moved", STREAMS,
                         ids=[s for s, _ in STREAMS])
def test_assembler_counters_on_an_interleaved_stream(pushes, moved):
    from etl_tpu.config.pipeline import BatchEngine
    from etl_tpu.models.event import RelationEvent
    from etl_tpu.models.lsn import Lsn
    from etl_tpu.postgres.codec import pgoutput
    from etl_tpu.runtime.assembler import EventAssembler

    mixed, ints = _schemas()
    row = {"a": (mixed, pgoutput.encode_insert(7, [b"1", b"2.50", b"x"])),
           "b": (ints, pgoutput.encode_insert(
               8, [b"1", b"2024-01-01 00:00:00"]))}
    before = _counters()
    a = EventAssembler(BatchEngine.TPU)
    try:
        for i, what in enumerate(pushes):
            if what == "|":
                a.push_control(RelationEvent(Lsn(100 + i), Lsn(900), mixed))
            elif what == "B":
                a.push_raw_rows([row["b"][1]] * 2, ints,
                                [100 + i, 100 + i], 900, i)
            else:
                a.push_raw_row(row[what][1], row[what][0], Lsn(100 + i),
                               Lsn(900), i)
        events = a.flush()
        assert sum(len(e.tx_ordinals) for e in events
                   if hasattr(e, "tx_ordinals")) == moved["rows"]
    finally:
        a.close()
    after = _counters()
    assert {k: after[k] - before[k] for k in moved} == moved
    assert after["device_parsed"] == before["device_parsed"]


def test_a_size_seal_is_no_table_switch():
    from etl_tpu.config.pipeline import BatchEngine
    from etl_tpu.models.lsn import Lsn
    from etl_tpu.postgres.codec import pgoutput
    from etl_tpu.runtime.assembler import EventAssembler

    mixed, ints = _schemas()
    payload = pgoutput.encode_insert(8, [b"1", b"2024-01-01 00:00:00"])
    before = _counters()
    a = EventAssembler(BatchEngine.TPU)
    a.seal_rows = 4
    try:
        # the other table's one row rides in the group the first size
        # seal takes: sealed with it, and still no table switch
        a.push_raw_row(pgoutput.encode_insert(7, [b"1", b"2.50", b"x"]),
                       mixed, Lsn(99), Lsn(900), 0)
        for i in range(10):
            a.push_raw_row(payload, ints, Lsn(100 + i), Lsn(900), 1 + i)
        # sealed: mixed 1, ints 4, ints 4; one run open
        assert len(a) == 3 + 1
        a.push_raw_rows([payload] * 3, ints, [200, 201, 202], 900, 11)
        a.flush()
    finally:
        a.close()
    after = _counters()
    assert after["switch"] == before["switch"]
    assert after["seals"] - before["seals"] == 5
    assert after["rows"] - before["rows"] == 14
    assert after["cells"] - before["cells"] == 29


@pytest.mark.parametrize("rows,route", [(3, "oracle"), (8, "device")])
def test_device_parsed_cells_count_device_routed_batches(rows, route):
    from etl_tpu.ops.engine import DeviceDecoder
    from etl_tpu.ops.staging import synthetic_staged_batch

    mixed, _ = _schemas()
    decoder = DeviceDecoder(mixed, device_min_rows=8, host_min_rows=8,
                            mesh=None)
    assert (decoder.n_columns, decoder.n_device_kind_columns) == (3, 1)
    staged = synthetic_staged_batch(3, 256)
    staged.n_rows = rows
    before = _counters()
    assert decoder._route(staged)[0] == route
    moved = _counters()["device_parsed"] - before["device_parsed"]
    assert moved == (rows if route == "device" else 0)


# ---------------------------------------------------------------------------
# the cell, rehearsed
# ---------------------------------------------------------------------------


def test_the_cell_rehearses_correct_with_its_metrics(tmp_path):
    # the cell's own mix with five times its rehearsal backlog: since
    # PR 34 a CPU left to itself drains the file's 20,000 events a second
    # of rehearsal, and a run that sends them all is `backlog_exhausted`
    with open(TRAFFIC_PATH) as f:
        traffic = json.load(f)
    traffic["rehearsal"]["backlog_events_per_second"] *= 5
    mix = tmp_path / "standard-mix-drain.json"
    mix.write_text(json.dumps(traffic))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "2", "--trace", "1",
         "--rehearse", "--traffic-file", str(mix)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert all(v["value"] == 0 for v in line["checks"].values())
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # one run per table per flush, not one per table switch (PR 34: 4.0
    # runs a transaction and 98% table-switch seals before it)
    assert 0 < m["rehearsal.tpcc_runs_per_transaction_mean"] < 1.5
    assert m["rehearsal.tpcc_table_switch_seal_share_pct"] == 0
    assert m["rehearsal.tpcc_rows_per_run_mean"] > 9
    assert 0 < m["rehearsal.tpcc_device_kind_cell_share_pct"] < 100
    for name in ("tpcc_rows_per_run_mean", "tpcc_seal_s_per_mrow",
                 "tpcc_rows_per_flush_mean",
                 "tpcc_device_parsed_cell_share_pct",
                 "drain_rows_per_seal_p50", "drain_dispatch_blocked_pct"):
        assert "rehearsal." + name in m, sorted(m)
    assert m["rehearsal.drain_rows_per_seal_p50"] > 1
    # the per-row oracle parses the dense cells of its rows alone: no
    # TPC-C table has more than eight int4 / timestamp columns (where the
    # rehearsal's flushes sent no run to the oracle the metric is absent)
    fixup = m.get("rehearsal.tpcc_fixup_cells_per_oracle_row_mean")
    assert fixup is None or 0 < fixup <= 8
