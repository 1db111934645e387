"""The two pgbench configurations after the harness was opened to other
deployments: the source's bytes are the parent's byte for byte, the
generator makes the layout it made, and the reference counts what it
should."""

import hashlib
import json
import os
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

SEED = 2**31 + 11
# sha256 over what the source of commit ae1fc5d (PR 26, `pgbench.py`)
# prebuilds at --seed 2147483659 --seconds 2 and the rehearsal sizes: every
# transaction's send buffer, the summed payload bytes and the layout's four
# arrays for a CDC mix; the COPY stream and its row offsets for the copy
GOLDEN = {
    "backlog-drain":
        "6f5fc17120a4a4bbd384029f4900748996b1604824ac33711ee4bbbc98301989",
    "insert-paced":
        "9d29614e889618188ef861e423176fc055135dff3f936d833620acf988c0b799",
    "copy-1m":
        "cc94afec69b7a289fd4cf3a70b3e0bf45c9d35459b556c6e122d2fe00f176dd3",
}


def plan_of(config_name: str, traffic_name: str, seed: int, seconds: float):
    import source

    path = os.path.join(BENCH, "configs", config_name + ".json")
    with open(path) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic_name + ".json")) as f:
        traffic = json.load(f)
    config.update(config.get("rehearsal", {}))
    traffic.update(traffic.get("rehearsal", {}))
    plan = source.Plan(config, traffic, seed, seconds,
                       oplog.load_generator(config, path))
    plan.render()
    return plan


@pytest.mark.parametrize("traffic", sorted(GOLDEN))
@pytest.mark.parametrize("config", ["pgbench-s10-null",
                                    "pgbench-s10-clickhouse"])
def test_the_source_s_bytes_are_the_parent_s(config, traffic):
    plan = plan_of(config, traffic, 2147483659, 2.0)
    h = hashlib.sha256()
    if plan.kind == "copy":
        blob, offsets = plan.copies["public.pgbench_accounts"]
        h.update(bytes(blob))
        h.update(np.asarray(offsets, dtype=np.int64).tobytes())
    else:
        for buf in plan.bufs:
            h.update(buf)
        h.update(str(plan.payload_bytes).encode())
        lay = plan.layout
        for a in (lay.rows, lay.begin_lsn, lay.commit_lsn, lay.end_lsn):
            h.update(np.asarray(a, dtype=np.int64).tobytes())
    assert h.hexdigest() == GOLDEN[traffic]


def test_the_first_transaction_carries_the_relation():
    from etl_tpu.postgres.codec import pgoutput

    plan = plan_of("pgbench-s10-null", "insert-paced", SEED, 1.0)
    buf, kinds = plan.bufs[0], []
    at = 0
    while at < len(buf):
        n = int.from_bytes(buf[at + 1:at + 5], "big")
        frame = pgoutput.decode_replication_frame(buf[at + 5:at + 1 + n])
        kinds.append(frame.payload[:1])
        at += 1 + n
    assert kinds[:3] == [b"B", b"R", b"I"] and kinds[-1] == b"C"
    assert b"R" not in {plan.bufs[1][30:31]}
    msg = pgoutput.decode_logical_message(
        wire.relation_payload(plan.tables[0]))
    assert [(c.name, c.flags, c.type_oid, c.modifier) for c in msg.columns] \
        == [("aid", 1, 23, -1), ("bid", 0, 23, -1), ("abalance", 0, 23, -1),
            ("filler", 0, 1042, 88)]


def test_layout_places_bulks_and_sizes_the_backlog():
    gen = oplog.load_generator({}, os.path.join(BENCH, "configs", "x.json"))
    config = {"rows": 1000, "table": {"id": 16384}}
    paced = gen.stream(config, {
        "kind": "paced", "transaction_rows": 500,
        "transactions_per_second": 60, "warmup_seconds": 3,
        "bulk_every_transactions": 600, "bulk_rows": 16384}, 1, 30)
    rows = paced.layout.rows
    assert len(rows) == 1980
    assert np.flatnonzero(rows == 16384).tolist() == [599, 1199, 1799]
    assert set(rows.tolist()) == {500, 16384}
    assert int(paced.events[0].new[0].values[0]) == 1001
    assert paced.layout.durable_count(int(paced.layout.end_lsn[1])) == 2
    assert paced.layout.durable_count(int(paced.layout.end_lsn[1]) - 1) == 1
    drain = gen.stream(config, {
        "kind": "backlog", "transaction_rows": 500,
        "backlog_events_per_second": 150000, "warmup_seconds": 3,
        "bulk_every_transactions": 34, "bulk_rows": 16384}, 1, 30)
    assert abs(int(drain.layout.rows.sum()) - 5_100_000) < 33 * 500 + 16384
    assert (drain.layout.rows[33::34] == 16384).all()
    plain = gen.stream(config, {
        "kind": "backlog", "transaction_rows": 500,
        "backlog_events_per_second": 1000, "warmup_seconds": 1}, 1, 3)
    assert plain.layout.rows.tolist() == [500] * 10
    assert gen.stream(config, {"kind": "copy"}, 1, 3) is None
    assert oplog.n_rows(gen.snapshot(config, {"kind": "copy"}, 1)[16384]) \
        == 1000
    assert oplog.n_rows(gen.snapshot(config, {"kind": "paced"}, 1)[16384]) \
        == 0


TABLE = {"name": "public.pgbench_accounts", "id": 16384, "columns": [
    {"name": "aid", "type": "int4", "key": True},
    {"name": "bid", "type": "int4"}, {"name": "abalance", "type": "int4"},
    {"name": "filler", "type": "bpchar", "modifier": 88, "text_bytes": 84}]}


def delivered(stream, upto: int) -> dict:
    """What a sound sink holds of the first `upto` inserts."""
    import pyarrow as pa

    commit, ordinal = stream.layout.row_coordinates(
        0, len(stream.layout.rows))
    cols = [(c.values[:upto].copy(), np.zeros(upto, dtype=bool), None)
            for c in stream.events[0].new[:3]]
    cols.append((pa.array([" " * 84] * upto), np.zeros(upto, dtype=bool),
                 None))
    return {"cols": cols, "change": np.zeros(upto, dtype=np.uint8),
            "commit_lsn": commit[:upto].copy(),
            "tx_ordinal": ordinal[:upto].copy(), "old": None,
            "delete_is_key": None}


def test_reference_counts():
    gen = oplog.load_generator({}, os.path.join(BENCH, "configs", "x.json"))
    config = {"rows": 5000, "table": TABLE}
    stream = gen.stream(config, {
        "kind": "backlog", "transaction_rows": 250, "warmup_seconds": 0,
        "backlog_events_per_second": 250}, SEED, 3)
    assert stream.layout.rows.tolist() == [250] * 4
    kinds = wire.old_kinds([TABLE], stream)
    got = delivered(stream, 750)
    sound = reference.check_cdc([TABLE], {}, stream, kinds, 750, 500,
                                {16384: got})
    assert reference.judge(sound["numbers"])[0]
    assert sound["info"]["duplicate_rows"] == 0
    # a row lost, a value altered, a row of a transaction never sent, a
    # row booked to another row's place: one number each
    got["cols"][2][0][3] += 1
    got["commit_lsn"][7], got["tx_ordinal"][7] = \
        got["commit_lsn"][8], got["tx_ordinal"][8]
    keep = np.delete(np.arange(750), 10)
    lost = {k: (v[keep] if isinstance(v, np.ndarray) else v)
            for k, v in got.items()}
    lost["cols"] = [(reference._take(c[0], keep), c[1][keep], None)
                    for c in got["cols"]]
    all_rows = delivered(stream, 1000)
    for k in ("change", "commit_lsn", "tx_ordinal"):
        lost[k] = np.append(lost[k], all_rows[k][900])
    lost["cols"] = [(reference._take(c[0], np.append(np.arange(749), 0))
                     if not isinstance(c[0], np.ndarray)
                     else np.append(c[0], a[0][900]),
                     np.append(c[1], False), None)
                    for c, a in zip(lost["cols"], all_rows["cols"])]
    out = reference.check_cdc([TABLE], {}, stream, kinds, 750, 500,
                              {16384: lost})
    assert out["numbers"] == {"missing_rows": 2, "wrong_rows": 1,
                              "unknown_rows": 1, "misattributed_rows": 1,
                              "state_mismatch_rows": 2}
    correct, table = reference.judge(out["numbers"])
    assert not correct and all(limit == 0 for _, _, limit in table)


def test_a_copy_is_held_to_the_snapshot():
    import pyarrow as pa

    gen = oplog.load_generator({}, os.path.join(BENCH, "configs", "x.json"))
    snap = gen.snapshot({"rows": 1000, "table": TABLE}, {"kind": "copy"},
                        SEED)[16384]
    index = reference.SnapshotIndex(TABLE, snap)
    assert index.dense and index.n == 1000

    def copied(order):
        cols = [(c.values[order].copy(), np.zeros(len(order), bool), None)
                for c in snap[:3]]
        cols.append((pa.array([" " * 84] * len(order)),
                     np.zeros(len(order), bool), None))
        return {"cols": cols}

    whole = np.random.default_rng(1).permutation(1000)
    sound = reference.check_copy(index, copied(whole))
    assert reference.judge(sound["numbers"])[0]
    got = copied(whole[:-3])
    got["cols"][1][0][5] += 1          # a value altered
    got["cols"][0][0][9] = 5000        # a key the table never had
    got["cols"][3] = (pa.array([" " * 84] * 996 + [" " * 83]),
                      got["cols"][3][1], None)   # a filler cut short
    out = reference.check_copy(index, got)["numbers"]
    assert out == {"missing_rows": 4, "wrong_rows": 2, "unknown_rows": 1,
                   "misattributed_rows": 0, "state_mismatch_rows": 7}
    # a key of two parts, not in order: found by search, not by subtraction
    table = {"name": "public.t", "id": 9, "columns": [
        {"name": "w", "type": "int2", "key": 1},
        {"name": "o", "type": "int8", "key": 2},
        {"name": "v", "type": "text"}]}
    w = np.array([3, 1, 2, 1], dtype=np.int16)
    o = np.array([7, 1 << 40, 7, 5], dtype=np.int64)
    index = reference.SnapshotIndex(table, [
        oplog.Col(w), oplog.Col(o), oplog.Col(np.array([b"a", b"b", b"c",
                                                        b"d"]))])
    assert not index.dense
    assert index.find([np.array([1, 2, 9]), np.array([5, 7, 7])]).tolist() \
        == [3, 2, -1]
