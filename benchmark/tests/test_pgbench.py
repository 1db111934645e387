"""The bulk renderers agree with the program's own codecs, and the
reference counts what it should."""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import pgbench  # noqa: E402
import reference  # noqa: E402

SEED = 2**31 + 11


def test_frames_decode_to_the_generator_s_rows():
    from etl_tpu.postgres.codec import pgoutput

    rows = [500, 3, 1234]
    layout = pgbench.TxLayout.build(rows, 1_000_001)
    cols = pgbench.accounts_columns(SEED, sum(rows), 1_000_001)
    bufs, payload_bytes = pgbench.render_transactions(
        16384, layout, cols, 0, 3, 1_700_000_000_000_000, True)
    row = total = 0
    for k, buf in enumerate(bufs):
        at = seen = 0
        while at < len(buf):
            assert buf[at:at + 1] == b"d"
            n = int.from_bytes(buf[at + 1:at + 5], "big")
            frame = pgoutput.decode_replication_frame(buf[at + 5:at + 1 + n])
            at += 1 + n
            msg = pgoutput.decode_logical_message(frame.payload)
            if isinstance(msg, pgoutput.BeginMessage):
                assert int(frame.start_lsn) == layout.begin_lsn[k]
                assert int(msg.final_lsn) == layout.commit_lsn[k]
            elif isinstance(msg, pgoutput.CommitMessage):
                assert int(msg.end_lsn) == layout.end_lsn[k]
            elif isinstance(msg, pgoutput.InsertMessage):
                want = [b"%d" % c[row] for c in cols] + [pgbench.FILLER]
                assert msg.new_tuple.values == want
                assert int(frame.start_lsn) == \
                    layout.begin_lsn[k] + 8 * (seen + 1)
                total += len(frame.payload)
                seen += 1
                row += 1
        assert seen == rows[k]
    assert total == payload_bytes
    assert layout.durable_count(int(layout.end_lsn[1])) == 2
    assert layout.durable_count(int(layout.end_lsn[1]) - 1) == 1


def test_copy_rows_are_copy_text():
    from etl_tpu.postgres.codec.copy_text import encode_copy_row

    cols = (np.array([1, 9, 10, 99_999, 100_000, 1_000_000]),
            np.array([1, 1, 1, 1, 2, 10]),
            np.array([0, -1, 5, -10, 10**9 - 1, -10**9]))
    blob, off = pgbench.render_copy_rows(cols)
    blob = bytes(blob)
    for i in range(len(cols[0])):
        line = encode_copy_row([str(c[i]) for c in cols]
                               + [pgbench.FILLER.decode()]) + b"\n"
        assert blob[off[i]:off[i + 1]] == \
            b"d" + (len(line) + 4).to_bytes(4, "big") + line


def test_reference_counts():
    ref = pgbench.accounts_columns(SEED, 1000, 5001)
    layout = pgbench.TxLayout.build([250] * 4, 5001)
    coords = layout.row_coordinates(0, 4)
    got = {"aid": ref[0][:750].copy(), "bid": ref[1][:750].copy(),
           "abalance": ref[2][:750].copy(), "bad_text_rows": 0,
           "commit_lsn": coords[0][:750].copy(),
           "tx_ordinal": coords[1][:750].copy()}
    sound = reference.verify(ref, 5001, [[0, 500]], [[0, 750]], got, coords)
    assert reference.judge(sound["numbers"])[0]
    assert sound["info"]["duplicate_rows"] == 0
    # a row lost, a value altered, a row of a transaction never sent, a
    # row booked to another commit: one number each
    got["abalance"][3] += 1
    got["commit_lsn"][7] += 8
    lost = {k: (np.delete(v, 10) if hasattr(v, "__len__") else v)
            for k, v in got.items()}
    lost["aid"] = np.append(lost["aid"], 5001 + 900)
    for k in ("bid", "abalance", "commit_lsn", "tx_ordinal"):
        lost[k] = np.append(lost[k], 0)
    out = reference.verify(ref, 5001, [[0, 500]], [[0, 750]], lost, coords)
    assert out["numbers"] == {"missing_rows": 1, "wrong_rows": 1,
                              "unknown_rows": 1, "misattributed_rows": 1}
    correct, table = reference.judge(out["numbers"])
    assert not correct and all(limit == 0 for _, _, limit in table)


def test_layout_places_bulks_and_sizes_the_backlog():
    from source import layout_of

    config = {"rows": 1000}
    paced = layout_of(config, {
        "kind": "paced", "transaction_rows": 500,
        "transactions_per_second": 60, "warmup_seconds": 3,
        "bulk_every_transactions": 600, "bulk_rows": 16384}, 30)
    assert len(paced.rows) == 1980
    assert np.flatnonzero(paced.rows == 16384).tolist() == [599, 1199, 1799]
    assert set(paced.rows.tolist()) == {500, 16384}
    assert int(paced.first_aid[0]) == 1001
    drain = layout_of(config, {
        "kind": "backlog", "transaction_rows": 500,
        "backlog_events_per_second": 150000, "warmup_seconds": 3,
        "bulk_every_transactions": 34, "bulk_rows": 16384}, 30)
    assert abs(int(drain.rows.sum()) - 5_100_000) < 33 * 500 + 16384
    assert (drain.rows[33::34] == 16384).all()
    plain = layout_of(config, {
        "kind": "backlog", "transaction_rows": 500,
        "backlog_events_per_second": 1000, "warmup_seconds": 1}, 3)
    assert plain.rows.tolist() == [500] * 10
    assert layout_of(config, {"kind": "copy"}, 3) is None
