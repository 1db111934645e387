"""The fixture deployment's generator: the evidence that a new deployment
is one new file. Two tables — `fx_orders` (a key of two parts, replica
identity full, one column of every type the harness renders) and `fx_items`
(replica identity default, nullable columns) — and a change stream whose
transactions are of unequal length and interleave both tables with inserts,
key-preserving updates, key-changing updates and deletes, NULLs both ways
and an unchanged-TOAST column in some updates. Not a cell: it lives under
`tests/`, and is run with `--config-file` / `--traffic-file`.

Every row is an *entity* with a life: born (INSERT) in transaction b,
updated in b+2 (the key stays), moved in b+5 (the key changes), and — where
b is even — deleted in b+9. Entities born in the nine transactions before
the stream starts are the snapshot. A row's image is a function of the
entity and of how many updates it has had, so old and new images need no
state. Every `bulk_every_transactions`-th transaction also bears `bulk_rows`
entities of one table, which later come back as bulk updates: contiguous
runs large enough to be routed to the device.

The mix's own parameters sit in the traffic file under `generator`; the
harness passes them through unread.
"""

from __future__ import annotations

import numpy as np

from oplog import DELETE, INSERT, UPDATE, Col, Stream, TableEvents, TxLayout

LIFE = ((INSERT, 0, 0), (UPDATE, 2, 1), (UPDATE, 5, 2), (DELETE, 9, 2))
PAST = 9  # transactions before the stream whose entities are the snapshot
MOVE = (1 << 40, 100_000_000)  # what a key-changing update adds to the key
NOTES = np.array([
    b"", b"plain", b"tab\there", b"back\\slash", b"line\nbreak", b"cr\rhere",
    b"\\N", b"a longer value that a server would keep out of line " * 4,
    b"sp ace ", b"unicode \xc3\xa9\xc3\xa8", b"quote\"'", b"x"])


def _tx_count(traffic: dict, seconds: float) -> int:
    if traffic["kind"] == "paced":
        rate = float(traffic["transactions_per_second"])
        return round(float(traffic["warmup_seconds"]) * rate) \
            + round(float(seconds) * rate)
    rate = float(traffic["generator"]["backlog_transactions_per_second"])
    return int(rate * (float(traffic["warmup_seconds"]) + float(seconds) + 1))


def _births(traffic: dict, n_tx: int) -> np.ndarray:
    """int64[2, PAST + n_tx]: entities of each table born in each
    transaction, the PAST virtual ones before the stream first."""
    g = traffic["generator"]
    k = np.arange(-PAST, n_tx)
    out = np.stack([np.asarray(g["births"][t], dtype=np.int64)[
        k % len(g["births"][t])] for t in (0, 1)])
    out[:, :PAST] = int(g["snapshot_rows"]) // PAST
    every = int(g.get("bulk_every_transactions", 0))
    if every:
        live = k >= 0
        out[0, live & (k % every == every - 1)] += int(g["bulk_rows"])
        out[1, live & (k % every == every // 2)] += int(g["bulk_rows"])
    return out


def _image(t: int, seed: int, e: np.ndarray, ver: np.ndarray,
           unchanged: "np.ndarray | None" = None) -> list:
    """The row of entities `e` of table t after `ver` updates."""
    base = _mixed(e, seed, t)
    moved = (ver >= 2) * MOVE[t]
    if t == 1:
        amount = (base % 2_000_000_001 - 1_000_000_000) * (1 + ver)
        label = np.char.add(b"item-", (e % 997).astype("S"))
        return [Col(e + 1 + moved),
                Col((e % 30_000 - 15_000).astype(np.int16)),
                # NULL turns into a value and a value into NULL
                Col(amount, null=(e + ver) % 4 == 1),
                Col(label, null=(e + 2 * ver) % 3 == 2),
                Col(1_600_000_000_000_000 + base % 10**15 // 1000 * 1000
                    + ver, null=e % 2 == 0)]
    note_at = (base + (ver >= 1) * (e % 2 == 1)) % len(NOTES)
    return [Col((e % 64 + 1).astype(np.int16)), Col(e + 1 + moved),
            Col(base % 3 == 0), Col((base % 4001 - 2000 + 7 * ver)
                                    .astype(np.int32)),
            Col((base % (2 * 10**11) - 10**11) * (1 + ver)),
            Col((base % 1_000_003 - 500_000) / 64.0 + ver),
            Col(np.char.add(b"c", (e % 9973).astype("S"))),
            Col(np.char.add(b"name ", e.astype("S")), null=e % 5 == 0),
            Col(NOTES[note_at], unchanged=unchanged),
            Col((base % 40_000 - 20_000).astype(np.int32)),  # 1915..2024
            Col(base % 4_000_000_000_000_000 - 2_000_000_000_000_000 + ver),
            # whole seconds, milliseconds and microseconds: every fraction
            Col(base % 1_900_000_000_000_000 // 10**(2 * (e % 4))
                * 10**(2 * (e % 4)) + 10**6 * ver)]


def _mixed(e: np.ndarray, seed: int, t: int) -> np.ndarray:
    """int64 in [0, 2^62): splitmix64 of the entity, the seed and the
    table — the same entity reads the same whatever rows are asked for."""
    with np.errstate(over="ignore"):
        x = e.astype(np.uint64) + np.uint64((seed * 2 + t) % (1 << 63)) \
            * np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return (x >> np.uint64(2)).astype(np.int64)


def snapshot(config: dict, traffic: dict, seed: int) -> dict:
    births = _births(traffic, 0)
    out = {}
    for t, table in enumerate(config["tables"]):
        first = np.concatenate(([0], np.cumsum(births[t])))
        born = np.repeat(np.arange(-PAST, 0), births[t])
        e = np.arange(int(first[-1]))
        # updates that fell before the stream have been applied already
        ver = (born + 2 < 0).astype(np.int64) + (born + 5 < 0)
        out[int(table["id"])] = _image(t, seed, e, ver)
    return out


def stream(config: dict, traffic: dict, seed: int, seconds: float):
    if traffic["kind"] == "copy":
        return None
    n_tx = _tx_count(traffic, seconds)
    births = _births(traffic, n_tx)
    first = np.concatenate((np.zeros((2, 1), np.int64),
                            np.cumsum(births, axis=1)), axis=1)
    # segments: in every transaction, for each step of a life and each
    # table, the entities born that many transactions earlier
    k = np.arange(n_tx)
    seg = []
    for op, delay, ver in LIFE:
        for t in (0, 1):
            born = k - delay + PAST  # index into births
            ok = born >= 0
            if op == DELETE:
                ok &= (k - delay) % 2 == 0
            b = np.where(ok, born, 0)
            seg.append((k, np.full(n_tx, t), np.full(n_tx, op),
                        np.full(n_tx, ver), first[t, b],
                        np.where(ok, births[t, b], 0)))
    cols = [np.stack(c, axis=1).ravel() for c in zip(*seg)]
    seg_tx, seg_t, seg_op, seg_ver, seg_e0, seg_n = cols
    rows = np.bincount(seg_tx, weights=seg_n, minlength=n_tx).astype(np.int64)
    table = np.repeat(seg_t, seg_n).astype(np.uint8)
    op = np.repeat(seg_op, seg_n).astype(np.uint8)
    ver = np.repeat(seg_ver, seg_n)
    starts = np.concatenate(([0], np.cumsum(seg_n)[:-1]))
    entity = np.repeat(seg_e0, seg_n) + np.arange(len(table)) \
        - np.repeat(starts, seg_n)
    events = {}
    for t in (0, 1):
        mine = table == t
        e, after, kind = entity[mine], ver[mine], op[mine]
        before = np.where(kind == UPDATE, after - 1, after)
        # the first update of every other order leaves its note unsent
        unsent = (kind == UPDATE) & (after == 1) & (e % 2 == 0) \
            if t == 0 else None
        events[t] = TableEvents(_image(t, seed, e, after, unsent),
                                _image(t, seed, e, before))
    return Stream(TxLayout.build(rows), table, op, events)
