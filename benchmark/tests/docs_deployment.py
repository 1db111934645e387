"""The wide fixture's generator: the evidence that a deployment of wide
rows with JSON and out-of-line TOAST text is files. One table, `fx_docs`
(replica identity full, an int8 key, about a hundred columns: integers,
numeric(p,s), varchar, timestamps, a `jsonb` and a `json` column and a
`text` body), its columns read from the configuration's file by type. Not
a cell: it lives under `tests/`, and is run with `--config-file` /
`--traffic-file`.

Every row is an *entity* with a life, as in `fixture_deployment.py`: born
(INSERT) in transaction b, updated in b+2 and b+5 (the key stays), and,
where b is even, deleted in b+9 (a delete with the whole old row). Entities
born in the nine transactions before the stream are the snapshot; every
`bulk_every_transactions`-th transaction bears `bulk_rows` more, whose
updates and deletes come back as runs large enough for the device.

The body is `Coded` text from a pool made from the seed: one row in eight
holds a value of 2 to 64 KB (out of line in a server: past its ~2 KB
threshold), the rest short ones with escapes and non-ASCII. The first
update changes an odd entity's body, the second one of every third; an
update that leaves a long body as it was sends it as unchanged TOAST
(`u`), as a server does. The JSON columns hold objects, arrays, string
scalars with tabs, newlines, backslashes and non-ASCII, numbers such as
1e2, -0 and 0.10, JSON null, and SQL NULL: `attrs` as jsonb prints them,
`raw` verbatim as json keeps them.
"""

from __future__ import annotations

import numpy as np

from oplog import (DELETE, INSERT, UPDATE, Col, Coded, Pool, Stream,
                   TableEvents, TxLayout)

LIFE = ((INSERT, 0, 0), (UPDATE, 2, 1), (UPDATE, 5, 2), (DELETE, 9, 2))
PAST = 9  # transactions before the stream whose entities are the snapshot
LONG_EVERY = 8  # one body in eight is TOAST-sized
PHRASES = np.array([
    b"plain", b"tab\there", b"back\\slash", b"line\nbreak", b"cr\rhere",
    b"\\N", b"caf\xc3\xa9", b"sp ace ", b"quote\"'", b"", b"x", b"\xe2\x82\xac"])
# jsonb's text as the server prints it: keys by length then bytes, ", "
# and ": ", numbers as numeric keeps them
JSONB = np.array([
    b'{"a": 1, "b": [1, 2, 3]}', b'[]', b'{}', b'"tab\\there"',
    b'"back\\\\slash and line\\nbreak"', b'"caf\xc3\xa9 \\u00e9"', b'100',
    b'0', b'0.10', b'-1.5e-7', b'null', b'true', b'false',
    b'{"n": null, "s": "x", "deep": {"k": [{"z": 0.5}, "y", null]}}',
    b'[1, "two", 3.0, {"four": 4}]', b'"1e2"', b'12345678901234567890'])
# json keeps its text verbatim: other spacing, key orders, number spellings
JSON = np.array([
    b'{"b":[1,2,3],"a":1}', b'[ ]', b'{ }', b'"tab\\there"',
    b'  "back\\\\slash and line\\nbreak"  ', b'"caf\xc3\xa9 \\u00e9"',
    b'1e2', b'-0', b'0.10', b'-15e-8', b'null', b'true', b'false',
    b'{"deep":{"k":[{"z":5E-1},"y",null]},"s":"x","n":null}',
    b'[1,"two",3.0,{"four":4}]', b'"1e2"', b'{"a":1,"a":2}'])


def _tx_count(traffic: dict, seconds: float) -> int:
    if traffic["kind"] == "paced":
        rate = float(traffic["transactions_per_second"])
        return round(float(traffic["warmup_seconds"]) * rate) \
            + round(float(seconds) * rate)
    rate = float(traffic["generator"]["backlog_transactions_per_second"])
    return int(rate * (float(traffic["warmup_seconds"]) + float(seconds) + 1))


def _births(traffic: dict, n_tx: int) -> np.ndarray:
    """int64[PAST + n_tx]: entities born in each transaction, the PAST
    virtual ones before the stream first."""
    g = traffic["generator"]
    k = np.arange(-PAST, n_tx)
    out = np.asarray(g["births"], dtype=np.int64)[k % len(g["births"])]
    out[:PAST] = int(g["snapshot_rows"]) // PAST
    every = int(g.get("bulk_every_transactions", 0))
    if every:
        out[(k >= 0) & (k % every == every - 1)] += int(g["bulk_rows"])
    return out


def _mixed(e: np.ndarray, seed: int, j: int) -> np.ndarray:
    """int64 in [0, 2^62): splitmix64 of the entity, the seed and the
    column — the same entity reads the same whatever rows are asked for."""
    with np.errstate(over="ignore"):
        x = e.astype(np.uint64) + np.uint64((seed * 128 + j) % (1 << 63)) \
            * np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return (x >> np.uint64(2)).astype(np.int64)


def body_pool(seed: int) -> Pool:
    """The body's distinct values: short ones, one just under and one just
    over a kilobyte, then 24 of 2 to 64 KB (log-uniform), from the seed."""
    rng = np.random.default_rng(seed)
    alphabet = np.frombuffer(
        b"abcdefghijklmnopqrstuvwxyz ABCDEFGHIJ0123456789.,;\t\n\\", np.uint8)
    short = [bytes(p) + b" %d" % i for i, p in enumerate(PHRASES)]
    sizes = [1000, 1100] + np.exp(rng.uniform(
        np.log(2048), np.log(65536), 24)).astype(np.int64).tolist()
    wide = []
    for i, size in enumerate(sizes):
        text = alphabet[rng.integers(0, len(alphabet), size)].tobytes()
        wide.append(b"%d:" % i + text[:size - 8] + "é€".encode())
    return Pool(short + wide)


def _body(seed: int, pool: Pool, e: np.ndarray, ver: np.ndarray):
    """(codes, long) of the body after `ver` updates: it changes in the
    first update of an odd entity and the second of every third."""
    changes = (ver >= 1) * (e % 2 == 1) + (ver >= 2) * (e % 3 == 0)
    h = _mixed(e * 3 + changes, seed, 127)
    n_short = len(PHRASES)
    long = h % LONG_EVERY == 0
    codes = np.where(long, n_short + (h // LONG_EVERY) % (len(pool) - n_short),
                     (h // LONG_EVERY) % n_short)
    return codes, pool.lengths[codes] > 2048


def _image(columns: list, seed: int, pool: Pool, e: np.ndarray,
           ver: np.ndarray, unchanged: "np.ndarray | None" = None) -> list:
    """The row of entities `e` after `ver` updates, column by column."""
    out = []
    for j, c in enumerate(columns):
        kind = c["type"]
        base = _mixed(e, seed, j)
        if c.get("key"):
            values = e + 1
        elif kind == "int2":
            values = (base % 60_000 - 30_000 + ver).astype(np.int16)
        elif kind == "int4":
            values = (base % 2_000_000_000 - 1_000_000_000
                      + 7 * ver).astype(np.int32)
        elif kind == "int8":
            values = base % (2 * 10**18) - 10**18 + ver
        elif kind == "numeric":
            top = 10 ** (int(c["precision"]) - 1)
            values = base % (2 * top) - top + ver
        elif kind == "varchar":
            values = np.char.add(PHRASES[(base + ver) % len(PHRASES)],
                                 ((base >> 8) % 10**9).astype("S"))
        elif kind in ("timestamp", "timestamptz"):
            # whole seconds, milliseconds and microseconds: every fraction
            step = 10 ** (2 * (e % 4))
            values = base % 3_000_000_000_000_000 // step * step \
                - 1_000_000_000_000_000 + 10**6 * ver
        elif kind in ("json", "jsonb"):
            texts = JSONB if kind == "jsonb" else JSON
            values = texts[(base + ver) % len(texts)]
        elif kind == "text":
            codes, long = _body(seed, pool, e, ver)
            out.append(Col(Coded(codes, pool), unchanged=None
                           if unchanged is None else unchanged & long))
            continue
        else:
            raise ValueError(f"the wide fixture makes no {kind!r} column")
        null = (e + ver + j) % 7 == 0 if c.get("nullable") else None
        out.append(Col(values, null))
    return out


def snapshot(config: dict, traffic: dict, seed: int) -> dict:
    table = config["tables"][0]
    births = _births(traffic, 0)
    born = np.repeat(np.arange(-PAST, 0), births)
    e = np.arange(len(born))
    # updates that fell before the stream have been applied already
    ver = (born + 2 < 0).astype(np.int64) + (born + 5 < 0)
    return {int(table["id"]): _image(table["columns"], seed, body_pool(seed),
                                     e, ver)}


def stream(config: dict, traffic: dict, seed: int, seconds: float):
    if traffic["kind"] == "copy":
        return None
    columns = config["tables"][0]["columns"]
    n_tx = _tx_count(traffic, seconds)
    births = _births(traffic, n_tx)
    first = np.concatenate(([0], np.cumsum(births)))
    # segments: in every transaction, for each step of a life, the
    # entities born that many transactions earlier
    k = np.arange(n_tx)
    seg = []
    for op, delay, ver in LIFE:
        born = k - delay + PAST  # index into births
        ok = born >= 0
        if op == DELETE:
            ok &= (k - delay) % 2 == 0
        b = np.where(ok, born, 0)
        seg.append((k, np.full(n_tx, op), np.full(n_tx, ver), first[b],
                    np.where(ok, births[b], 0)))
    seg_tx, seg_op, seg_ver, seg_e0, seg_n = (
        np.stack(c, axis=1).ravel() for c in zip(*seg))
    rows = np.bincount(seg_tx, weights=seg_n, minlength=n_tx).astype(np.int64)
    op = np.repeat(seg_op, seg_n).astype(np.uint8)
    after = np.repeat(seg_ver, seg_n)
    starts = np.concatenate(([0], np.cumsum(seg_n)[:-1]))
    e = np.repeat(seg_e0, seg_n) + np.arange(len(op)) \
        - np.repeat(starts, seg_n)
    before = np.where(op == UPDATE, after - 1, after)
    # an update that leaves the body as it was sends a long one as `u`
    unsent = (op == UPDATE) & ~(((after == 1) & (e % 2 == 1))
                                | ((after == 2) & (e % 3 == 0)))
    pool = body_pool(seed)
    events = {0: TableEvents(_image(columns, seed, pool, e, after, unsent),
                             _image(columns, seed, pool, e, before))}
    return Stream(TxLayout.build(rows), np.zeros(len(op), dtype=np.uint8),
                  op, events)
