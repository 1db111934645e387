"""Wire bytes of any configuration's tables, rendered in bulk from the
generator's columns: nothing here imports the program or JAX.

Whole column arrays become pgoutput XLogData frames (one CopyData message
per WAL entry, as a walsender sends them: `Relation`, `Begin`, `Insert`,
`Update`, `Delete`, `Commit`) and COPY text rows (one CopyData per row),
with numpy and no per-row Python, so that a source process can prebuild a
whole run's bytes during set-up and only `send` inside the window. (One
exception: `float8` text is Python's shortest repr, made per value.)

Every renderer works on "parts": (bytes[n,w], valid[n,w] or None,
length[n] or None) column blocks laid side by side in a row matrix; the
bytes of a row are the valid ones, in order.

Text forms (`TEXT_BLOCKS`): bool, int2, int4, int8, float8, numeric(p,s),
bpchar(n), varchar(n), text, date, timestamp, timestamptz — as a server
with DateStyle ISO and TimeZone UTC prints them. A deployment's generator
module may bring more under `TEXT_BLOCKS` of its own (`render_*` take them
as `extra`): `name -> fn(column, values) -> (block, valid, length)`.
"""

from __future__ import annotations

import threading

import numpy as np

from oplog import (DELETE, INSERT, TEXT_TYPES, UPDATE, WAL_STEP, char_width,
                   key_indices)

PG_EPOCH_US = 946_684_800_000_000  # 2000-01-01 in unix microseconds
# pg_type's well-known OIDs (a generator module may bring more, TYPE_OIDS)
TYPE_OIDS = {"bool": 16, "int8": 20, "int2": 21, "int4": 23, "text": 25,
             "float8": 701, "bpchar": 1042, "varchar": 1043, "date": 1082,
             "timestamp": 1114, "timestamptz": 1184, "numeric": 1700}
DAY_US = 86_400_000_000

_POW10 = 10 ** np.arange(19, dtype=np.int64)
_T5 = ((np.arange(100_000)[:, None] // 10 ** np.arange(4, -1, -1)) % 10
       + ord("0")).astype(np.uint8)  # 5 zero-padded digits of 0..99999
_T2 = _T5[:100, 3:]
_COPY_ESCAPE = np.zeros(256, dtype=np.uint8)
for _raw, _esc in ((b"\\", b"\\"), (b"\t", b"t"), (b"\n", b"n"),
                   (b"\r", b"r"), (b"\b", b"b"), (b"\f", b"f"),
                   (b"\v", b"v")):
    _COPY_ESCAPE[_raw[0]] = _esc[0]


# ---------------------------------------------------------------------------
# the text of each type
# ---------------------------------------------------------------------------


def _digits(a: np.ndarray, chunks: int, out=None) -> np.ndarray:
    """uint8[n, 5*chunks]: the zero-padded decimal digits of `a` >= 0,
    written into `out` where one is given."""
    if out is None:
        out = np.empty((len(a), 5 * chunks), dtype=np.uint8)
    for j in range(chunks - 1, 0, -1):
        hi = a // 100_000
        out[:, 5 * j:5 * j + 5] = _T5[a - hi * 100_000]
        a = hi
    out[:, :5] = _T5[a]
    return out


def _int_text_block(v: np.ndarray):
    """(bytes[n,1+5k], valid, length[n]) — the decimal text of the integers
    `v`: column 0 is an optional '-', then the zero-padded digits, of which
    `valid` keeps the significant."""
    v = np.asarray(v).astype(np.int64, copy=False)
    n = len(v)
    a = np.abs(v)
    if n and int(a.min()) < 0:  # int8's lowest: |v| does not fit
        return _s_text_block(np.array([b"%d" % x for x in v.tolist()]))
    top = int(a.max()) if n else 0
    chunks = 2 if top < 10**10 else 3 if top < 10**15 else 4
    nd = np.maximum(1, np.searchsorted(_POW10, a, side="right"))
    out = np.empty((n, 1 + 5 * chunks), dtype=np.uint8)
    out[:, 0] = ord("-")
    _digits(a, chunks, out[:, 1:])
    valid = np.empty((n, 1 + 5 * chunks), dtype=bool)
    valid[:, 0] = v < 0
    valid[:, 1:] = np.arange(5 * chunks)[None, :] >= (5 * chunks - nd)[:, None]
    return out, valid, nd + (v < 0)


def _numeric_text_block(column: dict, v: np.ndarray):
    """numeric(p,s) from integers scaled by 10^s: sign, integer digits, and
    exactly s fraction digits, as the server prints the type."""
    scale = int(column.get("scale", 0))
    if not scale:
        return _int_text_block(v)
    v = np.asarray(v).astype(np.int64, copy=False)
    a = np.abs(v)
    whole = a // 10**scale
    head, head_valid, head_len = _int_text_block(np.where(v < 0, -whole, whole))
    head_valid[:, 0] = v < 0  # -0.5: the sign is the value's, not the whole's
    head_len = head_len + ((v < 0) & (whole == 0))
    chunks = -(-scale // 5)
    frac = _digits(a - whole * 10**scale, chunks)[:, 5 * chunks - scale:]
    n = len(v)
    out = np.concatenate((head, np.full((n, 1), ord("."), np.uint8), frac),
                         axis=1)
    valid = np.concatenate((head_valid, np.ones((n, 1 + scale), bool)),
                           axis=1)
    return out, valid, head_len + 1 + scale


def _s_text_block(values):
    """The text block of a numpy `S` array."""
    values = np.ascontiguousarray(values)
    n, w = len(values), values.dtype.itemsize
    block = values.view(np.uint8).reshape(n, w)
    length = np.char.str_len(values).astype(np.int64)
    return block, np.arange(w)[None, :] < length[:, None], length


def _text_block(column: dict, values, n: int):
    kind = column["type"]
    if isinstance(values, bytes):
        if kind == "bpchar":
            values = values.decode().ljust(char_width(column)).encode()
        return (np.broadcast_to(np.frombuffer(values, dtype=np.uint8),
                                (n, len(values))), None, None)
    block, valid, length = _s_text_block(values)
    if kind != "bpchar":
        return block, valid, length
    # char(n) pads to n characters, not bytes: a UTF-8 continuation byte
    # (10xxxxxx) is no character of its own
    width = char_width(column)
    pad = width - ((block & 0xC0 != 0x80) & valid).sum(axis=1)
    spaces = np.full((n, width), ord(" "), dtype=np.uint8)
    return (np.concatenate((block, spaces), axis=1),
            np.concatenate((valid, np.arange(width)[None, :] < pad[:, None]),
                           axis=1), length + pad)


def _date_block(days: np.ndarray) -> np.ndarray:
    """uint8[n,10] 'YYYY-MM-DD' of days since 1970 (years 0001..9999)."""
    text = np.datetime_as_string(
        np.asarray(days).astype("M8[D]"), unit="D").astype("S10")
    return text.view(np.uint8).reshape(len(text), 10)


def _timestamp_text_block(v: np.ndarray, zone: bytes):
    """'YYYY-MM-DD HH:MM:SS[.f{1,6}]' + zone, of microseconds since 1970:
    the fraction without its trailing zeros, as the server prints it."""
    v = np.asarray(v).astype(np.int64, copy=False)
    n = len(v)
    days = v // DAY_US
    tod = v - days * DAY_US
    sec, us = tod // 1_000_000, tod % 1_000_000
    w = 26 + len(zone)
    out = np.empty((n, w), dtype=np.uint8)
    out[:, :10] = _date_block(days)
    out[:, 10] = ord(" ")
    out[:, 11:13] = _T2[sec // 3600]
    out[:, 13] = out[:, 16] = ord(":")
    out[:, 14:16] = _T2[sec // 60 % 60]
    out[:, 17:19] = _T2[sec % 60]
    out[:, 19] = ord(".")
    out[:, 20:25] = _T5[us // 10]
    out[:, 25] = us % 10 + ord("0")
    out[:, 26:] = np.frombuffer(zone, dtype=np.uint8)
    kept = 6 - sum((us % 10**k == 0).astype(np.int64) for k in range(1, 7))
    kept = np.where(us == 0, 0, kept)
    valid = np.ones((n, w), dtype=bool)
    valid[:, 19] = us != 0
    valid[:, 20:26] = np.arange(6)[None, :] < kept[:, None]
    return out, valid, 19 + (us != 0) + kept + len(zone)


def _float8_texts(v: np.ndarray) -> np.ndarray:
    out = []
    for x in np.asarray(v, dtype=np.float64).tolist():
        if x != x:
            out.append(b"NaN")
        elif x in (float("inf"), float("-inf")):
            out.append(b"Infinity" if x > 0 else b"-Infinity")
        else:
            r = repr(x)
            out.append((r[:-2] if r.endswith(".0") else r).encode())
    return np.array(out, dtype="S") if out else np.zeros(0, dtype="S1")


TEXT_BLOCKS = {
    "bool": lambda c, v: (np.where(np.asarray(v, dtype=bool), ord("t"),
                                   ord("f")).astype(np.uint8)[:, None],
                          None, None),
    "int2": lambda c, v: _int_text_block(v),
    "int4": lambda c, v: _int_text_block(v),
    "int8": lambda c, v: _int_text_block(v),
    "float8": lambda c, v: _s_text_block(_float8_texts(v)),
    "numeric": _numeric_text_block,
    "date": lambda c, v: (_date_block(v), None, None),
    "timestamp": lambda c, v: _timestamp_text_block(v, b""),
    "timestamptz": lambda c, v: _timestamp_text_block(v, b"+00"),
}


def text_block(column: dict, values, n: int, extra=None):
    """(bytes[n,w], valid or None, length[n] or None) of one column's
    values as the server's text (length None: every row is w long)."""
    kind = column["type"]
    if extra and kind in extra:
        return extra[kind](column, values)
    if kind in TEXT_TYPES:
        return _text_block(column, values, n)
    if kind not in TEXT_BLOCKS:
        raise KeyError(f"no text form for type {kind!r}: the deployment's "
                       f"generator has to bring one under TEXT_BLOCKS")
    return TEXT_BLOCKS[kind](column, values)


def _copy_escaped(block, valid, length):
    """COPY's backslash escapes, where a text value holds a byte that
    needs one."""
    if block.strides[0] == 0 and valid is None \
            and not _COPY_ESCAPE[block[:1]].any():
        return block, valid, length  # one shared value with nothing to escape
    escape = _COPY_ESCAPE[block]
    needs = escape != 0 if valid is None else (escape != 0) & valid
    if not needs.any():
        return block, valid, length
    n, w = block.shape
    out = np.empty((n, 2 * w), dtype=np.uint8)
    out[:, 0::2] = ord("\\")
    out[:, 1::2] = np.where(needs, escape, block)
    keep = np.empty((n, 2 * w), dtype=bool)
    keep[:, 0::2] = needs
    keep[:, 1::2] = True if valid is None else valid
    base = np.full(n, w, dtype=np.int64) if length is None else length
    return out, keep, base + needs.sum(axis=1)


# ---------------------------------------------------------------------------
# rows of parts, as one blob
# ---------------------------------------------------------------------------


def _be(values: np.ndarray, dtype: str) -> np.ndarray:
    """Big-endian bytes of each value, as uint8[n, width]."""
    a = np.ascontiguousarray(values.astype(dtype))
    return a.view(np.uint8).reshape(len(values), -1)


def _const(n: int, data: bytes) -> tuple:
    return (np.broadcast_to(np.frombuffer(data, dtype=np.uint8),
                            (n, len(data))), None, None)


def _merged(parts: list, n: int) -> list:
    """`parts` with every run of constant neighbours made one part: each
    part costs a pass over the row matrix, however narrow it is."""
    out, run = [], b""
    for part in parts:
        block, valid, _ = part
        if valid is None and block.strides[0] == 0 and n:
            run += bytes(block[0])
            continue
        if run:
            out.append(_const(n, run))
            run = b""
        out.append(part)
    if run:
        out.append(_const(n, run))
    return out


def _lengths(parts: list, n: int) -> np.ndarray:
    """int64[n]: bytes of each row of `parts`."""
    total = np.zeros(n, dtype=np.int64)
    for block, valid, length in parts:
        if valid is None:
            total += block.shape[1]
        else:
            total += length if length is not None else valid.sum(axis=1)
    return total


_SCRATCH = threading.local()  # .held: {width: (matrix, mask)}, rows to spare


def _rows_matrix(parts: list, n: int, scratch: bool = True):
    """The row matrix and its mask of valid bytes. With `scratch` both are
    views of arrays kept between calls, per thread: a source renders a run
    in chunks of about the same size, and fresh 40 MB arrays each time cost
    more in page faults than the rendering itself."""
    width = sum(p[0].shape[1] for p in parts)
    if scratch:
        held = _SCRATCH.__dict__.setdefault("held", {})
        if width not in held or len(held[width][0]) < n:
            held.clear()
            room = -(-n // 65_536) * 65_536
            held[width] = (np.empty((room, width), dtype=np.uint8),
                           np.empty((room, width), dtype=bool))
        mat, mask = (a[:n] for a in held[width])
    else:
        mat = np.empty((n, width), dtype=np.uint8)
        mask = np.empty((n, width), dtype=bool)
    mask[:] = True
    at = 0
    for block, valid, _ in parts:
        w = block.shape[1]
        mat[:, at:at + w] = block
        if valid is not None:
            mask[:, at:at + w] = valid
        at += w
    return mat, mask


def _rows_blob(parts: list, n: int):
    """Concatenate per-row pieces into one uint8 array plus row offsets."""
    mat, mask = _rows_matrix(parts, n)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(mask.sum(axis=1), out=offsets[1:])
    return mat[mask], offsets


# ---------------------------------------------------------------------------
# pgoutput
# ---------------------------------------------------------------------------


def _tuple_parts(table: dict, cols: list, n: int, extra, only=None) -> list:
    """TupleData of `n` rows: int16 column count, then per column its kind
    ('t' text, 'n' NULL, 'u' unchanged TOAST) and, for 't', int32 length
    and the text. `only`: the column indices that are sent at all (a key
    image); every other column goes as NULL."""
    columns = table["columns"]
    parts = [_const(n, len(columns).to_bytes(2, "big"))]
    for i, (column, col) in enumerate(zip(columns, cols)):
        if only is not None and i not in only:
            parts.append(_const(n, b"n"))
            continue
        block, valid, length = text_block(column, col.values, n, extra)
        absent = col.null
        if col.unchanged is not None:
            absent = col.unchanged if absent is None \
                else absent | col.unchanged
        if absent is None or not absent.any():
            parts += [_const(n, b"t"),
                      _const(n, block.shape[1].to_bytes(4, "big"))
                      if length is None else (_be(length, ">i4"), None, None),
                      (block, valid, length)]
            continue
        if length is None:
            length = np.full(n, block.shape[1], dtype=np.int64)
        kind = np.where(col.null, ord("n"), ord("t")).astype(np.uint8) \
            if col.null is not None else np.full(n, ord("t"), dtype=np.uint8)
        if col.unchanged is not None:
            kind[col.unchanged] = ord("u")
        sent = ~absent
        keep = sent[:, None] if valid is None else valid & sent[:, None]
        parts += [(kind[:, None], None, None),
                  (_be(length, ">i4"), np.broadcast_to(sent[:, None], (n, 4)),
                   4 * sent),
                  (block, np.broadcast_to(keep, block.shape),
                   length * sent)]
    return parts


def _change_parts(table: dict, op: int, old_kind: int, new, old, n: int,
                  lsns, end_lsns, clock_us: int, extra):
    """Parts of `n` CopyData('d') messages, one XLogData frame each, that
    carry one kind of change to one table, and the pgoutput payload length
    of each (the payload is what the pipeline's framer reads)."""
    relid = int(table["id"]).to_bytes(4, "big")
    payload = [_const(n, bytes([op]) + relid)]
    if old_kind:
        payload.append(_const(n, bytes([old_kind])))
        payload += _tuple_parts(
            table, old, n, extra,
            set(key_indices(table)) if old_kind == ord("K") else None)
    if op != DELETE:
        payload.append(_const(n, b"N"))
        payload += _tuple_parts(table, new, n, extra)
    payload_len = _lengths(payload, n)
    body_len = 1 + 8 + 8 + 8 + payload_len  # 'w' start end clock
    head = [_const(n, b"d"), (_be(body_len + 4, ">i4"), None, None),
            _const(n, b"w"), (_be(lsns, ">u8"), None, None),
            (_be(end_lsns, ">u8"), None, None),
            _const(n, np.array([clock_us - PG_EPOCH_US], dtype=">i8")
                   .tobytes())]
    return _merged(head + payload, n), payload_len


def render_change_frames(table: dict, op: int, old_kind: int, new, old,
                         lsns: np.ndarray, end_lsns: np.ndarray,
                         clock_us: int, extra=None):
    """One XLogData frame per row, all of one kind of change to one table
    (`op`, and `old_kind`: 0, 'K' or 'O'). Returns (bytes, offsets[n+1],
    payload_lengths[n])."""
    n = len(lsns)
    parts, payload_len = _change_parts(table, op, old_kind, new, old, n,
                                       lsns, end_lsns, clock_us, extra)
    blob, offsets = _rows_blob(parts, n)
    return blob, offsets, payload_len


def old_kinds(tables: list, stream) -> np.ndarray:
    """uint8[n_events]: what of the old row each event carries — 0
    nothing, 'K' the key, 'O' the whole row — by the table's replica
    identity and by whether an update changed the key."""
    out = np.zeros(len(stream.op), dtype=np.uint8)
    if not (stream.op != INSERT).any():
        return out
    for t, ev in stream.events.items():
        mine = np.flatnonzero(stream.table == t)
        op = stream.op[mine]
        if tables[t].get("replica_identity", "d") == "f":
            out[mine[op != INSERT]] = ord("O")
            continue
        out[mine[op == DELETE]] = ord("K")
        if ev.old is None:
            continue
        moved = np.zeros(len(mine), dtype=bool)
        for i in key_indices(tables[t]):
            moved |= np.asarray(ev.new[i].values != ev.old[i].values)
        out[mine[(op == UPDATE) & moved]] = ord("K")
    return out


def xlog_frame(start_lsn: int, end_lsn: int, clock_us: int,
                payload: bytes) -> bytes:
    """One CopyData-wrapped XLogData frame."""
    return _copy_data(b"w" + int(start_lsn).to_bytes(8, "big")
                      + int(end_lsn).to_bytes(8, "big")
                      + (clock_us - PG_EPOCH_US).to_bytes(8, "big",
                                                          signed=True)
                      + payload)


def _copy_data(body: bytes) -> bytes:
    return b"d" + (len(body) + 4).to_bytes(4, "big") + body


def keepalive_frame(end_lsn: int, clock_us: int, reply: bool) -> bytes:
    return _copy_data(b"k" + int(end_lsn).to_bytes(8, "big")
                      + (clock_us - PG_EPOCH_US).to_bytes(8, "big",
                                                          signed=True)
                      + (b"\x01" if reply else b"\x00"))


def relation_payload(table: dict, type_oids: dict = TYPE_OIDS) -> bytes:
    """pgoutput RELATION of one table: its key columns flagged, its
    replica identity in the header."""
    namespace, name = table["name"].split(".")
    columns = table["columns"]
    out = b"R" + int(table["id"]).to_bytes(4, "big") \
        + namespace.encode() + b"\x00" + name.encode() + b"\x00" \
        + table.get("replica_identity", "d").encode() \
        + len(columns).to_bytes(2, "big")
    for c in columns:
        out += bytes([1 if c.get("key") else 0]) + c["name"].encode() \
            + b"\x00" + int(type_oids[c["type"]]).to_bytes(4, "big") \
            + int(c.get("modifier", -1)).to_bytes(4, "big", signed=True)
    return out


def render_transactions(tables: list, stream, kinds: np.ndarray, local,
                        k0: int, k1: int, clock_us: int, relations=None,
                        extra=None) -> tuple:
    """One prebuilt send buffer per transaction k0..k1-1 (BEGIN, every
    table's RELATION once if `relations` — their payloads — are given,
    every change, COMMIT), and the summed pgoutput payload bytes of their
    change messages. `kinds` = old_kinds(tables, stream), `local` =
    stream.local_index(): both span the whole stream."""
    layout = stream.layout
    rows = layout.rows[k0:k1]
    starts = np.concatenate(([0], np.cumsum(rows)))
    n = int(starts[-1])
    e0 = int(layout.rows[:k0].sum())
    ordinal = np.arange(n, dtype=np.int64) - np.repeat(starts[:-1], rows)
    lsns = np.repeat(layout.begin_lsn[k0:k1], rows) + WAL_STEP * (ordinal + 1)
    ends = np.repeat(layout.end_lsn[k0:k1], rows)
    group = (stream.table[e0:e0 + n].astype(np.int64) << 16) \
        | (stream.op[e0:e0 + n].astype(np.int64) << 8) | kinds[e0:e0 + n]
    if not n:
        keys = []
    elif (group == group[0]).all():
        keys = [int(group[0])]
    else:
        keys = np.unique(group).tolist()
    rendered = []
    for key in keys:
        t, op, old_kind = key >> 16, (key >> 8) & 0xFF, key & 0xFF
        if len(keys) == 1:
            # one table's one kind of change: its events are neighbours in
            # the table's own arrays too, and the blob needs no reordering
            idx, m = slice(0, n), n
            lo = e0 if local is None else int(local[e0])
            at = slice(lo, lo + n)
        else:
            idx = np.flatnonzero(group == key)
            m = len(idx)
            at = e0 + idx if local is None else local[e0 + idx]
        ev = stream.events[t]
        new = [c.pick(at) for c in ev.new] if op != DELETE else None
        old = [c.pick(at) for c in ev.old] if old_kind else None
        parts, payload_len = _change_parts(
            tables[t], op, old_kind, new, old, m, lsns[idx], ends[idx],
            clock_us, extra)
        rendered.append((idx, parts, payload_len))
    if len(rendered) == 1:
        _, parts, payload_len = rendered[0]
        blob, offsets = _rows_blob(parts, n)
        payload_bytes = int(payload_len.sum())
    elif rendered:
        blob, offsets = _interleaved(rendered, n)
        payload_bytes = int(sum(p.sum() for _, _, p in rendered))
    else:
        blob, offsets, payload_bytes = np.zeros(0, np.uint8), \
            np.zeros(1, np.int64), 0
    view = memoryview(blob)
    pg_ts = (clock_us - PG_EPOCH_US).to_bytes(8, "big", signed=True)
    bufs = []
    for j, k in enumerate(range(k0, k1)):
        b, c, e = (int(layout.begin_lsn[k]), int(layout.commit_lsn[k]),
                   int(layout.end_lsn[k]))
        head = xlog_frame(b, e, clock_us, b"B" + c.to_bytes(8, "big") + pg_ts
                           + (1000 + k).to_bytes(4, "big"))
        if relations and j == 0:
            for payload in relations:
                head += xlog_frame(b + WAL_STEP, e, clock_us, payload)
        tail = xlog_frame(c, e, clock_us, b"C\x00" + c.to_bytes(8, "big")
                           + e.to_bytes(8, "big") + pg_ts)
        bufs.append(b"".join((
            head, view[offsets[starts[j]]:offsets[starts[j + 1]]], tail)))
    return bufs, payload_bytes


def _interleaved(rendered: list, n: int):
    """The blob of `n` events that several kinds of change render: each
    kind's row matrix is laid into one matrix as wide as the widest, at
    its events' own places, so the valid bytes come out in WAL order."""
    mats = [(idx, *_rows_matrix(parts, len(idx), scratch=False))
            for idx, parts, _ in rendered]
    width = max(mat.shape[1] for _, mat, _ in mats)
    whole = np.empty((n, width), dtype=np.uint8)
    keep = np.zeros((n, width), dtype=bool)
    for idx, mat, mask in mats:
        whole[idx, :mat.shape[1]] = mat
        keep[idx, :mat.shape[1]] = mask
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(keep.sum(axis=1), out=offsets[1:])
    return whole[keep], offsets


# ---------------------------------------------------------------------------
# COPY
# ---------------------------------------------------------------------------


def render_copy_rows(table: dict, cols: list, n: int, extra=None):
    """CopyData('d') messages of COPY text, one row each: the columns'
    texts between tabs (NULL as \\N, COPY's escapes in text values) and a
    newline, as a server sends them. Returns (uint8 array, offsets[n+1])."""
    parts = []
    for i, (column, col) in enumerate(zip(table["columns"], cols)):
        block, valid, length = text_block(column, col.values, n, extra)
        if column["type"] in TEXT_TYPES or (extra and column["type"] in extra):
            block, valid, length = _copy_escaped(block, valid, length)
        if col.null is not None and col.null.any():
            w = max(2, block.shape[1])
            wide = np.empty((n, w), dtype=np.uint8)
            wide[:, :block.shape[1]] = block
            keep = np.zeros((n, w), dtype=bool)
            keep[:, :block.shape[1]] = True if valid is None else valid
            wide[col.null, 0], wide[col.null, 1] = ord("\\"), ord("N")
            keep[col.null] = np.arange(w) < 2
            length = np.where(col.null, 2, length if length is not None
                              else block.shape[1])
            block, valid = wide, keep
        parts += [(block, valid, length),
                  _const(n, b"\n" if i == len(cols) - 1 else b"\t")]
    line_len = _lengths(parts, n)
    return _rows_blob(_merged(
        [_const(n, b"d"), (_be(line_len + 4, ">i4"), None, None)] + parts,
        n), n)
