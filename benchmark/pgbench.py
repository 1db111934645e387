"""pgbench_accounts data from a seed, and its wire bytes, rendered in bulk.

The yardstick's own generator: nothing here imports the program or JAX.
`accounts_columns` is copied from `chip_smoke.py` (PR 21), where it was
judged sound (its checksum fold is not: the comparison here is exact, row by
row); the renderers below are new. They turn whole
column arrays into pgoutput XLogData frames (one CopyData message per WAL
entry, as a walsender sends them) and into COPY text rows (one CopyData per
row), with numpy and no per-row Python, so that a source process can prebuild
a whole run's bytes during set-up and only `send` inside the window.

WAL layout (the fake database's own, `etl_tpu/postgres/fake.py`): every WAL
entry advances the LSN by 8; a transaction of n rows is BEGIN at B, rows at
B+8..B+8n, COMMIT at C=B+8(n+1), and ends at E=C+8, where the next BEGIN
lands. A transaction is durable once the slot's flush position is >= E.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

import numpy as np

ACCOUNTS_PER_BRANCH = 100_000  # pgbench's naccounts: bid = (aid-1)/100000+1
FILLER_WIDTH = 84              # pgbench leaves filler char(84) blank-padded
FILLER = b" " * FILLER_WIDTH
INT4_OID = 23
BPCHAR_OID = 1042
PG_EPOCH_US = 946_684_800_000_000  # 2000-01-01 in unix microseconds
WAL_STEP = 8
BASE_LSN = 0x0100_0000  # where the source's WAL starts (slots are made here)

_POW10 = 10 ** np.arange(19, dtype=np.int64)


# ---------------------------------------------------------------------------
# data: made from the seed, with the truth kept as columns
# ---------------------------------------------------------------------------


def accounts_columns(seed: int, n: int, first_aid: int = 1):
    """`n` pgbench_accounts rows from `first_aid` on, as int64 columns:
    aid sequential, bid by pgbench's rule, abalance uniform in +-10^9
    (pgbench initialises 0; listed under `assumed` in the configurations)."""
    rng = np.random.default_rng([seed, first_aid])
    aid = np.arange(first_aid, first_aid + n, dtype=np.int64)
    bid = (aid - 1) // ACCOUNTS_PER_BRANCH + 1
    abalance = rng.integers(-10**9, 10**9, size=n, dtype=np.int64)
    return aid, bid, abalance


# ---------------------------------------------------------------------------
# bulk rendering
# ---------------------------------------------------------------------------


_T5 = ((np.arange(100_000)[:, None] // 10 ** np.arange(4, -1, -1)) % 10
       + ord("0")).astype(np.uint8)  # 5 zero-padded digits of 0..99999


def _int_text_block(v: np.ndarray):
    """(bytes[n,11], valid[n,11], length[n]) — the decimal text of `v`
    (|v| < 10^10, which covers int4): column 0 is an optional '-', columns
    1..10 the zero-padded digits, of which `valid` keeps the significant."""
    n = len(v)
    a = np.abs(v)
    if n and int(a.max()) >= 10**10:
        raise ValueError("int text block holds at most 10 digits")
    nd = np.maximum(1, np.searchsorted(_POW10, a, side="right"))
    hi = a // 100_000
    out = np.empty((n, 11), dtype=np.uint8)
    out[:, 0] = ord("-")
    out[:, 1:6] = _T5[hi]
    out[:, 6:] = _T5[a - hi * 100_000]
    valid = np.empty((n, 11), dtype=bool)
    valid[:, 0] = v < 0
    valid[:, 1:] = np.arange(10)[None, :] >= (10 - nd)[:, None]
    return out, valid, nd + (v < 0)


def _be(values: np.ndarray, dtype: str) -> np.ndarray:
    """Big-endian bytes of each value, as uint8[n, width]."""
    a = np.ascontiguousarray(values.astype(dtype))
    return a.view(np.uint8).reshape(len(values), -1)


_SCRATCH = threading.local()  # .held: {(n, width): (matrix, mask)}


def _rows_blob(parts: list, n: int):
    """Concatenate per-row pieces into one uint8 array plus row offsets.
    `parts` is a list of (bytes[n,w], valid[n,w] or None) column blocks.
    The row matrix is scratch kept between calls, per thread: a source renders a run
    in equal chunks, and fresh 40 MB arrays each time cost more in page
    faults than the rendering itself."""
    width = sum(b.shape[1] for b, _ in parts)
    held = _SCRATCH.__dict__.setdefault("held", {})
    if (n, width) not in held:
        held.clear()
        held[(n, width)] = (np.empty((n, width), dtype=np.uint8),
                            np.empty((n, width), dtype=bool))
    mat, mask = held[(n, width)]
    mask[:] = True
    at = 0
    for block, valid in parts:
        w = block.shape[1]
        mat[:, at:at + w] = block
        if valid is not None:
            mask[:, at:at + w] = valid
        at += w
    lengths = mask.sum(axis=1)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    return mat[mask], offsets


def _const(n: int, data: bytes) -> tuple:
    return np.broadcast_to(np.frombuffer(data, dtype=np.uint8),
                           (n, len(data))), None


def render_insert_frames(table_id: int, cols, lsns: np.ndarray,
                         end_lsns: np.ndarray, clock_us: int):
    """CopyData('d') messages, one XLogData INSERT frame per row of the
    pgbench_accounts columns `cols`. Returns (bytes, offsets[n+1],
    payload_lengths[n]) — payload = the pgoutput message, what the
    pipeline's framer reads."""
    aid, bid, abalance = cols
    n = len(aid)
    texts = [_int_text_block(c) for c in (aid, bid, abalance)]
    text_len = sum(t[2] for t in texts)
    # 'I' relid 'N' ncols + 4 x ('t' len) + filler + the three texts
    payload_len = 1 + 4 + 1 + 2 + 4 * 5 + FILLER_WIDTH + text_len
    body_len = 1 + 8 + 8 + 8 + payload_len  # 'w' start end clock
    parts = [_const(n, b"d"), (_be(body_len + 4, ">i4"), None),
             _const(n, b"w"), (_be(lsns, ">u8"), None),
             (_be(end_lsns, ">u8"), None),
             _const(n, np.array([clock_us - PG_EPOCH_US], dtype=">i8")
                    .tobytes()),
             _const(n, b"I" + int(table_id).to_bytes(4, "big") + b"N"
                    + (4).to_bytes(2, "big"))]
    for block, valid, length in texts:
        parts += [_const(n, b"t"), (_be(length, ">i4"), None),
                  (block, valid)]
    parts.append(_const(n, b"t" + FILLER_WIDTH.to_bytes(4, "big") + FILLER))
    blob, offsets = _rows_blob(parts, n)
    return blob, offsets, payload_len


def render_copy_rows(cols):
    """CopyData('d') messages of COPY text, one row each:
    aid \\t bid \\t abalance \\t filler \\n, as a server sends them.
    Returns (uint8 array, offsets[n+1])."""
    aid, bid, abalance = cols
    n = len(aid)
    texts = [_int_text_block(c) for c in (aid, bid, abalance)]
    line_len = sum(t[2] for t in texts) + 3 + FILLER_WIDTH + 1
    parts = [_const(n, b"d"), (_be(line_len + 4, ">i4"), None)]
    for block, valid, _ in texts:
        parts += [(block, valid), _const(n, b"\t")]
    parts.append(_const(n, FILLER + b"\n"))
    return _rows_blob(parts, n)


def _copy_data(body: bytes) -> bytes:
    return b"d" + (len(body) + 4).to_bytes(4, "big") + body


def xlog_frame(start_lsn: int, end_lsn: int, clock_us: int,
               payload: bytes) -> bytes:
    """One CopyData-wrapped XLogData frame."""
    return _copy_data(b"w" + int(start_lsn).to_bytes(8, "big")
                      + int(end_lsn).to_bytes(8, "big")
                      + (clock_us - PG_EPOCH_US).to_bytes(8, "big",
                                                          signed=True)
                      + payload)


def keepalive_frame(end_lsn: int, clock_us: int, reply: bool) -> bytes:
    return _copy_data(b"k" + int(end_lsn).to_bytes(8, "big")
                      + (clock_us - PG_EPOCH_US).to_bytes(8, "big",
                                                          signed=True)
                      + (b"\x01" if reply else b"\x00"))


def relation_payload(table_id: int) -> bytes:
    """pgoutput RELATION of public.pgbench_accounts (aid is the key)."""
    out = b"R" + int(table_id).to_bytes(4, "big") \
        + b"public\x00pgbench_accounts\x00" + b"d" + (4).to_bytes(2, "big")
    for flags, name, oid, mod in ((1, b"aid", INT4_OID, -1),
                                  (0, b"bid", INT4_OID, -1),
                                  (0, b"abalance", INT4_OID, -1),
                                  (0, b"filler", BPCHAR_OID,
                                   FILLER_WIDTH + 4)):
        out += bytes([flags]) + name + b"\x00" + oid.to_bytes(4, "big") \
            + mod.to_bytes(4, "big", signed=True)
    return out


@dataclass
class TxLayout:
    """Where each transaction of a stream sits in the WAL and in the
    table: transaction k inserts aids [first_aid[k], first_aid[k]+rows[k])
    and is durable once the flush position reaches end_lsn[k]."""

    rows: np.ndarray        # int64[n_tx]
    first_aid: np.ndarray   # int64[n_tx]
    begin_lsn: np.ndarray   # int64[n_tx]
    commit_lsn: np.ndarray  # int64[n_tx]
    end_lsn: np.ndarray     # int64[n_tx]

    @classmethod
    def build(cls, tx_rows, first_aid: int, base_lsn: int = BASE_LSN
              ) -> "TxLayout":
        rows = np.asarray(tx_rows, dtype=np.int64)
        span = WAL_STEP * (rows + 2)
        end = base_lsn + WAL_STEP + np.cumsum(span)
        begin = end - span
        firsts = first_aid + np.concatenate(([0], np.cumsum(rows)[:-1]))
        return cls(rows, firsts, begin, end - WAL_STEP, end)

    def durable_count(self, flush_lsn: int) -> int:
        """How many leading transactions the flush position has passed."""
        return int(np.searchsorted(self.end_lsn, flush_lsn, side="right"))

    def row_coordinates(self, k0: int, k1: int):
        """(commit_lsn, tx_ordinal) of every row of transactions k0..k1-1,
        in WAL order — what the program must attribute each row to."""
        rows = self.rows[k0:k1]
        commit = np.repeat(self.commit_lsn[k0:k1], rows)
        starts = np.concatenate(([0], np.cumsum(rows)[:-1]))
        ordinal = np.arange(int(rows.sum()), dtype=np.int64) \
            - np.repeat(starts, rows)
        return commit, ordinal


def render_transactions(table_id: int, layout: TxLayout, stream_cols,
                        k0: int, k1: int, clock_us: int,
                        with_relation: bool) -> tuple:
    """One prebuilt send buffer per transaction k0..k1-1 (BEGIN, the
    RELATION once if asked, every INSERT, COMMIT), and the summed pgoutput
    payload bytes of their row messages. `stream_cols` are the columns of
    the WHOLE stream (row 0 = layout.first_aid[0])."""
    rows = layout.rows[k0:k1]
    n = int(rows.sum())
    lo = int(layout.first_aid[k0] - layout.first_aid[0])
    cols = tuple(c[lo:lo + n] for c in stream_cols)
    starts = np.concatenate(([0], np.cumsum(rows)))
    ordinal = np.arange(n, dtype=np.int64) - np.repeat(starts[:-1], rows)
    lsns = np.repeat(layout.begin_lsn[k0:k1], rows) + WAL_STEP * (ordinal + 1)
    ends = np.repeat(layout.end_lsn[k0:k1], rows)
    blob, offsets, payload_len = render_insert_frames(
        table_id, cols, lsns, ends, clock_us)
    view = memoryview(blob)
    pg_ts = (clock_us - PG_EPOCH_US).to_bytes(8, "big", signed=True)
    bufs = []
    for j, k in enumerate(range(k0, k1)):
        b, c, e = (int(layout.begin_lsn[k]), int(layout.commit_lsn[k]),
                   int(layout.end_lsn[k]))
        head = xlog_frame(b, e, clock_us, b"B" + c.to_bytes(8, "big") + pg_ts
                          + (1000 + k).to_bytes(4, "big"))
        if with_relation and j == 0:
            head += xlog_frame(b + WAL_STEP, e, clock_us,
                               relation_payload(table_id))
        tail = xlog_frame(c, e, clock_us, b"C\x00" + c.to_bytes(8, "big")
                          + e.to_bytes(8, "big") + pg_ts)
        bufs.append(b"".join((
            head, view[offsets[starts[j]]:offsets[starts[j + 1]]], tail)))
    return bufs, int(payload_len.sum())
