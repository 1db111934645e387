"""Fuzz targets for the codec parsers + native framer, COPY scans and
line assembly.

Reference parity: cargo-fuzz targets `parse_copy_row`, `parse_text_cell`,
`numeric_text_roundtrip`, `parse_bytea_hex_string`
(fuzz/fuzz_targets/ + src/fuzzing.rs). No coverage-guided fuzzer exists in
this environment, so this is a seeded random byte fuzzer with structured
mutations (truncate/splice/bitflip over valid corpora), a wall-clock
budget, and crash seeds printed for replay — the same contract the
reference's fuzz entry points enforce:

  THE PARSERS MUST NEVER CRASH UNCONTROLLED. Any input either parses or
  raises a typed EtlError; the native framer must flag malformed frames
  (bad_from) or raise EtlError, never segfault or throw bare exceptions.

Run ad hoc:  python -m etl_tpu.testing.fuzz --seconds 30 [--seed N]
CI-sized runs live in tests/test_fuzz.py.
"""

from __future__ import annotations

import random
import time

from ..models.errors import EtlError
from ..models.pgtypes import Oid

# every OID the text parser dispatches on — fuzz coverage must include
# each branch
_OIDS = [Oid.BOOL, Oid.INT2, Oid.INT4, Oid.INT8, Oid.FLOAT4, Oid.FLOAT8,
         Oid.NUMERIC, Oid.TEXT, Oid.VARCHAR, Oid.BPCHAR, Oid.DATE, Oid.TIME,
         Oid.TIMETZ, Oid.TIMESTAMP, Oid.TIMESTAMPTZ, Oid.UUID, Oid.JSON,
         Oid.JSONB, Oid.BYTEA, Oid.INTERVAL]

_SEED_TEXTS = [
    "0", "-1", "12345678901234567890123456789", "+5", "-", "--", "1e309",
    "1.5", "-0.0", "NaN", "Infinity", "-Infinity", "nan", "1e", "e1", ".",
    "2024-02-29", "0001-01-01", "9999-12-31", "0044-03-15 BC", "infinity",
    "-infinity", "24:00:00", "23:59:60", "12:00:00.1234567",
    "2024-05-01 12:34:56.789+02", "2024-05-01 12:34:56-15:59:59",
    "a0eebc99-9c0b-4ef8-bb6d-6bb9bd380a11", "{}", "[1,2]", "null",
    '{"k": "v"}', "\\xdeadbeef", "\\x", "\\xg", "1 year 2 mons",
    "t", "f", "true", "", " ", "\t", "\\N", "\\", "{1,2,3}", "{NULL}",
    '{"a","b"}', "0.000000000000000012345", "9" * 40,
]

_MUT_CHARS = "0123456789-+.:eE aftTxX{}\\\"',N\x00\x7fé"


def _mutate(rng: random.Random, s: str) -> str:
    ops = rng.randint(1, 3)
    out = s
    for _ in range(ops):
        c = rng.random()
        if c < 0.25 and out:
            i = rng.randrange(len(out))
            out = out[:i] + rng.choice(_MUT_CHARS) + out[i + 1:]
        elif c < 0.5:
            i = rng.randrange(len(out) + 1)
            out = out[:i] + rng.choice(_MUT_CHARS) + out[i:]
        elif c < 0.7 and out:
            i = rng.randrange(len(out))
            out = out[:i] + out[i + 1:]
        elif c < 0.85 and out:
            i, j = sorted((rng.randrange(len(out) + 1),
                           rng.randrange(len(out) + 1)))
            other = rng.choice(_SEED_TEXTS)
            out = out[:i] + other + out[j:]
        else:
            out = out * rng.randint(1, 3)
    return out[:4096]


class FuzzFailure(AssertionError):
    def __init__(self, target: str, seed: int, case: int, detail: str):
        super().__init__(
            f"fuzz target {target} failed at seed={seed} case={case}: "
            f"{detail}\nreplay: python -m etl_tpu.testing.fuzz "
            f"--target {target} --seed {seed}")


def fuzz_parse_text_cell(rng: random.Random, _ignored=None) -> None:
    from ..postgres.codec.text import parse_cell_text

    text = _mutate(rng, rng.choice(_SEED_TEXTS))
    oid = rng.choice(_OIDS)
    try:
        parse_cell_text(text, oid)
    except EtlError:
        pass  # typed rejection is the contract


def fuzz_parse_copy_row(rng: random.Random, _ignored=None) -> None:
    from ..postgres.codec.copy_text import parse_copy_row

    n_cols = rng.randint(1, 6)
    oids = [rng.choice(_OIDS) for _ in range(n_cols)]
    fields = [_mutate(rng, rng.choice(_SEED_TEXTS))
              for _ in range(rng.randint(0, n_cols + 1))]
    line = "\t".join(fields).encode("utf-8", "surrogatepass")[:2048]
    try:
        parse_copy_row(line, oids)
    except (EtlError, UnicodeDecodeError):
        pass


def fuzz_numeric_roundtrip(rng: random.Random, _ignored=None) -> None:
    """Valid numeric text must survive parse → pg_text exactly (the
    reference numeric_text_roundtrip target); arbitrary text must parse or
    fail typed."""
    from ..models.cell import PgNumeric
    from ..postgres.codec.text import parse_cell_text

    digits = rng.randint(1, 35)
    scale = rng.randint(0, digits)
    n = rng.randint(0, 10**digits - 1)
    s = str(n).rjust(scale + 1, "0")
    text = (("-" if rng.random() < 0.5 else "")
            + (s[:-scale] + "." + s[-scale:] if scale else s))
    v = parse_cell_text(text, Oid.NUMERIC)
    assert isinstance(v, PgNumeric)
    assert v.pg_text() == text, (v.pg_text(), text)
    # and the mutated form must never crash untyped
    try:
        parse_cell_text(_mutate(rng, text), Oid.NUMERIC)
    except EtlError:
        pass


def fuzz_bytea_hex(rng: random.Random, _ignored=None) -> None:
    from ..postgres.codec.text import parse_cell_text

    body = "".join(rng.choice("0123456789abcdefABCDEFxg \\")
                   for _ in range(rng.randint(0, 64)))
    for text in (f"\\x{body}", body):
        try:
            parse_cell_text(text, Oid.BYTEA)
        except EtlError:
            pass


def fuzz_framer(rng: random.Random, _ignored=None) -> None:
    """Random bytes through the native pgoutput framer: it must return a
    FramedBatch with bad_from set, or raise EtlError — never crash the
    process or return out-of-bounds offsets."""
    import numpy as np

    from ..native import frame_pgoutput
    from ..postgres.codec import pgoutput

    msgs = []
    for _ in range(rng.randint(1, 8)):
        c = rng.random()
        if c < 0.4:  # valid insert, possibly corrupted below
            msgs.append(pgoutput.encode_insert(
                rng.randrange(1, 1 << 31),
                [str(rng.randrange(1000)).encode()
                 for _ in range(rng.randint(0, 4))]))
        elif c < 0.6:
            msgs.append(pgoutput.encode_begin(rng.randrange(1 << 40),
                                              rng.randrange(1 << 50), 7))
        else:
            msgs.append(bytes(rng.randrange(256)
                              for _ in range(rng.randint(0, 64))))
    if msgs and rng.random() < 0.5:  # corrupt one
        i = rng.randrange(len(msgs))
        b = bytearray(msgs[i])
        if b:
            b[rng.randrange(len(b))] ^= 1 << rng.randrange(8)
        msgs[i] = bytes(b)
    buf = b"".join(msgs)
    lens = np.array([len(m) for m in msgs], dtype=np.int32)
    offs = np.zeros(len(msgs), dtype=np.int64)
    np.cumsum(lens[:-1], out=offs[1:])
    n_cols = rng.randint(1, 8)
    try:
        framed, bad = frame_pgoutput(buf, offs, lens, n_cols)
    except EtlError:
        return
    upto = framed.n_msgs if bad < 0 else bad
    # offsets/lengths within bounds for every framed field
    total = len(buf)
    for arr_off, arr_len in ((framed.new_off[:upto], framed.new_len[:upto]),
                             (framed.old_off[:upto], framed.old_len[:upto])):
        ends = arr_off.astype(np.int64) + arr_len
        assert (arr_off >= 0).all() and (arr_len >= 0).all() \
                and (ends <= total).all(), \
            "framer emitted out-of-bounds field"


def backend_message(tag: bytes, payload: bytes = b"") -> bytes:
    import struct

    return tag + struct.pack(">i", len(payload) + 4) + payload


def copy_stream_reference(stream: bytes):
    """The obvious parser of a COPY OUT response: one message at a time
    from the whole byte string. Returns (CopyData payloads joined,
    outcome, bytes after ReadyForQuery); outcome is None for a clean end,
    the server's error message, the ErrorKind of a typed failure, or
    "eof" where the bytes end first (then nothing is "after")."""
    import struct

    from ..models.errors import ErrorKind
    from ..postgres.wire import _parse_error_fields

    pos, payloads, started, error = 0, [], False, None
    while True:
        if pos + 5 > len(stream):
            return b"".join(payloads), "eof", None
        tag = stream[pos:pos + 1]
        (length,) = struct.unpack(">i", stream[pos + 1:pos + 5])
        if length < 4 or length - 4 > 1 << 30:
            return b"".join(payloads), \
                ErrorKind.SOURCE_PROTOCOL_VIOLATION, None
        if pos + 1 + length > len(stream):
            return b"".join(payloads), "eof", None
        payload = stream[pos + 5:pos + 1 + length]
        pos += 1 + length
        if tag == b"d":
            payloads.append(payload)
        elif tag == b"H":
            started = True
        elif tag == b"E":
            error = _parse_error_fields(payload)["M"]
        elif tag == b"Z":
            outcome = error if error is not None else (
                None if started else ErrorKind.SOURCE_QUERY_FAILED)
            return b"".join(payloads), outcome, stream[pos:]


class ScriptedReader:
    """Stands where a connection's StreamReader does. `read(n)` returns
    the next scripted piece and never more, so the ends of the pieces are
    the block cuts under test; an exhausted script is the peer's EOF."""

    def __init__(self, pieces):
        self.pieces = [p for p in pieces if p]

    async def read(self, n: int) -> bytes:
        if not self.pieces:
            return b""
        piece = self.pieces.pop(0)
        if len(piece) > n:
            self.pieces.insert(0, piece[n:])
        return piece[:n]

    async def readexactly(self, n: int) -> bytes:
        import asyncio

        out = b""
        while len(out) < n:
            got = await self.read(n - len(out))
            if not got:
                raise asyncio.IncompleteReadError(out, n)
            out += got
        return out


class _NullWriter:
    def write(self, data: bytes) -> None:
        pass

    async def drain(self) -> None:
        pass

    def close(self) -> None:
        pass

    async def wait_closed(self) -> None:
        pass


def scripted_connection(pieces):
    """A PgWireConnection past its start-up whose peer is `pieces`."""
    from ..native import native_available
    from ..postgres.wire import PgWireConnection

    native_available()  # what `connect()` does, off the loop
    conn = PgWireConnection(host="scripted", port=0, database="d", user="u")
    conn._reader, conn._writer = ScriptedReader(pieces), _NullWriter()
    return conn


async def run_copy_out(conn):
    """(yields, outcome) of one `copy_out`, outcome as the reference's."""
    import asyncio

    from ..postgres.wire import PgServerError

    got, outcome = [], None
    try:
        async for rows in conn.copy_out("COPY t TO STDOUT"):
            got.append(rows)
    except PgServerError as e:
        outcome = e.fields["M"]
    except EtlError as e:
        outcome = e.kind
    except asyncio.IncompleteReadError:
        outcome = "eof"
    return got, outcome


def fuzz_copy_stream(rng: random.Random, _ignored=None) -> None:
    """Random backend messages (sizes, tags, corrupt lengths) cut into
    random blocks, through `PgWireConnection.copy_out` — the C scan or its
    Python walk, whichever this process loaded — against the obvious
    per-message parser of the same bytes: the same CopyData bytes in the
    same order, the same outcome (clean end, the server's error, a typed
    protocol violation, the peer's EOF), and every byte past
    ReadyForQuery still the connection's to read."""
    import asyncio
    import struct

    from ..native import (_scan_copy_data_py, native_available,
                          scan_copy_data)

    message = backend_message
    stream = bytearray(message(b"H", b"\x00\x00\x00")
                       if rng.random() < 0.9 else b"")
    for _ in range(rng.randint(0, 40)):
        c = rng.random()
        if c < 0.8:
            # now and then one larger than a block of the reader
            size = 300_000 if rng.random() < 0.01 else rng.choice(
                (0, 1, 7, 100, 100, 100, 1000, 70_000))
            stream += message(b"d", rng.randbytes(rng.randint(0, size)))
        elif c < 0.9:
            stream += message(rng.choice((b"N", b"S", b"c", b"C")),
                              rng.randbytes(rng.randint(0, 30)))
        elif c < 0.95:
            stream += message(b"E", b"SERROR\x00C57014\x00Mfuzz\x00\x00")
        elif c < 0.98:  # a length no message may have
            stream += rng.choice((b"d", b"N")) + struct.pack(
                ">i", rng.choice((-1, 0, 3, (1 << 30) + 5, -(1 << 31))))
        else:  # ReadyForQuery mid-stream: the rest is another statement's
            stream += message(b"Z", b"I")
    stream += message(b"c") + message(b"Z", b"I")
    stream += rng.randbytes(rng.randint(0, 12))
    if rng.random() < 0.1:  # the peer goes away
        del stream[rng.randrange(len(stream) + 1):]
    stream = bytes(stream)
    want, outcome, after = copy_stream_reference(stream)

    cuts = sorted(rng.randrange(len(stream) + 1)
                  for _ in range(rng.randint(0, 12)))
    pieces = [stream[a:b] for a, b in zip([0, *cuts], [*cuts, len(stream)])]
    if native_available():
        for piece in pieces:
            assert scan_copy_data(piece) == _scan_copy_data_py(piece), \
                "C scan and Python walk differ"

    conn = scripted_connection(pieces)
    got, seen = asyncio.run(asyncio.wait_for(run_copy_out(conn), 30))
    assert seen == outcome, f"outcome {seen!r}, want {outcome!r}"
    assert b"".join(got) == want, "CopyData bytes differ"
    if after is not None:
        assert conn._unread + b"".join(conn._reader.pieces) == after, \
            "bytes after ReadyForQuery dropped"


def stage_copy_chunk_reference(chunk: bytes, n_cols: int):
    """The obvious staging of a COPY chunk: rows by `split`, fields by
    `split`. Returns (n_rows, cells, fallback_rows) — cells[r][c] =
    (offset, length, is_null), length 0 where the field is a bare \\N;
    fallback_rows those with any other backslash — or the message of the
    COPY_FORMAT_INVALID that `ops/staging.stage_copy_chunk` raises."""
    if not chunk:
        return 0, [], []
    if not chunk.endswith(b"\n"):
        chunk += b"\n"
    lines = chunk.split(b"\n")[:-1]
    n_delims = chunk.count(b"\t") + len(lines)
    if n_delims != len(lines) * n_cols:
        return (f"COPY chunk: {n_delims} delimiters for {len(lines)} rows × "
                f"{n_cols} cols")
    pos, cells, fallback = 0, [], []
    for r, line in enumerate(lines):
        fields = line.split(b"\t")
        if len(fields) != n_cols:
            return "COPY chunk: ragged rows (tab/newline mismatch)"
        row = []
        for f in fields:
            null = f == b"\\N"
            row.append((pos, 0 if null else len(f), null))
            pos += len(f) + 1
        if any(b"\\" in f and f != b"\\N" for f in fields):
            fallback.append(r)
        cells.append(row)
    return len(lines), cells, fallback


def check_stage_copy_chunk(chunk: bytes, n_cols: int) -> None:
    """`stage_copy_chunk` — the C scan or its numpy twin, whichever this
    process loaded — against the reference above: every array of the
    StagedBatch by value, dtype and shape, the padding rows of the row
    bucket included, or the same COPY_FORMAT_INVALID message. Where the C
    scan is loaded, it and the twin must also agree with each other."""
    import numpy as np

    from ..models.errors import ErrorKind
    from ..native import native_available, scan_copy_chunk
    from ..ops.staging import (_scan_copy_chunk_np, bucket_rows,
                               stage_copy_chunk)

    native_available()  # what the copy does before its first chunk
    want = stage_copy_chunk_reference(chunk, n_cols)
    try:
        staged = stage_copy_chunk(chunk, n_cols)
    except EtlError as e:
        assert e.kind is ErrorKind.COPY_FORMAT_INVALID, e.kind
        assert e.detail == want, f"{e.detail!r}, want {want!r}"
        staged = None
    else:
        assert not isinstance(want, str), f"staged, want error {want!r}"
    whole = chunk if not chunk or chunk.endswith(b"\n") else chunk + b"\n"
    if whole and (c_scan := scan_copy_chunk(whole, n_cols)) is not None:
        twin = _scan_copy_chunk_np(np.frombuffer(whole, np.uint8), n_cols)
        assert c_scan[:3] == twin[:3], f"{c_scan[:3]} != {twin[:3]}"
        for c_arr, np_arr in zip(c_scan[3:], twin[3:]):
            if np_arr is not None:
                c_arr = c_arr[:len(np_arr)]
                assert c_arr.dtype == np_arr.dtype \
                    and np.array_equal(c_arr, np_arr), "C scan != numpy twin"
    if staged is None:
        return

    n_rows, cells, fallback = want
    cap = 0 if not chunk else bucket_rows(n_rows)
    assert staged.n_rows == n_rows and staged.copy_escapes == bool(chunk)
    assert staged.data.dtype == np.uint8 and staged.data.tobytes() == whole
    cells = np.array(cells, dtype=np.int64).reshape(n_rows, n_cols, 3)
    pad = np.zeros((cap - n_rows, n_cols), dtype=np.int64)
    for name, dtype, vals, fill in (
            ("offsets", np.int32, cells[:, :, 0], 0),
            ("lengths", np.int32, cells[:, :, 1], 0),
            ("nulls", np.bool_, cells[:, :, 2], 1),
            ("toast", np.bool_, 0 * cells[:, :, 0], 0)):
        got = getattr(staged, name)
        assert got.dtype == dtype and got.shape == (cap, n_cols), \
            f"{name}: {got.dtype}{got.shape}"
        assert np.array_equal(got, np.concatenate([vals, pad + fill])), name
    got = staged.cpu_fallback_rows
    assert got.dtype == np.int64 and got.tolist() == fallback, \
        f"fallback rows {got.tolist()}, want {fallback}"


# plain field bytes: none a tab, a newline or a backslash; 0x08, 0x0b and
# 0x5d are each one bit from one of them (the C scan's word-at-a-time test
# may flag the byte after a match falsely and must then look at it)
_COPY_PLAIN = b"0123456789 abN" + bytes((0x08, 0x0B, 0x5D, 0x80, 0xFF))
_COPY_ESCAPED = (b"\\\\", b"a\\tb", b"\\n", b"x\\N", b"\\Nx", b"\\N\\N",
                 b"\\")


def fuzz_stage_copy_chunk(rng: random.Random, _ignored=None) -> None:
    """COPY text chunks — rows of NULLs, empty fields, escapes and plain
    bytes, then some of them mutated, cut short or plain noise — through
    `check_stage_copy_chunk`."""
    n_cols = rng.randint(1, 6)

    def field() -> bytes:
        c = rng.random()
        if c < 0.15:
            return b"\\N"
        if c < 0.25:
            return b""
        if c < 0.26:
            return rng.choice(_COPY_ESCAPED)
        return bytes(rng.choice(_COPY_PLAIN)
                     for _ in range(rng.randint(0, 24)))

    alphabet = _COPY_PLAIN + b"\t\t\n\n\\"
    if rng.random() < 0.1:
        chunk = bytearray(rng.choice(alphabet)
                          for _ in range(rng.randint(0, 80)))
    else:
        chunk = bytearray(b"".join(
            b"\t".join(field() for _ in range(n_cols)) + b"\n"
            for _ in range(rng.randint(0, 40))))
        if rng.random() < 0.05:
            # long rows first: the C scan's guess of the row count, from
            # the chunk's head, runs out and it scans again from its bound
            chunk[:0] = (b"\t".join([b"0" * 5000] * n_cols) + b"\n") * 2
        if chunk and rng.random() < 0.15:
            del chunk[-1]  # the stream left the last newline out
        if chunk and rng.random() < 0.35:
            for _ in range(rng.randint(1, 3)):
                i = rng.randrange(len(chunk))
                c = rng.random()
                if c < 0.3:
                    chunk[i] = rng.choice(alphabet)
                elif c < 0.5:
                    chunk.insert(i, rng.choice(alphabet))
                elif c < 0.7 and len(chunk) > 1:
                    del chunk[i]
                else:  # as many delimiters, in other places: ragged
                    j = rng.randrange(len(chunk))
                    chunk[i], chunk[j] = chunk[j], chunk[i]
    if rng.random() < 0.05:
        n_cols = rng.choice((0, 7, 100))
    check_stage_copy_chunk(bytes(chunk), n_cols)


def fuzz_assemble_rows(rng: random.Random, _ignored=None) -> None:
    """Random piece tables — every kind of piece, zero-length fields,
    int32 and int64 lengths and offsets, views that are strided or
    read-only, overridden rows — through `ops/egress.assemble_rows`, and
    random integers through `int_text_fixed`: the C pass (native/framer.c
    `etl_assemble_rows`, `etl_int_text_fixed`) against the numpy bodies,
    array for array. A process without the native library runs the numpy
    bodies alone."""
    import numpy as np

    from .. import native
    from ..ops import egress as eg

    native.native_available()  # what a destination does at start-up
    nrng = np.random.default_rng(rng.getrandbits(32))
    n = rng.choice((0, 1, 2, 7, 64, 500)) if rng.random() < 0.9 \
        else rng.randint(0, 3000)

    def lens_of(hi):
        lens = nrng.integers(0, hi + 1, n)
        if rng.random() < 0.5:
            lens[nrng.random(n) < 0.3] = 0
        return lens.astype(rng.choice((np.int32, np.int64)))

    def piece():
        c = rng.random()
        if c < 0.35:
            return eg.const_piece(rng.randbytes(rng.choice((0, 1, 1, 2, 9))))
        if c < 0.7:
            w = rng.choice((1, 5, 21, 50))
            wide = nrng.integers(0, 256, (n + 2, w + 6), dtype=np.uint8)
            buf = rng.choice((
                lambda: wide[:n, :w].copy(), lambda: wide[:n, 3:3 + w],
                lambda: wide[1:n + 1, :w], lambda: wide[:n, :w][::-1],
                lambda: np.asfortranarray(wide[:n, :w])))()
            if rng.random() < 0.2:
                buf.flags.writeable = False
            return eg.fixed_piece(buf, lens_of(w))
        lens = lens_of(rng.choice((0, 3, 84)))
        first = rng.choice((0, 0, 5))
        offs = np.zeros(n + 1, dtype=lens.dtype)
        np.cumsum(lens, out=offs[1:])
        values = nrng.integers(0, 256, first + int(offs[-1]) + 2,
                               dtype=np.uint8)
        return ("var", values, offs + first)

    pieces = [piece() for _ in range(rng.randint(0, 9))]
    override = None
    if n and rng.random() < 0.4:
        rows = range(n) if rng.random() < 0.1 else \
            rng.sample(range(n), rng.randint(1, min(n, 6)))
        override = {r: rng.randbytes(rng.choice((0, 1, 30))) for r in rows}
    want = eg._assemble_rows_np(n, pieces, override)
    got = native.assemble_rows(n, pieces, override)
    for g, w in zip(got or want, want):
        assert g.dtype == w.dtype and np.array_equal(g, w), \
            "C assembly != numpy twin"

    dtype = rng.choice((np.int16, np.int32, np.uint32, np.int64))
    info = np.iinfo(dtype)
    vals = nrng.integers(info.min, info.max, n, dtype=dtype, endpoint=True)
    if n and rng.random() < 0.5:  # short values, and the powers of ten
        vals[: n // 2] = (vals[: n // 2] % 200).astype(dtype) - 100 \
            if info.min < 0 else vals[: n // 2] % 200
        vals[0] = info.max - info.max % 10 ** rng.randint(0, 4)
    want = eg._int_text_fixed_np(vals)
    got = native.int_text_fixed(vals)
    for g, w in zip(got or want, want):
        assert g.dtype == w.dtype and g.shape == w.shape \
            and np.array_equal(g, w), "C int text != numpy twin"
    for i in range(min(n, 4)):
        assert want[0][i, :want[1][i]].tobytes() == str(vals[i]).encode()


_AVRO_FUZZ_DIR: str | None = None  # one temp dir per process, not per case


def fuzz_avro_ocf(rng: random.Random, _ignored=None) -> None:
    """The Iceberg metadata pair: random manifest-shaped records through
    the OCF writer must round-trip EXACTLY through the independent
    reader (they share no code — VERDICT r3 #5), and bit-flipped files
    must raise cleanly (ValueError/EOF-shaped), never hang or emit
    silently-wrong records."""
    import tempfile
    from pathlib import Path

    from ..destinations.iceberg_meta import write_avro_ocf
    from .avro_reader import read_avro_ocf

    global _AVRO_FUZZ_DIR
    if _AVRO_FUZZ_DIR is None:
        _AVRO_FUZZ_DIR = tempfile.mkdtemp(prefix="avro_fuzz_")

    schema = {"type": "record", "name": "r", "fields": [
        {"name": "s", "type": "string"},
        {"name": "n", "type": "long"},
        {"name": "ob", "type": ["null", "bytes"]},
        {"name": "arr", "type": {"type": "array", "items": {
            "type": "record", "name": "kv", "fields": [
                {"name": "key", "type": "int"},
                {"name": "value", "type": "bytes"}]}}},
        {"name": "flag", "type": "boolean"},
    ]}
    records = []
    for _ in range(rng.randint(0, 6)):
        records.append({
            "s": "".join(chr(rng.randrange(32, 0x2FF))
                         for _ in range(rng.randint(0, 12))),
            "n": rng.randrange(-(1 << 62), 1 << 62),
            "ob": None if rng.random() < 0.3 else
            bytes(rng.randrange(256) for _ in range(rng.randint(0, 9))),
            "arr": [{"key": rng.randrange(1 << 20),
                     "value": bytes(rng.randrange(256) for _ in
                                    range(rng.randint(0, 5)))}
                    for _ in range(rng.randint(0, 3))],
            "flag": rng.random() < 0.5,
        })
    path = Path(_AVRO_FUZZ_DIR) / "f.avro"
    write_avro_ocf(path, schema, records)
    _, got, _ = read_avro_ocf(path)
    assert got == records, (got, records)
    # corruption: any single bit flip must raise ValueError (the
    # reader's one rejection type; UnicodeDecodeError is its subclass)
    # or KeyError/TypeError from a corrupt-but-valid-JSON schema — or
    # parse to something that simply differs. AssertionError stays
    # UNCAUGHT so consistency checks inside this block keep reporting.
    raw = bytearray(path.read_bytes())
    raw[rng.randrange(len(raw))] ^= 1 << rng.randrange(8)
    path.write_bytes(bytes(raw))
    try:
        read_avro_ocf(path)
    except (ValueError, KeyError, TypeError, RecursionError):
        pass  # typed rejection is the contract


def fuzz_pb_append_rows(rng: random.Random, _ignored=None) -> None:
    """The BigQuery protobuf pair: random AppendRowsRequest bytes decoded
    by BOTH in-repo decoders — the generic TLV one (bq_proto) and the
    spec-written independent one (pb_reader, which shares no code) —
    must agree field-for-field; bit-flipped requests must reject typed
    or parse to something that differs, never hang."""
    from ..destinations import bq_proto
    from ..models.cell import PgNumeric
    from ..models.pgtypes import Oid
    from ..models.schema import (ColumnSchema, ReplicatedTableSchema,
                                 TableName, TableSchema)
    from .pb_reader import decode_append_rows

    kinds = [(Oid.INT4, lambda: rng.randrange(-(1 << 31), 1 << 31)),
             (Oid.INT8, lambda: rng.randrange(-(1 << 62), 1 << 62)),
             (Oid.TEXT, lambda: "".join(chr(rng.randrange(32, 0x24F))
                                        for _ in range(rng.randint(0, 9)))),
             (Oid.BOOL, lambda: rng.random() < 0.5),
             (Oid.FLOAT8, lambda: rng.uniform(-1e12, 1e12)),
             (Oid.NUMERIC, lambda: PgNumeric(str(rng.randrange(10 ** 12))))]
    ncols = rng.randint(1, 5)
    chosen = [kinds[rng.randrange(len(kinds))] for _ in range(ncols)]
    schema = ReplicatedTableSchema.with_all_columns(TableSchema(
        999, TableName("public", "fz"),
        tuple(ColumnSchema(f"c{i}", oid, nullable=True,
                           primary_key_ordinal=1 if i == 0 else None)
              for i, (oid, _) in enumerate(chosen))))
    rows = []
    for r in range(rng.randint(1, 4)):
        values = [None if rng.random() < 0.25 else gen()
                  for _, gen in chosen]
        rows.append(bq_proto.encode_row(schema, values, "UPSERT",
                                        f"{r:016x}"))
    buf = bq_proto.append_rows_request(
        "projects/p/datasets/d/tables/t/streams/_default",
        bq_proto.row_descriptor(schema), rows, trace_id="fz",
        offset=rng.randrange(1 << 40) if rng.random() < 0.5 else None)
    ind = decode_append_rows(buf)
    own = bq_proto.decode_append_rows_request(buf)
    assert ind["write_stream"] == own.write_stream
    assert ind["trace_id"] == own.trace_id
    assert ind.get("offset") == own.offset
    # full descriptor agreement: (name, number, label, type) 4-tuples
    assert [(f["name"], f["number"], f["label"], f["type"])
            for f in ind["descriptor"]["fields"]] == \
        list(own.descriptor_fields)
    assert len(ind["rows"]) == len(own.serialized_rows) == len(rows)
    # row VALUES decoded by both lineages must agree field-for-field —
    # this is the assertion that actually breaks the encode/decode
    # self-confirmation loop for payloads
    assert ind["rows"] == own.decode_rows(), (ind["rows"],
                                              own.decode_rows())
    # corruption: one bit flip → typed rejection or a differing parse
    raw = bytearray(buf)
    raw[rng.randrange(len(raw))] ^= 1 << rng.randrange(8)
    try:
        decode_append_rows(bytes(raw))
    except (ValueError, KeyError):
        pass  # typed rejection is the contract


def fuzz_snowpipe_batches(rng: random.Random, _ignored=None) -> None:
    """The Snowpipe streaming-zstd batch builder: random NDJSON rows
    through RowBatchBuilder must re-decode EXACTLY (independent path:
    zstandard decompressor + stdlib json, none of the builder's chunking
    logic) with rows in order across batch splits, correct per-batch row
    counts and offset ranges, and every batch under the API body limit.
    Non-finite floats must reject typed."""
    import json as _json

    import zstandard

    from ..destinations.snowpipe import MAX_COMPRESSED_BYTES, RowBatchBuilder

    b = RowBatchBuilder()
    docs = []
    # ~5% of cases feed high-entropy megabyte rows so the compressed
    # stream passes BATCH_SPLIT_THRESHOLD and the mid-stream split path
    # (row order across batches, second batch's offset range) is REALLY
    # exercised, not vacuously skipped
    split_case = rng.random() < 0.05
    gens = [lambda: rng.randrange(-(1 << 60), 1 << 60),
            lambda: "".join(chr(rng.randrange(32, 0x2FF))
                            for _ in range(rng.randint(0, 2000))),
            lambda: None, lambda: rng.random() * 1e6,
            lambda: rng.random() < 0.5,
            lambda: {"nested": [1, "x", None]}]
    n = rng.randint(8, 12) if split_case else rng.randint(1, 40)
    for i in range(n):
        # split rows: 512KB of random bytes → 1MB hex, safely under the
        # 2MB per-row limit; ~4 bits/char entropy keeps zstd near 2:1 so
        # ~8 rows pass the 3.8MB compressed split threshold
        v = rng.randbytes(512 << 10).hex() if split_case \
            else rng.choice(gens)()
        doc = {"id": i, "v": v, "_cdc_sequence_number": f"{i:016x}"}
        b.push_row(doc, f"{i:016x}")
        docs.append(doc)
    batches = b.finish()
    if split_case:
        assert len(batches) >= 2, \
            f"split case produced {len(batches)} batch(es)"
    dctx = zstandard.ZstdDecompressor()
    got = []
    row_total = 0
    for rb in batches:
        assert len(rb.data) <= MAX_COMPRESSED_BYTES
        lines = dctx.decompressobj().decompress(rb.data).split(b"\n")
        rows = [_json.loads(l) for l in lines if l]
        assert len(rows) == rb.row_count, (len(rows), rb.row_count)
        # inclusive offset range must be exactly first/last row's token
        assert rb.start_offset == rows[0]["_cdc_sequence_number"]
        assert rb.end_offset == rows[-1]["_cdc_sequence_number"]
        row_total += rb.row_count
        got.extend(rows)
    assert row_total == n and got == docs, (row_total, n)
    # non-finite floats reject typed (encoding.rs stance)
    b2 = RowBatchBuilder()
    try:
        b2.push_row({"v": float("inf")}, "0")
    except EtlError:
        pass
    else:
        raise AssertionError("non-finite float accepted")


TARGETS = {
    "parse_text_cell": fuzz_parse_text_cell,
    "parse_copy_row": fuzz_parse_copy_row,
    "numeric_roundtrip": fuzz_numeric_roundtrip,
    "bytea_hex": fuzz_bytea_hex,
    "framer": fuzz_framer,
    "copy_stream": fuzz_copy_stream,
    "stage_copy_chunk": fuzz_stage_copy_chunk,
    "assemble_rows": fuzz_assemble_rows,
    "avro_ocf": fuzz_avro_ocf,
    "pb_append_rows": fuzz_pb_append_rows,
    "snowpipe_batches": fuzz_snowpipe_batches,
}


def run_target(name: str, *, seconds: float = 2.0, seed: int | None = None,
               min_cases: int = 200) -> int:
    """Run one target under a wall-clock budget; returns cases executed.
    Raises FuzzFailure with the replay seed on any contract violation."""
    fn = TARGETS[name]
    base_seed = seed if seed is not None else random.randrange(1 << 30)
    deadline = time.monotonic() + seconds
    case = 0
    while case < min_cases or time.monotonic() < deadline:
        case_seed = base_seed + case
        rng = random.Random(case_seed)
        try:
            fn(rng)
        except AssertionError as e:
            raise FuzzFailure(name, base_seed, case, str(e))
        except EtlError:
            pass
        except Exception as e:  # untyped escape = contract violation
            raise FuzzFailure(name, base_seed, case,
                              f"untyped {type(e).__name__}: {e}")
        case += 1
    return case


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="etl_tpu.testing.fuzz")
    p.add_argument("--target", choices=sorted(TARGETS), default=None)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=None)
    args = p.parse_args(argv)
    names = [args.target] if args.target else sorted(TARGETS)
    for name in names:
        n = run_target(name, seconds=args.seconds / len(names),
                       seed=args.seed)
        print(f"{name}: {n} cases OK")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
