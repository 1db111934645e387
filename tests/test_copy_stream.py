"""The COPY stream, read in blocks (postgres/wire.py `copy_out`, the scan
in native/framer.c with its Python walk, and the chunk cut of
runtime/copy.py `_copy_partition`).

The peer is scripted: a reader that hands `copy_out` exactly the pieces a
case names, so where a block ends — inside a header, inside a payload,
after ReadyForQuery — is the case's choice and not the kernel's. What the
stream must deliver is what the obvious per-message parser reads from the
same bytes."""

import asyncio
import struct

import pytest

from etl_tpu import native
from etl_tpu.destinations.base import WriteAck
from etl_tpu.models.pgtypes import Oid
from etl_tpu.models.schema import (ColumnSchema, ReplicatedTableSchema,
                                   TableName, TableSchema)
from etl_tpu.postgres.client import _WireCopyStream
from etl_tpu.postgres.wire import PgWireConnection
from etl_tpu.runtime import copy as copy_mod
from etl_tpu.runtime.copy import CopyPartition, CopyProgress, _copy_partition
from etl_tpu.telemetry.metrics import registry
from etl_tpu.testing.fuzz import backend_message as msg
from etl_tpu.testing.fuzz import (copy_stream_reference, run_copy_out,
                                  scripted_connection)

BLOCK = 1 << 18


def row(i: int, width: int = 0) -> bytes:
    return f"{i}\trow-{i}{'x' * width}\n".encode()


H = msg(b"H", b"\x00\x00\x01\x00\x00")
DONE = msg(b"c") + msg(b"C", b"COPY 0\x00") + msg(b"Z", b"I")
NOTICE = msg(b"N", b"SNOTICE\x00Mvacuum is running\x00\x00")
PARAM = msg(b"S", b"TimeZone\x00UTC\x00")
ERROR = msg(b"E", b"SERROR\x00C57014\x00Mcanceling statement\x00\x00")


def cut_at(stream: bytes, *offsets: int) -> list[bytes]:
    edges = [0, *sorted(offsets), len(stream)]
    return [stream[a:b] for a, b in zip(edges, edges[1:])]


ROWS = [row(i, i % 7) for i in range(40)]
PER_ROW = b"".join(msg(b"d", r) for r in ROWS)
BIG = b"9\t" + b"t" * (BLOCK + 1000) + b"\n"  # one TOAST-sized row
FIRST = len(H) + len(msg(b"d", ROWS[0]))  # where the second row starts
FEW = b"".join(msg(b"d", r) for r in ROWS[:6])

# name -> pieces the peer's reads return
CASES = {
    "per_row_one_block": [H + PER_ROW + DONE],
    "per_row_small_blocks": cut_at(H + PER_ROW + DONE,
                                   *range(97, len(PER_ROW), 97)),
    "per_row_one_message_a_read": [H] + [msg(b"d", r) for r in ROWS]
    + [DONE],
    # several rows in one CopyData, and a row split over two messages:
    # both legal, and what the consumer's rfind(b"\n") is for
    "rows_joined_and_split": [
        H + msg(b"d", ROWS[0] + ROWS[1] + ROWS[2][:3])
        + msg(b"d", ROWS[2][3:]) + msg(b"d", ROWS[3]) + DONE],
    **{f"cut_in_header_at_{k}": cut_at(H + PER_ROW + DONE, FIRST + k)
       for k in range(6)},
    "cut_in_payload": cut_at(H + PER_ROW + DONE,
                             FIRST + 5 + len(ROWS[1]) // 2),
    "cut_every_byte": [bytes([b]) for b in H + FEW + NOTICE + FEW + DONE],
    "message_larger_than_block": cut_at(
        H + msg(b"d", ROWS[0]) + msg(b"d", BIG) + msg(b"d", ROWS[1])
        + DONE, 50_000, 50_000 + BLOCK),
    "message_larger_than_block_first": [
        H + msg(b"d", BIG)[:4], msg(b"d", BIG)[4:] + DONE],
    "zero_rows": [H + DONE],
    "zero_rows_message_a_read": [H, msg(b"c"), msg(b"C", b"COPY 0\x00"),
                                 msg(b"Z", b"I")],
    "notice_and_parameter_between_runs": [
        H + msg(b"d", ROWS[0]) + NOTICE + msg(b"d", ROWS[1])
        + msg(b"d", ROWS[2]) + PARAM + NOTICE + msg(b"d", ROWS[3]) + DONE],
    "error_mid_stream": [
        H + msg(b"d", ROWS[0]) + msg(b"d", ROWS[1]) + ERROR
        + msg(b"Z", b"I")],
    "error_mid_stream_cut": cut_at(
        H + msg(b"d", ROWS[0]) + ERROR + msg(b"Z", b"I"),
        len(H) + len(msg(b"d", ROWS[0])) + 3),
    "error_before_copy_starts": [ERROR + msg(b"Z", b"I")],
    "not_a_copy_statement": [msg(b"T", b"\x00\x00") + msg(b"D", b"\x00\x00")
                             + msg(b"C", b"SELECT 1\x00") + msg(b"Z", b"I")],
    "length_under_four": [H + msg(b"d", ROWS[0])
                          + b"d" + struct.pack(">i", 3) + b"junk"],
    "length_negative": [H + b"d" + struct.pack(">i", -5) + b"junk"],
    "length_over_one_gib": [H + msg(b"d", ROWS[0])
                            + b"d" + struct.pack(">i", (1 << 30) + 5)],
    "length_corrupt_on_other_tag": [H + b"N" + struct.pack(">i", 0)],
    "bytes_after_ready": [H + PER_ROW + DONE + NOTICE + PARAM[:7],
                          PARAM[7:]],
}


@pytest.fixture(params=["native", "python"])
def scanner(request, monkeypatch):
    """Both halves of native.scan_copy_data: the C scan, and the Python
    walk a host without a compiler gets."""
    if not native.native_available():
        if request.param == "native":
            pytest.skip(f"no native build: {native._build_error}")
    elif request.param == "python":
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_build_error", "forced by the test")
    return request.param


class TestCopyOutBlocks:
    @pytest.mark.parametrize("case", sorted(CASES))
    async def test_delivers_what_the_per_message_parser_reads(self, case,
                                                              scanner):
        pieces = CASES[case]
        want, want_outcome, after = copy_stream_reference(b"".join(pieces))
        conn = scripted_connection(pieces)
        got, outcome = await asyncio.wait_for(run_copy_out(conn), 10)
        # rows before an error are delivered, in order, none twice
        assert b"".join(got) == want
        assert outcome == want_outcome
        # no byte read past ReadyForQuery is dropped: the connection's
        # next messages are read from it
        rest = b""
        while after is not None and len(rest) < len(after):
            m = await conn._read_message()
            rest += msg(m.tag, m.payload)
        assert rest == (after or b"")

    async def test_peer_closes_mid_message(self, scanner):
        got, outcome = await run_copy_out(
            scripted_connection([H + PER_ROW[:-10]]))
        assert outcome == "eof"
        assert b"".join(got) == b"".join(ROWS[:-1])

    async def test_stopped_consumer_leaves_the_connection_whole(self,
                                                                scanner):
        """A consumer that stops at the first block (shutdown, an error
        downstream) loses nothing it did not take: what the block held
        past the rows handed over is still the connection's to read."""
        conn = scripted_connection(
            [H + PER_ROW + NOTICE + msg(b"d", ROWS[0]) + DONE])
        stream = conn.copy_out("COPY t TO STDOUT")
        first = await stream.__anext__()
        await stream.aclose()
        assert first == b"".join(ROWS)
        assert (await conn._read_message()).tag == b"N"
        assert (await conn._read_message()).payload == ROWS[0]


class TestOverASocket:
    """The same stream through a real transport: `connect()`'s start-up
    exchange, then blocks as the kernel and asyncio's reader cut them."""

    @pytest.mark.parametrize("flush_every", [1, 7, 10_000])
    async def test_copy_then_query_on_one_connection(self, flush_every):
        many = [row(i, i % 13) for i in range(5000)]
        messages = [H] + [msg(b"d", r) for r in many[:2500]] \
            + [NOTICE, msg(b"d", BIG)] \
            + [msg(b"d", r) for r in many[2500:]] + [DONE]

        async def peer(reader, writer):
            (n,) = struct.unpack(">i", await reader.readexactly(4))
            await reader.readexactly(n - 4)  # StartupMessage
            writer.write(msg(b"R", struct.pack(">i", 0)) + msg(b"Z", b"I"))
            for _ in range(2):
                head = await reader.readexactly(5)  # Query
                await reader.readexactly(struct.unpack(">i", head[1:])[0]
                                         - 4)
                for i, m in enumerate(messages):
                    writer.write(m)
                    if i % flush_every == 0:
                        await writer.drain()
                        await asyncio.sleep(0)
                await writer.drain()
            writer.close()

        server = await asyncio.start_server(peer, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        conn = PgWireConnection(host="127.0.0.1", port=port, database="d",
                                user="u")
        try:
            await conn.connect()
            for _ in range(2):  # the second statement starts clean
                got, outcome = await asyncio.wait_for(run_copy_out(conn), 30)
                assert outcome is None
                assert b"".join(got) == \
                    b"".join(many[:2500]) + BIG + b"".join(many[2500:])
                assert conn._unread == b""
        finally:
            await conn.close()
            server.close()
            await server.wait_closed()


class TestScan:
    """native.scan_copy_data: the C function and the Python walk return
    the same four values for every block."""

    BLOCKS = sorted({
        piece for pieces in CASES.values() for piece in pieces
        if len(piece) < 4096} | {
        PER_ROW[:k] for k in range(0, 240, 7)} | {
        b"", b"d", b"Z", b"d\x00\x00", b"d\x00\x00\x00\x04",
        b"d\x00\x00\x00\x04d\x00\x00\x00\x05a",
        b"d\xff\xff\xff\xff", b"d\x7f\xff\xff\xff", b"d\x40\x00\x00\x04",
        b"d\x40\x00\x00\x05"})

    @pytest.mark.parametrize("i", range(len(BLOCKS)))
    def test_native_and_python_identical(self, i):
        if not native.native_available():
            pytest.skip(f"no native build: {native._build_error}")
        block = self.BLOCKS[i]
        assert native.scan_copy_data(block) == \
            native._scan_copy_data_py(block)

    def test_outputs(self):
        block = msg(b"d", b"ab\n") + msg(b"d", b"") + msg(b"d", b"c\n") \
            + NOTICE
        assert native.scan_copy_data(block) == \
            (b"ab\nc\n", 3 * 5 + 5, 3, native.COPY_SCAN_SLOW)
        assert native.scan_copy_data(block[:17]) == \
            (b"ab\n", 13, 2, native.COPY_SCAN_MORE)
        assert native.scan_copy_data(block[:20]) == \
            (b"ab\nc\n", 20, 3, native.COPY_SCAN_MORE)


class TestCounters:
    async def test_messages_per_read_is_the_hit_rate(self):
        from etl_tpu.telemetry.metrics import (
            ETL_COPY_STREAM_MESSAGES_TOTAL, ETL_COPY_STREAM_READS_TOTAL,
            ETL_COPY_STREAM_SLOW_MESSAGES_TOTAL)

        names = (ETL_COPY_STREAM_READS_TOTAL, ETL_COPY_STREAM_MESSAGES_TOTAL,
                 ETL_COPY_STREAM_SLOW_MESSAGES_TOTAL)
        before = [registry.sum_counter(n) for n in names]
        conn = scripted_connection(
            cut_at(H + PER_ROW + NOTICE + PER_ROW + DONE, 600, 1200))
        got, outcome = await run_copy_out(conn)
        assert outcome is None and b"".join(got) == b"".join(ROWS) * 2
        reads, messages, slow = (
            registry.sum_counter(n) - b for n, b in zip(names, before))
        # three blocks; every CopyData by the bulk scan; H, N, c, C, Z by
        # the per-message branch
        assert (reads, messages, slow) == (3, 2 * len(ROWS), 5)


SCHEMA = ReplicatedTableSchema.with_all_columns(TableSchema(
    7, TableName("public", "t"),
    (ColumnSchema("id", Oid.INT4, nullable=False, primary_key_ordinal=1),
     ColumnSchema("v", Oid.TEXT))))


class _ScriptedSource:
    def __init__(self, pieces):
        self._pieces = pieces

    async def copy_table_stream(self, *a, **kw):
        return _WireCopyStream(scripted_connection(self._pieces),
                               "COPY t TO STDOUT")


class _CountingDestination:
    telemetry_name = "counting"

    async def write_table_batch(self, schema, batch):
        return WriteAck.durable()


async def chunks_of_copy(pieces, threshold: int,
                         monkeypatch) -> list[bytes]:
    """Run `_copy_partition` (host path) over the scripted peer and
    return the chunks it cut, as handed to the chunk parser."""
    seen = []
    real = copy_mod.parse_copy_chunk_columns

    def recording(chunk, oids):
        seen.append(chunk)
        return real(chunk, oids)

    monkeypatch.setattr(copy_mod, "parse_copy_chunk_columns", recording)
    progress = CopyProgress()
    await asyncio.wait_for(_copy_partition(
        _ScriptedSource(pieces), SCHEMA, "snap", "pub",
        CopyPartition(0, None, 0), None, _CountingDestination(), progress,
        threshold), 20)
    assert progress.total_rows == sum(c.count(b"\n") for c in seen)
    return seen


def per_message_chunks(messages: list[bytes], threshold: int) -> list[bytes]:
    """The chunks the per-message loop cut (the contract the block reader
    keeps, pinned against that loop before it was deleted): append each
    CopyData payload; once the pending bytes reach the threshold, cut at
    the last newline and carry the rest."""
    chunks, pending = [], b""
    for m in messages:
        pending += m
        if len(pending) >= threshold:
            cut = pending.rfind(b"\n") + 1
            if cut:
                chunks.append(pending[:cut])
            pending = pending[cut:]
    if pending:
        chunks.append(pending)
    return chunks


MANY = [row(i, i % 11) for i in range(3000)]
MANY_STREAM = H + b"".join(msg(b"d", r) for r in MANY) + DONE


class TestChunkBoundaries:
    """`_copy_partition` cuts a one-message-per-row stream where the
    per-message loop did, to the row, however the blocks fall."""

    @pytest.mark.parametrize("threshold", [1, 50, 1000, 4096, 10_000,
                                           1 << 16, 1 << 20])
    @pytest.mark.parametrize("block", [64, 1500, 4096, BLOCK])
    async def test_per_row_stream(self, threshold, block, monkeypatch):
        pieces = cut_at(MANY_STREAM, *range(block, len(MANY_STREAM), block))
        got = await chunks_of_copy(pieces, threshold, monkeypatch)
        assert got == per_message_chunks(MANY, threshold)

    async def test_rows_joined_and_split_lose_nothing(self, monkeypatch):
        """Messages that are not rows: the boundaries may differ from the
        per-message loop's, the rows may not — every chunk ends on a row
        boundary and the chunks join to the stream."""
        text = b"".join(MANY)
        messages = cut_at(text, *range(333, len(text), 333))
        stream = H + b"".join(msg(b"d", m) for m in messages) + DONE
        got = await chunks_of_copy(cut_at(stream, 5000, 9000, 20_000),
                                   2000, monkeypatch)
        assert b"".join(got) == text
        assert all(c.endswith(b"\n") for c in got)
        assert len(got) > 10

    async def test_row_longer_than_threshold(self, monkeypatch):
        long_row = b"1\t" + b"y" * 5000 + b"\n"
        messages = [row(0), long_row[:2000], long_row[2000:], row(2)]
        stream = H + b"".join(msg(b"d", m) for m in messages) + DONE
        got = await chunks_of_copy(cut_at(stream, 700, 1900, 4000), 1000,
                                   monkeypatch)
        assert b"".join(got) == row(0) + long_row + row(2)
        assert all(c.endswith(b"\n") for c in got)
