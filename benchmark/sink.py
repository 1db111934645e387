"""The benchmark's fake ClickHouse: an HTTP endpoint in a process of its own
(never imports JAX) that, inside the window, only reads each request's body,
keeps the bytes and answers 200. It stamps its own service time — body
complete to response written — so the cell can show the sink did not set the
pace. Parsing the TSV bodies and comparing them with the reference happens
on the `verify` command, after the window has closed.

Control: JSON lines on stdin (`verify`, `quit`), events on stdout.
"""

from __future__ import annotations

import argparse
import asyncio
import io
import json
import os
import sys
import threading
import time
from urllib.parse import parse_qs, urlsplit

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import pgbench  # noqa: E402
import reference  # noqa: E402

COMMIT_LOG = "_etl_commit_log"  # the destination's own bookkeeping table
SEQ_WIDTH = 50  # {commit:016x}/{tx_ordinal:016x}/{ordinal:016x}


def emit(event: str, **fields) -> None:
    sys.stdout.write(json.dumps({"event": event, **fields}) + "\n")
    sys.stdout.flush()


class Sink:
    def __init__(self) -> None:
        self.requests: list = []  # (t_body, t_done, query, body)

    async def handle(self, reader: asyncio.StreamReader,
                     writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                head = await reader.readuntil(b"\r\n\r\n")
                lines = head.decode("latin-1").split("\r\n")
                target = lines[0].split(" ")[1]
                headers = {k.lower(): v.strip() for k, v in
                           (ln.split(":", 1) for ln in lines[1:] if ln)}
                if headers.get("expect", "").lower() == "100-continue":
                    writer.write(b"HTTP/1.1 100 Continue\r\n\r\n")
                if headers.get("transfer-encoding", "").lower() == "chunked":
                    parts = []
                    while True:
                        size = int((await reader.readline()).split(b";")[0],
                                   16)
                        if size == 0:
                            await reader.readline()
                            break
                        parts.append(await reader.readexactly(size))
                        await reader.readline()
                    body = b"".join(parts)
                else:
                    body = await reader.readexactly(
                        int(headers.get("content-length", "0")))
                t_body = time.perf_counter()
                query = parse_qs(urlsplit(target).query).get("query", [""])[0]
                writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: text/plain"
                             b"\r\nContent-Length: 0\r\n\r\n")
                await writer.drain()
                self.requests.append((t_body, time.perf_counter(), query,
                                      body))
        except (asyncio.IncompleteReadError, ConnectionResetError,
                BrokenPipeError):
            pass
        finally:
            writer.close()

    # -- after the window ----------------------------------------------------

    def service(self, t_open: float, t_close: float) -> dict:
        inside = [(b - a, len(body)) for a, b, _, body in self.requests
                  if t_open <= a <= t_close]
        return {"requests": len(inside),
                "service_s": float(sum(s for s, _ in inside)),
                "bytes": int(sum(n for _, n in inside))}

    def received(self) -> dict:
        """The data INSERTs' rows as columns, parsed from the kept bytes."""
        import pyarrow as pa
        import pyarrow.compute as pc
        from pyarrow import csv

        bodies = [body for _, _, q, body in self.requests
                  if q.startswith("INSERT INTO") and COMMIT_LOG not in q]
        names = ["aid", "bid", "abalance", "filler", "change", "seq"]
        if not bodies:
            z = np.zeros(0, dtype=np.int64)
            return {"aid": z, "bid": z, "abalance": z, "commit_lsn": z,
                    "tx_ordinal": z, "bad_text_rows": 0}
        tab = csv.read_csv(
            io.BytesIO(b"".join(bodies)),
            read_options=csv.ReadOptions(column_names=names),
            parse_options=csv.ParseOptions(delimiter="\t", quote_char=False,
                                           escape_char=False),
            convert_options=csv.ConvertOptions(column_types={
                "aid": pa.int64(), "bid": pa.int64(), "abalance": pa.int64(),
                "filler": pa.string(), "change": pa.string(),
                "seq": pa.string()}, strings_can_be_null=False))
        bad = pc.sum(pc.or_(
            pc.not_equal(tab["filler"], pgbench.FILLER.decode()),
            pc.not_equal(tab["change"], "UPSERT"))).as_py() or 0
        seq = tab["seq"].combine_chunks()
        n = len(seq)
        lengths = pc.binary_length(seq)
        if n and (pc.min(lengths).as_py() != SEQ_WIDTH
                  or pc.max(lengths).as_py() != SEQ_WIDTH):
            raise ValueError("a sequence key is not 50 characters wide")
        raw = np.frombuffer(seq.buffers()[2], dtype=np.uint8)
        start = seq.offset * SEQ_WIDTH
        text = raw[start:start + n * SEQ_WIDTH].reshape(n, SEQ_WIDTH)
        return {"aid": tab["aid"].to_numpy(), "bid": tab["bid"].to_numpy(),
                "abalance": tab["abalance"].to_numpy(),
                "commit_lsn": _hex16(text[:, 0:16]),
                "tx_ordinal": _hex16(text[:, 17:33]),
                "bad_text_rows": int(bad)}


def _hex16(chars: np.ndarray) -> np.ndarray:
    digits = np.where(chars >= ord("a"), chars - (ord("a") - 10),
                      chars - ord("0")).astype(np.uint64)
    shifts = (np.arange(15, -1, -1, dtype=np.uint64) * np.uint64(4))
    return (digits << shifts[None, :]).sum(axis=1).astype(np.int64)


def verify(sink: Sink, cmd: dict) -> dict:
    """Hold what the sink received to the reference. `cmd` carries the
    seed, the stream's layout (run lengths of transaction sizes) and the
    row ranges that were sent and that the flush position has passed."""
    rows = np.asarray(cmd["tx_rows"], dtype=np.int64)
    layout = pgbench.TxLayout.build(
        np.repeat(rows[:, 0], rows[:, 1]), cmd["first_aid"])
    ref = pgbench.accounts_columns(cmd["seed"], int(layout.rows.sum()),
                                   cmd["first_aid"])
    out = reference.verify(ref, cmd["first_aid"], cmd["need"], cmd["sent"],
                           sink.received(),
                           layout.row_coordinates(0, len(layout.rows)))
    out["service"] = sink.service(cmd["t_open"], cmd["t_close"])
    return out


async def serve(sink: Sink, commands: "asyncio.Queue") -> None:
    server = await asyncio.start_server(sink.handle, "127.0.0.1", 0,
                                        limit=1 << 20)
    emit("listening", port=server.sockets[0].getsockname()[1])
    while True:
        line = await commands.get()
        if line is None:
            break
        cmd = json.loads(line)
        if cmd["cmd"] == "verify":
            try:
                emit("verified", **verify(sink, cmd))
            except Exception as e:  # report, the harness decides
                emit("verified", error=f"{type(e).__name__}: {e}")
        elif cmd["cmd"] == "quit":
            break
    server.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpus", required=True)
    args = ap.parse_args(argv)
    os.sched_setaffinity(0, {int(c) for c in args.cpus.split(",")})

    async def amain() -> None:
        loop = asyncio.get_running_loop()
        commands: asyncio.Queue = asyncio.Queue()

        def read_stdin() -> None:
            for line in sys.stdin:
                loop.call_soon_threadsafe(commands.put_nowait, line.strip())
            loop.call_soon_threadsafe(commands.put_nowait, None)

        threading.Thread(target=read_stdin, daemon=True).start()
        await serve(Sink(), commands)

    asyncio.run(amain())
    return 0


if __name__ == "__main__":
    sys.exit(main())
