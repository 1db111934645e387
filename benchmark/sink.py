"""The benchmark's fake ClickHouse: an HTTP endpoint in a process of its own
(never imports JAX) that, inside the window, only reads each request's body,
keeps the bytes and answers 200. It stamps its own service time — body
complete to response written — so the cell can show the sink did not set the
pace. Parsing the TSV bodies — each table's by that table's columns from the
configuration's file — and comparing them with the reference happens on the
`verify` command, after the window has closed.

Control: JSON lines on stdin (`verify`, `quit`), events on stdout.
"""

from __future__ import annotations

import argparse
import asyncio
import io
import json
import os
import re
import sys
import threading
import time
from urllib.parse import parse_qs, urlsplit

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import oplog  # noqa: E402
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

    def received(self, tables: list, database: str) -> dict:
        """{table id: {"copy": rows or None, "cdc": rows or None}}: the data
        INSERTs' rows of each table, parsed from the kept bytes in the order
        they came. A row whose sequence key has no commit is a copied row."""
        out = {}
        for table in tables:
            name = "`%s`.`%s`" % (database, _escaped(table["name"]))
            bodies = [body for _, _, q, body in self.requests
                      if q.startswith(f"INSERT INTO {name} ")]
            rows = _parse_tsv(table, b"".join(bodies)) if bodies else None
            out[int(table["id"])] = {"copy": None, "cdc": None}
            if rows is None:
                continue
            copied = rows["commit_lsn"] == 0
            for key, mask in (("copy", copied), ("cdc", ~copied)):
                if mask.all():
                    out[int(table["id"])][key] = rows
                elif mask.any():
                    out[int(table["id"])][key] = _only(rows, mask)
        return out


def _only(rows: dict, mask: np.ndarray) -> dict:
    """The parsed rows that `mask` keeps."""
    out = dict(rows)
    for k in ("change", "commit_lsn", "tx_ordinal"):
        out[k] = rows[k][mask]
    out["cols"] = [tuple(None if a is None else reference._pick(a, mask)
                         for a in c) for c in rows["cols"]]
    return out


def _escaped(name: str) -> str:
    """The destination's table name: `schema_table`, underscores doubled."""
    schema, table = name.split(".")
    return schema.replace("_", "__") + "_" + table.replace("_", "__")


_TSV_UNESCAPE = {"\\\\": "\\", "\\t": "\t", "\\n": "\n", "\\r": "\r"}


def _parse_tsv(table: dict, body: bytes) -> dict:
    """One table's TabSeparated rows as plain columns (`reference.py`'s
    form): the table's columns by their types, then the change label and
    the sequence key `{commit:016x}/{tx_ordinal:016x}/{ordinal:016x}`."""
    import pyarrow as pa
    import pyarrow.compute as pc
    from pyarrow import csv

    columns = table["columns"]
    names = [c["name"] for c in columns] + ["_change", "_seq"]
    arrow_type = {"bool": pa.string(), "int2": pa.int64(), "int4": pa.int64(),
                  "int8": pa.int64(), "float8": pa.float64(),
                  "date": pa.date32(), "timestamp": pa.timestamp("us"),
                  "timestamptz": pa.timestamp("us")}
    tab = csv.read_csv(
        io.BytesIO(body),
        read_options=csv.ReadOptions(column_names=names),
        parse_options=csv.ParseOptions(delimiter="\t", quote_char=False,
                                       escape_char=False),
        convert_options=csv.ConvertOptions(
            column_types={c["name"]: arrow_type.get(c["type"], pa.string())
                          for c in columns}
            | {"_change": pa.string(), "_seq": pa.string()},
            null_values=["\\N"], strings_can_be_null=True))
    n = len(tab)
    cols = []
    for c in columns:
        col = tab[c["name"]].combine_chunks()
        null = col.is_null().to_numpy(zero_copy_only=False)
        kind = c["type"]
        if kind in reference.TEXT_TYPES:
            if pc.any(pc.match_substring(col, "\\")).as_py():
                col = pa.array([None if v is None else re.sub(
                    r"\\[\\tnr]", lambda m: _TSV_UNESCAPE[m.group(0)], v)
                    for v in col.to_pylist()], type=pa.string())
            values = col
        elif kind == "numeric" or kind not in arrow_type:
            values = col.to_pylist()
        elif kind == "bool":
            values = pc.equal(col, "true").fill_null(False).to_numpy(
                zero_copy_only=False)
        elif kind == "float8":
            values = col.fill_null(0.0).to_numpy(zero_copy_only=False)
        else:
            if kind == "date":
                col = col.cast(pa.int32())
            values = col.cast(pa.int64()).fill_null(0).to_numpy(
                zero_copy_only=False)
        cols.append((values, null, None))
    label = tab["_change"].combine_chunks()
    seq = tab["_seq"].combine_chunks()
    lengths = pc.binary_length(seq)
    if n and (pc.min(lengths).as_py() != SEQ_WIDTH
              or pc.max(lengths).as_py() != SEQ_WIDTH):
        raise ValueError("a sequence key is not 50 characters wide")
    raw = np.frombuffer(seq.buffers()[2], dtype=np.uint8)
    start = seq.offset * SEQ_WIDTH
    text = raw[start:start + n * SEQ_WIDTH].reshape(n, SEQ_WIDTH)
    # a label that is neither UPSERT nor DELETE matches no change kind
    change = np.where(
        pc.equal(label, "UPSERT").fill_null(False).to_numpy(
            zero_copy_only=False), reference.GOT_UPSERT,
        np.where(pc.equal(label, "DELETE").fill_null(False).to_numpy(
            zero_copy_only=False), reference.GOT_DELETE, 255)
    ).astype(np.uint8)
    return {"cols": cols, "change": change,
            "commit_lsn": _hex16(text[:, 0:16]),
            "tx_ordinal": _hex16(text[:, 17:33]),
            "old": None, "delete_is_key": None}


def _hex16(chars: np.ndarray) -> np.ndarray:
    digits = np.where(chars >= ord("a"), chars - (ord("a") - 10),
                      chars - ord("0")).astype(np.uint64)
    shifts = (np.arange(15, -1, -1, dtype=np.uint64) * np.uint64(4))
    return (digits << shifts[None, :]).sum(axis=1).astype(np.int64)


def verify(sink: Sink, cmd: dict) -> dict:
    """Hold what the sink received to the reference. `cmd` names the
    configuration and the mix, the seed and the seconds — the generator
    makes the log again from them — and how many of the log's events were
    sent and how many the flush position has passed."""
    with open(cmd["config"]) as f:
        config = json.load(f)
    with open(cmd["traffic"]) as f:
        traffic = json.load(f)
    if cmd["rehearse"]:
        config.update(config.get("rehearsal", {}))
        traffic.update(traffic.get("rehearsal", {}))
    generator = oplog.load_generator(config, cmd["config"])
    tables = oplog.tables_of(config)
    out = reference.verify_received(
        tables, generator.snapshot(config, traffic, cmd["seed"]),
        generator.stream(config, traffic, cmd["seed"], cmd["seconds"]),
        cmd["sent"], cmd["need"],
        sink.received(tables, config["destination"].get("database",
                                                        "default")))
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
