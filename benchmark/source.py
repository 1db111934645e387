"""The benchmark's source: a wire-level fake Postgres and the load generator,
in one process of their own (never imports JAX).

It answers the pipeline's catalog, slot and snapshot queries with the
program's own `FakePgServer` (startup, simple/extended query, slot
management), and replaces the two hot paths with prebuilt bytes:

  * COPY OUT sends each table's CopyData rows, rendered during set-up from
    the deployment's snapshot (`wire.py`, `deployments/<generator>.py`);
  * START_REPLICATION on the apply slot plays one traffic mix over the
    deployment's operation log: every transaction is one prebuilt buffer, written on its due time (paced) or
    as fast as the socket takes it (backlog). Standby status updates are
    read by a task of their own and stamped on arrival, on the same clock
    as the due times.

Control: JSON lines on stdin (`{"cmd": "go" | "report" | "quit"}`), events
and the final report as JSON lines on stdout. What the numbers mean is in `PERF.md`.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import re
import struct
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import oplog  # noqa: E402
import wire  # noqa: E402

CLOCK_US = 1_700_000_000_000_000  # fixed send/commit stamp inside frames
RENDER_CHUNK_ROWS = 256_000
COPY_CHUNK_ROWS = 250_000
RENDER_THREADS = 2
COPY_SEND_BYTES = 1 << 18


def emit(event: str, **fields) -> None:
    sys.stdout.write(json.dumps({"event": event, **fields}) + "\n")
    sys.stdout.flush()


def clipped(intervals, lo: float, hi: float) -> float:
    """Seconds of `intervals` [(t0, t1), ...] that fall inside [lo, hi]."""
    return float(sum(max(0.0, min(b, hi) - max(a, lo))
                     for a, b in intervals))


class Plan:
    """One traffic mix over one configuration: the transaction layout, the
    prebuilt buffers, and the readings taken while it plays."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, generator):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.seconds = float(seconds)
        self.kind = traffic["kind"]
        self.tables = oplog.tables_of(config)
        self.generator = generator
        self.go = asyncio.Event()
        self.stop = asyncio.Event()
        self.flush_t: list = []
        self.flush_lsn: list = []
        self.blocked: list = []   # (t0, t1) of every wait in drain()
        self.sent_t: list = []
        self.sent_tx = 0
        self.exhausted = False
        self.t_open = self.t_close = None
        self.open_lsn = self.close_lsn = None
        self.capped = False
        self.copy_serving_s = self.copy_blocked_s = 0.0
        self.copies_served = 0
        self.stream = self.layout = None
        self.bufs: list = []
        self.payload_bytes = 0
        self.copies: dict = {}  # table name -> (blob, row offsets)

    def render(self) -> None:
        t0 = time.perf_counter()
        extra = getattr(self.generator, "TEXT_BLOCKS", None)
        self.stream = self.generator.stream(self.config, self.traffic,
                                            self.seed, self.seconds)
        if self.stream is not None:
            self.layout = self.stream.layout
        snapshot = self.generator.snapshot(self.config, self.traffic,
                                           self.seed)
        for table in self.tables:
            cols = snapshot.get(int(table["id"]))
            n = oplog.n_rows(cols) if cols else 0
            blobs, offsets, at = [], [np.zeros(1, dtype=np.int64)], 0
            for i in range(0, n, COPY_CHUNK_ROWS):
                rows = slice(i, min(n, i + COPY_CHUNK_ROWS))
                blob, off = wire.render_copy_rows(
                    table, [c.pick(rows) for c in cols],
                    rows.stop - rows.start, extra)
                blobs.append(blob)
                offsets.append(off[1:] + at)
                at += len(blob)
            self.copies[table["name"]] = (
                memoryview(np.concatenate(blobs)) if blobs else b"",
                np.concatenate(offsets))
        if self.stream is not None:
            stream = self.stream
            kinds = wire.old_kinds(self.tables, stream)
            local = stream.local_index()
            oids = {**wire.TYPE_OIDS,
                    **getattr(self.generator, "TYPE_OIDS", {})}
            relations = [wire.relation_payload(t, oids) for t in self.tables]
            cuts = chunk_cuts(self.layout.rows)

            def chunk(j: int):
                return wire.render_transactions(
                    self.tables, stream, kinds, local, int(cuts[j]),
                    int(cuts[j + 1]), CLOCK_US,
                    relations if j == 0 else None, extra)

            # numpy releases the interpreter lock in the large copies, so
            # two threads render nearly twice as fast on the source's cores
            with ThreadPoolExecutor(RENDER_THREADS) as pool:
                for bufs, nbytes in pool.map(chunk, range(len(cuts) - 1)):
                    self.bufs += bufs
                    self.payload_bytes += nbytes
        self.render_s = time.perf_counter() - t0

    # -- readings ------------------------------------------------------------

    def on_flush(self, t: float, lsn: int) -> None:
        self.flush_t.append(t)
        self.flush_lsn.append(lsn)
        if self.kind != "backlog" or not self.sent_t:
            return
        if self.t_open is None:
            if t >= self.sent_t[0] + float(self.traffic["warmup_seconds"]):
                self.t_open, self.open_lsn = t, lsn
                emit("window_open", t=t)
        elif self.t_close is None and t >= self.t_open + self.seconds:
            self._close(t, lsn)

    def _close(self, t: float, lsn: int) -> None:
        self.t_close, self.close_lsn = t, lsn
        self.stop.set()
        emit("window_close", t=t)

    async def close_cap(self) -> None:
        """A run whose closing ack never comes ends at the cap, and the
        time up to the cap counts."""
        cap = float(self.traffic["close_cap_seconds"])
        while self.t_close is None:
            await asyncio.sleep(0.05)
            now = time.perf_counter()
            if self.t_open is not None \
                    and now >= self.t_open + self.seconds + cap:
                self.capped = True
                self._close(now, self.flush_lsn[-1])

    # -- the mixes -----------------------------------------------------------

    async def _send(self, w, db, k: int) -> None:
        self.sent_t.append(time.perf_counter())
        w.write(self.bufs[k])
        t0 = time.perf_counter()
        await w.drain()
        self.blocked.append((t0, time.perf_counter()))
        self.sent_tx = k + 1
        db._lsn = int(self.layout.end_lsn[k])
        # drain() returns without yielding while the kernel takes every
        # byte; the status reader must still get its turn
        await asyncio.sleep(0)

    async def run_backlog(self, w, db) -> None:
        cap = asyncio.ensure_future(self.close_cap())
        try:
            for k in range(len(self.bufs)):
                if self.stop.is_set():
                    break
                await self._send(w, db, k)
            else:
                self.exhausted = True
                emit("backlog_exhausted", sent=self.sent_tx)
            await self.stop.wait()
        finally:
            cap.cancel()

    async def run_paced(self, w, db) -> None:
        tr = self.traffic
        period = 1.0 / float(tr["transactions_per_second"])
        n = self.n_paced
        self.t_start = time.perf_counter() + 0.05
        self.due = self.t_start + period * np.arange(n)
        for k in range(n):
            if k == self.n_warm:
                self.t_open = float(self.due[k])
                emit("window_open", t=self.t_open)
            while True:
                wait = self.due[k] - time.perf_counter()
                if wait <= 0:
                    break
                # sleep coarsely, then yield-spin the last 5 ms: the event
                # loop's timer ran up to a millisecond late on the chip's
                # host (PERF.md, the paced sweep)
                await asyncio.sleep(wait - 0.005 if wait > 0.005 else 0)
            await self._send(w, db, k)
        grace = time.perf_counter() + float(tr["grace_seconds"])
        last = int(self.layout.end_lsn[n - 1])
        while time.perf_counter() < grace and \
                (not self.flush_lsn or self.flush_lsn[-1] < last):
            await asyncio.sleep(0.005)
        self.t_close = time.perf_counter()
        emit("window_close", t=self.t_close)
        self.stop.set()

    @property
    def n_warm(self) -> int:
        tr = self.traffic
        return round(float(tr["warmup_seconds"])
                     * float(tr["transactions_per_second"]))

    @property
    def n_paced(self) -> int:
        """The generator makes one transaction for each due time."""
        return len(self.layout.rows)

    # -- the report ----------------------------------------------------------

    def report(self) -> dict:
        out = {"kind": self.kind, "render_s": self.render_s,
               "status_updates": len(self.flush_t),
               "sent_tx": self.sent_tx,
               "final_flush_lsn": self.flush_lsn[-1] if self.flush_lsn else 0}
        lay = self.layout
        if lay is not None:
            out["durable_tx"] = min(self.sent_tx, lay.durable_count(
                out["final_flush_lsn"]))
            out["payload_bytes_per_row"] = \
                self.payload_bytes / max(1, int(lay.rows.sum()))
            out["table_event_share"] = {
                self.tables[t]["name"]: float(n) / max(1, len(
                    self.stream.table))
                for t, n in enumerate(np.bincount(
                    self.stream.table, minlength=len(self.tables)))}
        if self.kind == "backlog":
            cum = np.concatenate(([0], np.cumsum(lay.rows)))
            if self.t_open is None or self.t_close is None:
                out["error"] = "the window never opened or never closed"
                return out
            k0 = min(self.sent_tx, lay.durable_count(self.open_lsn))
            k1 = min(self.sent_tx, lay.durable_count(self.close_lsn))
            window = self.t_close - self.t_open
            out.update(
                events=int(cum[k1] - cum[k0]), transactions=int(k1 - k0),
                window_s=window,
                window_over_s=window - self.seconds, capped=self.capped,
                exhausted=self.exhausted, t_open=self.t_open,
                t_close=self.t_close,
                blocked_s=clipped(self.blocked, self.t_open, self.t_close),
                acks_in_window=int(np.searchsorted(self.flush_t, self.t_close)
                                   - np.searchsorted(self.flush_t,
                                                     self.t_open)))
        elif self.kind == "paced":
            n0, n1 = self.n_warm, self.n_paced
            flush_t = np.asarray(self.flush_t)
            flush_lsn = np.maximum.accumulate(np.asarray(self.flush_lsn))
            at = np.searchsorted(flush_lsn, lay.end_lsn[n0:n1], side="left")
            done = at < len(flush_t)
            lag = np.full(n1 - n0, np.inf)
            lag[done] = flush_t[at[done]] - self.due[n0:n1][done]
            late = np.asarray(self.sent_t[n0:n1]) - self.due[n0:n1]
            half = (n1 - n0) // 2
            out.update(
                attempted=int(n1 - n0), unflushed=int((~done).sum()),
                lag_ms=(lag * 1e3).tolist(), late_ms=(late * 1e3).tolist(),
                lag_first_half_ms=_pcts(lag[:half]),
                lag_second_half_ms=_pcts(lag[half:]),
                t_open=self.t_open, t_close=self.t_close,
                window_s=self.t_close - self.t_open,
                blocked_s=clipped(self.blocked, self.t_open, self.t_close))
        else:
            out.update(copies_served=self.copies_served,
                       copy_serving_s=self.copy_serving_s,
                       copy_blocked_s=self.copy_blocked_s)
        return out


def chunk_cuts(rows: np.ndarray) -> np.ndarray:
    """Transaction indices at which the render is cut into chunks of
    RENDER_CHUNK_ROWS events or so. Where the transactions' sizes repeat
    with a period, every chunk is a whole number of periods and so of one
    size, and the renderer's scratch arrays are made once."""
    n = len(rows)
    period = next((p for p in range(1, min(n // 2, 2048) + 1)
                   if np.array_equal(rows[p:], rows[:-p])), 0)
    if period:
        step = period * max(1, RENDER_CHUNK_ROWS
                            // max(1, int(rows[:period].sum())))
        return np.append(np.arange(0, n, step), n)
    cum = np.cumsum(rows)
    total = int(cum[-1]) if n else 0
    return np.unique(np.concatenate((
        [0], np.searchsorted(cum, np.arange(
            RENDER_CHUNK_ROWS, total, RENDER_CHUNK_ROWS)) + 1, [n])))


def _pcts(lag: np.ndarray) -> dict:
    ms = np.sort(lag) * 1e3
    if not len(ms):
        return {}
    return {"p50": float(ms[len(ms) // 2]),
            "p95": float(ms[min(len(ms) - 1, int(len(ms) * 0.95))])}


def table_schema(table: dict, type_oids: "dict | None" = None):
    """The program's TableSchema of one of a configuration's tables. A
    column's `key` is true or its 1-based place in the key; `nullable`
    defaults to "not a key column"; the type modifier is the file's
    `modifier`, or follows from `text_bytes` / `precision` and `scale`."""
    from etl_tpu.models import ColumnSchema, Oid, TableName, TableSchema

    def oid(kind: str) -> int:
        if type_oids and kind in type_oids:
            return int(type_oids[kind])
        return getattr(Oid, kind.upper())

    def modifier(c: dict) -> dict:
        if "modifier" in c:
            return {"modifier": int(c["modifier"])}
        if c["type"] in ("bpchar", "varchar") and "text_bytes" in c:
            return {"modifier": int(c["text_bytes"]) + 4}
        if c["type"] == "numeric" and "precision" in c:
            return {"modifier": ((int(c["precision"]) << 16)
                                 | int(c.get("scale", 0))) + 4}
        return {}

    ordinal = {i: k + 1 for k, i in enumerate(oplog.key_indices(table))}
    namespace, name = table["name"].split(".")
    return TableSchema(
        int(table["id"]), TableName(namespace, name),
        tuple(ColumnSchema(c["name"], oid(c["type"]),
                           nullable=bool(c.get("nullable", i not in ordinal)),
                           primary_key_ordinal=ordinal.get(i), **modifier(c))
              for i, c in enumerate(table["columns"])))


def make_server(plan: Plan):
    """The program's wire-level fake server with the hot paths replaced."""
    from etl_tpu.postgres.fake import FakeDatabase
    from etl_tpu.testing import fake_pg_server as fps

    db = FakeDatabase()
    oids = getattr(plan.generator, "TYPE_OIDS", None)
    for table in plan.tables:
        db.create_table(table_schema(table, oids), rows=[])
        db.set_replica_identity(int(table["id"]),
                                table.get("replica_identity", "d"))
    db.create_publication(plan.config["pipeline"]["publication"],
                          [int(t["id"]) for t in plan.tables])
    db._lsn = oplog.BASE_LSN
    snapshot_rows = {int(t["id"]): len(plan.copies[t["name"]][1]) - 1
                     for t in plan.tables}

    class BenchPgServer(fps.FakePgServer):
        async def _try_handle(self, sess, norm, sql):
            m = re.search(r"FROM pg_class WHERE oid = (\d+)", norm)
            if m and "reltuples" in norm:
                # planner statistics of the snapshot: 64 rows to a page,
                # as the base server counts them
                n = snapshot_rows.get(int(m.group(1)), 0)
                self._send_rows(sess.writer, ["reltuples", "relpages"],
                                [[str(n), str(max(1, n // 64))]])
                return True
            return await super()._try_handle(sess, norm, sql)

        async def _copy_out(self, sess, m):
            w = sess.writer
            blob, off = plan.copies.get(f"{m.group(2)}.{m.group(3)}",
                                        (b"", np.zeros(1, dtype=np.int64)))
            n = len(off) - 1
            lo = int(m.group(4)) * 64 if m.group(4) else 0
            hi = min(int(m.group(5)) * 64 if m.group(5) else n, n)
            n_cols = len(m.group(1).split(","))
            w.write(fps._msg(b"H", struct.pack(">bh", 0, n_cols)
                             + b"\x00\x00" * n_cols))
            t_begin = time.perf_counter()
            blocked = 0.0
            if hi > lo:
                at, end = int(off[lo]), int(off[hi])
                while at < end:
                    w.write(blob[at:min(end, at + COPY_SEND_BYTES)])
                    at += COPY_SEND_BYTES
                    t0 = time.perf_counter()
                    await w.drain()
                    blocked += time.perf_counter() - t0
            w.write(fps._msg(b"c"))
            w.write(fps._command_complete(f"COPY {max(0, hi - lo)}"))
            w.write(fps.READY)
            await w.drain()
            if hi > lo:
                plan.copy_serving_s += time.perf_counter() - t_begin
                plan.copy_blocked_s += blocked
                plan.copies_served += 1

        async def _start_replication(self, sess, slot_name, start_lsn, opts):
            w = sess.writer
            slot = db.slots.get(slot_name)
            if slot is None:
                w.write(fps._error("42704",
                                   f'slot "{slot_name}" does not exist'))
                w.write(fps.READY)
                await w.drain()
                return
            is_apply = "_apply_" in slot_name
            slot.active = True
            w.write(fps._msg(b"W", struct.pack(">bh", 0, 0)))
            await w.drain()
            reader = asyncio.ensure_future(
                self._read_status(sess, slot, is_apply))
            try:
                if is_apply and plan.kind != "copy":
                    await self._idle(w, reader, plan.go)
                    if plan.go.is_set():
                        emit("traffic_start", t=time.perf_counter())
                        await (plan.run_backlog if plan.kind == "backlog"
                               else plan.run_paced)(w, db)
                await self._idle(w, reader, None)
            except (ConnectionResetError, BrokenPipeError):
                pass
            finally:
                slot.active = False
                reader.cancel()
                await asyncio.gather(reader, return_exceptions=True)

        async def _idle(self, w, reader, until) -> None:
            """Keepalives that ask for a reply, every 50 ms, as an idle
            walsender is asked for them; none while traffic plays."""
            waits = {reader}
            if until is not None:
                waits.add(asyncio.ensure_future(until.wait()))
            try:
                while not any(t.done() for t in waits):
                    w.write(wire.keepalive_frame(
                        int(db.current_lsn), CLOCK_US, True))
                    await w.drain()
                    await asyncio.wait(waits, timeout=0.05,
                                       return_when=asyncio.FIRST_COMPLETED)
            finally:
                for t in waits - {reader}:
                    t.cancel()

        async def _read_status(self, sess, slot, is_apply) -> None:
            r = sess.reader
            try:
                while True:
                    header = await r.readexactly(5)
                    (length,) = struct.unpack(">i", header[1:5])
                    payload = await r.readexactly(length - 4)
                    if header[:1] == b"d" and payload[:1] == b"r":
                        now = time.perf_counter()
                        flushed = int.from_bytes(payload[9:17], "big")
                        if flushed > int(slot.confirmed_flush):
                            slot.confirmed_flush = type(
                                slot.confirmed_flush)(flushed)
                            if is_apply:
                                plan.on_flush(now, flushed)
                    elif header[:1] in (b"c", b"X"):
                        return
            except (asyncio.IncompleteReadError, ConnectionResetError):
                return

    return BenchPgServer(db)


async def serve(plan: Plan, commands: "asyncio.Queue") -> None:
    server = make_server(plan)
    await server.start()
    emit("listening", port=server.port, render_s=plan.render_s,
         transactions=len(plan.bufs))
    while True:
        line = await commands.get()
        cmd = json.loads(line)["cmd"] if line else "quit"
        if cmd == "go":
            plan.go.set()
        elif cmd == "report":
            emit("report", **plan.report())
        elif cmd == "quit":
            break
    await server.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cpus", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    os.sched_setaffinity(0, {int(c) for c in args.cpus.split(",")})
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    if args.rehearse:
        config.update(config.get("rehearsal", {}))
        traffic.update(traffic.get("rehearsal", {}))

    async def amain() -> None:
        plan = Plan(config, traffic, args.seed, args.seconds,
                    oplog.load_generator(config, args.config))
        plan.render()
        loop = asyncio.get_running_loop()
        commands: asyncio.Queue = asyncio.Queue()

        def read_stdin() -> None:
            for line in sys.stdin:
                loop.call_soon_threadsafe(commands.put_nowait, line.strip())
            loop.call_soon_threadsafe(commands.put_nowait, None)

        threading.Thread(target=read_stdin, daemon=True).start()
        await serve(plan, commands)

    asyncio.run(amain())
    return 0


if __name__ == "__main__":
    sys.exit(main())
