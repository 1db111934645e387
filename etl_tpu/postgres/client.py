"""PgReplicationClient: ReplicationSource over the wire protocol.

Reference parity: `PgReplicationClient` (crates/etl/src/postgres/client/
raw.rs:212) + `PgReplicationTransaction` (transaction.rs:727):
replication-protocol connections with per-worker application names
(raw.rs:237-270), slot CRUD with exported snapshots (raw.rs:419-529),
publication queries (raw.rs:531-622), schema introspection with replica
identity and PG15 publication column lists (transaction.rs:750-768),
CTID-bounded COPY streams (transaction.rs:780,868), START_REPLICATION with
pgoutput options (raw.rs:623), server version detection (raw.rs:308).
"""

from __future__ import annotations

import logging
import ssl as ssl_mod
import time
from typing import AsyncIterator

from ..config.pipeline import PgConnectionConfig
from ..models.errors import ErrorKind, EtlError
from ..models.lsn import Lsn
from ..telemetry import spans
from ..telemetry.metrics import (ETL_INTAKE_BYTES_TOTAL,
                                 ETL_INTAKE_DRAIN_SECONDS,
                                 ETL_INTAKE_FRAMES_TOTAL, registry)
from ..models.schema import (ColumnMask, ColumnSchema, ReplicatedTableSchema,
                             TableId, TableName, TableSchema)
from .codec import pgoutput
from .version import POSTGRES_15, meets_version, parse_server_version
from .source import (CopyStream, CreatedSlot, ReplicationSource,
                     ReplicationStream, SlotInfo)
from .wire import PgServerError, PgWireConnection

logger = logging.getLogger("etl_tpu.postgres.client")


def _quote_literal(s: str) -> str:
    return "'" + s.replace("'", "''") + "'"


def wire_connection_from_config(config: PgConnectionConfig, *,
                                application_name: str,
                                replication: bool = False
                                ) -> PgWireConnection:
    """THE connection builder shared by the replication client and the
    PostgresStore: TLS context from config.tls, secret-wrapper password
    unwrapping via .expose() — divergence here means the store and the
    client authenticate differently against the same config."""
    ssl_context = None
    if config.tls.enabled:
        ssl_context = ssl_mod.create_default_context()
        if config.tls.trusted_root_certs:
            ssl_context.load_verify_locations(
                cadata=config.tls.trusted_root_certs)
    password = config.password
    expose = getattr(password, "expose", None)
    return PgWireConnection(
        host=config.host, port=config.port, database=config.name,
        user=config.username, password=expose() if expose else password,
        application_name=application_name, replication=replication,
        ssl_context=ssl_context, connect_timeout_s=config.connect_timeout_s)


class _WireReplicationStream(ReplicationStream):
    def __init__(self, conn: PgWireConnection):
        self._conn = conn
        self._closed = False
        self._pending_error: Exception | None = None

    def __aiter__(self) -> AsyncIterator[pgoutput.ReplicationFrame]:
        return self._frames()

    async def _frames(self):
        while not self._closed:
            if self._pending_error is not None:
                err, self._pending_error = self._pending_error, None
                raise err
            payload = await self._conn.copy_both_read()
            if payload is None:
                return
            yield pgoutput.decode_replication_frame(payload)

    def drain_buffered(self, max_n: int) -> list:
        """Parse CopyData frames already sitting in the stream reader's
        buffer without awaiting — under a WAL burst the socket delivers
        many frames per event-loop wakeup and paying a select() per frame
        caps CDC throughput (CPython StreamReader internals; degrades to
        the awaited path when unavailable)."""
        out: list = []
        if self._pending_error is not None:
            err, self._pending_error = self._pending_error, None
            raise err
        reader = getattr(self._conn, "_reader", None)
        buf = getattr(reader, "_buffer", None)
        if buf is None or self._closed:
            return out
        # one span per non-empty drain (never per frame): the per-frame
        # parse, apart from the segmentation that follows it in
        # ReplicationStream.drain_spans
        with spans.span("intake.drain", ETL_INTAKE_DRAIN_SECONDS) as sp:
            buffered = len(buf)
            while len(out) < max_n and len(buf) >= 5:
                length = int.from_bytes(buf[1:5], "big")
                if len(buf) < 1 + length:
                    break
                tag = buf[0:1]
                payload = bytes(buf[5 : 1 + length])
                del buf[: 1 + length]
                if tag == b"d":
                    out.append(pgoutput.decode_replication_frame(payload))
                elif tag == b"E":
                    # do NOT raise here: frames already parsed in this
                    # pass were deleted from the reader buffer and would
                    # be lost, forcing a restart-from-durable
                    # re-delivery. Hand the caller what it has; the
                    # stored error surfaces on the next drain/iteration.
                    from .wire import PgServerError, _parse_error_fields

                    self._pending_error = PgServerError(
                        _parse_error_fields(payload))
                    break
                elif tag == b"Z":
                    self._closed = True
                    break
                # 'c'/'C' and other tags: skip, same as copy_both_read
            getattr(reader, "_maybe_resume_transport", lambda: None)()
            if out:
                registry.counter_inc(ETL_INTAKE_FRAMES_TOTAL, len(out))
                registry.counter_inc(ETL_INTAKE_BYTES_TOTAL,
                                     buffered - len(buf))
            else:
                sp.drop()
        return out

    async def send_status_update(self, written: Lsn, flushed: Lsn,
                                 applied: Lsn,
                                 reply_requested: bool = False) -> None:
        await self._conn.copy_both_send(pgoutput.encode_standby_status_update(
            int(written), int(flushed), int(applied),
            int(time.time() * 1e6), reply_requested))

    async def close(self) -> None:
        self._closed = True
        await self._conn.close()


class _WireCopyStream(CopyStream):
    """Owns its connection; closes it when the COPY ends (or fails)."""

    def __init__(self, conn: PgWireConnection, sql: str):
        self._conn = conn
        self._sql = sql

    def __aiter__(self):
        return self._chunks()

    async def _chunks(self):
        try:
            async for chunk in self._conn.copy_out(self._sql):
                yield chunk
        finally:
            await self._conn.close()


class PgReplicationClient(ReplicationSource):
    """One replication-protocol connection to a real Postgres."""

    def __init__(self, config: PgConnectionConfig, *,
                 application_name: str = "etl_tpu"):
        self.config = config
        self.application_name = application_name
        self._conn: PgWireConnection | None = None
        self.server_version: int = 0  # e.g. 150004

    def _new_conn(self, replication: bool) -> PgWireConnection:
        return wire_connection_from_config(
            self.config, application_name=self.application_name,
            replication=replication)

    @property
    def conn(self) -> PgWireConnection:
        if self._conn is None:
            raise EtlError(ErrorKind.SOURCE_CONNECTION_FAILED,
                           "not connected")
        return self._conn

    async def connect(self) -> None:
        self._conn = self._new_conn(replication=True)
        await self._conn.connect()
        ver = self._conn.parameters.get("server_version", "0")
        self.server_version = parse_server_version(ver)

    async def close(self) -> None:
        if self._conn is not None:
            await self._conn.close()
            self._conn = None

    # -- catalog ----------------------------------------------------------------

    async def publication_exists(self, publication: str) -> bool:
        r = await self.conn.query(
            f"SELECT 1 FROM pg_publication WHERE pubname = "
            f"{_quote_literal(publication)}")
        return bool(r.rows)

    async def get_publication_table_ids(self,
                                        publication: str) -> list[TableId]:
        r = await self.conn.query(
            "SELECT c.oid FROM pg_publication_tables pt "
            "JOIN pg_namespace n ON n.nspname = pt.schemaname "
            "JOIN pg_class c ON c.relnamespace = n.oid "
            "AND c.relname = pt.tablename "
            f"WHERE pt.pubname = {_quote_literal(publication)} "
            "ORDER BY c.oid")
        return [int(row[0]) for row in r.rows]

    async def get_table_schema(self, table_id: TableId, publication: str,
                               snapshot_id: str | None = None
                               ) -> ReplicatedTableSchema:
        # schema + replica identity (reference transaction.rs:750-767)
        r = await self.conn.query(
            "SELECT n.nspname, c.relname, c.relreplident "
            "FROM pg_class c JOIN pg_namespace n ON n.oid = c.relnamespace "
            f"WHERE c.oid = {int(table_id)}")
        if not r.rows:
            raise EtlError(ErrorKind.PUBLICATION_TABLE_MISSING,
                           f"table {table_id}")
        nspname, relname, replident = r.rows[0]
        cols = await self.conn.query(
            "SELECT a.attname, a.atttypid, a.atttypmod, a.attnotnull, "
            "COALESCE(ikey.ord, 0), pg_get_expr(d.adbin, d.adrelid) "
            "FROM pg_attribute a "
            "LEFT JOIN pg_attrdef d ON d.adrelid = a.attrelid "
            "AND d.adnum = a.attnum "
            "LEFT JOIN (SELECT x.attnum_ord AS ord, x.attnum FROM ("
            "  SELECT generate_subscripts(i.indkey, 1) + 1 AS attnum_ord, "
            "         unnest(i.indkey) AS attnum FROM pg_index i "
            f"  WHERE i.indrelid = {int(table_id)} AND i.indisprimary"
            ") x) ikey ON ikey.attnum = a.attnum "
            f"WHERE a.attrelid = {int(table_id)} AND a.attnum > 0 "
            "AND NOT a.attisdropped ORDER BY a.attnum")
        columns = tuple(
            ColumnSchema(
                name=row[0], type_oid=int(row[1]), modifier=int(row[2]),
                nullable=row[3] == "f",
                primary_key_ordinal=int(row[4]) or None,
                default_expression=row[5])
            for row in cols.rows)
        schema = TableSchema(id=table_id,
                             name=TableName(nspname, relname),
                             columns=columns)
        n = len(columns)
        # publication column lists exist only on PG15+ (version gate per
        # reference transaction.rs:268 — pg_publication_tables.attnames is
        # not even a column on 14, the query would error); pre-15 every
        # column replicates
        repl_mask = ColumnMask.all_set(n)
        rowfilter_sql = None
        if meets_version(self.server_version, POSTGRES_15):
            filt = await self.conn.query(
                "SELECT pt.attnames, pt.rowfilter "
                "FROM pg_publication_tables pt "
                "JOIN pg_namespace ns ON ns.nspname = pt.schemaname "
                "JOIN pg_class pc ON pc.relnamespace = ns.oid "
                "AND pc.relname = pt.tablename "
                f"WHERE pt.pubname = {_quote_literal(publication)} "
                f"AND pc.oid = {int(table_id)}")
            if filt.rows and filt.rows[0][0] is not None:
                names = _parse_name_array(filt.rows[0][0])
                if names:
                    repl_mask = ColumnMask.from_column_names(schema, names)
            if filt.rows and len(filt.rows[0]) > 1:
                rowfilter_sql = filt.rows[0][1]
        identity = ColumnMask(c.is_primary_key for c in columns)
        if identity.count() == 0 and replident == "f":
            identity = ColumnMask.all_set(n)
        out = ReplicatedTableSchema(schema, repl_mask, identity)
        if rowfilter_sql:
            # fused decode filtering (ops/predicate.py): the publication's
            # WHERE clause rides the schema so the decoder compiles it
            # into the device program. Unsupported expressions stay
            # server-side only — the walsender filters them on PG15+.
            from ..ops.predicate import RowFilterError, parse_row_filter

            try:
                out = out.with_row_predicate(parse_row_filter(rowfilter_sql))
            except RowFilterError:
                logger.info("row filter %r on table %s is outside the "
                            "client-side envelope; relying on the "
                            "walsender", rowfilter_sql, table_id)
        return out

    async def get_row_filters(self, publication: str) -> "dict[TableId, str]":
        if not meets_version(self.server_version, POSTGRES_15):
            return {}  # row filters were added in Postgres 15
        r = await self.conn.query(
            "SELECT pc.oid, pt.rowfilter FROM pg_publication_tables pt "
            "JOIN pg_namespace ns ON ns.nspname = pt.schemaname "
            "JOIN pg_class pc ON pc.relnamespace = ns.oid "
            "AND pc.relname = pt.tablename "
            f"WHERE pt.pubname = {_quote_literal(publication)}")
        return {int(row[0]): row[1] for row in r.rows
                if len(row) > 1 and row[1]}

    async def get_current_wal_lsn(self) -> Lsn:
        r = await self.conn.query("SELECT pg_current_wal_lsn()")
        return Lsn(r.rows[0][0])

    # -- source migrations (reference postgres/migrations.rs:102-122) --------

    async def is_in_recovery(self) -> bool:
        r = await self.conn.query("SELECT pg_is_in_recovery()")
        return r.rows[0][0] == "t"

    async def applied_source_migrations(self) -> list[str]:
        from .wire import PgServerError

        try:
            r = await self.conn.query(
                "SELECT name FROM etl.source_migrations ORDER BY name")
        except PgServerError as e:
            # only 'relation/schema does not exist' means not-installed;
            # permission or transient errors must NOT trigger a re-run of
            # the migration script (it would fail or double-apply)
            if e.fields.get("C") in ("42P01", "3F000"):
                return []
            raise
        return [row[0] for row in r.rows]

    async def apply_source_migration(self, name: str, sql: str) -> None:
        await self.conn.query(sql)
        await self.conn.query(
            "INSERT INTO etl.source_migrations (name) VALUES "
            f"({_quote_literal(name)}) ON CONFLICT (name) DO NOTHING")

    # -- slots ------------------------------------------------------------------

    async def get_slot(self, name: str) -> SlotInfo | None:
        r = await self.conn.query(
            "SELECT confirmed_flush_lsn, active, "
            "COALESCE(wal_status, 'reserved') FROM pg_replication_slots "
            f"WHERE slot_name = {_quote_literal(name)}")
        if not r.rows:
            return None
        flush, active, wal_status = r.rows[0]
        return SlotInfo(
            name=name,
            confirmed_flush_lsn=Lsn(flush) if flush else Lsn.ZERO,
            active=active == "t",
            invalidated=wal_status == "lost")

    async def create_slot(self, name: str) -> CreatedSlot:
        """CREATE_REPLICATION_SLOT ... EXPORT_SNAPSHOT: the returned
        snapshot name fences copies via SET TRANSACTION SNAPSHOT on child
        connections (reference raw.rs:419-529, transaction.rs:794,827)."""
        r = await self.conn.query(
            f'CREATE_REPLICATION_SLOT "{name}" LOGICAL pgoutput '
            "(SNAPSHOT 'export')")
        row = r.rows[0]
        return CreatedSlot(name=row[0], consistent_point=Lsn(row[1]),
                           snapshot_id=row[2] or "")

    async def delete_slot(self, name: str) -> None:
        try:
            await self.conn.query(f'DROP_REPLICATION_SLOT "{name}" WAIT')
        except PgServerError as e:
            if e.kind is not ErrorKind.SLOT_NOT_FOUND:
                raise

    # -- data -------------------------------------------------------------------

    async def copy_table_stream(self, table_id: TableId, publication: str,
                                snapshot_id: str,
                                ctid_range: "tuple[int, int] | None" = None,
                                publication_table_id: "TableId | None" = None
                                ) -> CopyStream:
        """COPY in a REPEATABLE READ transaction pinned to the exported
        snapshot; fresh connection per stream (copy workers fork children,
        reference copy.rs:346-363). `publication_table_id` names the
        PUBLISHED relation when it differs from the physical one — a leaf
        partition under publish_via_partition_root inherits the root's
        column list and row filter (pg_publication_tables lists only the
        root)."""
        conn = self._new_conn(replication=False)
        await conn.connect()
        try:
            qualified, names, rowfilter = await self._table_and_columns(
                conn, table_id, publication,
                publication_table_id=publication_table_id)
            cols = ", ".join(f'"{c}"' for c in names)
            conds = []
            if ctid_range is not None:
                lo, hi = ctid_range
                conds.append(f"ctid >= '({lo},0)' AND ctid < '({hi},0)'")
            if rowfilter:
                # PG15 publication row filter: the snapshot COPY must apply
                # the same predicate the walsender applies to CDC, or the
                # initial copy includes rows the publication excludes
                # (reference transaction.rs:868)
                conds.append(f"({rowfilter})")
            where = f" WHERE {' AND '.join(conds)}" if conds else ""
            await conn.query(
                "BEGIN ISOLATION LEVEL REPEATABLE READ READ ONLY")
            if snapshot_id:
                await conn.query(
                    f"SET TRANSACTION SNAPSHOT {_quote_literal(snapshot_id)}")
        except BaseException:
            await conn.close()  # don't leak the socket / open transaction
            raise
        sql = f"COPY (SELECT {cols} FROM {qualified}{where}) TO STDOUT"
        return _WireCopyStream(conn, sql)

    async def _table_and_columns(self, conn: PgWireConnection,
                                 table_id: TableId,
                                 publication: str, *,
                                 publication_table_id: "TableId | None" = None
                                 ) -> tuple[str, list[str], "str | None"]:
        r = await conn.query(
            "SELECT n.nspname, c.relname FROM pg_class c "
            "JOIN pg_namespace n ON n.oid = c.relnamespace "
            f"WHERE c.oid = {int(table_id)}")
        if not r.rows:
            raise EtlError(ErrorKind.PUBLICATION_TABLE_MISSING,
                           f"table {table_id}")
        qualified = TableName(r.rows[0][0], r.rows[0][1]).quoted()
        pub_oid = int(publication_table_id
                      if publication_table_id is not None else table_id)
        # attnames/rowfilter are PG15+ columns; on 14 the COPY takes every
        # column and no predicate exists (reference transaction.rs:661:
        # "Row filters on publications were added in Postgres 15")
        ver = parse_server_version(
            conn.parameters.get("server_version", "0"))
        rowfilter = None
        names: list[str] = []
        if meets_version(ver, POSTGRES_15):
            filt = await conn.query(
                "SELECT pt.attnames, pt.rowfilter "
                "FROM pg_publication_tables pt "
                "JOIN pg_namespace ns ON ns.nspname = pt.schemaname "
                "JOIN pg_class pc ON pc.relnamespace = ns.oid "
                "AND pc.relname = pt.tablename "
                f"WHERE pt.pubname = {_quote_literal(publication)} "
                f"AND pc.oid = {pub_oid}")
            rowfilter = filt.rows[0][1] \
                if filt.rows and len(filt.rows[0]) > 1 else None
            if filt.rows and filt.rows[0][0]:
                names = _parse_name_array(filt.rows[0][0])
        if not names:
            cols = await conn.query(
                f"SELECT a.attname FROM pg_attribute a WHERE a.attrelid = "
                f"{int(table_id)} AND a.attnum > 0 AND NOT a.attisdropped "
                "ORDER BY a.attnum")
            names = [row[0] for row in cols.rows]
        return qualified, names, rowfilter

    async def estimate_table_stats(self, table_id: TableId) -> tuple[int, int]:
        r = await self.conn.query(
            "SELECT GREATEST(reltuples::bigint, 0), "
            "GREATEST(relpages::bigint, 1) "
            f"FROM pg_class WHERE oid = {int(table_id)}")
        if not r.rows:
            return 0, 1
        return int(r.rows[0][0]), int(r.rows[0][1])

    async def get_partition_leaves(
            self, table_id: TableId) -> list[tuple[TableId, int, int]]:
        """Leaf partitions with stats for per-leaf copy planning
        (reference transaction.rs:808-825)."""
        r = await self.conn.query(
            "SELECT c.oid, GREATEST(c.reltuples::bigint, 0), "
            "GREATEST(c.relpages::bigint, 1) "
            f"FROM pg_partition_tree({int(table_id)}) pt "
            "JOIN pg_class c ON c.oid = pt.relid "
            "WHERE pt.isleaf AND pt.level > 0 ORDER BY c.oid")
        return [(int(a), int(b), int(c)) for a, b, c in r.rows]

    async def start_replication(self, slot_name: str, publication: str,
                                start_lsn: Lsn) -> ReplicationStream:
        conn = self._new_conn(replication=True)
        await conn.connect()
        try:
            opts = (f"proto_version '2', publication_names "
                    f"{_quote_literal(publication)}, messages 'true'")
            await conn.start_copy_both(
                f'START_REPLICATION SLOT "{slot_name}" LOGICAL '
                f"{start_lsn} ({opts})")
        except BaseException:
            await conn.close()
            raise
        return _WireReplicationStream(conn)


def _parse_name_array(raw) -> list[str]:
    """Parse a pg name[] text literal like '{id,name}'."""
    if isinstance(raw, list):
        return raw
    raw = raw.strip()
    if raw.startswith("{") and raw.endswith("}"):
        inner = raw[1:-1]
        return [p.strip().strip('"') for p in inner.split(",") if p.strip()]
    return []
