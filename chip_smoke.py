#!/usr/bin/env python3
"""chip_smoke.py — does the pgbench copy + CDC pipeline still start on the chip?

One process, no arguments needed, no network, no child that needs the
chip. It drives the main path through the entry points a user calls and
exits non-zero the moment a check fails; the last line of stdout is one
JSON object with exactly these keys, `{"ok": true, "device": {"platform":
"tpu", "kind": "...", "count": 1}}` (the run's full record is the
`"phase": "summary"` line before it), and the exit code is 0 only if
every check passed. On a machine where JAX finds no TPU it exits 3
before doing any work and prints no result: there is no CPU run under
this script's name (tests/test_chip_smoke.py calls the phase functions at
a tiny size instead).

Phases:

  preflight  the platform is `tpu`; the C framer built here; the link
             probe (ops/autotune) returns a model; a CPU backend sits
             beside the chip.
  engine     one full batch per program family — XLA, Pallas (compiled by
             Mosaic, not interpreted, not flipped to XLA), the fused row
             filter of both, and TSV egress — over a 262,144-row
             `pgbench_accounts` insert batch and a 65,536-row batch that
             carries every kind in `DEVICE_KINDS`, each result compared
             byte for byte with the per-tuple CPU codecs on the same
             bytes. With more than one chip the batches also run under
             `default_decode_mesh()`.
  pipeline   FakeDatabase → Pipeline(batch_engine=tpu) → a destination
             that resolves every batch and folds each column into a
             checksum: initial copy of 1,000,000 `pgbench_accounts` rows
             (pgbench scale factor 10), then 1,048,576 insert events in
             500-row transactions committed faster than they drain, then
             `shutdown_and_wait()`. Delivered rows and checksums equal
             the generator's truth; durable progress reached the last
             commit; rows were routed to the device in the CDC phase; no
             quiet exit (supervision degrade, OOM fallback, failed
             background compile, egress failure) fired.

Data is made from `--seed`. The rates and times printed are one run's
readings for the record, not a benchmark.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import threading
import time

SEED = 7
ACCOUNTS_ROWS = 262_144
KINDS_ROWS = 65_536
COPY_ROWS = 1_000_000
CDC_EVENTS = 1_048_576
TX_ROWS = 500
# CDC warm-up transactions, each awaited to delivery so they cannot
# coalesce: one per small row bucket (256 / 1024 / 4096 / 16384)
WARM_WAVES = (200, 800, 3000, 13_000)

TID_ACCOUNTS = 16384
TID_KINDS = 16400
ACCOUNTS_PER_BRANCH = 100_000  # pgbench's naccounts: bid = (aid-1)/100000+1
FILLER = " " * 84  # pgbench leaves filler char(84) blank-padded


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------------
# data: made from the seed, with the truth kept beside the bytes
# ---------------------------------------------------------------------------


def accounts_columns(seed: int, n: int, first_aid: int = 1):
    """`n` pgbench_accounts rows from `first_aid` on, as int64 columns:
    aid sequential, bid by pgbench's rule, abalance uniform in ±10^9 (the
    r01–r05 decode shape: every text width an int4 can take)."""
    import numpy as np

    rng = np.random.default_rng([seed, first_aid])
    aid = np.arange(first_aid, first_aid + n, dtype=np.int64)
    bid = (aid - 1) // ACCOUNTS_PER_BRANCH + 1
    abalance = rng.integers(-10**9, 10**9, size=n, dtype=np.int64)
    return aid, bid, abalance


def accounts_schema():
    from etl_tpu.models import ColumnSchema, Oid, TableName, TableSchema

    return TableSchema(
        TID_ACCOUNTS, TableName("public", "pgbench_accounts"),
        (ColumnSchema("aid", Oid.INT4, nullable=False, primary_key_ordinal=1),
         ColumnSchema("bid", Oid.INT4),
         ColumnSchema("abalance", Oid.INT4),
         ColumnSchema("filler", Oid.BPCHAR, modifier=88)))


def accounts_texts(cols) -> list:
    """Per-column Postgres text of the given int columns, plus filler."""
    return [[b"%d" % v for v in c.tolist()] for c in cols] \
        + [[FILLER.encode()] * len(cols[0])]


def insert_payloads(table, texts, cols: "list[int] | None" = None) -> list:
    """pgoutput INSERT payloads of `table` cut down to the columns `cols`
    (all of them by default), from per-column text lists."""
    from etl_tpu.postgres.codec.pgoutput import encode_insert

    picked = texts if cols is None else [texts[j] for j in cols]
    return [encode_insert(table.id, list(vals)) for vals in zip(*picked)]


def kinds_table(seed: int, n: int):
    """(TableSchema, per-column text lists) for a table carrying every
    kind in `DEVICE_KINDS`, with NULLs, special values and exponent
    floats sprinkled in so the oracle fix-up path runs too. Column 2
    (`c_int4`, never NULL) is what the row filter reads."""
    import datetime as dt

    import numpy as np

    from etl_tpu.models import ColumnSchema, Oid, TableName, TableSchema
    from etl_tpu.ops import DEVICE_KINDS

    rng = np.random.default_rng([seed, 11])
    epoch = dt.datetime(2000, 1, 1)
    us = rng.integers(-10**15, 3 * 10**15, size=n).tolist()
    stamps = [epoch + dt.timedelta(microseconds=u) for u in us]
    secs = rng.integers(0, 86_400, size=n).tolist()
    frac = rng.integers(0, 10**6, size=n).tolist()

    def ints(lo, hi):
        return [b"%d" % v for v in
                rng.integers(lo, hi, size=n, dtype=np.int64).tolist()]

    def floats(digits):
        mant = rng.integers(-10**digits, 10**digits, size=n).tolist()
        scale = rng.integers(0, digits, size=n).tolist()
        out = []
        for i, (m, s) in enumerate(zip(mant, scale)):
            if i % 997 == 0:
                out.append((b"NaN", b"Infinity", b"-Infinity")[i % 3])
            elif i % 101 == 0:
                out.append(b"%de-%02d" % (m, s + 5))
            else:
                out.append(b"%.*f" % (s, m / 10**s))
        return out

    columns = (
        (ColumnSchema("c_bool", Oid.BOOL),
         [b"t" if v else b"f" for v in rng.integers(0, 2, size=n).tolist()]),
        (ColumnSchema("c_int2", Oid.INT2), ints(-2**15, 2**15)),
        (ColumnSchema("c_int4", Oid.INT4, nullable=False), ints(-2**31, 2**31)),
        (ColumnSchema("c_int8", Oid.INT8, nullable=False,
                      primary_key_ordinal=1), ints(-2**62, 2**62)),
        (ColumnSchema("c_oid", Oid.OID), ints(0, 2**32)),
        (ColumnSchema("c_float4", Oid.FLOAT4), floats(6)),
        (ColumnSchema("c_float8", Oid.FLOAT8), floats(14)),
        (ColumnSchema("c_date", Oid.DATE),
         [b"infinity" if i % 499 == 0 else s.date().isoformat().encode()
          for i, s in enumerate(stamps)]),
        (ColumnSchema("c_time", Oid.TIME),
         [b"%02d:%02d:%02d" % (s // 3600, s // 60 % 60, s % 60)
          + (b".%06d" % f if i % 2 else b"")
          for i, (s, f) in enumerate(zip(secs, frac))]),
        (ColumnSchema("c_timestamp", Oid.TIMESTAMP),
         [s.isoformat(sep=" ").encode() for s in stamps]),
        (ColumnSchema("c_timestamptz", Oid.TIMESTAMPTZ),
         [s.isoformat(sep=" ").encode() + b"+00" for s in stamps]),
    )
    schema = TableSchema(TID_KINDS, TableName("public", "every_kind"),
                         tuple(c for c, _ in columns))
    check({c.kind for c in schema.columns} == set(DEVICE_KINDS),
          "the kinds table no longer covers DEVICE_KINDS")
    texts = [t for _, t in columns]
    for j, (col, _) in enumerate(columns):
        if col.nullable:
            for i in range(j, n, 89 + j):
                texts[j][i] = None
    return schema, texts


# ---------------------------------------------------------------------------
# preflight
# ---------------------------------------------------------------------------


def device_record() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def preflight() -> dict:
    """What has to be true of the machine before anything is decoded."""
    from importlib import metadata

    from etl_tpu import native
    from etl_tpu.models import ReplicatedTableSchema
    from etl_tpu.ops import autotune
    from etl_tpu.ops.engine import DeviceDecoder, host_cpu_device

    versions = {p: metadata.version(p) for p in ("jax", "jaxlib", "libtpu")}
    check(native.native_available(),
          f"the C framer did not build here: {native._build_error}")
    model = autotune.measure()
    check(model is not None, "autotune.measure() returned no model on a "
                             "TPU backend")
    host = host_cpu_device()  # raises when no CPU backend sits beside it
    dec = DeviceDecoder(
        ReplicatedTableSchema.with_all_columns(accounts_schema()))
    return {
        "versions": versions,
        "native_framer": True,
        "host_device": str(host),
        "autotune": {"fixed_s": model.fixed_s,
                     "bytes_per_s": model.bytes_per_s,
                     "host_col_rows_per_s": model.host_col_rows_per_s,
                     "backend": model.backend},
        "device_min_rows_pgbench_accounts": dec.device_min_rows,
    }


# ---------------------------------------------------------------------------
# engine phase
# ---------------------------------------------------------------------------


class Case:
    """One table's batch: its bytes, the same bytes through the per-tuple
    CPU codecs (postgres/codec — the repo's oracle), and a row filter on
    int4 column `filter_col` that keeps about half, with the keep mask
    taken from the generator's own text."""

    def __init__(self, name: str, table, texts: list, filter_col: int):
        import numpy as np

        from etl_tpu.models import ReplicatedTableSchema

        self.name = name
        self.table = table
        self.texts = texts
        self.filter_col = filter_col
        self.filter_sql = f"{table.columns[filter_col].name} < 0"
        self.keep = np.array([int(t) for t in texts[filter_col]]) < 0
        self.schema = ReplicatedTableSchema.with_all_columns(table)
        self.payloads = insert_payloads(table, texts)
        self.oracle = oracle_batch(self.schema, self.payloads)

    def subset(self, cols: "list[int]"):
        """(schema, payloads, oracle) cut down to the columns `cols`."""
        from etl_tpu.models import ReplicatedTableSchema, TableSchema
        from etl_tpu.models.table_row import ColumnarBatch

        schema = ReplicatedTableSchema.with_all_columns(TableSchema(
            self.table.id, self.table.name,
            tuple(self.table.columns[j] for j in cols)))
        return (schema, insert_payloads(self.table, self.texts, cols),
                ColumnarBatch(schema, [self.oracle.columns[j] for j in cols]))


def accounts_case(seed: int, n: int) -> Case:
    return Case("accounts", accounts_schema(),
                accounts_texts(accounts_columns(seed, n)), filter_col=2)


def kinds_case(seed: int, n: int) -> Case:
    return Case("kinds", *kinds_table(seed, n), filter_col=2)


def oracle_batch(schema, payloads):
    from etl_tpu.models.lsn import Lsn
    from etl_tpu.models.table_row import ColumnarBatch
    from etl_tpu.postgres.codec import decode_insert, decode_logical_message

    rows = [decode_insert(decode_logical_message(p), schema,
                          Lsn(1), Lsn(2), i).row
            for i, p in enumerate(payloads)]
    return ColumnarBatch.from_rows(schema, rows)


def _stager(schema, payloads):
    """() -> a fresh StagedBatch of the payloads (a decode may consume
    its staging), the bytes concatenated once."""
    from etl_tpu.ops.wal import concat_payloads, stage_wal_batch

    wire = concat_payloads(payloads)

    def stage():
        wal = stage_wal_batch(*wire, len(schema.replicated_columns))
        check(wal.bad_from < 0,
              f"the framer rejected message {wal.bad_from}")
        return wal.staged

    return stage


def _decode_checks(schema, payloads, oracle, case: Case, label: str,
                   **decoder_kw) -> dict:
    """One engine over one batch: the plain program and its fused-filter
    variant, each held to the oracle."""
    import numpy as np

    from etl_tpu.ops.engine import DeviceDecoder
    from etl_tpu.ops.predicate import parse_row_filter
    from etl_tpu.testing.batches import batches_identical

    stage = _stager(schema, payloads)
    dec = DeviceDecoder(schema, device_min_rows=0, **decoder_kw)
    t0 = time.perf_counter()
    batch = dec.decode(stage())
    out = {"first_decode_s": round(time.perf_counter() - t0, 3)}
    check(batches_identical(batch, oracle),
          f"{label}: differs from the CPU codecs")
    t0 = time.perf_counter()
    dec.decode(stage())
    out["warm_decode_s"] = round(time.perf_counter() - t0, 4)

    fdec = DeviceDecoder(
        schema.with_row_predicate(parse_row_filter(case.filter_sql)),
        device_min_rows=0, **decoder_kw)
    staged = stage()
    check(fdec._device_filter_for(staged) is not None,
          f"{label}: the row filter did not fuse into the program")
    survivors = np.flatnonzero(case.keep).astype(np.int64)
    want = oracle.take(survivors)
    want.source_rows = survivors
    check(batches_identical(fdec.decode(staged), want),
          f"{label}: fused filter differs from the CPU codecs")
    out["filter_keep"] = round(len(survivors) / len(case.keep), 4)
    if decoder_kw.get("use_pallas"):
        # interpret mode is the CPU backend's; on the chip Mosaic compiled
        # what ran, and a decoder that ended on XLA never ran the kernel
        check(dec.use_pallas and fdec.use_pallas,
              f"{label}: the decoder flipped from Pallas to XLA")
    return out


def _egress_checks(case: Case, label: str, **decoder_kw) -> dict:
    """TSV egress: wire text rendered on the device, spliced by the
    ClickHouse fast path, against the host encoder over the oracle."""
    import numpy as np

    from etl_tpu.destinations.clickhouse import (render_batch_tsv_columnar,
                                                 render_batch_tsv_fast)
    from etl_tpu.destinations.util import (sequence_number_batch,
                                           sequence_number_buffer)
    from etl_tpu.ops.egress import ENCODER_TSV
    from etl_tpu.ops.engine import DeviceDecoder
    from etl_tpu.testing.batches import batches_identical

    batch = DeviceDecoder(case.schema, device_min_rows=0, egress=ENCODER_TSV,
                          **decoder_kw).decode(
                              _stager(case.schema, case.payloads)())
    check(batch.device_egress is not None,
          f"{label}: no device egress buffers attached")
    check(batches_identical(batch, case.oracle),
          f"{label}: egress decode differs from the CPU codecs")
    n = batch.num_rows
    lsns = np.arange(n, dtype=np.uint64) + (1 << 40)
    ords = np.arange(n, dtype=np.uint64)
    body, used_device = render_batch_tsv_fast(
        case.schema, batch, "UPSERT",
        sequence_number_buffer(lsns, ords, ords), egress=batch.device_egress)
    check(used_device, f"{label}: the TSV fast path used no device buffer")
    seqs = [s.decode() for s in sequence_number_batch(lsns, ords, ords)]
    check(body == render_batch_tsv_columnar(case.schema, case.oracle,
                                            "UPSERT", seqs),
          f"{label}: device TSV differs from the host encoder")
    return {"tsv_bytes": len(body),
            "device_fields": len(batch.device_egress.fields)}


def xla_checks(case: Case, mesh=None, mesh_min_rows=None) -> dict:
    """The XLA program, its fused filter and its TSV egress stage; under
    `mesh`, the row-sharded build of each. `mesh_min_rows` is the
    decoder's (None = production's 65,536); a tiny run lowers it so its
    batches still shard."""
    kw = {"mesh": mesh, "mesh_min_rows": mesh_min_rows}
    label = f"{case.name}/xla" + ("/mesh" if mesh is not None else "")
    return {**_decode_checks(case.schema, case.payloads, case.oracle, case,
                             label, **kw),
            "egress": _egress_checks(case, label + "/egress", **kw)}


def pallas_checks(case: Case) -> dict:
    """The Pallas kernel and its fused filter. The kernel's width bound
    (pallas_kernel.MAX_TOTAL_WIDTH) can sit below a table's total gather
    width, so the table runs in column groups that fit, each with the
    filter's column; every column must land in one."""
    from etl_tpu.ops.engine import DeviceDecoder
    from etl_tpu.ops.pallas_kernel import MAX_TOTAL_WIDTH

    dec = DeviceDecoder(case.schema, device_min_rows=0, mesh=None)
    widths = dict(zip((s.index for s in dec._dense),
                      dec._widths(_stager(case.schema, case.payloads)())))
    groups = [[case.filter_col]]
    for j in range(len(case.table.columns)):
        w = widths.get(j, 0)  # host-gathered columns cost no positions
        if j == case.filter_col:
            continue
        if sum(widths.get(i, 0) for i in groups[-1]) + w > MAX_TOTAL_WIDTH:
            groups.append([case.filter_col])
        groups[-1].append(j)
    out = {}
    for g, group in enumerate(sorted(g) for g in groups):
        out[f"group{g}"] = {
            "columns": [case.table.columns[j].name for j in group],
            **_decode_checks(*case.subset(group), case,
                             f"{case.name}/pallas[{g}]",
                             mesh=None, use_pallas=True)}
    return out


def sharding_check(case: Case, mesh, mesh_min_rows=None) -> dict:
    """Where the sharded program's output lives: every device of the mesh
    must hold an equal block of it, not the first device all of it."""
    from etl_tpu.ops.engine import DeviceDecoder
    from etl_tpu.telemetry.metrics import ETL_DECODE_MESH_SHARDS, registry

    pending = DeviceDecoder(
        case.schema, device_min_rows=0, mesh=mesh,
        mesh_min_rows=mesh_min_rows).decode_async(
            _stager(case.schema, case.payloads)())
    check(isinstance(pending._packed, tuple),
          "the batch did not take the sharded program")
    words = pending._packed[0]
    shards = words.addressable_shards
    rows = sorted(s.data.shape[1] for s in shards)
    check(len({s.device for s in shards}) == mesh.size
          and rows[0] == rows[-1] == words.shape[1] // mesh.size,
          f"sharded decode left work off some device: shard rows {rows} "
          f"over {mesh.size} devices")
    pending.result()
    return {"etl_decode_mesh_shards":
            registry.get_gauge(ETL_DECODE_MESH_SHARDS),
            "shard_rows": rows[0]}


def engine_phase(seed: int = SEED, accounts_rows: int = ACCOUNTS_ROWS,
                 kinds_rows: int = KINDS_ROWS, mesh_min_rows=None) -> dict:
    """One full batch per program family, each held to the CPU codecs.
    Raises SmokeFailure on the first difference."""
    import jax

    from etl_tpu.parallel.mesh import default_decode_mesh

    out: dict = {}
    for case in (accounts_case(seed, accounts_rows),
                 kinds_case(seed, kinds_rows)):
        res = {"rows": len(case.payloads), "xla": xla_checks(case),
               "pallas": pallas_checks(case)}
        if len(jax.devices()) > 1:
            # the production decoder takes every visible device
            mesh = default_decode_mesh()
            check(mesh is not None and mesh.size == len(jax.devices()),
                  "the decode mesh does not take every visible device")
            res["mesh"] = {
                **xla_checks(case, mesh, mesh_min_rows),
                "sharding": sharding_check(case, mesh, mesh_min_rows)}
        out[case.name] = res
    return out


# ---------------------------------------------------------------------------
# pipeline phase
# ---------------------------------------------------------------------------


def _weights(aid):
    """Per-row checksum weight: odd and keyed by `aid`, so a value landing
    on another row changes the sum, whatever order batches arrive in."""
    import numpy as np

    return (aid.astype(np.uint64) << np.uint64(1)) | np.uint64(1)


def fold_columns(aid, bid, abalance) -> dict:
    """Order-independent checksum of each column (mod 2^64)."""
    import numpy as np

    w = _weights(aid)
    with np.errstate(over="ignore"):
        return {"rows": int(len(aid)),
                "aid": int((w * w).sum()),
                "bid": int((bid.astype(np.uint64) * w).sum()),
                "abalance": int((abalance.astype(np.int64)
                                 .view(np.uint64) * w).sum()),
                "filler": int((np.uint64(len(FILLER)) * w).sum())}


def fold_batch(batch) -> dict:
    """`fold_columns` of a decoded pgbench_accounts batch; the filler
    column folds its per-row byte length and must equal the generator's
    text in every row."""
    import numpy as np

    for c in batch.columns:
        check(bool(np.asarray(c.validity).all()),
              f"NULL delivered in {c.schema.name}")
    aid, bid, abalance = (np.asarray(c.data).astype(np.int64)
                          for c in batch.columns[:3])
    filler = batch.columns[3]
    texts = filler.data.to_pylist() if filler.is_arrow else list(filler.data)
    check(all(t == FILLER for t in set(texts)),
          "filler text differs from the generator's")
    return fold_columns(aid, bid, abalance)


def _add(total: dict, part: dict) -> None:
    for k, v in part.items():
        total[k] = (total.get(k, 0) + v) % (1 << 64)


def _counters() -> dict:
    from etl_tpu.telemetry.metrics import (
        ETL_DECODE_BACKGROUND_COMPILES_TOTAL,
        ETL_DECODE_DEVICE_OOM_FALLBACKS_TOTAL,
        ETL_DECODE_ROUTED_DEVICE_ROWS_TOTAL,
        ETL_DECODE_ROUTED_HOST_ROWS_TOTAL,
        ETL_DECODE_ROUTED_ORACLE_ROWS_TOTAL,
        ETL_EGRESS_DEVICE_FAILURES_TOTAL, ETL_PROGRAMS_COMPILED_TOTAL,
        registry)

    return {k: int(registry.sum_counter(name)) for k, name in (
        ("device_rows", ETL_DECODE_ROUTED_DEVICE_ROWS_TOTAL),
        ("host_rows", ETL_DECODE_ROUTED_HOST_ROWS_TOTAL),
        ("oracle_rows", ETL_DECODE_ROUTED_ORACLE_ROWS_TOTAL),
        ("programs_compiled", ETL_PROGRAMS_COMPILED_TOTAL),
        ("background_compiles", ETL_DECODE_BACKGROUND_COMPILES_TOTAL),
        ("oom_fallbacks", ETL_DECODE_DEVICE_OOM_FALLBACKS_TOTAL),
        ("egress_failures", ETL_EGRESS_DEVICE_FAILURES_TOTAL))}


class CompileMeter:
    """Seconds JAX spent in backend compiles (a persistent-cache hit
    counts its retrieval) and how many were cache hits, from
    jax.monitoring. Compiles run on worker threads too."""

    def __init__(self) -> None:
        import jax.monitoring

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += seconds
                self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def read(self) -> dict:
        with self._lock:
            return {"compile_seconds": round(self.seconds, 3),
                    "jax_compiles": self.compiles,
                    "jax_cache_hits": self.cache_hits}


async def _pipeline_phase(seed: int, copy_rows: int, cdc_events: int,
                          tx_rows: int, warm_waves: tuple,
                          meter: "CompileMeter | None") -> dict:
    import numpy as np

    from etl_tpu.config import BatchConfig, BatchEngine, PipelineConfig
    from etl_tpu.destinations.base import Destination, WriteAck
    from etl_tpu.models import ReplicatedTableSchema
    from etl_tpu.models.event import DecodedBatchEvent
    from etl_tpu.models.table_state import TableStateType
    from etl_tpu.ops import egress, engine, program_store
    from etl_tpu.ops.pipeline import global_admission, reset_global_admission
    from etl_tpu.ops.staging import ROW_BUCKETS, bucket_rows
    from etl_tpu.ops.wal import concat_payloads, stage_wal_batch
    from etl_tpu.postgres.fake import FakeDatabase, FakeSource
    from etl_tpu.postgres.slots import apply_slot_name
    from etl_tpu.runtime import Pipeline
    from etl_tpu.store import NotifyingStore

    import jax

    host_min_rows = engine.DeviceDecoder.HOST_MIN_ROWS

    class ChecksumDestination(Destination):
        """Resolves every batch (so the decode is on the path) and folds
        each column into the running checksum of its phase."""

        def __init__(self) -> None:
            self.copy: dict = {}
            self.cdc: dict = {}
            self.cdc_rows = 0
            # rows that arrived in batches below HOST_MIN_ROWS: the only
            # rows the router sends to the per-row oracle by design
            self.small_rows = 0
            self.max_batch_rows = 0

        def _note(self, n: int) -> None:
            if n < host_min_rows:
                self.small_rows += n
            self.max_batch_rows = max(self.max_batch_rows, n)

        async def startup(self):
            return None

        async def write_table_rows(self, schema, batch):
            self._note(batch.num_rows)
            _add(self.copy, fold_batch(batch))
            return WriteAck.durable()

        async def write_events(self, events):
            for e in events:
                if isinstance(e, DecodedBatchEvent):
                    batch = e.batch
                    check(not np.asarray(e.change_types).any()
                          and len(e.old_rows) == 0,
                          "a change other than INSERT was delivered")
                    self._note(batch.num_rows)
                    _add(self.cdc, fold_batch(batch))
                    self.cdc_rows += batch.num_rows
                else:
                    check(not hasattr(e, "row"),
                          "a row event bypassed the batch engine")
            return WriteAck.durable()

        async def drop_table(self, table_id, schema=None):
            return None

        async def truncate_table(self, table_id):
            return None

    def meter_read() -> dict:
        return meter.read() if meter is not None else {}

    def delta(after: dict, before: dict) -> dict:
        return {k: round(after[k] - before[k], 3) for k in after}

    # -- set-up: data from the seed, truth kept as columns -----------------
    t_setup = time.perf_counter()
    table = accounts_schema()
    copy_cols = accounts_columns(seed, copy_rows)
    rows = [[str(a), str(b), str(c), FILLER]
            for a, b, c in zip(*(c.tolist() for c in copy_cols))]
    n_warm = sum(warm_waves)
    cdc_cols = accounts_columns(seed, n_warm + cdc_events,
                                first_aid=copy_rows + 1)
    payloads = insert_payloads(table, accounts_texts(cdc_cols))
    db = FakeDatabase()
    db.create_table(table, rows=rows)
    db.create_publication("pub", [TID_ACCOUNTS])
    store = NotifyingStore()
    dest = ChecksumDestination()
    reset_global_admission()
    pipeline = Pipeline(
        config=PipelineConfig(
            pipeline_id=1, publication_name="pub",
            batch=BatchConfig(max_fill_ms=30,
                              batch_engine=BatchEngine.TPU)),
        store=store, destination=dest,
        source_factory=lambda: FakeSource(db))
    data_s = time.perf_counter() - t_setup

    # the counters and failure sets are the process's: hold what THIS
    # phase adds at zero
    base = _counters()
    failed_before = engine._BG_COMPILE_FAILED | egress._EGRESS_BG_FAILED

    def quiet_exits(where: str, c: dict) -> None:
        check(not engine.host_oracle_forced(),
              f"{where}: the batch engine is degraded to the host oracle")
        degrades = [ev for ev in pipeline.supervisor.events
                    if ev.kind == "degrade"]
        check(not degrades, f"{where}: supervision degraded: {degrades}")
        check(c["oom_fallbacks"] == base["oom_fallbacks"],
              f"{where}: device OOM fallbacks")
        check(c["egress_failures"] == base["egress_failures"],
              f"{where}: egress failures")
        check((engine._BG_COMPILE_FAILED | egress._EGRESS_BG_FAILED)
              <= failed_before, f"{where}: a background compile failed")

    def oracle_explained(where: str, phase: dict, small: int) -> None:
        # before the window the router may also park a batch on the
        # oracle while its host program compiles in the background
        # (engine._route, nonblocking_compile) — and for no other reason
        check(phase["oracle_rows"] <= small
              or phase["background_compiles"] > 0,
              f"{where}: {phase['oracle_rows']} rows went to the per-row "
              f"oracle with no cold compile to explain them")

    def surface_pipeline_error() -> None:
        if pipeline._apply_task is not None and pipeline._apply_task.done():
            pipeline._apply_task.result()
            raise SmokeFailure("the apply worker stopped early")

    out: dict = {"copy_rows": copy_rows, "cdc_events": cdc_events,
                 "tx_rows": tx_rows, "data_seconds": round(data_s, 2)}
    try:
        # -- initial copy ---------------------------------------------------
        c0, m0 = base, meter_read()
        t0 = time.perf_counter()
        await pipeline.start()
        t_started = time.perf_counter()
        await asyncio.wait_for(
            store.notify_on(TID_ACCOUNTS, TableStateType.READY), 600)
        copy_s = time.perf_counter() - t_started
        # a compile the copy's last batches started is the copy's: where it
        # outlived the copy, the warm-up's first wave would meet it on the
        # oracle with no compile of its own to explain it
        while engine.background_compiles_inflight():
            await asyncio.sleep(0.05)
        c1, m1 = _counters(), meter_read()
        check(dest.copy == fold_columns(*copy_cols),
              f"copy delivered {dest.copy}, the generator made "
              f"{fold_columns(*copy_cols)}")
        quiet_exits("copy", c1)
        copy_small = dest.small_rows
        oracle_explained("copy", delta(c1, c0), copy_small)
        out["copy"] = {
            "pipeline_start_s": round(t_started - t0, 2),
            "seconds": round(copy_s, 3),
            "rows_per_second": copy_rows / copy_s,
            "max_batch_rows": dest.max_batch_rows,
            "rows_in_batches_under_host_min_rows": copy_small,
            **delta(c1, c0), **delta(m1, m0)}

        # -- CDC warm-up: every program the window can touch ---------------
        async def delivered_at_least(n: int) -> None:
            while dest.cdc_rows < n:
                surface_pipeline_error()
                await asyncio.sleep(0.02)

        produced = 0
        for wave in warm_waves:
            tx = db.transaction()
            for _ in range(wave):
                tx.insert_preencoded(TID_ACCOUNTS, payloads[produced])
                produced += 1
            await tx.commit()
            await asyncio.wait_for(delivered_at_least(produced), 300)
        schema = ReplicatedTableSchema.with_all_columns(table)
        buckets = [b for b in ROW_BUCKETS if b <= bucket_rows(cdc_events)]
        # the host program of every bucket (fixed widths), then whatever
        # program production routing picks for a full bucket of the
        # window's own bytes (the device's, at its data-dependent widths,
        # from the autotuned threshold up)
        warm_dec = engine.DeviceDecoder(schema)

        def warm() -> None:
            program_store.warm_host_programs([schema], buckets, wait=True)
            for bucket in buckets:
                buf, offs, lens = concat_payloads(
                    payloads[n_warm:n_warm + bucket])
                warm_dec.decode(stage_wal_batch(buf, offs, lens, 4).staged)

        # off the loop: the pipeline idles behind it and keeps beating
        await asyncio.get_running_loop().run_in_executor(None, warm)
        while engine.background_compiles_inflight():
            await asyncio.sleep(0.05)
        c2, m2 = _counters(), meter_read()
        quiet_exits("warm-up", c2)
        oracle_explained("warm-up", delta(c2, c1),
                         dest.small_rows - copy_small)
        out["warm_up"] = {"events": n_warm,
                          "device_min_rows": warm_dec.device_min_rows,
                          **delta(c2, c1), **delta(m2, m1)}

        # -- the streamed window -------------------------------------------
        small0 = dest.small_rows
        dest.max_batch_rows = 0
        last_commit = None
        t_prod0 = time.perf_counter()
        end = n_warm + cdc_events
        while produced < end:
            tx = db.transaction()
            for _ in range(min(tx_rows, end - produced)):
                tx.insert_preencoded(TID_ACCOUNTS, payloads[produced])
                produced += 1
            last_commit = await tx.commit()
        t_prod1 = time.perf_counter()
        await asyncio.wait_for(delivered_at_least(end), 600)
        t_e2e = time.perf_counter()
        c3, m3 = _counters(), meter_read()
        await pipeline.shutdown_and_wait()
        shutdown_s = time.perf_counter() - t_e2e
    except BaseException:
        # a failed check must not leave the workers running behind it
        pipeline.shutdown_signal.trigger()
        raise
    check(dest.cdc == fold_columns(*cdc_cols),
          f"CDC delivered {dest.cdc}, the generator made "
          f"{fold_columns(*cdc_cols)}")
    durable = await store.get_durable_progress(apply_slot_name(1))
    check(durable is not None and int(durable) >= int(last_commit),
          f"durable progress {durable} is short of the last commit "
          f"{last_commit}")
    quiet_exits("streamed window", c3)
    window = delta(c3, c2)
    small = dest.small_rows - small0
    check(window["oracle_rows"] == small,
          f"{window['oracle_rows']} rows went to the per-row oracle after "
          f"warm-up; only the {small} in batches under {host_min_rows} "
          f"rows belong there")
    if jax.default_backend() == "tpu":
        check(window["device_rows"] > 0,
              "no row was routed to the device in the CDC phase")
    capacity = global_admission().capacity
    check(capacity == max(4, 2 * len(jax.devices())),
          f"admission capacity {capacity} was not sized from the devices")
    out["streamed_window"] = {
        "events": cdc_events,
        "producer_events_per_second": cdc_events / (t_prod1 - t_prod0),
        "end_to_end_events_per_second": cdc_events / (t_e2e - t_prod0),
        "seconds": round(t_e2e - t_prod0, 3),
        "shutdown_seconds": round(shutdown_s, 3),
        "max_batch_rows": dest.max_batch_rows,
        "rows_in_batches_under_host_min_rows": small,
        **window, **delta(m3, m2)}
    out["durable_lsn"] = int(durable)
    out["last_commit_lsn"] = int(last_commit)
    out["supervision_events"] = sorted(
        {ev.kind for ev in pipeline.supervisor.events})
    out["admission_capacity"] = capacity
    return out


def pipeline_phase(seed: int = SEED, copy_rows: int = COPY_ROWS,
                   cdc_events: int = CDC_EVENTS, tx_rows: int = TX_ROWS,
                   warm_waves: tuple = WARM_WAVES,
                   meter: "CompileMeter | None" = None) -> dict:
    """Initial copy, CDC warm-up, the streamed window, shutdown — with
    every delivered column checked against the generator. Raises
    SmokeFailure on the first check that does not hold."""
    return asyncio.run(_pipeline_phase(seed, copy_rows, cdc_events, tx_rows,
                                       warm_waves, meter))


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(1 for name in os.listdir(path) if name.endswith("-cache"))


def run_phases(seed: int, device: dict) -> dict:
    """Every phase in turn; the full record of the run, `"ok"` true only
    if every check held. A SmokeFailure ends the run at the check that
    did not hold and is named under `"failed"`."""
    import jax

    from etl_tpu.ops.program_store import place_jax_compile_cache

    t_start = time.perf_counter()
    cache_dir = place_jax_compile_cache()
    entries_before = _cache_entries(cache_dir)
    say("compile_cache", dir=cache_dir, entries_before=entries_before)
    meter = CompileMeter()
    result = {"ok": False, "device": device, "seed": seed}
    try:
        say("device", **device)
        result["preflight"] = preflight()
        say("preflight", **result["preflight"])
        m0 = meter.read()
        result["engine"] = engine_phase(seed)
        result["engine"]["setup"] = {
            k: round(v - m0[k], 3) for k, v in meter.read().items()}
        say("engine", **result["engine"])
        result["pipeline"] = pipeline_phase(seed, meter=meter)
        say("pipeline", **result["pipeline"])
        check(jax.config.jax_compilation_cache_dir == cache_dir,
              f"JAX's compile cache is at "
              f"{jax.config.jax_compilation_cache_dir}, not {cache_dir}")
        result["compile_cache"] = {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": _cache_entries(cache_dir)}
        result["setup_compile"] = meter.read()
        result["wall_seconds"] = round(time.perf_counter() - t_start, 1)
        result["ok"] = True
    except SmokeFailure as e:
        result["failed"] = str(e)
    return result


def main(argv: "list[str] | None" = None) -> int:
    parser = argparse.ArgumentParser(prog="chip_smoke.py",
                                     description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=SEED,
                        help="data generator seed (default %(default)s)")
    args = parser.parse_args(argv)

    device = device_record()
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX found platform {device['platform']!r}, not "
              "'tpu'; nothing was run", file=sys.stderr)
        return 3
    import etl_tpu  # noqa: F401 — alone, without the program: no result

    ok = False
    try:
        result = run_phases(args.seed, device)
        say("summary", **result)
        ok = result["ok"]
    finally:
        # the driver reads the last line and admits these keys only; the
        # run's record is the "summary" line above it
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
