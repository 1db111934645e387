"""Benchmark harness: the reference etl-benchmarks surface.

Modes (reference crates/etl-benchmarks/src/{table_copy,table_streaming}.rs):
  decode           WAL records/sec decoded, TPU vs CPU (bench.py default)
  table_copy       full-pipeline initial copy: rows/s, MiB/s, phase timings
  table_streaming  CDC through the pipeline: producer + end-to-end events/s
  wide_row         100-column mixed-type decode (BASELINE.json config)

Each mode emits a JSON report; `python -m etl_tpu.benchmarks.compare A B`
diffs two reports (reference `cargo x benchmark-compare`).
"""

from __future__ import annotations

import asyncio
import statistics
import time


async def _wait_background_compiles(timeout_s: float = 240.0) -> None:
    """Poll engine.background_compiles_inflight() to zero before opening a
    measured window, failing loudly instead of hanging the bench when a
    build wedges (or a spawn failure leaks a key)."""
    from ..ops import engine as _engine

    deadline = time.monotonic() + timeout_s
    while _engine.background_compiles_inflight():
        if time.monotonic() >= deadline:
            raise TimeoutError(
                "background host-program compiles still in flight after "
                f"{timeout_s:.0f}s; refusing to open a measured window")
        await asyncio.sleep(0.05)


def _median(xs):
    return statistics.median(xs)


def _pipeline_metrics() -> dict:
    """Snapshot of the decode-pipeline stage metrics (ops/pipeline.py):
    per-stage totals + the overlap counters. Benches report the DELTA over
    their measured window (snapshot before and after, subtract)."""
    from ..telemetry.metrics import (
        ETL_DECODE_DISPATCH_SECONDS, ETL_DECODE_FETCH_SECONDS,
        ETL_DECODE_PACK_SECONDS, ETL_DECODE_PIPELINE_OVERLAP_SECONDS_TOTAL,
        ETL_DECODE_PIPELINE_PACK_SECONDS_TOTAL, registry)

    out = {}
    for key, name in (("pack", ETL_DECODE_PACK_SECONDS),
                      ("dispatch", ETL_DECODE_DISPATCH_SECONDS),
                      ("fetch", ETL_DECODE_FETCH_SECONDS)):
        count, total = registry.get_histogram(name)
        out[f"{key}_batches"] = count
        out[f"{key}_seconds"] = total
    out["overlap_seconds"] = registry.get_counter(
        ETL_DECODE_PIPELINE_OVERLAP_SECONDS_TOTAL)
    out["pipeline_pack_seconds"] = registry.get_counter(
        ETL_DECODE_PIPELINE_PACK_SECONDS_TOTAL)
    return out


def _admission_metrics() -> dict:
    """Snapshot of the fair batch-admission scheduler and mesh-sharded
    decode counters (ops/pipeline.AdmissionScheduler, ops/engine mesh
    path). Per-tenant labels roll up via sum_* — benches report the delta
    over their measured window."""
    from ..telemetry.metrics import (
        ETL_DECODE_ADMISSION_BYPASS_GRANTS_TOTAL,
        ETL_DECODE_ADMISSION_GRANTS_TOTAL,
        ETL_DECODE_ADMISSION_STARVATION_GRANTS_TOTAL,
        ETL_DECODE_ADMISSION_WAIT_SECONDS, ETL_DECODE_MESH_BATCHES_TOTAL,
        ETL_DECODE_MESH_PADDED_ROWS_TOTAL, ETL_DECODE_MESH_ROWS_TOTAL,
        registry)

    waits, wait_seconds = registry.sum_histogram(
        ETL_DECODE_ADMISSION_WAIT_SECONDS)
    return {
        "admission_grants": registry.sum_counter(
            ETL_DECODE_ADMISSION_GRANTS_TOTAL),
        "admission_starvation_grants": registry.sum_counter(
            ETL_DECODE_ADMISSION_STARVATION_GRANTS_TOTAL),
        "admission_bypass_grants": registry.sum_counter(
            ETL_DECODE_ADMISSION_BYPASS_GRANTS_TOTAL),
        "admission_waits": waits,
        "admission_wait_seconds": wait_seconds,
        "mesh_batches": registry.get_counter(ETL_DECODE_MESH_BATCHES_TOTAL),
        "mesh_rows": registry.get_counter(ETL_DECODE_MESH_ROWS_TOTAL),
        "mesh_padded_rows": registry.get_counter(
            ETL_DECODE_MESH_PADDED_ROWS_TOTAL),
    }


def _filter_metrics() -> dict:
    """Snapshot of the fused publication-row-filter counters (ops/engine
    filtered completion): rows compacted out of decode output and the
    bytes the packed-result fetch actually moved. Benches report the
    delta over their measured window — the fetched-bytes delta is the
    MEASURED evidence behind the "fetch scales with selectivity" claim,
    not an assumption."""
    from ..telemetry.metrics import (ETL_DECODE_FETCHED_BYTES_TOTAL,
                                     ETL_DECODE_ROWS_FILTERED_TOTAL,
                                     registry)

    return {
        "decode_rows_filtered": registry.get_counter(
            ETL_DECODE_ROWS_FILTERED_TOTAL),
        "decode_fetched_bytes": registry.get_counter(
            ETL_DECODE_FETCHED_BYTES_TOTAL),
    }


def _compile_metrics() -> dict:
    """Snapshot of the program-store counters (ops/program_store.py).
    Benches report the delta over their measured window: nonzero
    programs_compiled inside a window means the warmup missed a
    signature and the window paid an XLA build — the cost the canonical
    layout cache + prewarm exist to make visible and then kill."""
    from ..telemetry.metrics import (ETL_COMPILE_CACHE_HITS_TOTAL,
                                     ETL_COMPILE_CACHE_MISSES_TOTAL,
                                     ETL_PROGRAMS_COMPILED_TOTAL, registry)

    return {
        "programs_compiled":
            registry.get_counter(ETL_PROGRAMS_COMPILED_TOTAL),
        "compile_cache_hits_memory": registry.get_counter(
            ETL_COMPILE_CACHE_HITS_TOTAL, {"layer": "memory"}),
        "compile_cache_hits_disk": registry.get_counter(
            ETL_COMPILE_CACHE_HITS_TOTAL, {"layer": "disk"}),
        "compile_cache_misses": registry.sum_counter(
            ETL_COMPILE_CACHE_MISSES_TOTAL),
    }


# ---------------------------------------------------------------------------
# table_copy (reference table_copy.rs:74-183)
# ---------------------------------------------------------------------------


async def run_table_copy(n_rows: int = 1_000_000, samples: int = 3,
                         engine: str = "tpu",
                         destination: str = "null") -> dict:
    """Initial-copy throughput. 1M rows (reference table_copy.rs seeds
    1M-row pgbench tables): at 100k rows the ~0.1s state-machine handoff
    latency — not copy throughput — dominates the window."""
    from ..config import BatchConfig, BatchEngine, PipelineConfig
    from ..destinations import MemoryDestination
    from ..destinations.base import Destination, WriteAck
    from ..models import ColumnSchema, Oid, TableName, TableSchema
    from ..models.table_state import TableStateType
    from ..postgres.codec.copy_text import encode_copy_row
    from ..postgres.fake import FakeDatabase, FakeSource
    from ..runtime import Pipeline
    from ..store import NotifyingStore

    TID = 16384
    rows = [[str(i), str(i % 100), str(i * 7 % 10**9), "x" * 64]
            for i in range(n_rows)]
    copy_bytes = sum(len(encode_copy_row(r)) + 1 for r in rows)
    schema_def = TableSchema(
        TID, TableName("public", "bench_copy"),
        (ColumnSchema("id", Oid.INT8, nullable=False,
                      primary_key_ordinal=1),
         ColumnSchema("bucket", Oid.INT4),
         ColumnSchema("val", Oid.INT8),
         ColumnSchema("filler", Oid.TEXT)))

    class CopyCountDestination(Destination):
        """Counts copied rows; resolving batch.num_rows forces the decode,
        so device/host decode stays on the measured path — the reference
        null-destination stance (etl-benchmarks), matching
        run_table_streaming."""

        def __init__(self):
            self.rows_delivered = 0

        async def startup(self):
            return None

        async def write_table_rows(self, schema, batch):
            self.rows_delivered += batch.num_rows
            return WriteAck.durable()

        async def write_events(self, events):
            return WriteAck.durable()

        async def drop_table(self, table_id, schema=None):
            return None

        async def truncate_table(self, table_id):
            return None

    # warmup OFF the clock: backend init and the per-(schema, row-bucket)
    # decode-program compiles are one-time process costs a steady-state
    # pipeline has already paid
    from ..models.schema import ReplicatedTableSchema
    from ..ops.engine import DeviceDecoder
    from ..ops.staging import stage_copy_chunk

    if engine == "tpu":
        warm_schema = ReplicatedTableSchema.with_all_columns(schema_def)
        warm_dec = DeviceDecoder(warm_schema)
        # every row bucket a partition flush can stage (the 8 MiB batch
        # threshold lands ~98k-row chunks in the 131072 bucket); 131_071
        # not 131_072 — the exact bucket size would route to the DEVICE
        # path (n_rows ≥ device_min_rows) while in-window chunks stay
        # under it and need the HOST program for that bucket
        warm_lines = [encode_copy_row(r) for r in rows[:131_071]]
        for k in (512, 4096, 16_384, 65_536, 131_071):
            chunk = b"\n".join(warm_lines[:min(k, len(warm_lines))]) + b"\n"
            warm_dec.decode(stage_copy_chunk(chunk, 4))

    results = []
    for _ in range(samples):
        db = FakeDatabase()
        db.create_table(schema_def, rows=rows)
        db.create_publication("pub", [TID])
        store = NotifyingStore()
        dest = CopyCountDestination() if destination == "null" \
            else MemoryDestination()
        pipeline = Pipeline(
            config=PipelineConfig(
                pipeline_id=1, publication_name="pub",
                batch=BatchConfig(max_fill_ms=40,
                                  batch_engine=BatchEngine(engine))),
            store=store, destination=dest,
            source_factory=lambda: FakeSource(db))
        t0 = time.perf_counter()
        await pipeline.start()
        t_started = time.perf_counter()
        await asyncio.wait_for(store.notify_on(TID, TableStateType.READY), 300)
        t_copied = time.perf_counter()
        await pipeline.shutdown_and_wait()
        t_done = time.perf_counter()
        results.append({
            "pipeline_start_ms": (t_started - t0) * 1000,
            "copy_wait_ms": (t_copied - t_started) * 1000,
            "shutdown_ms": (t_done - t_copied) * 1000,
            "total_ms": (t_done - t0) * 1000,
            "rows_per_second": n_rows / (t_copied - t_started),
            "mib_per_second":
                copy_bytes / (1 << 20) / (t_copied - t_started),
        })
    agg = {k: _median([r[k] for r in results]) for k in results[0]}
    return {"mode": "table_copy", "rows": n_rows, "samples": samples,
            "engine": engine, "destination": destination,
            **{k: round(v, 2) for k, v in agg.items()}}


# ---------------------------------------------------------------------------
# table_streaming (reference table_streaming.rs:86-118)
# ---------------------------------------------------------------------------


async def run_table_streaming(n_events: int = 500_000, tx_size: int = 500,
                              engine: str = "tpu",
                              destination: str = "null",
                              max_fill_ms: int = 30,
                              arrival_rate: int | None = None) -> dict:
    """CDC throughput + p50 end-to-end replication lag.

    destination='null' counts delivered rows without materializing
    per-row Python objects (reference etl-benchmarks null destination
    mode) — it still RESOLVES every decoded batch, so the device decode
    is on the measured path; 'memory' exercises full row expansion.
    The default fill window (30 ms, measured optimum in a 5-80 ms sweep)
    keeps one flush in flight continuously: the XLA host-backend decode
    executes on its own thread pool, so steady small flushes overlap
    decode/resolve with WAL intake where a large window would alternate
    idle-accumulate and burst-decode phases on this single-core host.

    arrival_rate=None produces as fast as possible (drain-style: the
    throughput number is the headline, lag measures queue depth under
    saturation). arrival_rate=N paces production to N events/s in 10 ms
    ticks — the lag percentiles then measure real end-to-end latency at
    that offered load (the BASELINE.md "p50 end-to-end replication lag"
    reading; see run_lag_vs_rate).
    """
    from ..config import BatchConfig, BatchEngine, PipelineConfig
    from ..destinations import MemoryDestination
    from ..destinations.base import Destination, WriteAck
    from ..models import (ColumnSchema, InsertEvent, Oid, TableName,
                          TableSchema)
    from ..models.event import DecodedBatchEvent
    from ..models.table_state import TableStateType
    from ..postgres.fake import FakeDatabase, FakeSource
    from ..runtime import Pipeline
    from ..store import NotifyingStore

    TID = 16385
    db = FakeDatabase()
    db.create_table(TableSchema(
        TID, TableName("public", "bench_stream"),
        (ColumnSchema("id", Oid.INT8, nullable=False, primary_key_ordinal=1),
         ColumnSchema("v", Oid.INT4),
         ColumnSchema("note", Oid.TEXT))))
    db.create_publication("pub", [TID])
    store = NotifyingStore()

    # p50 end-to-end replication lag (a named BASELINE metric): per-event
    # lag = destination arrival − source commit of its transaction
    commit_times: dict[int, float] = {}
    arrivals: list[tuple[int, float]] = []

    class NullDestination(Destination):
        """Counts delivered rows; resolves (but never row-expands) decoded
        batches — the reference null-destination stance."""

        def __init__(self):
            self.rows_delivered = 0

        async def startup(self):
            return None

        async def write_table_rows(self, schema, batch):
            return WriteAck.durable()

        async def write_events(self, events):
            import numpy as np

            now = time.perf_counter()
            for e in events:
                if isinstance(e, DecodedBatchEvent):
                    self.rows_delivered += e.batch.num_rows  # forces decode
                    for lsn in np.unique(e.commit_lsns).tolist():
                        arrivals.append((int(lsn), now))
                elif isinstance(e, InsertEvent):
                    self.rows_delivered += 1
                    arrivals.append((int(e.commit_lsn), now))
            return WriteAck.durable()

        async def drop_table(self, table_id, schema=None):
            return None

        async def truncate_table(self, table_id):
            return None

    class LagMeasuringDestination(MemoryDestination):
        rows_delivered = property(lambda self: sum(
            1 for e in self.events if isinstance(e, InsertEvent)))

        async def write_events(self, events):
            from ..destinations.base import expand_batch_events

            ack = await super().write_events(events)
            now = time.perf_counter()
            for e in expand_batch_events(events):
                if isinstance(e, InsertEvent):
                    arrivals.append((int(e.commit_lsn), now))
            return ack

    dest = NullDestination() if destination == "null" \
        else LagMeasuringDestination()
    pipeline = Pipeline(
        config=PipelineConfig(
            pipeline_id=1, publication_name="pub",
            batch=BatchConfig(max_fill_ms=max_fill_ms,
                              batch_engine=BatchEngine(engine))),
        store=store, destination=dest,
        source_factory=lambda: FakeSource(db))
    await pipeline.start()
    await asyncio.wait_for(store.notify_on(TID, TableStateType.READY), 60)

    # warmup: drive transactions through the full path so the per-schema
    # jit compiles of the host-vectorized decode program (a one-time cost,
    # like the decode bench's warmup) land outside the measured window.
    # The decode program is keyed by (row bucket, field-width signature),
    # so the waves are sized to touch every ROW_BUCKET a measured flush
    # can land in (1024 / 4096 / 16384) and encode the SAME value shapes
    # as the measured payloads (a different field width would compile a
    # different program and the warmup would warm nothing).
    from ..postgres.codec.pgoutput import encode_insert as _enc

    def _payload(i: int) -> bytes:
        return _enc(TID, [str(i).encode(), str(i % 97).encode(),
                          b"note-%d" % i])

    async def wait_delivered_at_least(n: int) -> None:
        while dest.rows_delivered < n:
            if pipeline._apply_task is not None \
                    and pipeline._apply_task.done():
                pipeline._apply_task.result()  # surface the pipeline error
                raise RuntimeError("pipeline stopped during warmup")
            await asyncio.sleep(0.02)

    # each wave is awaited to delivery before the next starts so waves
    # can't coalesce into one run (which would warm only the largest
    # bucket); sizes land in buckets 256 / 1024 / 4096 / 16384 — runs
    # seal at RUN_SEAL_ROWS so no measured flush can stage beyond 16384
    warmup_rows = 0
    w = 0
    for wave in (200, 800, 3000, 13000):
        tx = db.transaction()
        for _ in range(wave):
            tx.insert_preencoded(TID, _payload(w))
            w += 1
        await tx.commit()
        warmup_rows += wave
        await asyncio.wait_for(wait_delivered_at_least(warmup_rows), 120)
    # the streaming decoders compile cold host programs on BACKGROUND
    # threads (engine.nonblocking_compile) and serve the triggering
    # batches from the oracle — wait the builds out so the measured
    # window runs the warm programs, not the transient fallback
    await _wait_background_compiles()
    arrivals.clear()
    commit_times.clear()
    # baseline BEFORE production starts: measured rows deliver concurrently
    # with the producer loop, so a later capture would double-count them
    base_delivered = dest.rows_delivered

    # payload encode happens OFF the clock: the reference bench's producer
    # is a separate Postgres server, not a Python encoder stealing the
    # pipeline's only core — the measured window covers walsender framing
    # + wire + pipeline, which is the system under test
    from ..postgres.codec.pgoutput import encode_insert
    payloads = [encode_insert(TID, [str(i).encode(), str(i % 97).encode(),
                                    b"note-%d" % i])
                for i in range(n_events)]

    # ALSO off the clock: the device decode programs for the mega-seal
    # buckets backlog growth can reach. Saturation drains grow seals
    # 16384 → 65536 → 262144 (runtime/assembler.MEGA_SEAL_ROWS); on a
    # real accelerator each unwarmed (bucket, widths) program costs a
    # 10-40s compile that would otherwise land mid-window. Staging the
    # MEASURED payloads keeps the width signature identical.
    import jax as _jax

    if engine == "tpu" and _jax.default_backend() != "cpu" \
            and arrival_rate is None and n_events >= 65_536:
        from ..models.schema import ReplicatedTableSchema as _RTS
        from ..ops.engine import DeviceDecoder as _DD
        from ..ops.wal import concat_payloads as _concat
        from ..ops.wal import stage_wal_batch as _stage

        _wdec = _DD(_RTS.with_all_columns(db.tables[TID].schema))
        for _bucket in (65_536, 131_072, 262_144):
            if _bucket > len(payloads):
                break
            _buf, _offs, _lens = _concat(payloads[:_bucket])
            _wal = _stage(_buf, _offs, _lens, 3)
            _wdec.decode(_wal.staged)

    from ..telemetry.metrics import (ETL_DECODE_ROUTED_DEVICE_ROWS_TOTAL,
                                     ETL_DECODE_ROUTED_HOST_ROWS_TOTAL,
                                     ETL_DECODE_ROUTED_ORACLE_ROWS_TOTAL,
                                     registry as _registry)

    def _routed():
        return {k: _registry.get_counter(n) for k, n in (
            ("device", ETL_DECODE_ROUTED_DEVICE_ROWS_TOTAL),
            ("host", ETL_DECODE_ROUTED_HOST_ROWS_TOTAL),
            ("oracle", ETL_DECODE_ROUTED_ORACLE_ROWS_TOTAL))}

    routed0 = _routed()
    stages0 = _pipeline_metrics()
    adm0 = _admission_metrics()
    filt0 = _filter_metrics()
    comp0 = _compile_metrics()
    # row-materialization gate input: zero constructions over the measured
    # window = the egress path stayed columnar fetch-to-wire (the smoke
    # gate asserts this on the null destination; 'memory' exercises the
    # row-expansion shim and reports its cost honestly)
    from ..telemetry.metrics import publish_table_rows_constructed

    rows_constructed0 = publish_table_rows_constructed()

    t_prod0 = time.perf_counter()
    produced = 0
    if arrival_rate:
        tick = 0.01
        per_tick = max(1, int(arrival_rate * tick))
        next_t = t_prod0
        while produced < n_events:
            tx = db.transaction()
            for _ in range(min(per_tick, n_events - produced)):
                tx.insert_preencoded(TID, payloads[produced])
                produced += 1
            lsn = await tx.commit()
            commit_times[int(lsn)] = time.perf_counter()
            next_t += tick
            delay = next_t - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
    else:
        while produced < n_events:
            tx = db.transaction()
            for _ in range(min(tx_size, n_events - produced)):
                tx.insert_preencoded(TID, payloads[produced])
                produced += 1
            lsn = await tx.commit()
            commit_times[int(lsn)] = time.perf_counter()
    t_prod1 = time.perf_counter()

    def delivered():
        return dest.rows_delivered - base_delivered

    async def wait_delivered():
        while delivered() < n_events:
            if pipeline._apply_task is not None \
                    and pipeline._apply_task.done():
                pipeline._apply_task.result()  # surface the pipeline error
                raise RuntimeError("pipeline stopped before delivering")
            await asyncio.sleep(0.02)

    await asyncio.wait_for(wait_delivered(), timeout=300)
    t_e2e = time.perf_counter()
    await pipeline.shutdown_and_wait()
    t_drain = time.perf_counter()
    # decode routing over the measured window: under saturation the
    # backlog signal grows seals past the measured device threshold, so
    # the device share reports how much of the steady-state data plane
    # actually ran on the accelerator (VERDICT r4 #1c — a host-only
    # steady state can no longer hide behind the throughput number)
    routed1 = _routed()
    routed = {k: routed1[k] - routed0[k] for k in routed1}
    routed_total = sum(routed.values())
    stages1 = _pipeline_metrics()
    stages = {k: stages1[k] - stages0[k] for k in stages1}
    adm1 = _admission_metrics()
    adm = {k: adm1[k] - adm0[k] for k in adm1}
    filt1 = _filter_metrics()
    filt = {k: filt1[k] - filt0[k] for k in filt1}
    comp1 = _compile_metrics()
    comp = {k: comp1[k] - comp0[k] for k in comp1}
    pack_s = stages["pipeline_pack_seconds"]
    lags_ms = [(t - commit_times[lsn]) * 1000 for lsn, t in arrivals
               if lsn in commit_times]
    lags_ms.sort()

    def pct(p):
        return lags_ms[min(len(lags_ms) - 1,
                           int(p * len(lags_ms)))] if lags_ms else None

    return {
        "mode": "table_streaming", "events": n_events, "engine": engine,
        "destination": destination, "arrival_rate": arrival_rate,
        "producer_events_per_second":
            round(n_events / (t_prod1 - t_prod0)),
        "end_to_end_events_per_second":
            round(n_events / (t_e2e - t_prod0)),
        "end_to_end_with_shutdown_events_per_second":
            round(n_events / (t_drain - t_prod0)),
        "throughput_events": delivered(),
        "decode_rows_device": int(routed["device"]),
        "decode_rows_host": int(routed["host"]),
        "decode_rows_oracle": int(routed["oracle"]),
        "device_decoded_share":
            round(routed["device"] / routed_total, 3) if routed_total else 0.0,
        # decode pipeline stage activity over the measured window: the
        # overlap ratio is the share of pack time that ran concurrently
        # with another batch in flight (the three-stage scheduler's win)
        "decode_pack_seconds": round(stages["pack_seconds"], 4),
        "decode_dispatch_seconds": round(stages["dispatch_seconds"], 4),
        "decode_fetch_seconds": round(stages["fetch_seconds"], 4),
        "decode_overlap_seconds": round(stages["overlap_seconds"], 4),
        "decode_overlap_ratio":
            round(stages["overlap_seconds"] / pack_s, 3) if pack_s else 0.0,
        # fair-admission + mesh activity over the measured window: a lone
        # stream should see zero wait time (uncontended grants), and
        # mesh_* stay zero off-mesh — nonzero padded_rows/mesh_rows is
        # the padding waste the operator tunes batch sizes against
        "admission_grants": int(adm["admission_grants"]),
        "admission_starvation_grants":
            int(adm["admission_starvation_grants"]),
        "admission_wait_seconds": round(adm["admission_wait_seconds"], 4),
        "mesh_batches": int(adm["mesh_batches"]),
        "mesh_padded_rows": int(adm["mesh_padded_rows"]),
        # fused row-filter activity over the measured window (zero on
        # unfiltered publications): filtered rows never reach the fetch
        # path, and fetched_bytes is the link traffic the packed-result
        # fetches actually moved
        "decode_rows_filtered": int(filt["decode_rows_filtered"]),
        "decode_fetched_bytes": int(filt["decode_fetched_bytes"]),
        # program-store activity over the measured window: nonzero
        # programs_compiled means the window paid an XLA build the
        # warmup should have absorbed — warmup cost stops hiding
        "programs_compiled": int(comp["programs_compiled"]),
        "compile_cache_hits_memory":
            int(comp["compile_cache_hits_memory"]),
        "compile_cache_hits_disk": int(comp["compile_cache_hits_disk"]),
        "compile_cache_misses": int(comp["compile_cache_misses"]),
        "replication_lag_p50_ms":
            round(pct(0.50), 2) if lags_ms else None,
        "replication_lag_p95_ms":
            round(pct(0.95), 2) if lags_ms else None,
        "replication_lag_max_ms": round(lags_ms[-1], 2) if lags_ms else None,
        "table_rows_constructed":
            publish_table_rows_constructed() - rows_constructed0,
    }


async def run_lag_vs_rate(engine: str = "tpu",
                          fractions: tuple = (0.25, 0.5, 0.75),
                          probe_events: int = 60_000,
                          max_fill_ms: int = 5,
                          per_rate_cap: int = 240_000) -> dict:
    """p50/p95 end-to-end replication lag at fixed offered loads.

    The drain-style streaming bench saturates the pipeline, so its lag
    percentiles measure queue depth, not latency. This mode first probes
    the sustainable maximum, then replays at 25/50/75% of it with paced
    production and reports real lag per rate (BASELINE.md names "p50
    end-to-end replication lag" as a headline metric; reference gauges:
    crates/etl/src/observability.rs:46-50). The fill window is 5 ms — a
    lag-oriented batching config, reported in the output; the reference
    default (10 s, pipeline.rs:52-68) optimizes throughput instead and
    would floor every percentile at the batch deadline.
    """
    probe = await run_table_streaming(n_events=probe_events, engine=engine,
                                      max_fill_ms=max_fill_ms)
    max_rate = probe["end_to_end_events_per_second"]
    rows = []
    for f in fractions:
        rate = max(1000, int(max_rate * f))
        # ~3 s of paced traffic per rate, bounded for bench wall-clock
        # (smoke tests pass a small per_rate_cap — the paced replay
        # scales with the MEASURED host rate, not probe_events)
        n = min(max(int(rate * 3), 3000), per_rate_cap)
        out = await run_table_streaming(n_events=n, engine=engine,
                                        max_fill_ms=max_fill_ms,
                                        arrival_rate=rate)
        rows.append({
            "fraction": f,
            # the 1000 ev/s floor can raise the rate above f*max on slow
            # hosts — report the load actually offered, not the request
            "effective_fraction": round(rate / max_rate, 3) if max_rate
            else None,
            "target_rate": rate, "events": n,
            "p50_ms": out["replication_lag_p50_ms"],
            "p95_ms": out["replication_lag_p95_ms"],
            "max_ms": out["replication_lag_max_ms"],
        })
    return {
        "mode": "lag_vs_rate", "engine": engine,
        "max_events_per_second": max_rate,
        "max_fill_ms": max_fill_ms,
        "rates": rows,
    }


# ---------------------------------------------------------------------------
# workload matrix (ISSUE 7: per-profile CDC throughput beyond insert-CDC)
# ---------------------------------------------------------------------------


async def run_workload_streaming(profile: str = "update_heavy_default",
                                 seed: int = 7, steps: int | None = None,
                                 engine: str = "tpu",
                                 target_ops: int = 3_000,
                                 verify_timeout_s: float = 240.0) -> dict:
    """CDC throughput for ONE workload profile (etl_tpu/workloads) through
    the full pipeline, with end-state verification: the destination's
    reconstructed final view must equal the generator's committed source
    truth (the same collapse rules the chaos invariant checker applies) —
    a throughput number over silently-wrong deliveries would be worse
    than no number.

    The memory destination is deliberate: non-insert profiles need the
    delivered events retained for verification, and every profile pays
    the same row-expansion cost, so per-profile numbers stay comparable.
    `steps` defaults to whatever reaches ~`target_ops` row ops for the
    profile's transaction shape."""
    from ..config import BatchConfig, BatchEngine, PipelineConfig
    from ..models.table_state import TableStateType
    from ..postgres.fake import FakeSource
    from ..runtime import Pipeline
    from ..store import NotifyingStore
    from ..workloads import WorkloadGenerator, get_profile

    p = get_profile(profile)
    gen = WorkloadGenerator(p, seed=seed)
    db = gen.build_db()
    store = NotifyingStore()
    from ..chaos.runner import TracingDestination

    dest = TracingDestination()
    pipeline = Pipeline(
        config=PipelineConfig(
            pipeline_id=1, publication_name="pub",
            batch=BatchConfig(max_fill_ms=30,
                              batch_engine=BatchEngine(engine))),
        store=store, destination=dest,
        source_factory=lambda: FakeSource(db))
    async def wait_delivered():
        # `delivered()` reconstructs the destination's full final view —
        # O(events × columns) of synchronous work ON the event loop — so
        # run it only when the event stream has QUIESCED (no new events
        # across a poll interval); while deliveries are still flowing the
        # wait costs nothing but a length check (a 20 ms reconstruct
        # cadence measurably starved the apply loop on the 120-column
        # profile)
        seen = -1
        while True:
            n = len(dest.events)
            if n == seen and gen.delivered(dest):
                return
            seen = n
            if pipeline._apply_task is not None \
                    and pipeline._apply_task.done():
                pipeline._apply_task.result()
                raise RuntimeError("pipeline stopped before delivering")
            await asyncio.sleep(0.1)

    try:
        # start + READY wait inside the try: a copy-path regression that
        # keeps a table from READY must still shut the pipeline down, not
        # leak its tasks past asyncio.run()
        await pipeline.start()
        for tid in gen.table_ids:
            await asyncio.wait_for(
                store.notify_on(tid, TableStateType.READY), 120)
        # warmup OFF the clock: the decode engine compiles one program per
        # (schema, row bucket, width signature) — on the 120-column mix a
        # single compile costs tens of seconds on the host backend, so an
        # unwarmed window measures XLA compile amortization, not throughput
        # (the same stance as run_table_streaming's warmup waves)
        # the warmup wait keeps the full budget regardless of
        # verify_timeout_s: a slow first delivery is compile/stall
        # headroom, not the end-state verification the knob bounds
        warm_target = max(100, target_ops // 5)
        while gen.row_ops < warm_target:
            await gen.run_tx(db)
        await asyncio.wait_for(wait_delivered(), timeout=240)
        # wait out background host-program builds (see
        # run_table_streaming's warmup) so the measured window runs warm
        # programs
        await _wait_background_compiles()

        # explicit `steps` runs exactly that many generator steps (the
        # smoke slice); otherwise step until ~target_ops row ops
        # committed — ops per step vary wildly across profiles (a DDL
        # backfill updates every live row), so a step-count heuristic
        # alone would run away
        ops0 = gen.row_ops
        t0 = time.perf_counter()
        steps_run = 0
        while (steps_run < steps if steps is not None
               else gen.row_ops - ops0 < target_ops):
            await gen.run_tx(db)
            steps_run += 1
        t_prod = time.perf_counter()
        # wait_delivered only returns once gen.delivered(dest) held on
        # the quiesced stream; recomputing the O(events x columns)
        # reconstruction here would just repeat it
        try:
            await asyncio.wait_for(wait_delivered(),
                                   timeout=verify_timeout_s)
            verified = True
        except asyncio.TimeoutError:
            # the stream either quiesced with a destination view that
            # never matched the generator's committed truth, or stalled
            # outright — both are delivery correctness failures the
            # caller gates on, not harness errors worth a traceback
            verified = False
        t_done = time.perf_counter()
    finally:
        # guard: wait() asserts a started pipeline, and a start() that
        # raised mid-way has nothing for shutdown_and_wait to join
        if pipeline._apply_task is not None:
            await pipeline.shutdown_and_wait()
    measured = gen.row_ops - ops0
    return {
        "profile": profile,
        "seed": seed,
        "steps": steps_run,
        "row_ops": measured,
        "warmup_ops": ops0,
        "producer_events_per_second":
            round(measured / max(t_prod - t0, 1e-9)),
        "events_per_second": round(measured / max(t_done - t0, 1e-9)),
        "verified": bool(verified),
        "expected_rows": sum(len(v) for v in gen.expected.values()),
    }


async def run_workload_matrix(profiles=None, seed: int = 7,
                              engine: str = "tpu",
                              target_ops: int = 3_000) -> dict:
    """`run_workload_streaming` across the whole profile catalog (or a
    selected subset): the per-workload throughput matrix published as
    `workload_floors` in BENCH_FLOOR.json."""
    from ..workloads import profile_names

    names = list(profiles) if profiles else profile_names()
    rows = {}
    ok = True
    for name in names:
        out = await run_workload_streaming(name, seed=seed, engine=engine,
                                           target_ops=target_ops)
        rows[name] = out
        ok = ok and out["verified"]
    return {
        "mode": "workload_matrix", "engine": engine, "seed": seed,
        "profiles": rows,
        "events_per_second": {n: r["events_per_second"]
                              for n, r in rows.items()},
        "all_verified": bool(ok),
    }


async def run_multi_pipeline(profiles=None, seed: int = 7,
                             engine: str = "tpu",
                             target_ops: int = 1_000,
                             admission_capacity: int = 0,
                             verify_timeout_s: float = 240.0) -> dict:
    """N concurrent replication streams — one full Pipeline per workload
    profile (the tenancy mix) — sharing ONE device set through the fair
    batch-admission scheduler (ops/pipeline.AdmissionScheduler): the
    one-device-set-serves-many-streams shape. Every stream runs the whole
    path (fake walsender → apply loop → pipelined decode → memory
    destination) with end-state verification, so the aggregate number
    can't hide a tenant whose deliveries went wrong while the others
    kept the scheduler busy.

    Reports per-stream and AGGREGATE events/s over one shared measured
    window, the scheduler's per-tenant grant/weight stats captured while
    the tenants were still registered, the admission wait/grant counter
    deltas, and whether the scheduler drained clean (no tickets or
    tenants left after shutdown — the leak half of the chaos satellite,
    asserted here on the happy path)."""
    from ..chaos.runner import TracingDestination
    from ..config import BatchConfig, BatchEngine, PipelineConfig
    from ..models.table_state import TableStateType
    from ..ops.pipeline import global_admission, reset_global_admission
    from ..postgres.fake import FakeSource
    from ..runtime import Pipeline
    from ..store import NotifyingStore
    from ..workloads import WorkloadGenerator, get_profile

    # default mix pairs a small-flush tenant with a 512-row-transaction
    # tenant: giant_tx flushes cross the host-XLA row threshold, so the
    # run provably takes admission tickets (sub-threshold flushes decode
    # on the per-row oracle, which holds no device capacity by design)
    names = list(profiles) if profiles \
        else ["insert_heavy", "giant_tx"]
    # fresh process-wide scheduler: THIS run's capacity knob wins, and a
    # previous bench/test can't leave a different capacity behind
    reset_global_admission()

    streams = []
    for i, name in enumerate(names):
        label = name if names.count(name) == 1 else f"{name}-{i}"
        gen = WorkloadGenerator(get_profile(name), seed=seed + i)
        db = gen.build_db()
        pipeline = Pipeline(
            config=PipelineConfig(
                pipeline_id=i + 1, publication_name="pub",
                batch=BatchConfig(max_fill_ms=30,
                                  batch_engine=BatchEngine(engine),
                                  admission_capacity=admission_capacity)),
            store=(store := NotifyingStore()),
            destination=(dest := TracingDestination()),
            source_factory=lambda db=db: FakeSource(db))
        streams.append({"label": label, "gen": gen, "db": db,
                        "store": store, "dest": dest, "pipeline": pipeline})

    async def wait_verified(s) -> None:
        # same quiesce-then-reconstruct stance as run_workload_streaming:
        # the O(events × columns) final-view rebuild runs only when the
        # stream stops moving, so verification can't starve the loop
        seen = -1
        while True:
            n = len(s["dest"].events)
            if n == seen and s["gen"].delivered(s["dest"]):
                return
            seen = n
            task = s["pipeline"]._apply_task
            if task is not None and task.done():
                task.result()
                raise RuntimeError(
                    f"stream {s['label']} stopped before delivering")
            await asyncio.sleep(0.1)

    started = []
    verified: dict[str, bool] = {}
    try:
        for s in streams:
            await s["pipeline"].start()
            started.append(s)
        await asyncio.gather(*(
            asyncio.wait_for(
                s["store"].notify_on(tid, TableStateType.READY), 120)
            for s in streams for tid in s["gen"].table_ids))

        # warmup off the clock (per-schema decode-program compiles — the
        # same stance as every other harness mode), CONCURRENTLY: the
        # warmup traffic itself runs through the shared scheduler
        async def warm(s) -> None:
            warm_target = max(60, target_ops // 5)
            while s["gen"].row_ops < warm_target:
                await s["gen"].run_tx(s["db"])
            # full budget regardless of verify_timeout_s (the
            # run_workload_streaming stance): a slow first delivery is
            # compile/stall headroom, not the end-state verification
            # the knob bounds
            await asyncio.wait_for(wait_verified(s), 240)

        await asyncio.gather(*(warm(s) for s in streams))
        await _wait_background_compiles()

        adm0 = _admission_metrics()
        ops0 = {s["label"]: s["gen"].row_ops for s in streams}
        t0 = time.perf_counter()

        async def produce(s) -> None:
            base = s["gen"].row_ops
            while s["gen"].row_ops - base < target_ops:
                await s["gen"].run_tx(s["db"])

        await asyncio.gather(*(produce(s) for s in streams))
        t_prod = time.perf_counter()

        async def settle(s) -> None:
            try:
                await asyncio.wait_for(wait_verified(s), verify_timeout_s)
                verified[s["label"]] = True
            except asyncio.TimeoutError:
                verified[s["label"]] = False

        await asyncio.gather(*(settle(s) for s in streams))
        t_done = time.perf_counter()
        # tenant stats BEFORE shutdown deregisters them
        sched = global_admission(admission_capacity or None)
        sched_stats = sched.stats()
        adm1 = _admission_metrics()
    finally:
        for s in started:
            if s["pipeline"]._apply_task is not None:
                await s["pipeline"].shutdown_and_wait()

    adm = {k: adm1[k] - adm0[k] for k in adm1}
    per_stream = {}
    total_ops = 0
    for s in streams:
        measured = s["gen"].row_ops - ops0[s["label"]]
        total_ops += measured
        per_stream[s["label"]] = {
            "profile": s["gen"].profile.name,
            "row_ops": measured,
            "events_per_second": round(measured / max(t_done - t0, 1e-9)),
            "verified": bool(verified.get(s["label"], False)),
        }
    drained = sched.stats()
    return {
        "mode": "multi_pipeline", "engine": engine, "seed": seed,
        "streams": len(streams),
        "per_stream": per_stream,
        "aggregate_row_ops": total_ops,
        "aggregate_events_per_second":
            round(total_ops / max(t_done - t0, 1e-9)),
        "producer_events_per_second":
            round(total_ops / max(t_prod - t0, 1e-9)),
        "all_verified": all(per_stream[k]["verified"] for k in per_stream),
        "admission_capacity": sched_stats["capacity"],
        "admission_tenants": sched_stats["tenants"],
        "admission_grants": int(adm["admission_grants"]),
        "admission_starvation_grants":
            int(adm["admission_starvation_grants"]),
        "admission_bypass_grants": int(adm["admission_bypass_grants"]),
        "admission_wait_seconds": round(adm["admission_wait_seconds"], 4),
        "scheduler_drained": drained["in_flight"] == 0
                             and not drained["tenants"],
    }


async def run_sharded_processes(shards: int = 2,
                                profile: str = "insert_heavy",
                                seed: int = 7, tables: int = 8,
                                target_ops: int = 2_000,
                                engine: str = "tpu",
                                timeout_s: float = 600.0) -> dict:
    """K shard replicators as K OS PROCESSES (benchmarks/shard_worker.py)
    — separate interpreters, GILs, and XLA runtimes, the pod resource
    model — each replaying the identical publication WAL (the workload
    generator's byte-identical `(profile, seed)` contract) and applying
    only its ShardMap slice. The parent asserts the slices cover every
    table exactly once, every worker's slice verifies, and reports the
    aggregate events/s (sum of per-worker rates over their concurrent
    measured windows — the same aggregation run_multi_pipeline uses).

    `shards=1` spawns ONE unsharded worker over the same workload: the
    single-apply-loop baseline the acceptance bar compares against."""
    import json as _json
    import os
    import sys as _sys

    from ..sharding import ShardMap
    from ..workloads import get_profile

    get_profile(profile)  # fail fast on a typo'd profile name
    specs = []
    if shards <= 1:
        specs.append({"shard": None, "shard_count": 1})
    else:
        part = ShardMap(shards).partition(range(16384, 16384 + tables))
        if any(not owned for owned in part.values()):
            raise ValueError(
                f"degenerate shard map over {tables} tables: "
                f"{ {s: len(v) for s, v in part.items()} }")
        specs = [{"shard": s, "shard_count": shards}
                 for s in range(shards)]
    # CPU-forced: a chip belongs to one process, K workers cannot share it
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    async def spawn(spec: dict):
        spec = dict(spec, profile=profile, seed=seed, tables=tables,
                    target_ops=target_ops, engine=engine)
        proc = await asyncio.create_subprocess_exec(
            _sys.executable, "-m", "etl_tpu.benchmarks.shard_worker",
            _json.dumps(spec),
            stdout=asyncio.subprocess.PIPE,
            stderr=asyncio.subprocess.PIPE, env=env)
        try:
            out, err = await asyncio.wait_for(proc.communicate(),
                                              timeout_s)
        except asyncio.TimeoutError:
            proc.kill()
            await proc.wait()
            raise TimeoutError(
                f"shard worker {spec.get('shard')} did not finish in "
                f"{timeout_s:.0f}s")
        lines = out.decode().strip().splitlines()
        if proc.returncode != 0 or not lines:
            raise RuntimeError(
                f"shard worker {spec.get('shard')} failed "
                f"(rc={proc.returncode}): {err.decode()[-400:]}")
        return _json.loads(lines[-1])

    results = await asyncio.gather(*(spawn(s) for s in specs))

    owned_union: list = []
    for r in results:
        owned_union.extend(r["owned_table_ids"])
    expected_ids = list(range(16384, 16384 + tables))
    union_ok = sorted(owned_union) == expected_ids if shards > 1 \
        else results[0]["owned_table_ids"] == expected_ids
    return {
        "mode": "sharded", "engine": engine, "seed": seed,
        "profile": profile, "shards": max(1, shards), "tables": tables,
        "per_shard": {str(r["shard"]): r for r in results},
        "tables_per_shard": {str(r["shard"]): r["tables"]
                             for r in results},
        "aggregate_row_events": sum(r["delivered_row_events"]
                                    for r in results),
        "aggregate_events_per_second": sum(r["events_per_second"]
                                           for r in results),
        "all_verified": all(r["verified"] for r in results),
        "union_covers_all_tables": bool(union_ok),
    }


# ---------------------------------------------------------------------------
# egress (per-destination encoder isolation: ColumnarBatch → wire bytes)
# ---------------------------------------------------------------------------


def _egress_batch(n_rows: int, egress: "str | None" = None):
    """A decode-engine-shaped ColumnarBatch (dense ints + Arrow strings)
    on the pgbench-CDC column mix, produced through the REAL staging +
    decode path so the encoders see production column storage. With
    `egress` set the decode fuses the wire-encoding stage and the batch
    carries `device_egress` buffers (ops/egress.py)."""
    from ..models import (ColumnSchema, Oid, ReplicatedTableSchema,
                          TableName, TableSchema)
    from ..ops.engine import DeviceDecoder
    from ..ops.wal import concat_payloads, stage_wal_batch
    from ..postgres.codec.pgoutput import encode_insert

    tid = 16390
    schema = ReplicatedTableSchema.with_all_columns(TableSchema(
        tid, TableName("public", "bench_egress"),
        (ColumnSchema("id", Oid.INT8, nullable=False, primary_key_ordinal=1),
         ColumnSchema("bucket", Oid.INT4),
         ColumnSchema("val", Oid.FLOAT8),
         ColumnSchema("note", Oid.TEXT))))
    payloads = [encode_insert(tid, [str(i).encode(), str(i % 97).encode(),
                                    (b"%d.5" % i), b"note-%d" % i])
                for i in range(n_rows)]
    buf, offs, lens = concat_payloads(payloads)
    wal = stage_wal_batch(buf, offs, lens, 4)
    batch = DeviceDecoder(schema, egress=egress).decode(wal.staged)
    return schema, batch


def run_egress(n_rows: int = 16_384, n_iters: int = 5,
               device: bool = False) -> dict:
    """Measure each destination encoder in ISOLATION (ColumnarBatch →
    wire bytes): rows/s and bytes/s for the BigQuery proto encoder, the
    ClickHouse TSV renderer, and the Parquet row-group writer — so an
    egress regression names the guilty encoder instead of hiding inside
    the end-to-end streaming number. Floors: BENCH_FLOOR.json
    `egress_floors` (rows/s, min over encoders asserted by --smoke).

    `device=True` additionally measures the device-resident egress seam
    (ISSUE 17): batches decoded WITH the fused wire-encoding stage run
    through the piece-assembly fast paths splicing the device-rendered
    buffers — and the produced bytes are compared against the columnar
    oracles (`*_identical`, gated by --smoke: byte identity is the
    contract that lets the fast path exist at all)."""
    import io

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from ..destinations import bq_proto
    from ..destinations.clickhouse import render_batch_tsv_columnar
    from ..destinations.util import (CHANGE_SEQUENCE_COLUMN,
                                     CHANGE_TYPE_COLUMN, change_type_arrow,
                                     change_type_batch,
                                     sequence_number_arrow,
                                     sequence_number_batch)

    schema, batch = _egress_batch(n_rows)
    cts = np.zeros(n_rows, dtype=np.int64)
    lsns = np.arange(n_rows, dtype=np.uint64) + (1 << 40)
    txos = np.arange(n_rows, dtype=np.uint64)
    ords = np.arange(n_rows, dtype=np.uint64)

    def timed(fn):
        times = []
        nbytes = 0
        for _ in range(n_iters):
            t0 = time.perf_counter()
            nbytes = fn()
            times.append(time.perf_counter() - t0)
        # min over iters: shared-host noise is one-sided (bench.py policy)
        dt = min(times)
        return round(n_rows / dt), round(nbytes / dt)

    def bq():
        labels = change_type_batch(cts).tolist()
        seqs = sequence_number_batch(lsns, txos, ords)
        rows = bq_proto.encode_batch(schema, batch, labels, seqs)
        return sum(len(r) for r in rows)

    def clickhouse():
        labels = [t.decode() for t in change_type_batch(cts).tolist()]
        seqs = [s.decode()
                for s in sequence_number_batch(lsns, txos, ords)]
        return len(render_batch_tsv_columnar(schema, batch, labels, seqs))

    def parquet():
        rb = batch.to_arrow()
        rb = rb.append_column(CHANGE_TYPE_COLUMN, change_type_arrow(cts))
        rb = rb.append_column(CHANGE_SEQUENCE_COLUMN,
                              sequence_number_arrow(lsns, txos, ords))
        sink = io.BytesIO()
        pq.write_table(pa.Table.from_batches([rb]), sink)
        return sink.tell()

    def snowpipe():
        # NDJSON line encoding only — zstd compression is a C library
        # pass-through unchanged by the columnar refactor (and absent on
        # this container); the Python-cost part the floor guards is the
        # per-row dict + json.dumps the columnar encoder eliminated
        from ..destinations.snowflake import (encode_batch_ndjson,
                                              offset_token_batch)

        labels = ["insert"] * n_rows
        seqs = offset_token_batch(lsns, txos)
        lines = encode_batch_ndjson(schema, batch, labels, seqs)
        return sum(len(ln) for ln in lines)

    out: dict = {"mode": "egress", "rows": n_rows, "iters": n_iters}
    for name, fn in (("bq_proto", bq), ("clickhouse_tsv", clickhouse),
                     ("parquet", parquet), ("snowpipe_ndjson", snowpipe)):
        rps, bps = timed(fn)
        out[f"{name}_rows_per_sec"] = rps
        out[f"{name}_bytes_per_sec"] = bps
    if device:
        out.update(_run_egress_device(n_rows, n_iters, timed,
                                      lsns, txos, ords))
    return out


def _run_egress_device(n_rows: int, n_iters: int, timed, lsns, txos,
                       ords) -> dict:
    """The device-egress half of run_egress: decode once WITH the fused
    wire-encoding stage (blocking compile — bench, not streaming), then
    time the destination fast paths splicing the attached buffers and
    gate their bytes against the columnar oracles."""
    from ..destinations.clickhouse import (render_batch_tsv_columnar,
                                           render_batch_tsv_fast)
    from ..destinations.snowflake import (encode_batch_ndjson,
                                          encode_batch_ndjson_fast,
                                          offset_token_batch)
    from ..destinations.util import (sequence_number_batch,
                                     sequence_number_buffer)
    from ..ops.egress import ENCODER_JSON, ENCODER_TSV

    out: dict = {}
    seq_buf = sequence_number_buffer(lsns, txos, ords)
    seq_strs = [s.decode() for s in sequence_number_batch(lsns, txos,
                                                          ords)]
    schema, tsv_batch = _egress_batch(n_rows, egress=ENCODER_TSV)
    dev_tsv = tsv_batch.device_egress
    out["device_tsv_attached"] = dev_tsv is not None

    used = {"tsv": False, "json": False}

    def tsv():
        body, used_device = render_batch_tsv_fast(
            schema, tsv_batch, "UPSERT", seq_buf, egress=dev_tsv)
        used["tsv"] = used_device
        return len(body)

    rps, bps = timed(tsv)
    out["device_tsv_rows_per_sec"] = rps
    out["device_tsv_bytes_per_sec"] = bps
    out["device_tsv_used_device"] = used["tsv"]
    body, _ = render_batch_tsv_fast(schema, tsv_batch, "UPSERT", seq_buf,
                                    egress=dev_tsv)
    out["device_tsv_identical"] = body == render_batch_tsv_columnar(
        schema, tsv_batch, "UPSERT", seq_strs)

    _, json_batch = _egress_batch(n_rows, egress=ENCODER_JSON)
    dev_json = json_batch.device_egress
    out["device_json_attached"] = dev_json is not None
    ops = ["insert"] * n_rows
    seqs = offset_token_batch(lsns, txos)

    def ndjson():
        lines, used_device = encode_batch_ndjson_fast(
            schema, json_batch, ops, seqs, egress=dev_json)
        used["json"] = used_device
        return sum(len(ln) for ln in lines)

    rps, bps = timed(ndjson)
    out["device_json_rows_per_sec"] = rps
    out["device_json_bytes_per_sec"] = bps
    out["device_json_used_device"] = used["json"]
    lines, _ = encode_batch_ndjson_fast(schema, json_batch, ops, seqs,
                                        egress=dev_json)
    out["device_json_identical"] = lines == encode_batch_ndjson(
        schema, json_batch, ops, seqs)
    return out


# ---------------------------------------------------------------------------
# coldstart (ISSUE 12): restart-to-first-durable-batch, cold vs warm cache
# ---------------------------------------------------------------------------


def run_coldstart(n_tables: int = 3, rows_per_tx: int = 800,
                  txs_per_table: int = 2,
                  cache_dir: "str | None" = None) -> dict:
    """Two replicator lifetimes (subprocesses — jax program caches are
    process state, so cold vs warm MUST be separate processes) against
    one program-cache dir: the cold start compiles, the warm restart
    loads. Gates (asserted by --smoke):

      - warm restart compiles ZERO fresh XLA programs and serves its
        first durable batch off cached programs (no oracle rows);
      - the cold start's compile count proves canonicalization — the
        permuted-column tables share ONE layout, so compiles are bounded
        by the prewarm bucket count, not tables × buckets.

    Wall-clock numbers (start / first-durable / total) are recorded, not
    gated, on this CPU container: the XLA builds they eliminate are
    seconds here and tens of seconds on wide schemas."""
    import json as _json
    import os
    import shutil
    import subprocess
    import sys
    import tempfile

    owned = cache_dir is None
    if owned:
        cache_dir = tempfile.mkdtemp(prefix="etl-coldstart-cache-")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    def one_run() -> dict:
        proc = subprocess.run(
            [sys.executable, "-m", "etl_tpu.benchmarks.coldstart_worker",
             "--cache-dir", cache_dir, "--tables", str(n_tables),
             "--rows-per-tx", str(rows_per_tx),
             "--txs-per-table", str(txs_per_table)],
            capture_output=True, text=True, timeout=600, env=env, cwd=repo)
        if proc.returncode != 0:
            raise RuntimeError(
                f"coldstart worker failed: {proc.stderr[-1500:]}")
        return _json.loads(proc.stdout.strip().splitlines()[-1])

    try:
        cold = one_run()
        warm = one_run()
    finally:
        if owned:
            shutil.rmtree(cache_dir, ignore_errors=True)
    buckets = cold["prewarm_buckets"]  # emitted by the worker, so the
    #                                    gate can never drift from its
    #                                    PREWARM_BUCKETS tuple
    failures = []
    if warm["programs_compiled"] != 0:
        failures.append(f"warm restart compiled "
                        f"{warm['programs_compiled']} programs (want 0)")
    if warm["cache_hits_disk"] < 1:
        failures.append("warm restart never loaded a program from disk")
    if warm["oracle_rows"] != 0:
        failures.append(f"warm restart decoded {warm['oracle_rows']} rows "
                        "on the oracle (first batch not served from "
                        "cached programs)")
    if warm["host_rows"] <= 0:
        failures.append("warm restart routed nothing to the host program")
    if cold["programs_compiled"] > buckets:
        failures.append(
            f"cold start compiled {cold['programs_compiled']} programs "
            f"for {n_tables} tables — canonicalization should bound it "
            f"by the {buckets} prewarm buckets")
    if cold["canonical_layouts"] != 1:
        failures.append(f"{cold['canonical_layouts']} canonical layouts "
                        f"for {n_tables} same-multiset tables (want 1)")
    return {
        "mode": "coldstart", "ok": not failures, "failures": failures,
        "cold": cold, "warm": warm,
        "warm_zero_compiles": warm["programs_compiled"] == 0,
        "warm_first_durable_seconds": warm["first_durable_seconds"],
        "cold_first_durable_seconds": cold["first_durable_seconds"],
        "cold_oracle_rows_during_warmup": cold["oracle_rows"],
    }


# ---------------------------------------------------------------------------
# wide_row (BASELINE.json config: 100-col mixed types)
# ---------------------------------------------------------------------------


def run_wide_row(n_rows: int = 16_384, n_iters: int = 5,
                 engine: str = "xla") -> dict:
    import random

    from ..models import (ColumnSchema, Oid, ReplicatedTableSchema,
                          TableName, TableSchema)
    from ..ops import DeviceDecoder, stage_tuples
    from ..postgres.codec.pgoutput import TUPLE_NULL, TUPLE_TEXT, TupleData

    rng = random.Random(11)
    kinds = [Oid.INT8, Oid.INT4, Oid.NUMERIC, Oid.TEXT, Oid.TIMESTAMPTZ,
             Oid.DATE, Oid.BOOL, Oid.FLOAT8, Oid.JSONB, Oid.UUID]
    oids = [kinds[i % len(kinds)] for i in range(100)]
    cols = tuple(ColumnSchema(f"c{i}", oid) for i, oid in enumerate(oids))
    schema = ReplicatedTableSchema.with_all_columns(TableSchema(
        9, TableName("public", "wide"), cols))

    def text_for(oid):
        if oid == Oid.INT8:
            return str(rng.randrange(-10**12, 10**12))
        if oid == Oid.INT4:
            return str(rng.randrange(-10**9, 10**9))
        if oid == Oid.NUMERIC:
            return f"{rng.randrange(0, 10**8)}.{rng.randrange(0, 100):02d}"
        if oid == Oid.TEXT:
            return "text-" + str(rng.randrange(10**6))
        if oid == Oid.TIMESTAMPTZ:
            return "2024-05-01 12:34:56.789+00"
        if oid == Oid.DATE:
            return "2024-05-01"
        if oid == Oid.BOOL:
            return rng.choice(["t", "f"])
        if oid == Oid.FLOAT8:
            return f"{rng.uniform(-1e6, 1e6):.6f}"
        if oid == Oid.JSONB:
            return '{"k": %d}' % rng.randrange(1000)
        return "a0eebc99-9c0b-4ef8-bb6d-6bb9bd380a11"

    tuples = []
    for _ in range(n_rows):
        vals = []
        for oid in oids:
            if rng.random() < 0.05:
                vals.append(None)
            else:
                vals.append(text_for(oid).encode())
        tuples.append(TupleData(
            [TUPLE_NULL if v is None else TUPLE_TEXT for v in vals], vals))

    staged = stage_tuples(tuples, 100)
    # this mode MEASURES THE DEVICE PATH by definition — pin the routing
    # so the production DEVICE_MIN_ROWS (tuned for streaming flushes)
    # can't silently reroute the benchmark to the host backend
    dec = DeviceDecoder(schema, use_pallas=(engine == "pallas"),
                        device_min_rows=1)
    dec.decode(staged)  # warmup
    times = []
    for _ in range(n_iters):
        t0 = time.perf_counter()
        dec.decode(staged)
        times.append(time.perf_counter() - t0)
    rps = n_rows / _median(times)
    # the kernel's width bound (pallas_supported) sends a wide schema to
    # the XLA program and flips the flag — report the engine that ran
    ran = "pallas" if dec.use_pallas and engine == "pallas" else "xla"
    return {"mode": "wide_row", "rows": n_rows, "columns": 100,
            "engine": ran,
            "rows_per_second": round(rps),
            "cells_per_second": round(rps * 100)}


# ---------------------------------------------------------------------------
# selectivity (fused publication row filtering, ROADMAP item 4)
# ---------------------------------------------------------------------------


def _filtered_batches_identical(a, b) -> bool:
    """Byte-level equality of two compacted decode outputs, INCLUDING the
    survivor row mapping — a filter that dropped the right count but the
    wrong rows must fail here."""
    import numpy as np

    if a.num_rows != b.num_rows:
        return False
    sa = getattr(a, "source_rows", None)
    sb = getattr(b, "source_rows", None)
    if (sa is None) != (sb is None):
        return False
    if sa is not None and not np.array_equal(sa, sb):
        return False
    for ca, cb in zip(a.columns, b.columns):
        if not np.array_equal(ca.validity, cb.validity):
            return False
        if ca.is_dense and cb.is_dense:
            if not np.array_equal(ca.data[ca.validity],
                                  cb.data[cb.validity]):
                return False
        else:
            for i in range(a.num_rows):
                if ca.validity[i] and ca.value(i) != cb.value(i):
                    return False
    return True


def run_selectivity(n_rows: int = 16_384, n_iters: int = 5,
                    keep_fractions=(0.1, 0.5, 0.9),
                    fetch_slack: float = 0.11) -> dict:
    """Fused-filter decode matrix: both device engines (XLA jnp.where-mask
    twin and the Pallas fused kernel) across publication-filter
    selectivities, against the host oracle.

    Per selectivity: rows/s for each engine (filtered, compacted output),
    byte identity Pallas == XLA == host-oracle on the compacted batch AND
    the survivor mapping, and the MEASURED fetched-bytes ratio vs the
    unfiltered program — gated at (selectivity + fetch_slack), where the
    slack covers the keep-mask (1 bit/row), the survivor-count words and
    the fetch-slice bucket granularity (max(R/16, 256) rows,
    staging.slice_rows). Wall-clock speedup vs the unfiltered decode is
    recorded, NOT gated, on CPU containers (PR 8 precedent: only real
    TPU hardware turns fetch-link savings into throughput)."""
    import numpy as np

    from ..models import (ColumnSchema, Oid, ReplicatedTableSchema,
                          TableName, TableSchema)
    from ..ops.engine import DeviceDecoder
    from ..ops.predicate import parse_row_filter
    from ..ops.wal import concat_payloads, stage_wal_batch
    from ..postgres.codec.pgoutput import encode_insert
    from ..telemetry.metrics import (ETL_DECODE_FETCHED_BYTES_TOTAL,
                                     registry)

    table = TableSchema(
        16384, TableName("public", "filter_bench"),
        (ColumnSchema("id", Oid.INT8, nullable=False, primary_key_ordinal=1),
         ColumnSchema("v", Oid.INT4),
         ColumnSchema("note", Oid.TEXT)))
    rng = np.random.RandomState(11)
    vals = rng.randint(-1_000_000, 1_000_000, size=n_rows)
    payloads = [encode_insert(16384, [str(i).encode(),
                                      str(int(v)).encode(),
                                      b"n-%d" % i])
                for i, v in enumerate(vals)]
    buf, offs, lens = concat_payloads(payloads)

    def stage():
        return stage_wal_batch(buf, offs, lens, 3).staged

    def fetched_delta(dec, staged):
        b0 = registry.get_counter(ETL_DECODE_FETCHED_BYTES_TOTAL)
        batch = dec.decode(staged)
        return batch, registry.get_counter(
            ETL_DECODE_FETCHED_BYTES_TOTAL) - b0

    def best_rate(dec):
        times = []
        for _ in range(n_iters):
            s = stage()
            t0 = time.perf_counter()
            dec.decode(s)
            times.append(time.perf_counter() - t0)
        return n_rows / min(times)

    plain = ReplicatedTableSchema.with_all_columns(table)
    base_dec = DeviceDecoder(plain, device_min_rows=1, mesh=None)
    _, unfiltered_bytes = fetched_delta(base_dec, stage())
    unfiltered_rate = best_rate(base_dec)

    out = {"mode": "selectivity", "rows": n_rows,
           "unfiltered_rows_per_sec": round(unfiltered_rate),
           "unfiltered_fetched_bytes": int(unfiltered_bytes),
           "fetch_slack": fetch_slack,
           "points": []}
    all_ok = True
    for keep in keep_fractions:
        threshold = int(-1_000_000 + 2_000_000 * keep)
        sql = f"v < {threshold}"
        rts = ReplicatedTableSchema.with_all_columns(table) \
            .with_row_predicate(parse_row_filter(sql))
        xla = DeviceDecoder(rts, device_min_rows=1, mesh=None)
        pallas = DeviceDecoder(rts, device_min_rows=1, mesh=None,
                               use_pallas=True)
        # host oracle reference: every row through the per-row CPU
        # decode, the filter applied over decoded values (host_keep)
        oracle = DeviceDecoder(rts, device_min_rows=10**9,
                               host_min_rows=10**9, mesh=None)
        bx, filtered_bytes = fetched_delta(xla, stage())
        bp = pallas.decode(stage())
        bo = oracle.decode(stage())
        identical = _filtered_batches_identical(bx, bp) \
            and _filtered_batches_identical(bx, bo)
        measured_keep = bx.num_rows / n_rows
        ratio = filtered_bytes / unfiltered_bytes if unfiltered_bytes else 0
        fetch_ok = ratio <= measured_keep + fetch_slack
        xla_rate = best_rate(xla)
        point = {
            "row_filter": sql,
            "target_keep": keep,
            "measured_keep": round(measured_keep, 4),
            "survivors": bx.num_rows,
            "xla_rows_per_sec": round(xla_rate),
            "pallas_rows_per_sec": round(best_rate(pallas)),
            # recorded NOT gated on CPU (the host backend has no
            # transfer cost for this fusion to save)
            "xla_speedup_vs_unfiltered":
                round(xla_rate / unfiltered_rate, 3),
            "filtered_fetched_bytes": int(filtered_bytes),
            "fetched_bytes_ratio": round(ratio, 4),
            "fetch_reduction_ok": bool(fetch_ok),
            "engines_and_oracle_identical": bool(identical),
            "pallas_engine_ran": bool(pallas.use_pallas),
        }
        all_ok = all_ok and identical and fetch_ok
        out["points"].append(point)
    out["ok"] = bool(all_ok)
    return out


async def _ack_latency_run(write_window: int, ack_ms: float,
                           n_events: int, tx_size: int,
                           max_size_bytes: int, max_fill_ms: int,
                           engine: str = "cpu") -> dict:
    """One full-pipeline CDC run against a destination whose every ack
    turns durable `ack_ms` later (destinations/delay.py). The producer
    pre-commits the whole workload, so the run measures BACKLOG DRAIN
    throughput with size-bounded batches: at window=1 each batch's ack
    round trip serializes the next dispatch (the `batch_size / ack_rtt`
    ceiling), at window=K the round trips overlap. Engine defaults to
    the CPU per-tuple path: the bench isolates ACK PIPELINING, and at
    the deliberately small batch sizes the latency model needs, the
    device engine's per-sealed-run machinery (staging + admission + a
    program call per ~threshold bytes) would dominate the measurement
    on this host. Every delivered row folds into a BATCH-BOUNDARY-
    INDEPENDENT digest (per-row records concatenate identically however
    flushes were split) — the byte-identity evidence across window
    depths."""
    import hashlib

    import numpy as np

    from ..config import BatchConfig, BatchEngine, PipelineConfig
    from ..destinations import DelayedAckDestination
    from ..destinations.base import Destination, WriteAck
    from ..models import (ColumnSchema, InsertEvent, Oid, TableName,
                          TableSchema)
    from ..models.event import DecodedBatchEvent
    from ..models.table_state import TableStateType
    from ..postgres.codec.pgoutput import encode_insert
    from ..postgres.fake import FakeDatabase, FakeSource
    from ..runtime import Pipeline
    from ..store import NotifyingStore
    from ..telemetry.metrics import (
        ETL_DESTINATION_ACK_BUSY_SECONDS_TOTAL,
        ETL_DESTINATION_ACK_OVERLAP_SECONDS_TOTAL, registry)

    TID = 16391
    db = FakeDatabase()
    # all-dense columns: the delivery digest covers full content via
    # column byte concatenation, no per-row Python on the measured path
    db.create_table(TableSchema(
        TID, TableName("public", "bench_ack"),
        (ColumnSchema("id", Oid.INT8, nullable=False, primary_key_ordinal=1),
         ColumnSchema("v", Oid.INT4))))
    db.create_publication("pub", [TID])
    store = NotifyingStore()

    def _digest_batch(digest, e) -> int:
        """Sync helper (host-side numpy — the batch is already resolved
        to host arrays): PER-ROW interleaving (column_stack) keeps the
        digest independent of how flushes were split — concatenating row
        records across batches yields the same byte stream at every
        window depth."""
        batch = e.batch
        fields = [np.asarray(e.change_types),
                  np.asarray(e.commit_lsns),
                  np.asarray(e.tx_ordinals)]
        for c in batch.columns:
            valid = np.asarray(c.validity)
            fields.append(valid)
            fields.append(np.where(valid, np.asarray(c.data), 0))
        digest.update(np.column_stack(
            [f.astype(np.uint64) for f in fields]).tobytes())
        return batch.num_rows

    def _digest_row(digest, e) -> int:
        """CPU engine: per-row events; same per-row record shape as one
        column_stack row, so the digest stays comparable across window
        depths (not engines)."""
        digest.update(np.asarray(
            [1, int(e.commit_lsn), e.tx_ordinal,
             1, int(e.row.values[0]), 1, int(e.row.values[1])],
            dtype=np.uint64).tobytes())
        return 1

    class DigestingDestination(Destination):
        def __init__(self):
            self.rows_delivered = 0
            self.digest = hashlib.sha256()

        async def startup(self):
            return None

        async def write_table_rows(self, schema, batch):
            return WriteAck.durable()

        async def write_events(self, events):
            for e in events:
                if isinstance(e, DecodedBatchEvent):
                    self.rows_delivered += _digest_batch(self.digest, e)
                elif isinstance(e, InsertEvent):
                    self.rows_delivered += _digest_row(self.digest, e)
            return WriteAck.durable()

        async def drop_table(self, table_id, schema=None):
            return None

        async def truncate_table(self, table_id):
            return None

    inner = DigestingDestination()
    dest = DelayedAckDestination(inner, ack_ms / 1000.0)
    labels = {"path": "apply"}
    busy0 = registry.get_counter(ETL_DESTINATION_ACK_BUSY_SECONDS_TOTAL,
                                 labels)
    overlap0 = registry.get_counter(
        ETL_DESTINATION_ACK_OVERLAP_SECONDS_TOTAL, labels)
    pipeline = Pipeline(
        config=PipelineConfig(
            pipeline_id=1, publication_name="pub",
            batch=BatchConfig(max_size_bytes=max_size_bytes,
                              max_fill_ms=max_fill_ms,
                              batch_engine=BatchEngine(engine),
                              write_window=write_window)),
        store=store, destination=dest,
        source_factory=lambda: FakeSource(db))
    await pipeline.start()
    await asyncio.wait_for(store.notify_on(TID, TableStateType.READY), 60)

    # warmup OFF the clock: one tx through the full path compiles the
    # host decode programs for the buckets this run stages into
    n_warm = 8
    tx = db.transaction()
    for i in range(n_warm):
        tx.insert_preencoded(TID, encode_insert(
            TID, [str(10**7 + i).encode(), b"0"]))
    await tx.commit()
    while inner.rows_delivered < n_warm:
        await asyncio.sleep(0.01)
    await _wait_background_compiles()
    inner.rows_delivered = 0
    inner.digest = hashlib.sha256()

    payloads = [encode_insert(TID, [str(i).encode(), str(i % 97).encode()])
                for i in range(n_events)]
    t0 = time.perf_counter()
    produced = 0
    while produced < n_events:
        tx = db.transaction()
        for _ in range(min(tx_size, n_events - produced)):
            tx.insert_preencoded(TID, payloads[produced])
            produced += 1
        await tx.commit()
    while inner.rows_delivered < n_events:
        if pipeline._apply_task is not None and pipeline._apply_task.done():
            pipeline._apply_task.result()
            raise RuntimeError("pipeline stopped before delivering")
        await asyncio.sleep(0.002)
    # durability barrier: every delayed ack must resolve (delivery alone
    # would flatter the windowed run, which by design has acks pending)
    while dest.pending > 0:
        await asyncio.sleep(0.002)
    elapsed = time.perf_counter() - t0
    await pipeline.shutdown_and_wait()

    busy = registry.get_counter(ETL_DESTINATION_ACK_BUSY_SECONDS_TOTAL,
                                labels) - busy0
    overlap = registry.get_counter(
        ETL_DESTINATION_ACK_OVERLAP_SECONDS_TOTAL, labels) - overlap0
    return {
        "write_window": write_window,
        "events_per_second": round(n_events / elapsed),
        "elapsed_seconds": round(elapsed, 4),
        "acks_issued": dest.acks_issued,
        "max_acks_pending": dest.max_pending,
        "delivery_digest": inner.digest.hexdigest(),
        "ack_busy_seconds": round(busy, 4),
        "ack_overlap_seconds": round(overlap, 4),
        "ack_overlap_ratio": round(overlap / busy, 3) if busy else 0.0,
    }


async def run_ack_latency(ack_ms: float = 20.0, n_events: int = 2000,
                          tx_size: int = 20, max_size_bytes: int = 2048,
                          max_fill_ms: int = 10,
                          write_window: "int | None" = None) -> dict:
    """The windowed-ack A/B gate (ISSUE 14): the SAME deterministic
    backlog drained through the default write window and through a
    forced window=1 run. GATES (caller applies the speedup floor):
    byte-identical delivery (order + content digests equal), window=1
    never holds more than one ack in flight, the windowed run provably
    overlaps (max pending ≥ 2, overlap ratio > 0)."""
    from ..config import BatchConfig

    window = write_window or BatchConfig().write_window
    windowed = await _ack_latency_run(window, ack_ms, n_events, tx_size,
                                      max_size_bytes, max_fill_ms)
    serial = await _ack_latency_run(1, ack_ms, n_events, tx_size,
                                    max_size_bytes, max_fill_ms)
    speedup = windowed["events_per_second"] \
        / max(serial["events_per_second"], 1)
    failures = []
    if windowed["delivery_digest"] != serial["delivery_digest"]:
        failures.append("windowed delivery is not byte-identical to the "
                        "window=1 run")
    if serial["max_acks_pending"] > 1:
        failures.append(
            f"window=1 held {serial['max_acks_pending']} acks in flight "
            f"(must be ≤ 1 — the one-in-flight contract)")
    if windowed["max_acks_pending"] < 2:
        failures.append("the windowed run never overlapped two acks")
    if windowed["ack_overlap_seconds"] <= 0:
        failures.append("the windowed run recorded zero overlap seconds")
    return {
        "mode": "ack_latency",
        "ack_latency_ms": ack_ms,
        "events": n_events,
        "max_size_bytes": max_size_bytes,
        "windowed": windowed,
        "window1": serial,
        "ack_window_speedup": round(speedup, 3),
        "failures": failures,
        "ok": not failures,
    }


async def _run_poison_pass(profile, seed: int, target_ops: int,
                           poisoned: bool,
                           verify_timeout_s: float = 120.0) -> dict:
    """One streamed-CDC measurement for the poison gate: the same
    (profile, seed) workload through the full pipeline, either clean
    (poison_rate=0, plain destination, view==truth verification) or
    poisoned (PoisonRejectingDestination + isolation live, union
    verification: delivered ∪ dead-lettered == committed truth)."""
    from dataclasses import replace as _replace

    from ..chaos.invariants import reconstruct_final_view, view_matches
    from ..chaos.runner import RecordingStore, TracingDestination
    from ..config import (BatchConfig, BatchEngine, PipelineConfig,
                          PoisonConfig)
    from ..destinations import PoisonRejectingDestination
    from ..dlq.codec import decode_cell
    from ..models.table_state import TableStateType
    from ..postgres.fake import FakeSource
    from ..runtime import Pipeline
    from ..runtime import poison as poison_mod
    from ..workloads import WorkloadGenerator

    if not poisoned:
        profile = _replace(profile, poison_rate=0.0)
    gen = WorkloadGenerator(profile, seed=seed)
    db = gen.build_db()
    store = RecordingStore()
    inner = TracingDestination()
    dest = PoisonRejectingDestination(inner) if poisoned else inner
    pipeline = Pipeline(
        config=PipelineConfig(
            pipeline_id=1, publication_name="pub",
            batch=BatchConfig(max_fill_ms=30,
                              batch_engine=BatchEngine("tpu")),
            # budget high enough that quarantine never trips: the gate
            # measures bisection + DLQ cost on a flowing stream, not the
            # (cheaper) parking path
            poison=PoisonConfig(budget_rows=1_000_000)),
        store=store, destination=dest,
        source_factory=lambda: FakeSource(db))

    async def settled() -> bool:
        if not poisoned:
            return view_matches(inner, gen.table_ids, gen.expected)
        entries = await store.list_dead_letters(status=None)
        import json as _json

        dlq: dict = {tid: {} for tid in gen.table_ids}
        for e in sorted(entries, key=lambda e: (e.commit_lsn,
                                                e.tx_ordinal)):
            doc = _json.loads(e.payload)
            values = tuple(decode_cell(v) for v in doc["values"])
            dlq.setdefault(e.table_id, {})[values[0]] = values
        view = reconstruct_final_view(inner, gen.table_ids)
        for tid in gen.table_ids:
            for pk, values in gen.expected[tid].items():
                if view[tid].get(pk) != values \
                        and dlq[tid].get(pk) != values:
                    return False
        return True

    async def wait_settled(timeout: float) -> bool:
        deadline = time.perf_counter() + timeout
        seen = -1
        while True:
            n = len(inner.events)
            if n == seen and await settled():
                return True
            seen = n
            if pipeline._apply_task is not None \
                    and pipeline._apply_task.done():
                pipeline._apply_task.result()
                raise RuntimeError("pipeline stopped before delivering")
            if time.perf_counter() >= deadline:
                return False
            await asyncio.sleep(0.1)

    poison_mod.reset_isolation_trace()
    try:
        await pipeline.start()
        for tid in gen.table_ids:
            await asyncio.wait_for(
                store.notify_on(tid, TableStateType.READY), 120)
        warm_target = max(100, target_ops // 5)
        while gen.row_ops < warm_target:
            await gen.run_tx(db)
        if not await wait_settled(240):
            raise RuntimeError("warmup never settled")
        await _wait_background_compiles()
        ops0 = gen.row_ops
        t0 = time.perf_counter()
        while gen.row_ops - ops0 < target_ops:
            await gen.run_tx(db)
        verified = await wait_settled(verify_timeout_s)
        t_done = time.perf_counter()
    finally:
        if pipeline._apply_task is not None:
            await pipeline.shutdown_and_wait()
    measured = gen.row_ops - ops0
    traces = list(poison_mod.ISOLATION_TRACE)
    probe_writes = sum(t["probe_writes"] for t in traces)
    probe_bound = sum(
        poison_mod.bisection_bound(t["rows"], t["tables"],
                                   t["poison_rows"]) for t in traces)
    dlq_entries = len(await store.list_dead_letters(status=None)) \
        if poisoned else 0
    return {
        "events_per_second": round(measured / max(t_done - t0, 1e-9)),
        "row_ops": measured,
        "verified": bool(verified),
        "poison_rows_committed": sum(len(v)
                                     for v in gen.poison_pks.values()),
        "dlq_entries": dlq_entries,
        "isolations": len(traces),
        "probe_writes": probe_writes,
        "probe_bound": probe_bound,
        "bound_ok": probe_writes <= probe_bound,
    }


async def run_poison_streaming(rate: float = 0.001, seed: int = 7,
                               target_ops: int = 3_000) -> dict:
    """The poison-resilience gate (bench.py --poison): the SAME seeded
    insert-CDC workload measured twice — clean, and with `rate` of rows
    poisoned against a rejecting destination with isolation live. GATES
    (caller applies floors): the poisoned rate must hold ≥
    poison_ratio_floor of the clean rate, the isolation probe writes
    must stay within the bisection bound, and BOTH runs must verify
    (clean: view == truth; poisoned: delivered ∪ dead-lettered ==
    truth, every poison row accounted)."""
    from dataclasses import replace as _replace

    from ..workloads import get_profile

    profile = _replace(get_profile("poison_rows"), poison_rate=rate)
    clean = await _run_poison_pass(profile, seed, target_ops,
                                   poisoned=False)
    poisoned = await _run_poison_pass(profile, seed, target_ops,
                                      poisoned=True)
    ratio = poisoned["events_per_second"] \
        / max(1, clean["events_per_second"])
    failures = []
    if not clean["verified"]:
        failures.append("clean pass failed end-state verification")
    if not poisoned["verified"]:
        failures.append("poisoned pass failed the union invariant "
                        "(delivered ∪ dead-lettered != committed truth)")
    if not poisoned["bound_ok"]:
        failures.append(
            f"bisection writes {poisoned['probe_writes']} exceeded the "
            f"bound {poisoned['probe_bound']}")
    if poisoned["poison_rows_committed"] == 0:
        failures.append("seed committed no poison rows — the gate "
                        "measured nothing; raise target_ops or rate")
    elif poisoned["dlq_entries"] == 0:
        failures.append("poison rows committed but none dead-lettered")
    return {
        "mode": "poison",
        "seed": seed,
        "poison_rate": rate,
        "clean": clean,
        "poisoned": poisoned,
        "clean_events_per_second": clean["events_per_second"],
        "poisoned_events_per_second": poisoned["events_per_second"],
        "poison_throughput_ratio": round(ratio, 3),
        "failures": failures,
        "ok": not failures,
    }


async def _exactly_once_table(tid: int):
    from ..models import ColumnSchema, Oid, TableName, TableSchema
    from ..postgres.fake import FakeDatabase

    db = FakeDatabase()
    db.create_table(TableSchema(
        tid, TableName("public", "bench_eo"),
        (ColumnSchema("id", Oid.INT8, nullable=False,
                      primary_key_ordinal=1),
         ColumnSchema("v", Oid.INT4))))
    db.create_publication("pub", [tid])
    return db


async def _exactly_once_drain(transactional: bool, n_events: int,
                              tx_size: int, max_size_bytes: int,
                              max_fill_ms: int) -> dict:
    """One full-pipeline CDC backlog drain into either the plain memory
    sink or the transactional one (write_event_batches_committed +
    coordinate bookkeeping on every flush) — the A/B legs of the
    exactly-once overhead ratio. CPU per-tuple engine for the same
    reason as the ack-latency bench: the gate isolates the SEAM's
    per-flush cost (CommitRange derivation, coordinate dedup filter,
    high-water accounting), which the device engine's per-run machinery
    would drown at these batch sizes."""
    from ..config import BatchConfig, BatchEngine, PipelineConfig
    from ..destinations import (MemoryDestination,
                                TransactionalMemoryDestination)
    from ..models.table_state import TableStateType
    from ..postgres.codec.pgoutput import encode_insert
    from ..postgres.fake import FakeSource
    from ..runtime import Pipeline
    from ..store import NotifyingStore

    TID = 16401
    db = await _exactly_once_table(TID)
    store = NotifyingStore()
    dest = TransactionalMemoryDestination() if transactional \
        else MemoryDestination()
    pipeline = Pipeline(
        config=PipelineConfig(
            pipeline_id=1, publication_name="pub",
            batch=BatchConfig(max_size_bytes=max_size_bytes,
                              max_fill_ms=max_fill_ms,
                              batch_engine=BatchEngine("cpu"))),
        store=store, destination=dest,
        source_factory=lambda: FakeSource(db))
    await pipeline.start()
    await asyncio.wait_for(store.notify_on(TID, TableStateType.READY), 60)

    n_warm = 8
    tx = db.transaction()
    for i in range(n_warm):
        tx.insert_preencoded(TID, encode_insert(
            TID, [str(10**7 + i).encode(), b"0"]))
    await tx.commit()
    while len(dest.events) < n_warm:
        await asyncio.sleep(0.01)
    await _wait_background_compiles()
    dest.events.clear()  # coordinates (high_water) survive; content reset

    payloads = [encode_insert(TID, [str(i).encode(), str(i % 97).encode()])
                for i in range(n_events)]
    t0 = time.perf_counter()
    produced = 0
    while produced < n_events:
        tx = db.transaction()
        for _ in range(min(tx_size, n_events - produced)):
            tx.insert_preencoded(TID, payloads[produced])
            produced += 1
        await tx.commit()
    while len(dest.events) < n_events:
        if pipeline._apply_task is not None and pipeline._apply_task.done():
            pipeline._apply_task.result()
            raise RuntimeError("pipeline stopped before delivering")
        await asyncio.sleep(0.002)
    elapsed = time.perf_counter() - t0
    await pipeline.shutdown_and_wait()
    out = {
        "transactional": transactional,
        "events_per_second": round(n_events / elapsed),
        "elapsed_seconds": round(elapsed, 4),
        "rows_delivered": len(dest.events),
    }
    if transactional:
        out["uncoordinated_writes"] = dest.uncoordinated_writes
        out["high_water"] = list(dest.high_water)
    return out


async def _exactly_once_restart_leg(n_events: int, tx_size: int,
                                    max_size_bytes: int,
                                    max_fill_ms: int) -> dict:
    """The recovery-trim leg: hard-kill a pipeline mid-backlog against
    the transactional sink, measure the unacked suffix (sink rows whose
    WAL coordinates lie beyond the store's durable progress at the kill
    instant), restart, and finish. The caller gates: zero duplicates,
    zero loss, and re-streamed-already-applied rows (the sink's
    coordinate-dedup counter) bounded by that suffix — the exactly-once
    analogue of `re-stream <= unacked window`."""
    from ..chaos.runner import _hard_kill
    from ..config import BatchConfig, BatchEngine, PipelineConfig
    from ..destinations import TransactionalMemoryDestination
    from ..destinations.base import event_coordinate
    from ..models.table_state import TableStateType
    from ..postgres.codec.pgoutput import encode_insert
    from ..postgres.fake import FakeSource
    from ..postgres.slots import apply_slot_name
    from ..runtime import Pipeline
    from ..store import NotifyingStore

    TID = 16402
    db = await _exactly_once_table(TID)
    store = NotifyingStore()
    dest = TransactionalMemoryDestination()

    def make_pipeline():
        return Pipeline(
            config=PipelineConfig(
                pipeline_id=1, publication_name="pub",
                batch=BatchConfig(max_size_bytes=max_size_bytes,
                                  max_fill_ms=max_fill_ms,
                                  batch_engine=BatchEngine("cpu"))),
            store=store, destination=dest,
            source_factory=lambda: FakeSource(db))

    def row_events() -> list:
        # the CPU engine delivers Begin/Commit/Relation envelopes too;
        # the dup/loss arithmetic counts data rows only
        return [e for e in dest.events
                if getattr(e, "row", None) is not None]

    def distinct_rows() -> int:
        return len({e.row.values[0] for e in row_events()})

    pipeline = make_pipeline()
    await pipeline.start()
    await asyncio.wait_for(store.notify_on(TID, TableStateType.READY), 60)
    payloads = [encode_insert(TID, [str(i).encode(), str(i % 97).encode()])
                for i in range(n_events)]
    produced = 0
    while produced < n_events // 2:
        tx = db.transaction()
        for _ in range(min(tx_size, n_events // 2 - produced)):
            tx.insert_preencoded(TID, payloads[produced])
            produced += 1
        await tx.commit()
    # kill once the drain is verifiably mid-flight: some rows applied,
    # the rest still streaming — the classic write-vs-progress gap
    kill_after = max(1, n_events // 8)
    deadline = time.perf_counter() + 60
    while len(row_events()) < kill_after:
        if time.perf_counter() >= deadline:
            raise RuntimeError("drain never reached the kill window")
        await asyncio.sleep(0.002)
    await _hard_kill(pipeline)
    durable = int(await store.get_durable_progress(apply_slot_name(1))
                  or 0)
    suffix = sum(1 for e in dest.events
                 if (c := event_coordinate(e)) is not None
                 and c[0] > durable)
    applied_at_kill = len(row_events())

    pipeline = make_pipeline()
    await pipeline.start()
    while produced < n_events:
        tx = db.transaction()
        for _ in range(min(tx_size, n_events - produced)):
            tx.insert_preencoded(TID, payloads[produced])
            produced += 1
        await tx.commit()
    deadline = time.perf_counter() + 120
    while distinct_rows() < n_events:
        if pipeline._apply_task is not None and pipeline._apply_task.done():
            pipeline._apply_task.result()
            raise RuntimeError("pipeline stopped before delivering")
        if time.perf_counter() >= deadline:
            raise RuntimeError(
                f"recovery leg never delivered: {distinct_rows()}"
                f"/{n_events}")
        await asyncio.sleep(0.005)
    await pipeline.shutdown_and_wait()
    return {
        "rows_applied_at_kill": applied_at_kill,
        "durable_lsn_at_kill": durable,
        "unacked_suffix_rows": suffix,
        "restreamed_deduped_rows": dest.dedup_skipped_rows,
        "duplicate_rows": len(row_events()) - distinct_rows(),
        "rows_delivered": distinct_rows(),
        "recover_calls": dest.recover_calls,
        "uncoordinated_writes": dest.uncoordinated_writes,
    }


async def run_exactly_once(n_events: int = 3_000, tx_size: int = 40,
                           max_size_bytes: int = 4096,
                           max_fill_ms: int = 10,
                           repeats: int = 3) -> dict:
    """The exactly-once overhead + recovery-trim gate (bench.py
    --exactly-once, ISSUE 19): the SAME deterministic CDC backlog
    drained into the plain memory sink and into the transactional one
    (coordinate range recorded atomically with every flush). GATES
    (caller applies exactly_once_ratio_floor): the transactional drain
    must hold >= floor of the plain rate, every CDC write must route
    through the committed seam (zero uncoordinated writes), and the
    hard-kill restart leg must deliver every row exactly once with its
    re-streamed-already-applied rows bounded by the unacked suffix at
    the kill. Each timed drain is best-of-`repeats`, A/B interleaved:
    a single ~0.2s pass on this shared-host container carries 30-40%
    scheduler noise, far above the coordination overhead under test."""
    plain = txn = None
    for _ in range(max(1, repeats)):
        p = await _exactly_once_drain(False, n_events, tx_size,
                                      max_size_bytes, max_fill_ms)
        t = await _exactly_once_drain(True, n_events, tx_size,
                                      max_size_bytes, max_fill_ms)
        if plain is None or p["events_per_second"] > \
                plain["events_per_second"]:
            plain = p
        if txn is None or t["events_per_second"] > \
                txn["events_per_second"]:
            txn = t
    leg = await _exactly_once_restart_leg(n_events, tx_size,
                                          max_size_bytes, max_fill_ms)
    ratio = txn["events_per_second"] / max(1, plain["events_per_second"])
    failures = []
    if txn["uncoordinated_writes"]:
        failures.append(
            f"{txn['uncoordinated_writes']} CDC write(s) bypassed the "
            f"transactional seam in the drain leg")
    if leg["uncoordinated_writes"]:
        failures.append(
            f"{leg['uncoordinated_writes']} CDC write(s) bypassed the "
            f"transactional seam in the restart leg")
    if leg["duplicate_rows"]:
        failures.append(
            f"exactly-once violated across the hard kill: "
            f"{leg['duplicate_rows']} duplicate row(s) reached the sink")
    if leg["rows_delivered"] < n_events:
        failures.append(
            f"loss across the hard kill: {leg['rows_delivered']}"
            f"/{n_events} rows delivered")
    if leg["restreamed_deduped_rows"] > leg["unacked_suffix_rows"]:
        failures.append(
            f"re-stream exceeded the unacked suffix: "
            f"{leg['restreamed_deduped_rows']} already-applied rows "
            f"re-delivered vs {leg['unacked_suffix_rows']} unacked at "
            f"the kill — recovery did not trim the resume point")
    if leg["recover_calls"] < 1:
        failures.append("the restart never queried the sink high-water "
                        "mark")
    return {
        "mode": "exactly_once",
        "events": n_events,
        "plain": plain,
        "transactional": txn,
        "restart": leg,
        "plain_events_per_second": plain["events_per_second"],
        "transactional_events_per_second": txn["events_per_second"],
        "exactly_once_overhead_ratio": round(ratio, 3),
        "failures": failures,
        "ok": not failures,
    }
