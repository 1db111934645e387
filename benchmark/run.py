#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It holds the chip and refuses to run off it (exit
3, nothing on stdout); it starts the source (fake Postgres + generator) and,
where the configuration has one, the ClickHouse sink as child processes
that never import JAX; warms only this cell's shapes from the placed
compile cache; measures; checks what the timed path delivered against the
plain reference; prints one JSON object as its last stdout line.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one host span is a data file found by the name in
`BENCHMARK.json` (`configs/`, `traffic/`, `metrics/`, `spans/`); a reader
kind that needs code is one file under `readers/`, a deployment's data one
generator file under `deployments/`, named by the configuration's file.
`README.md` beside this file says how to add a configuration or a cell and
how to rehearse one on a CPU.
"""

from __future__ import annotations

import argparse
import asyncio
import functools
import importlib
import json
import os
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

NO_CHIP = 3
CHILD_TIMEOUT_S = 240.0
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(*what) -> None:
    print(*what, file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def process_age_s() -> float:
    """Seconds since this process was started (interpreter start and
    imports belong to set-up)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - ticks / os.sysconf("SC_CLK_TCK")


# ---------------------------------------------------------------------------
# the cell, from data
# ---------------------------------------------------------------------------


class Cell:
    def __init__(self, workload: str, rehearse: bool,
                 traffic_file: "str | None" = None,
                 config_file: "str | None" = None):
        bench = load_json(ROOT, "BENCHMARK.json")
        entry = next((w for w in bench["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
        self.chips = int(entry["chips"])
        self.config_path = config_file or os.path.join(ROOT, cfg["file"])
        self.traffic_path = traffic_file or os.path.join(
            HERE, "traffic", entry["traffic"] + ".json")
        self.config = load_json(self.config_path)
        self.traffic = load_json(self.traffic_path)
        if rehearse:
            self.config.update(self.config.get("rehearsal", {}))
            self.traffic.update(self.traffic.get("rehearsal", {}))
        import oplog

        self.tables = oplog.tables_of(self.config)
        self.generator = oplog.load_generator(self.config, self.config_path)

        def mine(metric: dict) -> bool:
            return workload in metric.get(
                "workloads", [w["name"] for w in bench["workloads"]])

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]
        self.readers = {m["name"]: load_json(HERE, "metrics",
                                             m["name"] + ".json")
                        for m in self.per_layer}
        self.spans = [load_json(HERE, "spans", f)
                      for f in sorted(os.listdir(os.path.join(HERE, "spans")))
                      if f.endswith(".json")]


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------


class Child:
    """A helper process spoken to in JSON lines."""

    def __init__(self, script: str, args: list, cpus: list):
        cmd = [sys.executable, os.path.join(HERE, script), *args,
               "--cpus", ",".join(map(str, cpus))]
        self.name = script
        self.proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True)
        self.events: list = []
        self._cond = threading.Condition()
        self._thread = threading.Thread(target=self._read, daemon=True)
        self._thread.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            try:
                ev = json.loads(line)
            except ValueError:
                log(f"[{self.name}] {line.rstrip()}")
                continue
            with self._cond:
                self.events.append(ev)
                self._cond.notify_all()
        with self._cond:
            self.events.append({"event": "exited"})
            self._cond.notify_all()

    def send(self, cmd: str, **fields) -> None:
        self.proc.stdin.write(json.dumps({"cmd": cmd, **fields}) + "\n")
        self.proc.stdin.flush()

    def wait_sync(self, event: str, timeout: float = CHILD_TIMEOUT_S) -> dict:
        deadline = time.monotonic() + timeout
        seen = 0
        with self._cond:
            while True:
                for ev in self.events[seen:]:
                    if ev["event"] == event:
                        return ev
                    if ev["event"] == "exited":
                        raise RuntimeError(f"{self.name} exited before "
                                           f"{event!r}")
                seen = len(self.events)
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(f"{self.name}: no {event!r} within "
                                       f"{timeout:.0f}s")
                self._cond.wait(left)

    async def wait(self, event: str, timeout: float = CHILD_TIMEOUT_S) -> dict:
        return await asyncio.to_thread(self.wait_sync, event, timeout)

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send("quit")
                self.proc.wait(timeout=10)
            except (OSError, ValueError, subprocess.TimeoutExpired):
                self.proc.kill()
        self.proc.wait()
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except OSError:
                pass


def split_cpus(config: dict) -> dict:
    """Cores for the pipeline's process, the source and the sink, from the
    configuration's `assumed.affinity` counts: the source gets the highest
    cores of this process's set, the sink those below, the pipeline the
    rest. Too few cores is an error, not another placement."""
    aff = config["assumed"]["affinity"]
    cpus = sorted(os.sched_getaffinity(0))
    n_src = int(aff["source_cpus"])
    n_sink = int(aff["sink_cpus"]) \
        if config["destination"]["type"] != "null" else 0
    if len(cpus) < n_src + n_sink + int(aff["min_pipeline_cpus"]):
        raise SystemExit(
            f"{len(cpus)} cores: the configuration needs {n_src} for the "
            f"source, {n_sink} for the sink and "
            f"{aff['min_pipeline_cpus']} or more for the pipeline")
    rest = cpus[:len(cpus) - n_src - n_sink]
    return {"main": rest, "source": cpus[len(cpus) - n_src:],
            "sink": cpus[len(rest):len(rest) + n_sink]}


# ---------------------------------------------------------------------------
# probes: host spans and samples, from the benchmark's own files
# ---------------------------------------------------------------------------


class Probes:
    """Wraps the program's functions named in `spans/*.json` — only in a
    `--trace 1` run. Each call is stamped with perf_counter_ns; sync spans
    also open a `jax.profiler.TraceAnnotation`, so they show in the trace."""

    def __init__(self) -> None:
        self.spans: dict = {}
        self.samples: dict = {}
        self.priorities: dict = {}

    def install(self, specs: list, destination) -> None:
        import jax.profiler

        for spec in specs:
            name = spec["span"]
            self.priorities[name] = int(spec.get("priority", 0))
            store = self.spans.setdefault(name, [])
            where, attr = spec["target"].split(":")
            if where == "destination":
                if destination is None:
                    continue
                owner = destination
            else:
                owner = importlib.import_module(where)
                *path, attr = attr.split(".")
                for part in path:
                    owner = getattr(owner, part)
            if not hasattr(owner, attr):
                continue  # e.g. a destination without this method
            inner = getattr(owner, attr)
            sample = spec.get("sample")
            setattr(owner, attr, self._wrap(
                inner, name, store, spec.get("async", False), sample,
                jax.profiler.TraceAnnotation))

    def _wrap(self, inner, name, store, is_async, sample, annotation):
        clock = time.perf_counter_ns
        take = None
        if sample:
            values = self.samples.setdefault(sample["name"], [])
            path = sample["len_of"].split(".")

            def take(args):
                obj = args[0]
                for part in path:
                    obj = getattr(obj, part, None)
                    if obj is None:
                        return
                values.append((clock(), len(obj)))

        if is_async:
            async def wrapped(*args, **kw):
                if take:
                    take(args)
                t0 = clock()
                try:
                    return await inner(*args, **kw)
                finally:
                    store.append((t0, clock()))
        else:
            def wrapped(*args, **kw):
                if take:
                    take(args)
                t0 = clock()
                with annotation(name):
                    try:
                        return inner(*args, **kw)
                    finally:
                        store.append((t0, clock()))
        return wrapped


class CompileCounter:
    """Backend compiles JAX makes (a persistent-cache load counts), on any
    thread: the window must see none."""

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


# ---------------------------------------------------------------------------
# the null destination
# ---------------------------------------------------------------------------


def make_null_destination():
    return _null_destination_class()()


@functools.cache
def _null_destination_class():
    import numpy as np

    from etl_tpu.destinations.base import Destination, WriteAck
    from etl_tpu.models.event import DecodedBatchEvent

    class NullDestination(Destination):
        """Resolves every batch (so the decode is on the timed path),
        keeps what the comparison needs — the table, every column with its
        validity, the change kinds, old and key images, WAL coordinates:
        one copy per typed column, everything else by reference, no
        per-row work — and acks at once. The comparison with the
        reference happens after the window."""

        def __init__(self) -> None:
            self.copied: list = []  # (table id, columns)
            self.parts: list = []   # (table id, columns, change kinds,
            #                          commit LSNs, ordinals, old images)
            self.other_events = 0

        async def startup(self):
            return None

        @staticmethod
        def _columns(batch) -> list:
            return [(np.array(c.data) if isinstance(c.data, np.ndarray)
                     else c.data, np.array(c.validity),
                     None if c.toast_unchanged is None
                     else np.array(c.toast_unchanged), c.lazy_text_oid)
                    for c in batch.columns]

        def _keep(self, e) -> None:
            old = e.old_batch
            self.parts.append((
                int(e.schema.id), self._columns(e.batch),
                np.array(e.change_types),
                np.array(e.commit_lsns, dtype=np.int64),
                np.array(e.tx_ordinals, dtype=np.int64),
                None if old is None else (
                    self._columns(old), np.array(e.old_rows),
                    np.array(e.old_is_key)),
                None if e.delete_is_key is None
                else np.array(e.delete_is_key)))

        async def write_table_rows(self, schema, batch):
            self.copied.append((int(schema.id), self._columns(batch)))
            return WriteAck.durable()

        async def write_events(self, events):
            for e in events:
                if isinstance(e, DecodedBatchEvent):
                    self._keep(e)
                elif hasattr(e, "row") or hasattr(e, "old_row"):
                    # a change delivered row by row: no cell's engine does
                    self.other_events += 1
            return WriteAck.durable()

        async def drop_table(self, table_id, schema=None):
            return None

        async def truncate_table(self, table_id):
            return None

        def received(self, tables: list) -> dict:
            """{table id: {"copy": rows or None, "cdc": rows or None}} in
            the plain form `reference.py` reads, in delivery order."""
            out = {}
            for table in tables:
                tid = int(table["id"])
                copied = [c for t, c in self.copied if t == tid]
                parts = [p for p in self.parts if p[0] == tid]
                out[tid] = {
                    "copy": {"cols": _plain_columns(table, copied)}
                    if copied else None,
                    "cdc": _plain_events(table, parts) if parts else None}
            return out

    return NullDestination


def _plain_columns(table: dict, batches: list) -> list:
    """The kept columns of some batches of one table as (values, null,
    unchanged) in plain types: numpy for typed columns, a pyarrow string
    array for text, the server's text for NUMERIC, the JSON spelling
    (`reference.json_spelling`) for JSON."""
    import numpy as np
    import pyarrow as pa

    import oplog

    out = []
    for i, column in enumerate(table["columns"]):
        kept = [b[i] for b in batches]
        valid = np.concatenate([k[1] for k in kept])
        toast = np.concatenate([
            k[2] if k[2] is not None else np.zeros(len(k[1]), dtype=bool)
            for k in kept]) if any(k[2] is not None for k in kept) else None
        if oplog.comparison(column) == "json":
            values = _json_spellings(kept)
        elif all(isinstance(k[0], np.ndarray) for k in kept):
            values = np.concatenate([k[0] for k in kept])
        elif column["type"] == "numeric" or any(
                not isinstance(k[0], (pa.Array, pa.ChunkedArray))
                or k[3] is not None for k in kept):
            # host-side values (lazy text, PgNumeric, ...): their text
            values = []
            for data, ok, _, lazy in kept:
                items = data.to_pylist() if hasattr(data, "to_pylist") \
                    else list(data)
                values += [None if v is None or not good
                           else v if isinstance(v, str)
                           else v.pg_text() if hasattr(v, "pg_text")
                           else str(v)
                           for v, good in zip(items, ok.tolist())]
        else:
            values = pa.chunked_array([k[0] for k in kept]).cast(pa.string())
        null = None if valid.all() else \
            ~valid if toast is None else ~valid & ~toast
        out.append((values, null, toast))
    return out


def _json_spellings(kept: list) -> list:
    """The JSON spelling of every kept cell of a JSON column, None where
    there is no value: Arrow data is the server's text; anything else the
    values the host parsed, where a string is a JSON string."""
    import pyarrow as pa

    import reference
    from etl_tpu.models import JSON_NULL

    out = []
    for data, ok, _, _ in kept:
        items = data.to_pylist() if hasattr(data, "to_pylist") \
            else list(data)
        as_text = isinstance(data, (pa.Array, pa.ChunkedArray))
        out += [None if v is None or not good
                else reference.json_text_spelling(v) if as_text
                else reference.json_spelling(None if v is JSON_NULL else v)
                for v, good in zip(items, ok.tolist())]
    return out


def _plain_events(table: dict, parts: list) -> dict:
    import numpy as np

    n_before = np.cumsum([0] + [len(p[2]) for p in parts])
    olds = [(p[5], at) for p, at in zip(parts, n_before) if p[5] is not None]
    out = {"cols": _plain_columns(table, [p[1] for p in parts]),
           "change": np.concatenate([p[2] for p in parts]).astype(np.uint8),
           "commit_lsn": np.concatenate([p[3] for p in parts]),
           "tx_ordinal": np.concatenate([p[4] for p in parts]),
           "old": None, "delete_is_key": None}
    if olds:
        out["old"] = {
            "cols": _plain_columns(table, [o[0] for o, _ in olds]),
            "rows": np.concatenate([np.asarray(o[1], dtype=np.int64) + at
                                    for o, at in olds]),
            "is_key": np.concatenate([np.asarray(o[2], dtype=bool)
                                      for o, _ in olds])}
    if any(p[6] is not None for p in parts):
        out["delete_is_key"] = np.concatenate([
            p[6] if p[6] is not None else np.zeros(len(p[2]), dtype=bool)
            for p in parts]).astype(bool)
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class Run:
    def __init__(self, args, cell: Cell, t_start: float):
        self.args, self.cell, self.t_start = args, cell, t_start
        self.trace = bool(args.trace)
        self.source = self.sink = None
        self.probes = Probes()
        self.counters_at: dict = {}
        self.slice_ns = None
        self.marker_ns = None
        self.pipeline_ready_s = None
        self.stamps: dict = {}  # seconds since process start, for PERF.md

    # -- set-up --------------------------------------------------------------

    def start_children(self) -> None:
        cell, a = self.cell, self.args
        cpus = split_cpus(cell.config)
        self.cpus = cpus
        src_args = ["--config", cell.config_path, "--traffic",
                    cell.traffic_path, "--seed", str(a.seed),
                    "--seconds", str(a.seconds)]
        if a.rehearse:
            src_args.append("--rehearse")
        self.source = Child("source.py", src_args, cpus["source"])
        if cell.config["destination"]["type"] == "clickhouse":
            self.sink = Child("sink.py", [], cpus["sink"])
        os.sched_setaffinity(0, cpus["main"])

    def stop_children(self) -> None:
        for child in (self.source, self.sink):
            if child is not None:
                child.stop()

    def require_chip(self) -> dict:
        import jax

        devices = jax.devices()
        platform = devices[0].platform
        if self.args.rehearse:
            if os.environ.get("JAX_PLATFORMS") != "cpu":
                raise SystemExit("--rehearse needs JAX_PLATFORMS=cpu set "
                                 "explicitly")
        elif platform != "tpu" or len(devices) < self.cell.chips:
            log(f"refusing to run: found {len(devices)} {platform} "
                f"device(s), the cell asks for {self.cell.chips} TPU chip(s)")
            raise SystemExit(NO_CHIP)
        return {"platform": platform, "kind": devices[0].device_kind,
                "count": len(devices)}

    def names_to_snapshot(self) -> set:
        names = set()
        for reader in self.cell.readers.values():
            p = reader.get("params", {})
            for key in ("num", "den", "rows"):
                v = p.get(key, [])
                names.update([v] if isinstance(v, str) else v)
        return {n for n in names if n.startswith(("etl_", "hist_"))}

    def snapshot(self) -> dict:
        from etl_tpu.telemetry.metrics import registry

        out = {"harness.jax_compiles": self.compiles.count}
        for name in self._names:
            if name.startswith("hist_sum:"):
                out[name] = registry.sum_histogram(name[9:])[1]
            elif name.startswith("hist_count:"):
                out[name] = registry.sum_histogram(name[11:])[0]
            else:
                out[name] = registry.sum_counter(name)
        return out

    def pipeline_config(self, port: int, pipeline_id: int):
        from etl_tpu.config import (BatchConfig, PgConnectionConfig,
                                    PipelineConfig)

        doc = self.cell.config["pipeline"]
        return PipelineConfig(
            pipeline_id=pipeline_id, publication_name=doc["publication"],
            pg_connection=PgConnectionConfig(host="127.0.0.1", port=port),
            batch=BatchConfig(**doc.get("batch", {})))

    def make_destination(self, sink_port: "int | None"):
        dest = self.cell.config["destination"]
        if dest["type"] == "null":
            return make_null_destination()
        from etl_tpu.destinations.registry import build_destination

        return build_destination(
            {**dest, "url": f"http://127.0.0.1:{sink_port}"})

    def make_pipeline(self, port: int, destination, pipeline_id: int = 1):
        from etl_tpu.postgres.client import PgReplicationClient
        from etl_tpu.runtime import Pipeline
        from etl_tpu.store import NotifyingStore

        config = self.pipeline_config(port, pipeline_id)
        store = NotifyingStore()
        pipeline = Pipeline(
            config=config, store=store, destination=destination,
            source_factory=lambda: PgReplicationClient(config.pg_connection))
        return pipeline, store

    def warm_programs(self, destination) -> None:
        """Every decode program this cell's traffic can touch, through the
        program's own decoder and the cell's own bytes: for every published
        table the log has events of, the host program of each row bucket,
        then whatever production routing picks for a full bucket — fed with
        the table's first events of each kind of change from the generator,
        rendered as the source renders them. Persistent-cache hits after a checkout's first run.
        The log and the snapshot made here are the ones the comparison
        after the window reads."""
        import numpy as np

        import wire
        from etl_tpu.models import ReplicatedTableSchema
        from etl_tpu.ops import engine, program_store
        from etl_tpu.ops.wal import stage_wal_batch
        from source import table_schema

        cell, gen = self.cell, self.cell.generator
        self.stream = gen.stream(cell.config, cell.traffic, self.args.seed,
                                 self.args.seconds)
        self.table_rows = gen.snapshot(cell.config, cell.traffic,
                                     self.args.seed)
        buckets = cell.traffic.get("warm_row_buckets", [])
        if not buckets or self.stream is None:
            return
        oids = getattr(gen, "TYPE_OIDS", None)
        extra = getattr(gen, "TEXT_BLOCKS", None)
        counts = np.bincount(self.stream.table, minlength=len(cell.tables))
        used = [(t, ReplicatedTableSchema.with_all_columns(
            table_schema(table, oids)))
            for t, table in enumerate(cell.tables) if counts[t]]
        program_store.warm_host_programs([s for _, s in used], buckets,
                                         wait=True)
        egress = getattr(destination, "egress_encoder", None) \
            if cell.config["pipeline"].get("batch", {}) \
            .get("device_egress", True) else None
        n = max(buckets)
        zeros = np.zeros(n, dtype=np.int64)
        head = 5 + 25  # CopyData header + XLogData header
        stream = self.stream
        kinds = wire.old_kinds(cell.tables, stream)
        for t, schema in used:
            decoder = engine.DeviceDecoder(schema, egress=egress)
            table, ev = cell.tables[t], stream.events[t]
            mine = slice(None) if len(stream.events) == 1 \
                else np.flatnonzero(stream.table == t)
            # each kind of change the table sees (an insert, an update with
            # a key image, ...) has its own message shape and text widths
            group = (stream.op[mine].astype(np.uint16) << 8) | kinds[mine]
            keys = [int(group[0])] if (group == group[0]).all() \
                else np.unique(group).tolist()
            for key in keys:
                rows = np.arange(n) if len(keys) == 1 and len(group) >= n \
                    else np.resize(np.flatnonzero(group == key), n)
                op, old_kind = key >> 8, key & 0xFF
                blob, offsets, payload_len = wire.render_change_frames(
                    table, op, old_kind, [c.pick(rows) for c in ev.new],
                    [c.pick(rows) for c in ev.old] if old_kind else None,
                    zeros, zeros, 0, extra)
                for bucket in buckets:
                    wal = stage_wal_batch(
                        blob, offsets[:bucket] + head,
                        payload_len[:bucket].astype(np.int32),
                        len(table["columns"]))
                    decoder.decode(wal.staged)
                    if wal.old_staged is not None:
                        decoder.decode(wal.old_staged)
        while engine.background_compiles_inflight():
            time.sleep(0.02)

    # -- tracing -------------------------------------------------------------

    def trace_start(self) -> None:
        import shutil

        import jax.profiler

        import trace as trace_mod

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self.marker_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(trace_mod.MARKER):
            pass
        self._slice_start = time.perf_counter_ns()
        self.counters_at["slice_start"] = self.snapshot()

    def trace_stop(self) -> None:
        import jax.profiler

        self.counters_at["slice_stop"] = self.snapshot()
        self.slice_ns = (self._slice_start, time.perf_counter_ns())
        jax.profiler.stop_trace()

    # -- one cell ------------------------------------------------------------

    async def measure(self) -> dict:
        from etl_tpu.models.table_state import TableStateType
        from etl_tpu.ops.program_store import place_jax_compile_cache

        cell = self.cell
        place_jax_compile_cache()
        self.compiles = CompileCounter()
        self._names = self.names_to_snapshot()
        copying = cell.traffic["kind"] == "copy"
        sink_port = (await self.sink.wait("listening"))["port"] \
            if self.sink is not None else None
        destination = None if copying else self.make_destination(sink_port)
        if self.trace:
            self.probes.install(cell.spans, destination)
        # the programs warm here while the source still renders its bytes
        t0 = time.perf_counter()
        warming = asyncio.ensure_future(
            asyncio.to_thread(self.warm_programs, destination))
        listening = await self.source.wait("listening")
        self.render_s = listening["render_s"]
        await warming
        self.warm_s = time.perf_counter() - t0
        if copying:
            return await self.measure_copies(listening["port"])

        t0 = time.perf_counter()
        pipeline, store = self.make_pipeline(listening["port"], destination)
        try:
            await pipeline.start()
            for table in cell.tables:
                await asyncio.wait_for(store.notify_on(
                    int(table["id"]), TableStateType.READY), 120)
            self.pipeline_ready_s = time.perf_counter() - t0
            self.stamps["traffic_go_s"] = time.perf_counter() - self.t_start
            self.source.send("go")
            opened = await self.source.wait("window_open")
            self.counters_at["open"] = self.snapshot()
            self.setup_s = opened["t"] - self.t_start
            close = asyncio.ensure_future(self.source.wait("window_close"))
            if self.trace:
                # the slice is the window's last seconds, up to its close:
                # nothing outside the window is traced or counted
                lead = max(0.0, self.args.seconds
                           - float(cell.traffic["trace_seconds"]))
                await asyncio.sleep(max(0.0, opened["t"] + lead
                                        - time.perf_counter()))
                self.trace_start()
            closed = await close
            self.counters_at["close"] = self.snapshot()
            if self.trace:
                self.trace_stop()
            self.memory_peak = self.read_memory_peak()
            await pipeline.shutdown_and_wait()
        except BaseException:
            pipeline.shutdown_signal.trigger()
            raise
        self.source.send("report")
        report = await self.source.wait("report")
        if "error" in report:
            raise RuntimeError(f"source: {report['error']}")
        return self.finish_cdc(report, destination, opened, closed)

    def read_memory_peak(self) -> int:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()]
        return int(max(peaks)) if peaks else 0

    def finish_cdc(self, report: dict, destination, opened, closed) -> dict:
        import numpy as np

        import reference

        cell = self.cell
        starts = self.stream.layout.starts
        sent = int(starts[report["sent_tx"]])
        need = int(starts[report["durable_tx"]])
        if self.sink is not None:
            self.sink.send("verify", config=cell.config_path,
                           traffic=cell.traffic_path,
                           rehearse=bool(self.args.rehearse),
                           seed=self.args.seed, seconds=self.args.seconds,
                           sent=sent, need=need,
                           t_open=opened["t"], t_close=closed["t"])
            verdict = self.sink.wait_sync("verified")
            if "error" in verdict:
                raise RuntimeError(f"sink: {verdict['error']}")
            self.sink_service = verdict["service"]
        else:
            verdict = reference.verify_received(
                cell.tables, self.table_rows, self.stream, sent, need,
                destination.received(cell.tables))
            verdict["numbers"]["wrong_rows"] += destination.other_events
            self.sink_service = None
        numbers = dict(verdict["numbers"])
        metrics, extra = {}, {}
        kind = cell.traffic["kind"]
        if kind == "backlog":
            metrics["cdc_events_per_s"] = report["events"] / report["window_s"]
            attempted = report["transactions"]
            failed = 0
            numbers["backlog_exhausted"] = int(report["exhausted"])
        else:
            lag = np.sort(np.asarray(report["lag_ms"], dtype=np.float64))
            metrics["lag_p50_ms"] = float(lag[len(lag) // 2])
            extra["lag_percentiles_ms"] = {
                f"p{q}": float(lag[min(len(lag) - 1, len(lag) * q // 100)])
                for q in (50, 75, 90, 95, 99)}
            attempted, failed = report["attempted"], report["unflushed"]
            numbers["unflushed_transactions"] = failed
            extra["halves"] = [report["lag_first_half_ms"],
                               report["lag_second_half_ms"]]
        self.report = report
        return {"metrics": metrics, "attempted": int(attempted),
                "failed": int(failed), "numbers": numbers,
                "info": verdict["info"], "extra": extra}

    async def measure_copies(self, port: int) -> dict:
        """Whole copies back to back, each a new Pipeline into a fresh
        store and destination, the first one being the warm-up. The copy
        in flight at --seconds is finished and counted."""
        import reference
        from etl_tpu.models.table_state import TableStateType

        cell = self.cell
        truth = {int(t["id"]): reference.SnapshotIndex(
            t, self.table_rows.get(int(t["id"])) or []) for t in cell.tables}
        n_rows = sum(index.n for index in truth.values())
        copies: list = []
        numbers = {k: 0 for k in reference.LIMITS}
        failed = 0
        t_open = None
        pipeline_id = 0
        while True:
            pipeline_id += 1
            destination = make_null_destination()
            t0 = time.perf_counter()
            pipeline, store = self.make_pipeline(port, destination,
                                                 pipeline_id)
            try:
                await pipeline.start()
                t_started = time.perf_counter()
                if pipeline_id == 1:
                    self.pipeline_ready_s = t_started - t0
                tracing = self.trace and pipeline_id == 2
                if tracing:
                    self.trace_start()
                try:
                    for table in cell.tables:
                        await asyncio.wait_for(store.notify_on(
                            int(table["id"]), TableStateType.READY), 300)
                    ok = True
                except asyncio.TimeoutError:
                    ok = False
                t_ready = time.perf_counter()
                if tracing:
                    self.trace_stop()
                await pipeline.shutdown_and_wait()
            except BaseException:
                pipeline.shutdown_signal.trigger()
                raise
            if pipeline_id == 1:
                # the warm-up copy: every program now sits in the process
                t_open = time.perf_counter()
                self.setup_s = t_open - self.t_start
                self.counters_at["open"] = self.snapshot()
                if not ok:
                    raise RuntimeError("the warm-up copy never got ready")
                continue
            if ok:
                reference.check_copies(
                    truth, destination.received(cell.tables), numbers)
            else:
                failed += 1
            copies.append({"start": t_started, "ready": t_ready})
            if time.perf_counter() >= t_open + self.args.seconds:
                break
        t_close = copies[-1]["ready"]
        self.counters_at["close"] = self.snapshot()
        self.memory_peak = self.read_memory_peak()
        self.source.send("report")
        self.report = report = await self.source.wait("report")
        first = copies[0]["start"]
        durations = [c["ready"] - c["start"] for c in copies]
        report.update(window_s=t_close - first, t_open=first,
                      t_close=t_close, copy_seconds=durations,
                      between_copies_s=(t_close - first) - sum(durations))
        self.sink_service = None
        rate = n_rows * (len(copies) - failed) / (t_close - first)
        return {"metrics": {"copy_rows_per_s": rate},
                "attempted": len(copies), "failed": failed,
                "numbers": numbers,
                "info": {"copies": len(copies),
                         "copy_seconds": durations},
                "extra": {}}

    # -- per-layer metrics ---------------------------------------------------

    def per_layer(self, device: dict) -> tuple:
        import numpy as np

        import trace as trace_mod

        cell, report = self.cell, self.report
        window = {k: self.counters_at["close"][k] - self.counters_at["open"][k]
                  for k in self.counters_at["open"]}
        window["window.seconds"] = report["window_s"]
        window["window.over_seconds"] = report.get("window_over_s", 0.0)
        window["window.requested_seconds"] = float(self.args.seconds)
        window["setup.pipeline_ready_s"] = self.pipeline_ready_s
        window["const.one"] = 1.0
        for k in ("blocked_s", "copy_blocked_s", "copy_serving_s"):
            if k in report:
                window["source." + k] = report[k]
        if self.sink_service:
            window["sink.service_s"] = self.sink_service["service_s"]
            window["sink.requests"] = self.sink_service["requests"]
        samples = {k: list(v) for k, v in self.probes.samples.items()}
        if "late_ms" in report:
            samples["generator_late_ms"] = report["late_ms"]
            samples["lag_ms"] = report["lag_ms"]
        spans = {k: np.asarray(v, dtype=np.int64).reshape(-1, 2)
                 for k, v in self.probes.spans.items()}
        ctx = {"window": window, "samples": samples, "spans": spans,
               "slice_ns": self.slice_ns, "trace": None, "slice": None,
               "device_kind": device["kind"], "config": cell.config,
               "traffic": cell.traffic, "report": report,
               "opened_ns": int(report["t_open"] * 1e9),
               "closed_ns": int(report["t_close"] * 1e9)}
        breakdown = None
        if self.slice_ns is not None:
            xplane = trace_mod.read_xplane(trace_mod.newest_xplane(TRACE_DIR))
            if xplane["devices"] or not self.args.rehearse:
                ctx["trace"] = trace_mod.reduce_trace(
                    xplane, self.marker_ns, self.slice_ns, spans,
                    self.probes.priorities)
                a, b = (self.counters_at["slice_start"],
                        self.counters_at["slice_stop"])
                ctx["slice"] = {k: b[k] - a[k] for k in a}
                device["busy_s"] = ctx["trace"]["busy_s"]
                device["window_s"] = ctx["trace"]["window_s"]
                breakdown = {"device_ops": ctx["trace"]["device_ops"],
                             "idle_gaps": ctx["trace"]["idle_gaps"]}
        metrics = {}
        for m in cell.per_layer:
            spec = cell.readers[m["name"]]
            reader = importlib.import_module("readers." + spec["reader"])
            value = reader.read(ctx, spec.get("params", {}))
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        return metrics, breakdown


def main(argv=None) -> int:
    t_start = time.perf_counter() - process_age_s()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size (README.md); needs "
                         "JAX_PLATFORMS=cpu and prints no device metric")
    ap.add_argument("--traffic-file", default=None,
                    help="play this mix file in place of the cell's own "
                         "(sweep_paced.py; trying a new mix before a PR "
                         "adds it)")
    ap.add_argument("--config-file", default=None,
                    help="run this configuration file in place of the "
                         "cell's own: a deployment is rehearsed, and run on "
                         "the chip, before a PR makes it a cell")
    args = ap.parse_args(argv)
    cell = Cell(args.workload, args.rehearse, args.traffic_file,
                args.config_file)
    # the program has to be there before anything is started or printed
    import etl_tpu  # noqa: F401

    run = Run(args, cell, t_start)
    run.stamps["program_imported_s"] = time.perf_counter() - t_start
    run.start_children()
    try:
        device = run.require_chip()
        run.stamps["chip_open_s"] = time.perf_counter() - t_start
        result = asyncio.run(run.measure())
        device["memory_peak_bytes"] = run.memory_peak
        import reference

        correct, table = reference.judge(result["numbers"])
        if args.trace:
            metrics, breakdown = run.per_layer(device)
        else:
            metrics = {k: {"value": v, "unit": cell.units[k]}
                       for k, v in result["metrics"].items()}
            metrics["setup_s"] = {"value": run.setup_s, "unit": "s"}
            breakdown = None
    finally:
        run.stop_children()
    if args.rehearse:
        metrics = {"rehearsal." + k: v for k, v in metrics.items()}
    notes = {"setup": {**run.stamps, "render_s": run.render_s,
                       "warm_s": run.warm_s,
                       "pipeline_ready_s": run.pipeline_ready_s,
                       "setup_s": run.setup_s, "cpus": run.cpus},
             "info": result["info"], "extra": result["extra"],
             "report": {k: v for k, v in run.report.items()
                        if not isinstance(v, list)}}
    log(json.dumps(notes))
    line = {"correct": bool(correct) and result["failed"] == 0,
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in table}
    for name, v, lim in table:
        log(f"check {name}: {v} (limit {lim})")
    log(f"correct: {line['correct']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
