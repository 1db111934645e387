"""Benchmark: WAL records/sec decoded on the pgbench CDC workload.

Measures the full TPU decode pipeline (native framing → staging → device
parse → exact host combine → Arrow columnar output) against the CPU
pgoutput decoder (the reference-architecture per-tuple path:
decode_logical_message + decode_insert, mirroring
crates/etl/src/postgres/codec/event.rs).

Prints ONE JSON line:
  {"metric": "wal_records_per_sec_decoded", "value": N, "unit": "records/s",
   "vs_baseline": tpu_over_cpu_ratio, ...}

The device-measuring modes (`decode`, `wide_row`, and the pipeline modes
under `--engine tpu`) run on the chip or exit 3; `JAX_PLATFORMS=cpu`, given
explicitly, turns them into functional runs labelled `"backend": "cpu"`.
One process holds the chip: nothing here starts a child that needs it.
BASELINE.json target: vs_baseline ≥ 10.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

N_ROWS = 262_144
N_ITERS = 7
CPU_SAMPLE_ROWS = 16_384  # CPU path timed on a sample, scaled (it's O(n))


def build_workload(n_rows: int):
    """pgbench_accounts insert stream: begin + n inserts + commit."""
    import random

    from etl_tpu.postgres.codec import pgoutput

    rng = random.Random(7)
    ts = 1_700_000_000_000_000
    payloads = [pgoutput.encode_begin(0x5000, ts, 99)]
    for i in range(n_rows):
        payloads.append(pgoutput.encode_insert(
            16384,
            [str(i + 1).encode(), str(rng.randrange(1, 11)).encode(),
             str(rng.randrange(-10**9, 10**9)).encode(), b" " * 84]))
    payloads.append(pgoutput.encode_commit(0x5000, 0x5008, ts))
    return payloads


def make_schema():
    from etl_tpu.models import (ColumnSchema, Oid, ReplicatedTableSchema,
                                TableName, TableSchema)

    return ReplicatedTableSchema.with_all_columns(TableSchema(
        16384, TableName("public", "pgbench_accounts"),
        (ColumnSchema("aid", Oid.INT4, nullable=False, primary_key_ordinal=1),
         ColumnSchema("bid", Oid.INT4),
         ColumnSchema("abalance", Oid.INT4),
         ColumnSchema("filler", Oid.BPCHAR, modifier=88))))


def bench_cpu(payloads, schema, n_rows):
    """Reference-architecture CPU path: per-message decode into events."""
    from etl_tpu.models.lsn import Lsn
    from etl_tpu.postgres.codec import (decode_insert, decode_logical_message)
    from etl_tpu.postgres.codec.pgoutput import InsertMessage

    sample = payloads[1 : 1 + CPU_SAMPLE_ROWS]
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        ordinal = 0
        for p in sample:
            msg = decode_logical_message(p)
            if isinstance(msg, InsertMessage):
                decode_insert(msg, schema, Lsn(1), Lsn(2), ordinal)
                ordinal += 1
        times.append(time.perf_counter() - t0)
    # fastest sample = strongest baseline (the host is 1 core and shared;
    # a contended CPU run would flatter the ratio)
    per_row = min(times) / len(sample)
    return 1.0 / per_row  # records/sec


def bench_tpu(payloads, schema, n_rows, use_pallas: bool = False):
    """Sustained pipelined throughput through the three-stage decode
    scheduler (ops/pipeline.py): the pack of batch N+1 runs on the
    pipeline's worker thread into a pooled arena while batch N computes
    on the device and N-1 streams back — the same scheduler the copy and
    apply paths use in production."""
    from etl_tpu.ops import DecodePipeline, DeviceDecoder
    from etl_tpu.ops.wal import concat_payloads, stage_wal_batch

    buf, offs, lens = concat_payloads(payloads)
    decoder = DeviceDecoder(schema, use_pallas=use_pallas)

    def stage():
        return stage_wal_batch(buf, offs, lens, 4)

    # warmup: jit compile + transfer paths
    decoder.decode(stage().staged)

    pipe = DecodePipeline(window=3)
    n_batches = 6
    times = []
    for _ in range(N_ITERS):
        t0 = time.perf_counter()
        pending = []
        done = 0
        for _ in range(n_batches):
            wal = stage()
            pending.append(pipe.submit(decoder, wal.staged))
            if len(pending) > pipe.effective_window:
                batch = pending.pop(0).result()
                assert batch.num_rows == n_rows
                done += 1
        for p in pending:
            assert p.result().num_rows == n_rows
            done += 1
        dt = time.perf_counter() - t0
        times.append(dt / n_batches)
    stats = pipe.stats()
    pipe.close()
    # Return every iteration's rate; the caller aggregates (median
    # headline, peak window reported alongside).
    return sorted(n_rows / t for t in times), decoder, stats


def _batches_identical(a, b) -> bool:
    """Byte-identical ColumnarBatch comparison (validity, dense bits,
    object values) — the smoke gate for pipelined == serial decode."""
    if a.num_rows != b.num_rows:
        return False
    for ca, cb in zip(a.columns, b.columns):
        if not np.array_equal(np.asarray(ca.validity),
                              np.asarray(cb.validity)):
            return False
        if ca.is_dense != cb.is_dense:
            return False
        if ca.is_dense:
            da = np.where(ca.validity, ca.data, 0)
            db = np.where(cb.validity, cb.data, 0)
            if da.dtype != db.dtype or da.tobytes() != db.tobytes():
                return False
        else:
            for i in range(a.num_rows):
                if ca.validity[i] and ca.value(i) != cb.value(i):
                    return False
    return True


def run_mesh_check(n_rows: int = 65_536, iters: int = 5) -> dict:
    """Mesh-sharded decode gate. Run with
    `XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu`
    (bench.py --smoke spawns it that way): the full pack→decode→transpose
    program lifted onto NamedSharding(mesh, P('sp', None)) must be
    BYTE-IDENTICAL to the single-device program on a mesh-eligible batch.

    Byte identity is the CI-stable assertion. The wall-clock columns are
    measured honestly and recorded, NOT gated: on an N-core CI host the
    8 forced host shards share N cores (this container has 2), and the
    single-device XLA CPU program already uses intra-op threading across
    them — so forced-host wall clock stays ~flat by construction and only
    a real multi-chip mesh shows the per-device work division (rows/8 per
    shard, asserted structurally here and in tests/test_parallel.py) as
    throughput. device_program_* isolates the sharded computation from
    the host pack/fetch stages that never shard."""
    import jax

    from etl_tpu.ops.engine import DeviceDecoder
    from etl_tpu.ops.wal import concat_payloads, stage_wal_batch
    from etl_tpu.parallel.mesh import decode_mesh

    n_dev = len(jax.devices())
    schema = make_schema()
    payloads = build_workload(n_rows)
    buf, offs, lens = concat_payloads(payloads)

    def stage():
        return stage_wal_batch(buf, offs, lens, 4)

    single = DeviceDecoder(schema, device_min_rows=0, mesh=None)
    mesh = decode_mesh()
    out = {"mode": "mesh_check", "devices": n_dev,
           "mesh_shards": mesh.size if mesh is not None else 0,
           "rows": n_rows}
    if mesh is None:
        out.update(sharded_equals_single=None, ok=False,
                   error="no multi-device mesh (run with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
        return out
    sharded = DeviceDecoder(schema, device_min_rows=0, mesh=mesh,
                            mesh_min_rows=0)
    st = stage().staged
    identical = _batches_identical(single.decode(st), sharded.decode(st))

    # fused-filter case: per-shard in-program compaction must land the
    # SAME survivors with the SAME bytes as the single-device scatter
    # (ROADMAP item 4's mesh gate — bitpack.compact_packed stays
    # shard-local, so this proves the shard-block reshape and the host's
    # per-shard slice stitching agree)
    from etl_tpu.ops.predicate import parse_row_filter

    fschema = schema.with_row_predicate(parse_row_filter("abalance < 0"))
    fsingle = DeviceDecoder(fschema, device_min_rows=0, mesh=None)
    fsharded = DeviceDecoder(fschema, device_min_rows=0, mesh=mesh,
                             mesh_min_rows=0)
    fb1, fb8 = fsingle.decode(stage().staged), fsharded.decode(stage().staged)
    filtered_identical = (
        _batches_identical(fb1, fb8)
        and fb1.source_rows is not None and fb8.source_rows is not None
        and np.array_equal(fb1.source_rows, fb8.source_rows)
        and 0 < fb1.num_rows < n_rows)

    def best_decode(dec):
        ts = []
        for _ in range(iters):
            s2 = stage().staged
            t0 = time.perf_counter()
            dec.decode(s2)
            ts.append(time.perf_counter() - t0)
        return min(ts)

    def best_program(dec):
        # device program only (dispatch → ready), host pack off the clock;
        # CPU backend never donates, so re-dispatching one packed buffer
        # is safe
        specs = dec._specs(st, dec._widths(st))
        packed = dec._pack_stage(st, specs)

        def run():
            res = dec._dispatch_stage(st, specs, packed)
            for v in (res if isinstance(res, tuple) else (res,)):
                v.block_until_ready()

        run()  # warm
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            run()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t1, t8 = best_decode(single), best_decode(sharded)
    p1, p8 = best_program(single), best_program(sharded)
    out.update({
        "sharded_equals_single": bool(identical),
        "filtered_sharded_equals_single": bool(filtered_identical),
        "filtered_survivors": int(fb1.num_rows),
        "single_device_decode_ms": round(t1 * 1e3, 2),
        "sharded_decode_ms": round(t8 * 1e3, 2),
        "decode_wall_clock_speedup": round(t1 / t8, 2),
        "single_device_program_ms": round(p1 * 1e3, 2),
        "sharded_program_ms": round(p8 * 1e3, 2),
        "device_program_speedup": round(p1 / p8, 2),
        "ok": bool(identical and filtered_identical),
    })
    return out


def run_autoscale_bench(seed: int = 7, reaction_ticks_max: int = 3) -> dict:
    """Autoscale reaction-time gate (ISSUE 13): the seeded surge→drain
    timeline through the scaling policy with the applied-K loop closed.
    GATED: (a) the scale-up decision lands within `reaction_ticks_max`
    evaluation ticks of the surge onset; (b) the scale-down must NOT
    fire before the cooldown expires after the scale-up; (c) the
    topology returns to the starting K once the backlog drains; (d) the
    decision trace is bit-identical across two runs of the same seed —
    the determinism the chaos replay contract rests on. Pure policy
    arithmetic: no pipeline, no accelerator, milliseconds of wall
    clock."""
    from etl_tpu.autoscale import (ACTION_DOWN, ACTION_HOLD, ACTION_UP,
                                   AutoscalePolicy, AutoscalePolicyConfig,
                                   seeded_surge_timeline)
    from etl_tpu.autoscale.policy import simulate

    surge_at = 10
    config = AutoscalePolicyConfig(
        min_shards=2, max_shards=3, drain_slo_s=2.0,
        up_backlog_bytes=256 * 1024, down_backlog_bytes=64 * 1024,
        up_ticks=2, down_ticks=3, cooldown_ticks=5)
    policy = AutoscalePolicy(config)

    def trace():
        timeline = seeded_surge_timeline(seed, shards=2, ticks=40,
                                         surge_at=surge_at)
        return [d.describe()
                for d in simulate(timeline.frames, policy, 2)]

    first, second = trace(), trace()
    actions = [(d["tick"], d["action"], d["target_k"]) for d in first
               if d["action"] != ACTION_HOLD]
    up_ticks = [t for t, a, _ in actions if a == ACTION_UP]
    down_ticks = [t for t, a, _ in actions if a == ACTION_DOWN]
    failures = []
    if first != second:
        failures.append("decision trace not deterministic across two "
                        "runs of the same seed")
    if not up_ticks:
        failures.append("the surge never produced a scale-up decision")
    elif up_ticks[0] - surge_at > reaction_ticks_max:
        failures.append(
            f"scale-up reacted in {up_ticks[0] - surge_at} ticks, gate "
            f"is {reaction_ticks_max}")
    if not down_ticks:
        failures.append("the drain never produced a scale-down decision")
    elif up_ticks and down_ticks[0] - up_ticks[0] < config.cooldown_ticks:
        failures.append(
            f"scale-down fired {down_ticks[0] - up_ticks[0]} ticks after "
            f"the scale-up, inside the {config.cooldown_ticks}-tick "
            f"cooldown")
    final_k = actions[-1][2] if actions else 2
    if final_k != 2:
        failures.append(f"topology did not return to K=2 after the "
                        f"drain (final K={final_k})")
    return {
        "mode": "autoscale",
        "seed": seed,
        "surge_at_tick": surge_at,
        "scale_up_tick": up_ticks[0] if up_ticks else None,
        "scale_down_tick": down_ticks[0] if down_ticks else None,
        "reaction_ticks": (up_ticks[0] - surge_at) if up_ticks else None,
        "reaction_ticks_max": reaction_ticks_max,
        "cooldown_ticks": config.cooldown_ticks,
        "decisions": [{"tick": t, "action": a, "target_k": k}
                      for t, a, k in actions],
        "deterministic": first == second,
        "failures": failures,
        "ok": not failures,
    }


def run_fleet_bench(seed: int = 7, fleet_size: int = 100,
                    converge_ticks_max: int = 3) -> dict:
    """Fleet converge gate (docs/fleet.md): a `fleet_size`-pipeline
    seeded FleetSpec reconciles onto an empty simulated fleet, then
    through one versioned add/remove/resize edit. GATED: (a) each
    convergence completes within `converge_ticks_max` WORKING ticks;
    (b) zero double-actuations — every runtime call in the actuation
    log is backed 1:1 by an APPLIED record in the per-pipeline journals,
    and nothing stays pending; (c) the observed fleet equals the
    quota-clamped placement exactly (no leaks, no strays); (d) the
    actuation trace is bit-identical across two runs of the same seed.
    Wall clock is RECORDED, not gated — pure host arithmetic on this
    container, but the tick counts are the product's contract."""
    import asyncio

    from etl_tpu.fleet import (FleetReconciler, PipelineSpec,
                               SimulatedFleetRuntime, seeded_fleet_spec)
    from etl_tpu.fleet.reconciler import place_fleet
    from etl_tpu.store.memory import MemoryStore

    async def drive() -> dict:
        store = MemoryStore()
        runtime = SimulatedFleetRuntime(seed=seed)
        spec = seeded_fleet_spec(seed, fleet_size)
        await store.update_fleet_spec(spec.to_json())
        reconciler = FleetReconciler(store=store, runtime=runtime)
        t0 = time.perf_counter()
        ticks = await reconciler.converge(
            max_ticks=converge_ticks_max + 1)
        converge_s = time.perf_counter() - t0
        edited = spec.with_edit(
            remove=[1, 2], resize={10: 6, 11: 1},
            add=[PipelineSpec(pipeline_id=fleet_size + 1,
                              tenant_id="tenant-edit", shard_count=2)])
        await store.update_fleet_spec(edited.to_json())
        t0 = time.perf_counter()
        edit_ticks = await reconciler.converge(
            max_ticks=converge_ticks_max + 1)
        edit_s = time.perf_counter() - t0
        journals = await store.get_fleet_journals()
        statuses = [e.get("status") for doc in journals.values()
                    for e in doc.get("entries", [])]
        return {
            "ticks": ticks,
            "edit_ticks": edit_ticks,
            "converge_s": converge_s,
            "edit_s": edit_s,
            "applied": statuses.count("applied"),
            "pending": statuses.count("pending"),
            "actuations": list(runtime.actuation_log),
            "observed": await runtime.list_pipelines(),
            "targets": place_fleet(edited),
            "violations": runtime.violations(),
        }

    first = asyncio.run(drive())
    second = asyncio.run(drive())
    failures = []
    for label, ticks in (("initial", first["ticks"]),
                         ("edit", first["edit_ticks"])):
        if ticks > converge_ticks_max:
            failures.append(f"{label} converge took {ticks} working "
                            f"ticks, gate is {converge_ticks_max}")
    double = len(first["actuations"]) - first["applied"]
    if double != 0:
        failures.append(f"{double} runtime actuations not backed by an "
                        f"applied journal record")
    if first["pending"]:
        failures.append(f"{first['pending']} journal records still "
                        f"pending after convergence")
    if first["observed"] != first["targets"]:
        failures.append("observed fleet != quota-clamped placement")
    if first["violations"]:
        failures.extend(first["violations"][:5])
    if first["actuations"] != second["actuations"]:
        failures.append("actuation trace not deterministic across two "
                        "runs of the same seed")
    return {
        "mode": "fleet",
        "seed": seed,
        "fleet_size": fleet_size,
        "converge_ticks": first["ticks"],
        "edit_converge_ticks": first["edit_ticks"],
        "converge_ticks_max": converge_ticks_max,
        "converge_wall_clock_s": round(first["converge_s"], 4),
        "edit_wall_clock_s": round(first["edit_s"], 4),
        "actuations": len(first["actuations"]),
        "applied_records": first["applied"],
        "double_actuations": double,
        "deterministic": first["actuations"] == second["actuations"],
        "failures": failures,
        "ok": not failures,
    }


def run_smoke() -> dict:
    """CI gate: CPU backend, small batches, pipelined decode must be
    byte-identical to serial decode() and the stage histograms must have
    observations; then a short end-to-end `table_streaming` run is
    compared against the checked-in floor (BENCH_FLOOR.json) — the A/B
    regression gate that would have caught the round-5 3-4x CDC
    throughput collapse before it shipped. Runs on the CPU backend."""
    import os

    from etl_tpu.ops import DecodePipeline, DeviceDecoder
    from etl_tpu.ops.wal import concat_payloads, stage_wal_batch
    from etl_tpu.telemetry.metrics import (ETL_DECODE_DISPATCH_SECONDS,
                                           ETL_DECODE_FETCH_SECONDS,
                                           ETL_DECODE_PACK_SECONDS, registry)

    n_rows = 2048
    schema = make_schema()
    payloads = build_workload(n_rows)
    buf, offs, lens = concat_payloads(payloads)

    def stage():
        return stage_wal_batch(buf, offs, lens, 4)

    decoder = DeviceDecoder(schema)  # production routing: host XLA path
    serial = [decoder.decode(stage().staged) for _ in range(3)]
    pipe = DecodePipeline(window=2)
    handles = [pipe.submit(decoder, stage().staged) for _ in range(3)]
    pipelined = [h.result() for h in handles]
    stats = pipe.stats()
    pipe.close()

    identical = all(_batches_identical(s, p)
                    for s, p in zip(serial, pipelined))
    stages_observed = all(registry.get_histogram(n)[0] > 0 for n in (
        ETL_DECODE_PACK_SECONDS, ETL_DECODE_DISPATCH_SECONDS,
        ETL_DECODE_FETCH_SECONDS))

    # supervision heartbeat overhead gate (ISSUE 4 CI satellite): price
    # one beat, then charge it against the per-event budget the
    # BENCH_FLOOR streaming floor implies — even at a pessimistic one
    # beat per event (the apply loop actually beats once per select
    # wake, i.e. per drained WINDOW), instrumentation must cost <1% of
    # the floor's event budget. The streaming run below then re-measures
    # the REAL pipeline with supervision live against the same floor.
    from etl_tpu.supervision import Supervisor

    sup = Supervisor()
    hb = sup.register("bench")
    n_beats = 50_000
    rounds = []
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(n_beats):
            hb.beat(progress=i, busy=True)
        rounds.append((time.perf_counter() - t0) / n_beats)
    # min over rounds: scheduler noise on a shared host only ever SLOWS
    # a round (the same one-sided-noise policy as the decode headline)
    per_beat_s = min(rounds)

    # streaming A/B gate: a short saturation run through the FULL
    # pipeline (fake walsender -> apply loop -> pipelined decode -> null
    # destination), events/s vs the checked-in floor
    import asyncio

    from etl_tpu.benchmarks import harness

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_FLOOR.json")) as f:
        floors = json.load(f)
    floor = floors["table_streaming_events_per_sec_floor"]
    stream = asyncio.run(harness.run_table_streaming(
        n_events=floors.get("table_streaming_smoke_events", 30_000),
        tx_size=floors.get("table_streaming_smoke_tx_size", 200),
        engine="tpu", destination="null"))
    stream_eps = stream["end_to_end_events_per_second"]
    stream_ok = stream_eps >= floor
    # the heartbeat budget keeps its own calibration (PR 4's 12k ev/s
    # per-event budget) instead of riding the streaming floor: the floor
    # tripled for EGRESS reasons (columnar fetch-to-wire), and pricing
    # one pessimistic beat-per-event against the tightened budget would
    # fail the gate with zero instrumentation change (the loop actually
    # beats once per drained window, ≤1 per 4096 events under saturation)
    hb_budget = floors.get("heartbeat_budget_events_per_sec", 12_000)
    heartbeat_overhead_ratio = per_beat_s * hb_budget
    heartbeat_ok = heartbeat_overhead_ratio < 0.01

    # columnar-egress gates (ISSUE 6): (a) ZERO TableRow constructions on
    # the streamed CDC hot path — the decode engine's batches must reach
    # the destination columnar, the row path creeping back fails here
    # before it costs 10x in production; (b) each destination encoder in
    # isolation (ColumnarBatch → wire bytes) above its per-encoder floor,
    # so a regression names the guilty encoder
    rows_constructed = stream.get("table_rows_constructed", -1)
    no_row_path = rows_constructed == 0
    egress = harness.run_egress(
        n_rows=floors.get("egress_smoke_rows", 4096),
        n_iters=floors.get("egress_smoke_iters", 3),
        device=True)
    egress_floors = floors.get("egress_floors", {})
    egress_failures = [k for k, v in egress_floors.items()
                      if egress.get(k, 0) < v]
    # device-egress byte-identity gate (ISSUE 17): the wire bytes spliced
    # from device-rendered buffers must equal the columnar oracles, and
    # the fast paths must actually have consumed the device buffers —
    # a silently-degraded fast path (attach failure, buffer mismatch)
    # fails here instead of hiding behind a still-passing rate floor
    for flag in ("device_tsv_identical", "device_json_identical",
                 "device_tsv_used_device", "device_json_used_device"):
        if not egress.get(flag, False):
            egress_failures.append(flag)
    egress_ok = not egress_failures

    # workload-diversity gate (ISSUE 7): a fast mixed-profile slice
    # (update-heavy + truncate-storm by default) through the FULL
    # pipeline with end-state verification, against the per-workload
    # floors — so a regression that only bites non-insert traffic (an
    # old-tuple path, the truncate barrier, a decode stall-spiral) fails
    # CI instead of hiding behind the insert-CDC floor
    workload_failures = []
    workload_rates = {}
    wfloors = floors.get("workload_floors", {})
    for prof in floors.get("workload_smoke_profiles",
                           ["update_heavy_default", "truncate_storm"]):
        wrun = asyncio.run(harness.run_workload_streaming(
            prof, target_ops=floors.get("workload_smoke_ops", 400)))
        workload_rates[prof] = wrun["events_per_second"]
        if not wrun["verified"]:
            workload_failures.append(f"{prof}: end state not verified")
        elif prof in wfloors \
                and wrun["events_per_second"] < wfloors[prof]:
            workload_failures.append(
                f"{prof}: {wrun['events_per_second']} ev/s under floor "
                f"{wfloors[prof]}")
    workload_ok = not workload_failures

    # mesh byte-identity gate (ISSUE 8): sharded decode on a FORCED
    # 8-way host-platform mesh must equal single-device decode bit for
    # bit. XLA fixes the device count at backend init, so the gate runs
    # in a fresh subprocess with the forcing flag — this process's
    # backend (1 CPU device) stays untouched
    import subprocess
    import sys as _sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    _xf = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _xf:
        env["XLA_FLAGS"] = \
            _xf + " --xla_force_host_platform_device_count=8"
    _repo = os.path.dirname(os.path.abspath(__file__))
    mesh_proc = subprocess.run(
        [_sys.executable, os.path.join(_repo, "bench.py"), "--mesh-check",
         "--mesh-rows", str(floors.get("mesh_smoke_rows", 8192))],
        capture_output=True, text=True, timeout=600, env=env, cwd=_repo)
    try:
        mesh_out = json.loads(mesh_proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        mesh_out = {"error": (mesh_proc.stderr or "no output")[-400:]}
    mesh_ok = mesh_proc.returncode == 0 \
        and mesh_out.get("sharded_equals_single") is True \
        and mesh_out.get("filtered_sharded_equals_single") is True \
        and mesh_out.get("mesh_shards") == 8

    # fused-filter gate (ISSUE 11): both device engines across filter
    # selectivities — Pallas == XLA == host-oracle BYTE identity on the
    # compacted output (survivor mapping included), and the MEASURED
    # fetched bytes <= (selectivity + pad slack) x the unfiltered fetch.
    # Wall-clock speedup is recorded, not gated, on the CPU backend
    # (the fetch this fusion shrinks crosses no link there)
    selectivity = harness.run_selectivity(
        n_rows=floors.get("selectivity_smoke_rows", 8_192),
        n_iters=floors.get("selectivity_smoke_iters", 3),
        fetch_slack=floors.get("selectivity_fetch_slack", 0.11))
    selectivity_ok = selectivity["ok"]

    # autoscale gates (ISSUE 13): (a) the policy reaction-time gate —
    # seeded surge must produce a scale-up decision within the tick
    # budget, the scale-down must wait out the cooldown, and the
    # decision trace must be deterministic per seed (pure policy
    # arithmetic — milliseconds); (b) the end-to-end elasticity chaos
    # scenario — a seeded backlog surge scales a LIVE K=2 fleet to 3
    # via the controller while traffic flows, the drain scales back to
    # 2 only after the cooldown, and zero-loss/bounded-dup invariants
    # hold across both rebalances
    autoscale = run_autoscale_bench(
        reaction_ticks_max=floors.get("autoscale_reaction_ticks_max", 3))
    from etl_tpu.chaos.autoscale import run_autoscale_surge_drain

    autoscale_chaos = asyncio.run(run_autoscale_surge_drain(seed=7))
    autoscale_ok = autoscale["ok"] and autoscale_chaos.ok

    # fleet converge gate (ISSUE 18): the 100-pipeline declarative
    # reconcile — empty→steady and through one add/remove/resize edit
    # within the working-tick budget, zero double-actuations
    # (journal-verified), observed == quota-clamped placement, and a
    # deterministic actuation trace per seed. Wall clock recorded, not
    # gated. The kill-mid-roll successor proof is
    # `python -m etl_tpu.chaos --fleet`.
    fleet = run_fleet_bench(
        fleet_size=floors.get("fleet_bench_pipelines", 100),
        converge_ticks_max=floors.get("fleet_converge_ticks_max", 3))
    fleet_ok = fleet["ok"]

    # program-cache coldstart gate (ISSUE 12): two replicator subprocess
    # lifetimes against one cache dir — the warm restart must compile
    # ZERO fresh XLA programs and serve its first durable batch from
    # disk-loaded executables (no oracle rows), and the cold start's
    # compile count must be bounded by the prewarm buckets, not by the
    # table count (the canonical-layout sharing proof). Wall clock is
    # recorded, not gated, on this CPU container.
    coldstart = harness.run_coldstart(
        n_tables=floors.get("coldstart_smoke_tables", 3),
        rows_per_tx=floors.get("coldstart_smoke_rows_per_tx", 400),
        txs_per_table=floors.get("coldstart_smoke_txs_per_table", 1))
    coldstart_ok = coldstart["ok"]

    # windowed-ack gate (ISSUE 14): the same deterministic backlog
    # drained through the default write window and through a forced
    # window=1 run against a destination with real ack latency
    # (destinations/delay.py). GATED: aggregate speedup ≥
    # ack_window_speedup_floor, byte-identical delivery digests,
    # window=1 never holds >1 ack in flight, the windowed run provably
    # overlaps (max pending ≥ 2, nonzero overlap seconds)
    ack = asyncio.run(harness.run_ack_latency(
        ack_ms=floors.get("ack_latency_smoke_ms", 20)))
    ack_floor = floors.get("ack_window_speedup_floor", 0)
    ack_failures = list(ack["failures"])
    if ack["ack_window_speedup"] < ack_floor:
        ack_failures.append(
            f"ack-window speedup {ack['ack_window_speedup']} under floor "
            f"{ack_floor}")
    ack_ok = not ack_failures

    # poison-resilience gates (ISSUE 15): (a) the bench A/B — the same
    # seeded insert-CDC workload clean vs 0.1%-poisoned against a
    # rejecting destination with isolation live; the poisoned rate must
    # hold ≥ poison_ratio_floor of the clean rate, bisection probe
    # writes must stay inside the 2·log₂(batch) bound, and both runs
    # must verify (the poisoned one against the UNION invariant:
    # delivered ∪ dead-lettered == committed truth); (b) the dead-letter
    # chaos scenario — poison rows mid-stream isolate to the DLQ,
    # the poisoned table quarantines at budget while every survivor
    # delivers its full workload, and replay + unquarantine restores
    # exact committed truth idempotently
    poison = asyncio.run(harness.run_poison_streaming(
        rate=floors.get("poison_rate", 0.001),
        target_ops=floors.get("poison_smoke_ops", 12_000)))
    poison_floor = floors.get("poison_ratio_floor", 0.7)
    poison_failures = list(poison["failures"])
    if poison["poison_throughput_ratio"] < poison_floor:
        poison_failures.append(
            f"poisoned throughput ratio "
            f"{poison['poison_throughput_ratio']} under floor "
            f"{poison_floor}")
    from etl_tpu.chaos.dlq import run_dlq_poison

    dlq_chaos = asyncio.run(run_dlq_poison(seed=7))
    poison_ok = not poison_failures and dlq_chaos.ok

    # exactly-once gates (ISSUE 19): (a) the bench A/B — the same seeded
    # backlog drained through the plain memory sink and through the
    # transactional sink (dedup tokens derived from WAL coordinates on
    # every committed write); the transactional rate must hold ≥
    # exactly_once_ratio_floor of the plain rate, and the hard-kill
    # restart leg must deliver exactly once with the re-streamed prefix
    # bounded by the unacked suffix (recovery anchors on the sink's own
    # high-water mark, not on blind durable progress); (b) the hard-kill
    # chaos matrix — kills at mid-write, post-write-pre-progress-commit
    # and mid-recovery windows, asserting dup==0, zero loss, and
    # monotone sink high-water marks
    eo = asyncio.run(harness.run_exactly_once(
        n_events=floors.get("exactly_once_smoke_events", 3_000)))
    eo_floor = floors.get("exactly_once_ratio_floor", 0.8)
    eo_failures = list(eo["failures"])
    if eo["exactly_once_overhead_ratio"] < eo_floor:
        eo_failures.append(
            f"transactional throughput ratio "
            f"{eo['exactly_once_overhead_ratio']} under floor {eo_floor}")
    from etl_tpu.chaos.exactly_once import run_exactly_once_crash

    eo_chaos = asyncio.run(run_exactly_once_crash(seed=7))
    eo_ok = not eo_failures and eo_chaos.ok

    # multi-pipeline tenancy gate (ISSUE 8): ≥2 concurrent streams
    # sharing one device set through the fair batch-admission scheduler,
    # every stream's end state verified, aggregate events/s above the
    # floor, and the scheduler drained clean (no tickets/tenants left)
    mp = asyncio.run(harness.run_multi_pipeline(
        profiles=floors.get("multi_pipeline_smoke_profiles"),
        target_ops=floors.get("multi_pipeline_smoke_ops", 500)))
    mp_floor = floors.get("multi_pipeline_events_per_sec_floor", 0)
    mp_failures = []
    if mp["streams"] < 2:
        mp_failures.append(f"only {mp['streams']} streams")
    if not mp["all_verified"]:
        mp_failures.append("a stream's end state failed verification")
    if mp["aggregate_events_per_second"] < mp_floor:
        mp_failures.append(
            f"aggregate {mp['aggregate_events_per_second']} ev/s under "
            f"floor {mp_floor}")
    if not mp["scheduler_drained"]:
        mp_failures.append("admission scheduler did not drain")
    if mp["admission_grants"] <= 0:
        mp_failures.append("no admission grants — the scheduler was "
                           "never exercised")
    mp_ok = not mp_failures

    # sharded scale-out gates (ISSUE 9): (a) the K=2 pod-kill chaos
    # scenario — kill one of two shard replicators mid-stream; the
    # survivor must deliver its whole slice during the outage, the
    # victim must reconverge from durable state, and the per-shard AND
    # cross-shard-union invariants must hold; (b) a K=2 sharded bench
    # slice (one worker PROCESS per shard, the pod resource model)
    # against the sharded aggregate floor
    from etl_tpu.chaos.sharded import run_sharded_scenario

    sharded_chaos = asyncio.run(run_sharded_scenario(seed=7))
    sharded_chaos_ok = sharded_chaos.ok
    sharded = asyncio.run(harness.run_sharded_processes(
        shards=2, target_ops=floors.get("sharded_smoke_ops", 8_000)))
    sharded_floor = floors.get("sharded_events_per_sec_floor", 0)
    sharded_failures = []
    if not sharded["all_verified"]:
        sharded_failures.append("a shard's slice failed end-state "
                                "verification")
    if not sharded["union_covers_all_tables"]:
        sharded_failures.append("shard slices do not cover every table "
                                "exactly once")
    if sharded["aggregate_events_per_second"] < sharded_floor:
        sharded_failures.append(
            f"aggregate {sharded['aggregate_events_per_second']} ev/s "
            f"under floor {sharded_floor}")
    sharded_ok = not sharded_failures

    # static-analysis budget gate (ISSUE 5 CI satellite): the full
    # whole-program etl-lint pass (call graph + context propagation +
    # CFG rules over every module) must stay cheap enough to gate every
    # PR — the budget is wall-clock, generous vs the ~4s measured on the
    # CI CPU so container noise doesn't flake it, but tight enough that
    # an accidentally-quadratic traversal fails loudly here instead of
    # silently doubling tier-1 time
    from etl_tpu.analysis.rules import analyze_paths, repo_package_dir

    lint_budget_s = float(floors.get("static_analysis_budget_s", 30.0))
    t0 = time.perf_counter()
    lint_findings = analyze_paths([str(repo_package_dir())])
    lint_seconds = time.perf_counter() - t0

    # baseline-hygiene gate (ISSUE 20 satellite): the CI entry point in
    # --check-baseline mode — exits 1 when a baseline entry or inline
    # ignore no longer matches a live finding, so grandfathered debt
    # can only shrink. A subprocess on purpose: it exercises the exact
    # command CI runs (sys.path bootstrap included), inside the same
    # wall-clock budget as the in-process pass above.
    t0 = time.perf_counter()
    baseline_proc = subprocess.run(
        [_sys.executable, os.path.join(_repo, "scripts", "lint_repo.py"),
         "--check-baseline", "-q"],
        capture_output=True, text=True, timeout=600, cwd=_repo)
    baseline_seconds = time.perf_counter() - t0
    baseline_clean = baseline_proc.returncode == 0
    lint_ok = (lint_seconds < lint_budget_s and baseline_clean
               and baseline_seconds < lint_budget_s)

    # IR-tier gate (ISSUE 16 CI satellite): the compiled-program
    # contract pass — every enumerable canonical layout lowered through
    # the production jit constructor and checked (callbacks, donation,
    # collectives, widening, output budget, canonical dedup) — must run
    # CLEAN (exit 0: violations fail the gate, not just the budget) and
    # inside its wall-clock budget. Runs as a subprocess because the
    # --mesh slice re-inits jax with 8 forced host devices, which this
    # process's already-initialized single-device backend cannot do.
    ir_budget_s = float(floors.get("ir_analysis_budget_s", 120.0))
    ir_env = dict(os.environ)
    ir_env["JAX_PLATFORMS"] = "cpu"
    t0 = time.perf_counter()
    ir_proc = subprocess.run(
        [_sys.executable, "-m", "etl_tpu.analysis", "--programs",
         "--mesh", "-q"],
        capture_output=True, text=True, timeout=600, env=ir_env,
        cwd=_repo)
    ir_seconds = time.perf_counter() - t0
    ir_clean = ir_proc.returncode == 0
    ir_ok = ir_clean and ir_seconds < ir_budget_s

    return {
        "mode": "smoke",
        "ok": bool(identical and stages_observed and stream_ok
                   and heartbeat_ok and lint_ok and ir_ok
                   and no_row_path
                   and egress_ok and workload_ok and mesh_ok and mp_ok
                   and sharded_chaos_ok and sharded_ok
                   and selectivity_ok and coldstart_ok
                   and autoscale_ok and fleet_ok and ack_ok
                   and poison_ok and eo_ok),
        "exactly_once_ok": bool(eo_ok),
        "exactly_once_overhead_ratio": eo["exactly_once_overhead_ratio"],
        "exactly_once_ratio_floor": eo_floor,
        "exactly_once_restart_duplicates":
            eo["restart"]["duplicate_rows"],
        "exactly_once_restart_restreamed_deduped":
            eo["restart"]["restreamed_deduped_rows"],
        "exactly_once_restart_unacked_suffix":
            eo["restart"]["unacked_suffix_rows"],
        "exactly_once_failures": eo_failures,
        "exactly_once_chaos_ok": bool(eo_chaos.ok),
        "exactly_once_chaos": eo_chaos.describe(),
        "poison_ok": bool(poison_ok),
        "poison_throughput_ratio": poison["poison_throughput_ratio"],
        "poison_ratio_floor": poison_floor,
        "poison_probe_writes": poison["poisoned"]["probe_writes"],
        "poison_probe_bound": poison["poisoned"]["probe_bound"],
        "poison_dlq_entries": poison["poisoned"]["dlq_entries"],
        "poison_failures": poison_failures,
        "dlq_chaos_ok": bool(dlq_chaos.ok),
        "dlq_chaos": dlq_chaos.describe(),
        "ack_window_ok": bool(ack_ok),
        "ack_window_speedup": ack["ack_window_speedup"],
        "ack_window_speedup_floor": ack_floor,
        "ack_window_overlap_ratio":
            ack["windowed"]["ack_overlap_ratio"],
        "ack_window_max_pending": ack["windowed"]["max_acks_pending"],
        "ack_window_failures": ack_failures,
        "autoscale_ok": bool(autoscale_ok),
        "autoscale_reaction_ticks": autoscale["reaction_ticks"],
        "autoscale_scale_up_tick": autoscale["scale_up_tick"],
        "autoscale_scale_down_tick": autoscale["scale_down_tick"],
        "autoscale_deterministic": bool(autoscale["deterministic"]),
        "autoscale_failures": autoscale["failures"],
        "autoscale_chaos_ok": bool(autoscale_chaos.ok),
        "autoscale_chaos": autoscale_chaos.describe(),
        "fleet_ok": bool(fleet_ok),
        "fleet_converge_ticks": fleet["converge_ticks"],
        "fleet_edit_converge_ticks": fleet["edit_converge_ticks"],
        "fleet_converge_ticks_max": fleet["converge_ticks_max"],
        "fleet_double_actuations": fleet["double_actuations"],
        "fleet_deterministic": bool(fleet["deterministic"]),
        "fleet_converge_wall_clock_s": fleet["converge_wall_clock_s"],
        "fleet_failures": fleet["failures"],
        "selectivity_ok": bool(selectivity_ok),
        "selectivity": selectivity,
        "coldstart_ok": bool(coldstart_ok),
        "coldstart_warm_zero_compiles":
            bool(coldstart["warm_zero_compiles"]),
        "coldstart_failures": coldstart["failures"],
        "coldstart_warm_first_durable_seconds":
            coldstart["warm_first_durable_seconds"],
        "coldstart_cold_first_durable_seconds":
            coldstart["cold_first_durable_seconds"],
        "coldstart_cold_oracle_rows":
            coldstart["cold_oracle_rows_during_warmup"],
        "sharded_chaos_ok": bool(sharded_chaos_ok),
        "sharded_chaos": sharded_chaos.describe(),
        "sharded_events_per_sec":
            sharded["aggregate_events_per_second"],
        "sharded_floor_events_per_sec": sharded_floor,
        "sharded_shards": sharded["shards"],
        "sharded_all_verified": bool(sharded["all_verified"]),
        "sharded_union_covers_all_tables":
            bool(sharded["union_covers_all_tables"]),
        "sharded_ok": bool(sharded_ok),
        "sharded_failures": sharded_failures,
        "mesh_sharded_equals_single":
            bool(mesh_out.get("sharded_equals_single")),
        "mesh_shards": mesh_out.get("mesh_shards", 0),
        "mesh_check_ok": bool(mesh_ok),
        "mesh_check": mesh_out,
        "multi_pipeline_events_per_sec":
            mp["aggregate_events_per_second"],
        "multi_pipeline_floor_events_per_sec": mp_floor,
        "multi_pipeline_streams": mp["streams"],
        "multi_pipeline_all_verified": bool(mp["all_verified"]),
        "multi_pipeline_scheduler_drained":
            bool(mp["scheduler_drained"]),
        "multi_pipeline_admission_grants": mp["admission_grants"],
        "multi_pipeline_ok": bool(mp_ok),
        "multi_pipeline_failures": mp_failures,
        "workload_events_per_sec": workload_rates,
        "workload_profiles_above_floor": bool(workload_ok),
        "workload_failures": workload_failures,
        "streaming_table_rows_constructed": rows_constructed,
        "streaming_zero_row_materialization": bool(no_row_path),
        "egress_encoders_above_floor": bool(egress_ok),
        "egress_failures": egress_failures,
        **{k: v for k, v in egress.items() if k.endswith("_per_sec")},
        "static_analysis_seconds": round(lint_seconds, 3),
        "static_analysis_budget_s": lint_budget_s,
        "static_analysis_under_budget": bool(lint_ok),
        "static_analysis_findings": len(lint_findings),
        "static_analysis_baseline_clean": bool(baseline_clean),
        "static_analysis_baseline_seconds": round(baseline_seconds, 3),
        "static_analysis_baseline_error": "" if baseline_clean
        else (baseline_proc.stderr or baseline_proc.stdout or "")[-400:],
        "ir_analysis_seconds": round(ir_seconds, 3),
        "ir_analysis_budget_s": ir_budget_s,
        "ir_analysis_under_budget": bool(ir_seconds < ir_budget_s),
        "ir_analysis_clean": bool(ir_clean),
        "ir_analysis_error": "" if ir_clean
        else (ir_proc.stderr or ir_proc.stdout or "")[-400:],
        "pipelined_equals_serial": bool(identical),
        "stage_histograms_observed": bool(stages_observed),
        "streaming_events_per_sec": stream_eps,
        "streaming_floor_events_per_sec": floor,
        "streaming_above_floor": bool(stream_ok),
        "heartbeat_seconds_per_beat": per_beat_s,
        "heartbeat_overhead_ratio_at_floor": heartbeat_overhead_ratio,
        "heartbeat_overhead_under_1pct": bool(heartbeat_ok),
        "rows_per_batch": n_rows,
        "batches": 3,
        "overlap_seconds": round(stats["overlap_seconds_total"], 5),
        "arena": stats["arena"],
    }


def _require_chip(mode: str) -> None:
    """Device-measuring modes run on the chip or not at all. The check is
    in-process — a child that opened the chip first would be the one
    place a launcher hands it between processes. Exit 3 unless the
    platform is `tpu` or the caller named the CPU backend explicitly."""
    import os

    import jax

    try:
        platform = jax.devices()[0].platform
    except RuntimeError as e:
        print(json.dumps({"mode": mode,
                          "error": f"device backend unavailable: {e}"}))
        sys.exit(3)
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(json.dumps({
            "mode": mode,
            "error": f"platform is {platform!r}, not 'tpu': this mode "
                     "measures the device (set JAX_PLATFORMS=cpu for a "
                     "functional run on the CPU backend)"}))
        sys.exit(3)


def main():
    import argparse
    import os

    import jax

    from etl_tpu.ops.program_store import place_jax_compile_cache

    place_jax_compile_cache()
    parser = argparse.ArgumentParser(prog="bench.py")
    parser.add_argument("--mode", default="decode",
                        choices=["decode", "table_copy", "table_streaming",
                                 "wide_row", "lag", "egress", "workload",
                                 "multi_pipeline", "mesh_check",
                                 "selectivity", "coldstart", "autoscale",
                                 "fleet"])
    parser.add_argument("--multi-pipeline", dest="multi_pipeline",
                        action="store_true",
                        help="alias for --mode multi_pipeline: N "
                             "concurrent replication streams (workload "
                             "profiles as the tenancy mix) sharing one "
                             "device set through the fair batch-admission "
                             "scheduler; gates the aggregate events/s "
                             "against multi_pipeline_events_per_sec_floor "
                             "in BENCH_FLOOR.json")
    parser.add_argument("--sharded", dest="sharded", type=int, default=None,
                        metavar="K",
                        help="horizontal scale-out mode: run the same "
                             "publication workload through K shard "
                             "replicator PROCESSES (one per shard, the "
                             "pod resource model) and through one "
                             "unsharded baseline process; gates the "
                             "K-shard aggregate events/s against "
                             "sharded_events_per_sec_floor in "
                             "BENCH_FLOOR.json AND strictly above the "
                             "single-shard run")
    parser.add_argument("--streams", default=None, metavar="P1,P2,...",
                        help="comma-separated workload profiles for "
                             "--multi-pipeline (default: the "
                             "multi_pipeline_smoke_profiles mix)")
    parser.add_argument("--mesh-check", dest="mesh_check",
                        action="store_true",
                        help="alias for --mode mesh_check: assert "
                             "mesh-sharded decode is byte-identical to "
                             "single-device decode and record the "
                             "(honest) wall-clock + device-program "
                             "scaling; run under XLA_FLAGS="
                             "--xla_force_host_platform_device_count=8")
    parser.add_argument("--mesh-rows", type=int, default=65_536,
                        help="batch size for --mesh-check (default 65536)")
    parser.add_argument("--selectivity", dest="selectivity",
                        action="store_true",
                        help="alias for --mode selectivity: the fused "
                             "publication-row-filter matrix — both device "
                             "engines (XLA mask twin + Pallas fused "
                             "kernel) across filter selectivities, gating "
                             "Pallas == XLA == host-oracle byte identity "
                             "on the compacted output and fetched bytes "
                             "<= (selectivity + pad slack) x unfiltered; "
                             "wall-clock speedup recorded NOT gated off-"
                             "TPU")
    parser.add_argument("--egress", dest="egress", action="store_true",
                        help="alias for --mode egress: measure each "
                             "destination encoder in isolation "
                             "(ColumnarBatch → wire bytes) against the "
                             "egress_floors in BENCH_FLOOR.json")
    parser.add_argument("--device", dest="device", action="store_true",
                        help="with --egress: also measure the device-"
                             "resident egress seam (decode with the "
                             "fused wire-encoding stage, destination "
                             "fast paths splicing the device buffers) "
                             "against the device_* egress_floors, and "
                             "gate byte identity vs the columnar "
                             "oracles")
    parser.add_argument("--coldstart", dest="coldstart",
                        action="store_true",
                        help="alias for --mode coldstart: two replicator "
                             "subprocess lifetimes against one program-"
                             "cache dir — measure restart-to-first-"
                             "durable-batch and oracle-decoded rows "
                             "during warmup, cold vs warm; gate 'warm "
                             "restart performs 0 fresh XLA builds' via "
                             "the compile counter (wall clock recorded, "
                             "not gated, on this CPU container)")
    parser.add_argument("--autoscale", dest="autoscale",
                        action="store_true",
                        help="alias for --mode autoscale: the seeded "
                             "surge→drain timeline through the scaling "
                             "policy (etl_tpu/autoscale) with the "
                             "applied-K loop closed; gates scale-up "
                             "reaction time <= "
                             "autoscale_reaction_ticks_max evaluation "
                             "ticks, no scale-down inside the cooldown, "
                             "return to the starting K, and a "
                             "bit-identical decision trace per seed")
    parser.add_argument("--ack-latency", dest="ack_latency", type=float,
                        default=None, metavar="MS",
                        help="windowed-ack A/B mode: run the same "
                             "deterministic CDC backlog against a "
                             "destination whose acks turn durable MS "
                             "milliseconds late, once at the default "
                             "write window and once forced to window=1; "
                             "gates the aggregate speedup against "
                             "ack_window_speedup_floor in "
                             "BENCH_FLOOR.json plus byte-identical "
                             "delivery and the one-in-flight contract "
                             "at window=1")
    parser.add_argument("--poison", dest="poison", action="store_true",
                        help="poison-resilience mode: the same seeded "
                             "insert-CDC workload measured clean and "
                             "with poison_rate of rows poisoned against "
                             "a rejecting destination (isolation + "
                             "dead-letter live); gates the poisoned "
                             "rate >= poison_ratio_floor x the clean "
                             "rate, bisection probe writes within the "
                             "2·log2(batch) bound, and the union "
                             "invariant delivered ∪ dead-lettered == "
                             "committed truth")
    parser.add_argument("--poison-ops", dest="poison_ops", type=int,
                        default=None, metavar="N",
                        help="row ops per measured poison pass "
                             "(default: poison_smoke_ops from "
                             "BENCH_FLOOR.json)")
    parser.add_argument("--exactly-once", dest="exactly_once",
                        action="store_true",
                        help="exactly-once mode: the same seeded CDC "
                             "backlog drained through the plain memory "
                             "sink and the transactional sink (dedup "
                             "tokens keyed by WAL coordinates), plus a "
                             "hard-kill restart leg; gates the "
                             "transactional rate >= "
                             "exactly_once_ratio_floor x the plain "
                             "rate, zero duplicate rows after restart, "
                             "zero loss, and re-streamed-then-deduped "
                             "rows <= the unacked suffix at the kill")
    parser.add_argument("--workload", default=None, metavar="PROFILE",
                        help="workload matrix mode: run the named workload "
                             "profile (etl_tpu/workloads; 'all' = every "
                             "profile) through the full pipeline with "
                             "end-state verification, and gate each "
                             "measured profile against workload_floors in "
                             "BENCH_FLOOR.json")
    parser.add_argument("--seed", type=int, default=7,
                        help="workload generator seed (--workload mode)")
    parser.add_argument("--engine", default="tpu",
                        choices=["tpu", "cpu", "pallas"])
    parser.add_argument("--fleet", dest="fleet", action="store_true",
                        help="fleet converge gate: a 100-pipeline seeded "
                             "FleetSpec reconciles onto an empty "
                             "simulated fleet and through one "
                             "add/remove/resize edit; gates working "
                             "ticks <= fleet_converge_ticks_max, zero "
                             "double-actuations (journal-verified), "
                             "observed == quota-clamped placement, and "
                             "a deterministic actuation trace; wall "
                             "clock recorded, not gated")
    parser.add_argument("--smoke", action="store_true",
                        help="CI gate: CPU backend, small batches, assert "
                             "pipelined decode == serial decode; exit 1 on "
                             "mismatch")
    args = parser.parse_args()
    if args.selectivity:
        args.mode = "selectivity"
    if args.egress:
        args.mode = "egress"
    if args.coldstart:
        args.mode = "coldstart"
    if args.autoscale:
        args.mode = "autoscale"
    if args.fleet:
        args.mode = "fleet"
    if args.mode == "fleet":
        # pure host-side reconciliation arithmetic: never touches a
        # device backend
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_FLOOR.json")) as f:
            floors = json.load(f)
        out = run_fleet_bench(
            seed=args.seed,
            fleet_size=floors.get("fleet_bench_pipelines", 100),
            converge_ticks_max=floors.get("fleet_converge_ticks_max", 3))
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.mode == "autoscale":
        # pure policy arithmetic over the seeded synthetic timeline:
        # never touches a device backend
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_FLOOR.json")) as f:
            floors = json.load(f)
        out = run_autoscale_bench(
            seed=args.seed,
            reaction_ticks_max=floors.get("autoscale_reaction_ticks_max",
                                          3))
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.mode == "coldstart":
        # subprocess workers pin their own CPU platform; the parent never
        # inits a backend
        from etl_tpu.benchmarks import harness

        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_FLOOR.json")) as f:
            floors = json.load(f)
        out = harness.run_coldstart(
            n_tables=floors.get("coldstart_tables", 3),
            rows_per_tx=floors.get("coldstart_rows_per_tx", 800),
            txs_per_table=floors.get("coldstart_txs_per_table", 2))
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.ack_latency is not None:
        # full pipeline on the host CPU platform (CPU decode engine, fake
        # walsender, latency-wrapped memory-style destination) — the ack
        # window is the system under test; CPU-forced, never opens the chip
        import asyncio

        jax.config.update("jax_platforms", "cpu")
        from etl_tpu.benchmarks import harness

        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_FLOOR.json")) as f:
            floors = json.load(f)
        out = asyncio.run(harness.run_ack_latency(ack_ms=args.ack_latency))
        floor = floors.get("ack_window_speedup_floor", 0)
        out["speedup_floor"] = floor
        if out["ack_window_speedup"] < floor:
            out["failures"].append(
                f"ack-window speedup {out['ack_window_speedup']} under "
                f"floor {floor}")
            out["ok"] = False
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.poison:
        # full pipeline on the host CPU platform (fake walsender,
        # poison-rejecting memory destination) — the isolation protocol
        # is the system under test; CPU-forced, never opens the chip
        import asyncio

        jax.config.update("jax_platforms", "cpu")
        from etl_tpu.benchmarks import harness

        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_FLOOR.json")) as f:
            floors = json.load(f)
        out = asyncio.run(harness.run_poison_streaming(
            rate=floors.get("poison_rate", 0.001), seed=args.seed,
            target_ops=args.poison_ops
            or floors.get("poison_smoke_ops", 12_000)))
        floor = floors.get("poison_ratio_floor", 0.7)
        out["ratio_floor"] = floor
        if out["poison_throughput_ratio"] < floor:
            out["failures"].append(
                f"poisoned throughput ratio "
                f"{out['poison_throughput_ratio']} under floor {floor}")
            out["ok"] = False
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.exactly_once:
        # full pipeline on the host CPU platform (fake walsender, plain
        # vs transactional memory destination, one hard-kill restart) —
        # the commit-coordination seam is the system under test;
        # CPU-forced, never opens the chip
        import asyncio

        jax.config.update("jax_platforms", "cpu")
        from etl_tpu.benchmarks import harness

        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_FLOOR.json")) as f:
            floors = json.load(f)
        out = asyncio.run(harness.run_exactly_once(
            n_events=floors.get("exactly_once_smoke_events", 3_000)))
        floor = floors.get("exactly_once_ratio_floor", 0.8)
        out["ratio_floor"] = floor
        if out["exactly_once_overhead_ratio"] < floor:
            out["failures"].append(
                f"transactional throughput ratio "
                f"{out['exactly_once_overhead_ratio']} under floor "
                f"{floor}")
            out["ok"] = False
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.workload is not None:
        args.mode = "workload"
    if args.multi_pipeline:
        args.mode = "multi_pipeline"
    if args.mesh_check:
        args.mode = "mesh_check"
    if args.mode == "mesh_check":
        # the forcing flag only works at backend init: the caller (or the
        # smoke gate's subprocess spawn) sets XLA_FLAGS; here we only pin
        # the CPU platform so the check never opens the chip
        jax.config.update("jax_platforms", "cpu")
        out = run_mesh_check(n_rows=args.mesh_rows)
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.sharded is not None:
        # K shard worker processes + the single-shard baseline, CPU
        # platform (memory destinations + end-state verification per
        # shard — the workload-matrix stance); the parent never inits a
        # backend itself
        import asyncio

        jax.config.update("jax_platforms", "cpu")
        from etl_tpu.benchmarks import harness

        if args.sharded < 2:
            parser.error("--sharded needs K >= 2 (the single-shard "
                         "baseline runs automatically)")
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_FLOOR.json")) as f:
            floors = json.load(f)
        target = floors.get("sharded_bench_ops", 12_000)

        async def both():
            sharded = await harness.run_sharded_processes(
                shards=args.sharded, seed=args.seed, target_ops=target)
            single = await harness.run_sharded_processes(
                shards=1, seed=args.seed, target_ops=target)
            return sharded, single

        sharded, single = asyncio.run(both())
        floor = floors.get("sharded_events_per_sec_floor", 0)
        out = dict(sharded)
        out["single_shard_events_per_second"] = \
            single["aggregate_events_per_second"]
        out["single_shard_verified"] = single["all_verified"]
        out["speedup_vs_single"] = round(
            sharded["aggregate_events_per_second"]
            / max(single["aggregate_events_per_second"], 1), 3)
        out["floor_events_per_second"] = floor
        out["failures"] = []
        if not out["all_verified"]:
            out["failures"].append("a shard's slice failed end-state "
                                   "verification")
        if not out["union_covers_all_tables"]:
            out["failures"].append("shard slices do not cover every "
                                   "table exactly once")
        if not out["single_shard_verified"]:
            out["failures"].append("the single-shard baseline failed "
                                   "verification")
        if out["aggregate_events_per_second"] < floor:
            out["failures"].append(
                f"aggregate {out['aggregate_events_per_second']} ev/s "
                f"under floor {floor}")
        if out["aggregate_events_per_second"] <= \
                out["single_shard_events_per_second"]:
            out["failures"].append(
                f"sharded aggregate {out['aggregate_events_per_second']} "
                f"not strictly above the single-shard run "
                f"{out['single_shard_events_per_second']}")
        out["ok"] = not out["failures"]
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.mode == "multi_pipeline":
        # memory destinations + end-state verification per stream: host
        # CPU platform, same stance as the workload matrix
        import asyncio

        jax.config.update("jax_platforms", "cpu")
        from etl_tpu.benchmarks import harness

        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_FLOOR.json")) as f:
            floors = json.load(f)
        profiles = args.streams.split(",") if args.streams \
            else floors.get("multi_pipeline_smoke_profiles")
        out = asyncio.run(harness.run_multi_pipeline(
            profiles=profiles, seed=args.seed))
        floor = floors.get("multi_pipeline_events_per_sec_floor", 0)
        out["floor_events_per_second"] = floor
        out["failures"] = []
        if not out["all_verified"]:
            out["failures"].append("a stream's end state failed "
                                   "verification")
        if out["aggregate_events_per_second"] < floor:
            out["failures"].append(
                f"aggregate {out['aggregate_events_per_second']} ev/s "
                f"under floor {floor}")
        if not out["scheduler_drained"]:
            out["failures"].append("admission scheduler did not drain")
        out["ok"] = not out["failures"]
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.mode == "workload":
        if args.engine == "pallas":
            parser.error("--engine pallas applies to wide_row only")
        # the matrix verifies END STATE per profile, so it always runs
        # on the host CPU platform the way the smoke gate does — the
        # same pipeline code paths, no accelerator dependency.
        # --engine selects the DECODE PATH only (tpu = the XLA engine
        # compiled for host CPU, cpu = the oracle codecs); the floors in
        # BENCH_FLOOR.json are calibrated for this host backend
        import asyncio

        jax.config.update("jax_platforms", "cpu")
        from etl_tpu.benchmarks import harness
        from etl_tpu.workloads import profile_names

        names = profile_names() if args.workload in (None, "all") \
            else [args.workload]
        out = asyncio.run(harness.run_workload_matrix(
            names, seed=args.seed, engine=args.engine))
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_FLOOR.json")) as f:
            wfloors = json.load(f).get("workload_floors", {})
        out["floors"] = wfloors
        out["failures"] = [
            n for n, v in out["events_per_second"].items()
            if n in wfloors and v < wfloors[n]]
        out["ok"] = bool(out["all_verified"]) and not out["failures"]
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.mode == "selectivity":
        # decode-level matrix: identity + fetch-reduction gates are
        # backend-independent (they hold on the host CPU platform and on
        # a real chip alike); the wall-clock columns are only meaningful
        # on real TPU hardware and are recorded, never gated, elsewhere
        from etl_tpu.benchmarks import harness

        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_FLOOR.json")) as f:
            floors = json.load(f)
        out = harness.run_selectivity(
            n_rows=floors.get("selectivity_rows", 16_384),
            fetch_slack=floors.get("selectivity_fetch_slack", 0.11))
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.mode == "egress":
        # encoder isolation runs on the CPU backend by definition — the
        # encoders are host code; CPU-forced, never opens the chip
        jax.config.update("jax_platforms", "cpu")
        from etl_tpu.benchmarks import harness

        out = harness.run_egress(device=args.device)
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_FLOOR.json")) as f:
            efloors = json.load(f).get("egress_floors", {})
        out["floors"] = efloors
        # device_* floors gate only when --device ran the device seam;
        # the host-encoder floors always gate
        out["failures"] = [k for k, v in efloors.items()
                           if (k in out or not k.startswith("device_"))
                           and out.get(k, 0) < v]
        if args.device:
            out["failures"] += [
                flag for flag in ("device_tsv_identical",
                                  "device_json_identical",
                                  "device_tsv_used_device",
                                  "device_json_used_device")
                if not out.get(flag, False)]
        out["ok"] = not out["failures"]
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.smoke:
        # force the CPU backend — the smoke gate never opens the chip
        jax.config.update("jax_platforms", "cpu")
        out = run_smoke()
        print(json.dumps(out))
        sys.exit(0 if out["ok"] else 1)
    if args.engine == "pallas" and args.mode != "wide_row":
        parser.error("--engine pallas applies to wide_row only "
                     "(decode mode always measures both engines)")
    # decode and wide_row always run the device engine; pipeline modes
    # only need a device when the batch engine is tpu
    if args.mode in ("decode", "wide_row") or args.engine == "tpu":
        _require_chip(args.mode)
    if args.mode != "decode":
        import asyncio

        from etl_tpu.benchmarks import harness

        if args.mode == "table_copy":
            out = asyncio.run(harness.run_table_copy(engine=args.engine))
        elif args.mode == "table_streaming":
            out = asyncio.run(harness.run_table_streaming(engine=args.engine))
        elif args.mode == "lag":
            out = asyncio.run(harness.run_lag_vs_rate(engine=args.engine))
        else:
            out = harness.run_wide_row(
                engine="pallas" if args.engine == "pallas" else "xla")
        print(json.dumps(out))
        return

    payloads = build_workload(N_ROWS)
    schema = make_schema()
    cpu_rps = bench_cpu(payloads, schema, N_ROWS)
    # a FIXED 3 rounds on the chip (1 on the CPU backend, a functional
    # run), pooled: fixed rounds keep the pooled median's sample size
    # result-independent.
    rounds = 3 if jax.default_backend() == "tpu" else 1
    all_rates: list[float] = []
    pipe_stats: dict = {}
    for _ in range(rounds):
        rates, _, pipe_stats = bench_tpu(payloads, schema, N_ROWS)
        all_rates.extend(rates)
    all_rates.sort()
    xla_rps = all_rates[-1]
    xla_med = all_rates[len(all_rates) // 2]
    # measure the pallas kernel too (VERDICT r2 #8: decide with data);
    # a kernel Mosaic rejects raises. Off-TPU the kernel runs in
    # interpret mode (correctness only, ~1000× slower) — not a perf
    # measurement, skip it.
    if jax.default_backend() == "tpu":
        # SAME number of rounds as the XLA engine, pooled the same way,
        # so both engines headline a comparable statistic
        prates = []
        pallas_ok = True
        for _ in range(rounds):
            r, pdec, _ = bench_tpu(payloads, schema, N_ROWS, use_pallas=True)
            prates.extend(r)
            pallas_ok = pallas_ok and pdec.use_pallas
        prates = sorted(prates)
        pallas_rps = prates[-1]
        pallas_med = prates[len(prates) // 2]
    else:
        pallas_rps, pallas_med, pallas_ok = 0.0, 0.0, False
    # headline value/ratio = the MEDIAN (VERDICT r3 #9) of whichever
    # engine's median wins — same statistic for both engines so the
    # headline stays comparable across runs; the peak sustained window
    # is reported alongside
    if pallas_ok and pallas_med > xla_med:
        lead, best, engine = pallas_med, pallas_rps, "pallas"
    else:
        lead, best, engine = xla_med, xla_rps, "xla"
    result = {
        "metric": "wal_records_per_sec_decoded",
        "value": round(lead),
        "unit": "records/s",
        "vs_baseline": round(lead / cpu_rps, 2),
        "vs_baseline_peak": round(best / cpu_rps, 2),
        "cpu_baseline_records_per_sec": round(cpu_rps),
        "engine": engine,
        "xla_records_per_sec": round(xla_rps),
        "xla_median_records_per_sec": round(xla_med),
        "measurement_rounds": rounds,
        "pallas_records_per_sec": round(pallas_rps) if pallas_ok else None,
        # pallas_ok is False on the chip only when pallas_supported's
        # width bound sent the schema to the XLA program
        "pallas_status": "ok" if pallas_ok else (
            "width_bound" if jax.default_backend() == "tpu"
            else "not_measured"),
        "backend": jax.default_backend(),
        "workload": f"pgbench insert CDC, {N_ROWS} rows/batch",
        # three-stage pipeline evidence (last XLA round): pack of batch
        # N+1 concurrent with device compute of batch N, and arena reuse
        "pipeline_overlap_ratio":
            round(pipe_stats.get("overlap_ratio", 0.0), 3),
        "pipeline_overlap_seconds":
            round(pipe_stats.get("overlap_seconds_total", 0.0), 4),
        "pipeline_window": pipe_stats.get("window"),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
