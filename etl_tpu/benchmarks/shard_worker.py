"""One shard replicator as its own OS process — the bench pod model.

`python -m etl_tpu.benchmarks.shard_worker '<spec json>'` runs ONE
pipeline (shard-scoped or unsharded) against its own fake source replica
and prints a single JSON result line. The parent (`bench.py --sharded K`
via `harness.run_sharded_processes`) launches K of these concurrently:
separate interpreters, separate GILs, separate XLA runtimes — the same
resource split as K replicator pods, which is the whole point of
horizontal scale-out (an in-process K-way run shares one GIL and one
event loop and measures nothing).

Faithfulness contract: every worker replays the IDENTICAL publication
WAL — the workload generator's byte-identical `(profile, seed)` replay
contract (docs/workloads.md) makes K private FakeDatabase replicas
indistinguishable from K connections to one source. A sharded worker
applies only its ShardMap slice and verifies that slice against the
generator's committed truth; the parent asserts the slices cover every
table. The store is a per-process MemoryStore: this bench measures
decode/apply capacity — shared-store semantics (ownership fences, epoch
refusal, rebalancing) are covered by the chaos scenario and
tests/test_sharding.py.

Reported `events_per_second` counts ROW EVENTS DELIVERED at this
worker's destination over its measured window (produce start → slice
verified), so the K-shard aggregate and the single-shard baseline count
the same units.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import sys
import time


def _row_events(dest) -> int:
    from ..models.event import DeleteEvent, InsertEvent, UpdateEvent

    return sum(1 for e in dest.events
               if isinstance(e, (InsertEvent, UpdateEvent, DeleteEvent)))


async def run_worker(spec: dict) -> dict:
    from ..chaos.invariants import view_matches
    from ..chaos.runner import TracingDestination
    from ..config import BatchConfig, BatchEngine, PipelineConfig
    from ..models.table_state import TableStateType
    from ..postgres.fake import FakeSource
    from ..runtime import Pipeline
    from ..sharding import ShardMap
    from ..store import NotifyingStore
    from ..workloads import WorkloadGenerator, get_profile
    from .harness import _wait_background_compiles

    shard = spec.get("shard")  # None = unsharded baseline
    shard_count = int(spec.get("shard_count", 1))
    prof = dataclasses.replace(get_profile(spec.get("profile",
                                                    "insert_heavy")),
                               tables=int(spec.get("tables", 8)))
    gen = WorkloadGenerator(prof, seed=int(spec.get("seed", 7)))
    db = gen.build_db()
    owned = gen.table_ids if shard is None else \
        ShardMap(shard_count).tables_for_shard(gen.table_ids, shard)
    store = NotifyingStore()
    dest = TracingDestination()
    pipeline = Pipeline(
        config=PipelineConfig(
            pipeline_id=1, publication_name="pub",
            batch=BatchConfig(max_fill_ms=30,
                              batch_engine=BatchEngine(
                                  spec.get("engine", "tpu"))),
            lag_sample_interval_s=0,
            shard=shard, shard_count=shard_count),
        store=store, destination=dest,
        source_factory=lambda: FakeSource(db))

    def delivered() -> bool:
        return view_matches(dest, owned,
                            {tid: gen.expected[tid] for tid in owned})

    async def wait_verified() -> None:
        seen = -1
        while True:
            n = len(dest.events)
            if n == seen and delivered():
                return
            seen = n
            if pipeline._apply_task is not None \
                    and pipeline._apply_task.done():
                pipeline._apply_task.result()
                raise RuntimeError("pipeline stopped before delivering")
            await asyncio.sleep(0.1)

    target_ops = int(spec.get("target_ops", 2_000))
    verify_timeout_s = float(spec.get("verify_timeout_s", 240.0))
    try:
        await pipeline.start()
        for tid in owned:
            await asyncio.wait_for(
                store.notify_on(tid, TableStateType.READY), 120)
        warm_target = max(100, target_ops // 5)
        while gen.row_ops < warm_target:
            await gen.run_tx(db)
        await asyncio.wait_for(wait_verified(), 240)
        await _wait_background_compiles()

        ops0 = gen.row_ops
        ev0 = _row_events(dest)
        t0 = time.perf_counter()
        while gen.row_ops - ops0 < target_ops:
            await gen.run_tx(db)
        t_prod = time.perf_counter()
        try:
            await asyncio.wait_for(wait_verified(), verify_timeout_s)
            verified = True
        except asyncio.TimeoutError:
            verified = False
        t_done = time.perf_counter()
        ev1 = _row_events(dest)
    finally:
        if pipeline._apply_task is not None:
            await pipeline.shutdown_and_wait()

    window = max(t_done - t0, 1e-9)
    return {
        "shard": shard, "shard_count": shard_count,
        "profile": prof.name, "tables": len(owned),
        "owned_table_ids": list(owned),
        "committed_ops": gen.row_ops - ops0,
        "delivered_row_events": ev1 - ev0,
        "produce_seconds": round(t_prod - t0, 4),
        "window_seconds": round(window, 4),
        "events_per_second": round((ev1 - ev0) / window),
        "verified": bool(verified),
    }


def main(argv: "list[str] | None" = None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    if len(args) != 1:
        print(json.dumps({"error": "usage: shard_worker '<spec json>'"}))
        return 2
    import os

    if os.environ.get("JAX_PLATFORMS") is None:
        # CPU unless told otherwise: sibling workers cannot share a chip
        os.environ["JAX_PLATFORMS"] = "cpu"
    spec = json.loads(args[0])
    out = asyncio.run(run_worker(spec))
    print(json.dumps(out))
    return 0 if out.get("verified") else 1


if __name__ == "__main__":
    sys.exit(main())
